"""The host's own milliseconds per round of the counts engine: each
`round.counts` span less its `counts.sync` child, the wait for the
round's counters, averaged over the window's rounds that end before the
traced session begins (`bench.program_spans`): the host's dispatch and
bookkeeping in a round."""
from bench import program_spans


def from_spans(records, rounds, offset_s):
    found = program_spans.before_session(records, rounds, offset_s,
                                         "round.counts")
    if found is None:
        return None
    spans, children = found
    own = [s.seconds - sum(w.seconds for w in children[s.id]
                           if w.name == "counts.sync")
           for s in spans]
    return 1e3 * sum(own) / len(own)


def read(r):
    return from_spans(program_spans.ring(), r.counters.get("rounds"),
                      program_spans.session_offset_s())
