"""Milliseconds per round of the counts engine: the mean `round.counts`
span (one per round, `runtime.run_staged`) over the window's rounds that
end before the traced session begins (`bench.program_spans`). A round
ends at its one host sync, so this is the round's wall time, device and
host."""
from bench import program_spans


def from_spans(records, rounds, offset_s):
    found = program_spans.before_session(records, rounds, offset_s,
                                         "round.counts")
    if found is None:
        return None
    spans, _ = found
    return 1e3 * sum(s.seconds for s in spans) / len(spans)


def read(r):
    return from_spans(program_spans.ring(), r.counters.get("rounds"),
                      program_spans.session_offset_s())
