"""Host milliseconds of a batch job's build (`counts.build` span:
`shard_graph_padded`, the device placements and the step lookup of
`distributed_pagerank_counts`), averaged over the builds of the window's
jobs that end before the traced session begins (`bench.program_spans`):
in `batch.g500`, the first job's."""
from bench import program_spans


def from_spans(records, rounds, offset_s):
    found = program_spans.before_session(records, rounds, offset_s,
                                         "counts.build")
    if found is None:
        return None
    builds, _ = found
    return 1e3 * sum(b.seconds for b in builds) / len(builds)


def read(r):
    return from_spans(program_spans.ring(), r.counters.get("rounds"),
                      program_spans.session_offset_s())
