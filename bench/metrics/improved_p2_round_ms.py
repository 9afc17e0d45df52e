"""Milliseconds per stitch round of Algorithm 2: the mean `round.phase2`
span over the window jobs' stitch rounds that end before the traced
session begins (`bench.improved_spans`)."""
from bench import improved_spans


def read(r):
    return improved_spans.mean_ms(improved_spans.ring(),
                                  r.counters.get("rounds"),
                                  improved_spans.session_offset_s(),
                                  "round.phase2")
