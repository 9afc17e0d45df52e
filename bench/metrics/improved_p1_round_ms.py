"""Milliseconds per Phase-1 round of Algorithm 2: the mean `round.phase1`
span (request, sample and assign dispatches and the round's one sync)
over the window jobs' Phase-1 rounds that end before the traced session
begins (`bench.improved_spans`)."""
from bench import improved_spans


def read(r):
    return improved_spans.mean_ms(improved_spans.ring(),
                                  r.counters.get("rounds"),
                                  improved_spans.session_offset_s(),
                                  "round.phase1")
