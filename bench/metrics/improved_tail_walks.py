"""Walks per Algorithm-2 job that found their vertex's coupon pool empty
and finished by the naive per-walk tail: the `tail_walks` count of the
window's `improved.job` spans, averaged over the window's jobs."""
from bench import improved_spans


def read(r):
    return improved_spans.job_mean(improved_spans.ring(),
                                   r.counters.get("rounds"), "tail_walks")
