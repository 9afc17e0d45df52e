"""Rounds per Algorithm-2 job: the `rounds` count of the window's
`improved.job` spans (all phases: Phase 1, stitches, the counting round
and the naive tail), averaged over the window's jobs."""
from bench import improved_spans


def read(r):
    return improved_spans.job_mean(improved_spans.ring(),
                                   r.counters.get("rounds"), "rounds")
