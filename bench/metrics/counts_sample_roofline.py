"""Share of the HBM roofline that one round of the counts sampler reaches.

The least work of a round is memory traffic: read each vertex's coupon
count and degree and write one count per out-edge, 4 * (2n + m) bytes of
int32, counted from the graph's own n and m and never from the layout's
padding. Its least time is those bytes over the chip's peak HBM bandwidth
(`bench/peaks.json`); the share is that time over the sample program's
device time per round, in percent. Its FLOPs are a few per edge, so the
bound is memory.
"""


def least_bytes(n: int, m: int) -> int:
    return 4 * (2 * n + m)


def read(r):
    if r.trace is None:
        return None
    seconds, runs = r.trace.program("jit_sample")
    if not runs or "hbm_bytes_per_s" not in r.peaks:
        return None
    c = r.counters
    least_s = least_bytes(c["n"], c["m"]) / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / runs)
