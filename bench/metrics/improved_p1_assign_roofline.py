"""Share of the HBM roofline that one Phase-1 assign of Algorithm 2
reaches.

The least work of an assign is memory traffic in int32: read each
coupon's vertex and liveness and write its new vertex, liveness and
trajectory step (5 S, S the coupons the job created), and read one reply
cell per vertex and per out-edge with its destination (2 (n + m)):
4 (5 S + 2 (n + m)) bytes, from the graph's own n and m and never from
the layout's padding. Its least time is those bytes over the chip's peak
HBM bandwidth (`bench/peaks.json`); the share is that time over the
assign program's device time per execution, in percent.
"""


def least_bytes(n: int, m: int, coupons: int) -> int:
    return 4 * (5 * coupons + 2 * (n + m))


def read(r):
    if r.trace is None:
        return None
    seconds, runs = r.trace.program("jit_p1_assign")
    c = r.counters
    if not runs or "hbm_bytes_per_s" not in r.peaks or not c.get("coupons"):
        return None
    # the traced window lies in the first window job
    least_s = (least_bytes(c["n"], c["m"], c["coupons"][0])
               / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (seconds / runs)
