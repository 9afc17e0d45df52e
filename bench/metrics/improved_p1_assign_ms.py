"""Device milliseconds of Algorithm 2's Phase-1 assign program
(`jit_p1_assign`, `core/distributed_improved.py` `_p1_assign`) per
execution, i.e. per Phase-1 round, from the trace."""


def read(r):
    if r.trace is None:
        return None
    seconds, runs = r.trace.program("jit_p1_assign")
    return 1e3 * seconds / runs if runs else None
