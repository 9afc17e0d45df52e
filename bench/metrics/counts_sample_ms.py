"""Device milliseconds of the counts engine's sample program (`jit_sample`,
`core/distributed_counts.py` `_sample_step`) per execution, i.e. per
round, from the trace."""


def read(r):
    if r.trace is None:
        return None
    seconds, runs = r.trace.program("jit_sample")
    return 1e3 * seconds / runs if runs else None
