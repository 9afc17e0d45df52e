"""Device milliseconds of the counts engine's exchange program
(`jit_exchange`, `core/distributed_counts.py` `_exchange_step`) per
execution, i.e. per round, from the trace."""


def read(r):
    if r.trace is None:
        return None
    seconds, runs = r.trace.program("jit_exchange")
    return 1e3 * seconds / runs if runs else None
