"""Percent of the traced window in which no program ran on the device."""


def read(r):
    if r.trace is None or r.trace.devices == 0:
        return None
    return 100.0 * r.trace.idle_share
