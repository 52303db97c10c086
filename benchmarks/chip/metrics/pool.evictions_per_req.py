"""Preemptions (eviction rewinds) per request completed in the window."""


def read(ctx):
    c = ctx["counters"]
    return c["evictions"] / c["completed"] if c["completed"] else None
