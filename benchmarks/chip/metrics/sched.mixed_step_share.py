"""Share of the window's steps that were mixed (decode rows plus a
prefill chunk), from the scheduler's counters."""


def read(ctx):
    c = ctx["counters"]
    return c["mixed_steps"] / c["steps"] if c["steps"] else None
