"""Time the workers waited for the scheduler lock (in tick and complete),
per step, from the program's own timers."""


def read(ctx):
    c = ctx["counters"]
    if "lock_wait_ns" not in c or not c["steps"]:
        return None
    return c["lock_wait_ns"] / c["steps"] / 1e3
