"""Time the serving loop spent reclaiming blocks (every cleanup pass in
complete, eviction and the idle loop), per step, from the program's own
timers."""


def read(ctx):
    c = ctx["counters"]
    if "reclaim_ns" not in c or not c["steps"]:
        return None
    return c["reclaim_ns"] / c["steps"] / 1e3
