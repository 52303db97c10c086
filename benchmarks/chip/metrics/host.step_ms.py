"""Host time of a step: the program's own tick, dispatch (padding and
enqueue) and complete, summed over the window and divided by its steps."""


def read(ctx):
    c = ctx["counters"]
    if "tick_ns" not in c or not c["steps"]:
        return None
    return (c["tick_ns"] + c["dispatch_ns"] + c["complete_ns"]) \
        / c["steps"] / 1e6
