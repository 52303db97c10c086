"""Seconds from process start to the window's start: building the engine
and its weights, sizing the pool, loading or compiling every step shape,
and the set-up the traffic needs (a warm prefix cache, a full batch)."""


def read(ctx):
    return ctx["setup_s"]
