"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    if not ctx["window_s"]:
        return None
    return 1.0 - ctx["busy_s"] / ctx["window_s"]
