"""Output tokens delivered in the window per scheduler step."""

from chipbench import stats


def read(ctx):
    steps = ctx["counters"]["steps"]
    if not steps:
        return None
    return stats.tokens_in(ctx["recs"], ctx["t0"], ctx["t_end"]) / steps
