"""Output tokens first delivered in the window per second of window."""

from chipbench import stats


def read(ctx):
    return stats.tokens_in(ctx["recs"], ctx["t0"], ctx["t_end"]) \
        / ctx["seconds"]
