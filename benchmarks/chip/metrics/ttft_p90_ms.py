"""90th percentile of time to first token over every request due in the
window, from its due time; a request still waiting at the window's end
enters with the time it has waited."""

from chipbench import stats


def read(ctx):
    v = stats.pct(stats.ttfts(ctx["recs"], ctx["t0"], ctx["t_end"]), 90)
    return None if v is None else v * 1e3
