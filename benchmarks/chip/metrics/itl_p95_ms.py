"""95th percentile of every gap between consecutive output tokens in the
window (first deliveries only)."""

from chipbench import stats


def read(ctx):
    v = stats.pct(stats.itls(ctx["recs"], ctx["t0"], ctx["t_end"]), 95)
    return None if v is None else v * 1e3
