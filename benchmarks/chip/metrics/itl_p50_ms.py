"""Median of every gap between consecutive output tokens in the window
(first deliveries only): the cadence of a decoding row."""

from chipbench import stats


def read(ctx):
    v = stats.pct(stats.itls(ctx["recs"], ctx["t0"], ctx["t_end"]), 50)
    return None if v is None else v * 1e3
