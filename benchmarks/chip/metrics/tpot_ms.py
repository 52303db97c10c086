"""Time per output token: the mean of every gap between consecutive
output tokens in the window (first deliveries only), mixed steps and
stalls included."""

from chipbench import stats


def read(ctx):
    gaps = stats.itls(ctx["recs"], ctx["t0"], ctx["t_end"])
    return sum(gaps) / len(gaps) * 1e3 if gaps else None
