"""Latency arithmetic on the client's own clock.

Every time here is a ``time.perf_counter()`` reading taken by the
benchmark's client thread or by the token hook it hands the server.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def pct(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation between the two
    nearest ranks, numpy's default); None for an empty sample."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(records, t_start: float, t_end: float) -> List[float]:
    """Time to first token of every request DUE in ``[t_start, t_end)``,
    measured from its due time (the open-loop schedule), not from when
    the client got round to submitting it.  A request with no token by
    ``t_end`` enters with the time it has waited so far, so a stall that
    holds back first tokens cannot shrink the tail."""
    out = []
    for r in records:
        if not t_start <= r.due < t_end:
            continue
        first = r.token_times[0] if r.token_times else None
        if first is None or first >= t_end:
            out.append(t_end - r.due)
        else:
            out.append(first - r.due)
    return out


def itls(records, t_start: float, t_end: float) -> List[float]:
    """Every gap between consecutive output tokens of every request whose
    later token arrived in ``[t_start, t_end)``.  Token times are first
    deliveries (one per token index), so a replay after an eviction is
    not counted twice; its stall shows in the next new token's gap."""
    out = []
    for r in records:
        tt = r.token_times
        for i in range(1, len(tt)):
            if t_start <= tt[i] < t_end:
                out.append(tt[i] - tt[i - 1])
    return out


def tokens_in(records, t_start: float, t_end: float) -> int:
    """Output tokens first delivered in ``[t_start, t_end)``."""
    return sum(1 for r in records for t in r.token_times
               if t_start <= t < t_end)


def summary(values: Sequence[float]) -> Dict[str, Optional[float]]:
    return {"n": len(values), "p50": pct(values, 50), "p90": pct(values, 90),
            "p95": pct(values, 95), "max": max(values) if values else None}
