"""One generator for every traffic mix: a mix is a data file of
parameters (``traffic/<mix>.json``), read here.

Every seed gets the same requests in the same order: lengths and gaps
are the distribution's quantiles at ``(i + 0.5) / n``, shuffled once by
a fixed generator (``SCHEDULE_SEED``), so that the schedule, which
request comes when with which lengths, is one fixed amount of work.  The
seed draws the token ids (and the shared contexts), as it draws the
weights.  A window of a dozen requests is decided by which lengths
collide: shuffled by the seed, two seeds differed far more than two
runs of one seed did.

Parameters of a mix:

* ``loop``: ``"open"`` (Poisson arrivals at ``rate_per_s``, sent on
  schedule whatever the server does) or ``"closed"`` (``clients``
  callers, each sending its next request when the last one finishes);
* ``prompt`` and ``output``: length distributions, ``{"dist":
  "lognormal", "median", "sigma", "min", "max"}`` or ``{"dist":
  "uniform", "min", "max"}``;
* ``prefix`` (optional): ``{"contexts", "length", "zipf_s"}``: each
  prompt is one of ``contexts`` shared contexts of ``length`` tokens,
  chosen by Zipf(``zipf_s``), followed by a unique suffix whose length is
  the ``prompt`` distribution; set-up serves every context once.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

#: requests generated per closed-loop client (ample for one window)
PER_CLIENT = 16
#: the generator that orders every mix's lengths, gaps and contexts
SCHEDULE_SEED = 0


@dataclass
class Item:
    prompt: List[int]
    max_new: int
    due: float = 0.0  # open loop: seconds after the window opens
    client: int = 0  # closed loop: which caller sends it
    context: Optional[int] = None  # shared context index, if any


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, clipped
    to ``[min, max]`` and rounded to whole tokens (ascending)."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def max_len(spec: dict) -> int:
    """Longest request (prompt, shared context and output) the mix sends."""
    ctx = spec.get("prefix", {}).get("length", 0)
    return ctx + spec["prompt"]["max"] + spec["output"]["max"]


def count(spec: dict, seconds: float) -> int:
    """Requests of one run.  Open loop: as many as the rate brings in
    ``seconds``; their stratified gaps sum to less than ``seconds``, so
    every seed sends all of them inside the window."""
    if spec["loop"] == "open":
        return max(1, int(math.floor(spec["rate_per_s"] * seconds)))
    return spec["clients"] * PER_CLIENT


def contexts(spec: dict, seed: int, vocab: int) -> List[List[int]]:
    """The shared contexts of a prefix mix (empty without one)."""
    p = spec.get("prefix")
    if not p:
        return []
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab, p["length"]).tolist()
            for _ in range(p["contexts"])]


def generate(spec: dict, seed: int, vocab: int, seconds: float) -> List[Item]:
    """The requests of one run, in the order they are sent (open loop: by
    due time; closed loop: client ``i % clients`` sends item ``i``)."""
    n = count(spec, seconds)
    rng = np.random.default_rng([seed, 0])
    order = np.random.default_rng(SCHEDULE_SEED)
    prompts = order.permutation(quantiles(spec["prompt"], n))
    outputs = order.permutation(quantiles(spec["output"], n))
    ctx_ids = [None] * n
    shared = contexts(spec, seed, vocab)
    if shared:
        s = spec["prefix"]["zipf_s"]
        w = 1.0 / np.arange(1, len(shared) + 1) ** s
        cdf = np.cumsum(w) / w.sum()
        u = (np.arange(n) + 0.5) / n
        ctx_ids = order.permutation(np.searchsorted(cdf, u)).tolist()
    dues = np.zeros(n)
    if spec["loop"] == "open":
        u = (np.arange(n) + 0.5) / n
        gaps = order.permutation(-np.log1p(-u) / spec["rate_per_s"])
        dues = np.cumsum(gaps)
    items = []
    for i in range(n):
        body = rng.integers(0, vocab, int(prompts[i])).tolist()
        c = ctx_ids[i]
        prompt = (shared[c] + body) if c is not None else body
        items.append(Item(prompt, int(outputs[i]), float(dues[i]),
                           i % spec.get("clients", 1), c))
    return items
