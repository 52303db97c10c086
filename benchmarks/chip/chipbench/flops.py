"""Useful work of a served step, counted from the model's shapes.

Each count depends only on the configuration and on the real rows of a
step (how many query tokens, at which context), never on how the program
pads or buckets them: padded rows, padded chunk columns and dead table
slots count for nothing, and a prefix-cache hit is never prefilled, so
it adds no projection or MLP work (its pages are still read as context).

A step's rows come from the benchmark's own record of each plan the
scheduler handed the engine (``Row``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple


@dataclass(frozen=True)
class Row:
    """One real row of a step: ``n`` query tokens at positions
    ``ctx, ctx + 1, ..., ctx + n - 1`` (so the last one attends to
    ``ctx + n`` keys); ``emits`` when the row's last token yields a
    generated token (its logits are the ones sampled)."""

    ctx: int
    n: int
    emits: bool


def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]


def dense_flops_per_token(m: dict) -> int:
    """Projection and MLP operations of one token through every layer
    (two operations per multiply-add)."""
    d, h, kh, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    proj = d * h * hd + 2 * d * kh * hd + h * hd * d
    mlp = (3 if m["mlp"] == "swiglu" else 2) * d * f
    return 2 * m["n_layers"] * (proj + mlp)


def attention_keys(rows: Iterable[Row]) -> int:
    """Sum over every real query token of the keys it attends to
    (its causal context, itself included)."""
    return sum(r.n * r.ctx + r.n * (r.n + 1) // 2 for r in rows)


def step_flops(m: dict, rows: Iterable[Row]) -> int:
    """Operations the model needs for one step's real rows: projections
    and MLP for each real query token, attention over each token's real
    causal context, and the LM head once per generated token."""
    rows = list(rows)
    tokens = sum(r.n for r in rows)
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] \
        * attention_keys(rows)
    head = 2 * m["d_model"] * m["vocab_size"] * sum(r.emits for r in rows)
    return dense_flops_per_token(m) * tokens + attn + head


def kernel_call_cost(m: dict, rows: Iterable[Row],
                     block_size: int) -> Tuple[int, int]:
    """(operations, bytes) of ONE paged-attention call (one layer) over
    a step's real rows: 4 x query tokens x their causal context x heads
    x head_dim operations; the live K and V pages each row must read
    (whole pages up to its last key) plus its queries and outputs."""
    rows = list(rows)
    h, kh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    size = _itemsize(m["dtype"])
    ops = 4 * h * hd * attention_keys(rows)
    pages = sum(-(-(r.ctx + r.n) // block_size) for r in rows)
    byts = (2 * pages * block_size * kh * hd * size
            + 2 * sum(r.n for r in rows) * h * hd * size)
    return ops, byts


def kernel_step_bound_s(m: dict, rows: Iterable[Row], block_size: int,
                        peak_flops: float, peak_bw: float) -> float:
    """Least time the chip could spend in one step's paged-attention
    calls: per call the larger of operations over peak and bytes over
    bandwidth, times one call per layer."""
    ops, byts = kernel_call_cost(m, rows, block_size)
    return m["n_layers"] * max(ops / peak_flops, byts / peak_bw)
