"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: kernel tests sweep shapes/dtypes and
assert_allclose against these functions, and the CPU dry-run path uses them
directly (``kernels/platform.py`` selects the kernel on TPU and these
references everywhere else).

Paged pools are ``(N, bs, KH, D)``; the lane-packed ``(N, bs, KH*D)`` the
serving steps store (see ``paged_attention``) is accepted unchanged, since
the references only ever reshape a gathered page to ``(bs, KH, D)``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

INF_ERA32 = jnp.iinfo(jnp.int32).max


# ----------------------------------------------------------------- era_scan
def era_scan_interval_ref(alloc_eras: jax.Array, retire_eras: jax.Array,
                          res_lo: jax.Array, res_hi: jax.Array) -> jax.Array:
    """Generalized cleanup scan: block lifetimes vs reservation intervals.

    alloc_eras, retire_eras: (R,) int32 — lifetimes of retired blocks.
    res_lo, res_hi: (S,) int32 reservation interval bounds (lo == INF_ERA32
    marks an empty slot; point reservations pass lo == hi).
    Returns (R,) bool: True iff no valid reservation interval overlaps the
    block's lifetime — ``can_delete`` vectorized over blocks and schemes.
    """
    valid = res_lo != INF_ERA32
    conflict = ((res_lo[None, :] <= retire_eras[:, None])
                & (alloc_eras[:, None] <= res_hi[None, :])
                & valid[None, :])
    return ~jnp.any(conflict, axis=1)


def era_scan_ref(alloc_eras: jax.Array, retire_eras: jax.Array,
                 reservations: jax.Array) -> jax.Array:
    """WFE cleanup() point-era scan (paper Fig. 4): lo == hi == era."""
    res = reservations.reshape(-1)  # (T*H,)
    return era_scan_interval_ref(alloc_eras, retire_eras, res, res)


# ------------------------------------------------------ paged chunk attention
def paged_attention_chunk_ref(
    q: jax.Array,            # (B, C, KH, G, D)  a query CHUNK per request
    k_pool: jax.Array,       # (N, bs, KH, D) paged key pool
    v_pool: jax.Array,       # (N, bs, KH, D) paged value pool
    tables: jax.Array,       # (B, nblk) int32 block ids (padding: any valid id)
    q_positions: jax.Array,  # (B, C) int32 absolute positions of the queries
    num_live_blocks: Optional[jax.Array] = None,  # (B,) i32 live table slots
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Chunked-prefill attention through block tables: each chunk query at
    absolute position p attends over every pool token the table names at
    positions <= p — the table's prior context plus the chunk's own earlier
    tokens (scattered into the pool by the caller before attention).
    Returns (B, C, KH, G, D).

    ``num_live_blocks`` mirrors the kernel's length-bounded grid: table
    slots ``j >= num_live_blocks[b]`` are masked out of the softmax (None =
    all slots visible; positions beyond the causal mask are dead either
    way, so an exact bound changes nothing bitwise).
    """
    b, c, kh, g, d = q.shape
    bs = k_pool.shape[1]
    nblk = tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    k = k_pool[tables].reshape(b, nblk * bs, kh, d)
    v = v_pool[tables].reshape(b, nblk * bs, kh, d)
    s = jnp.einsum("bckgd,bskd->bkgcs", q, k,
                   preferred_element_type=jnp.float32) * scale
    kvpos = jnp.arange(nblk * bs)  # logical positions within the table
    mask = kvpos[None, None, :] <= q_positions[:, :, None]  # (B, C, S)
    if num_live_blocks is not None:
        live = jnp.asarray(num_live_blocks, jnp.int32)
        mask = mask & (kvpos[None, None, :] < (live * bs)[:, None, None])
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgcs,bskd->bckgd", w.astype(jnp.float32),
                     v.astype(jnp.float32))
    return out.astype(q.dtype)


# ------------------------------------------------ int8 paged chunk attention
def paged_attention_chunk_int8_ref(
    q: jax.Array,            # (B, C, KH, G, D) fp query chunk
    k_pool: jax.Array,       # (N, bs, KH, D) int8 key codes
    v_pool: jax.Array,       # (N, bs, KH, D) int8 value codes
    k_scales: jax.Array,     # (N, KH) f32 per-(block, kv-head) scales
    v_scales: jax.Array,     # (N, KH) f32
    tables: jax.Array,
    q_positions: jax.Array,
    num_live_blocks: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Oracle for the kernel's fused-dequant int8 mode: materialize the
    dequantized pools with EXACTLY the kernel's arithmetic (int8 -> f32 is
    exact, then one f32 multiply by the block/head scale — see
    ``kernels/quant.dequantize_pool``) and run the fp oracle on them.
    Everything downstream of the dequant is shared with the fp path, so
    kernel-vs-oracle checks compare only the quantization semantics."""
    from .quant import dequantize_pool

    return paged_attention_chunk_ref(
        q, dequantize_pool(k_pool, k_scales),
        dequantize_pool(v_pool, v_scales), tables, q_positions,
        num_live_blocks, scale=scale)


def paged_attention_int8_ref(
    q: jax.Array,            # (B, KH, G, D) one fp query token per request
    k_pool: jax.Array,       # (N, bs, KH, D) int8 key codes
    v_pool: jax.Array,
    k_scales: jax.Array,     # (N, KH) f32
    v_scales: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    num_live_blocks: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Decode (C == 1) specialization of the int8 oracle."""
    from .quant import dequantize_pool

    return paged_attention_ref(
        q, dequantize_pool(k_pool, k_scales),
        dequantize_pool(v_pool, v_scales), tables, lengths,
        num_live_blocks, scale=scale)


# ----------------------------------------------------- paged decode attention
def paged_attention_ref(
    q: jax.Array,          # (B, KH, G, D)  one query token per request
    k_pool: jax.Array,     # (N, bs, KH, D) paged key pool
    v_pool: jax.Array,     # (N, bs, KH, D) paged value pool
    tables: jax.Array,     # (B, nblk) int32 block ids (padding: any valid id)
    lengths: jax.Array,    # (B,) int32 tokens in cache (context length)
    num_live_blocks: Optional[jax.Array] = None,  # (B,) i32 live table slots
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Decode attention through block tables.  Returns (B, KH, G, D).

    ``num_live_blocks`` mirrors the kernel's length-bounded grid (see
    ``paged_attention_chunk_ref``); the exact bound ``ceil(lengths / bs)``
    is already implied by the length mask.
    """
    b, kh, g, d = q.shape
    bs = k_pool.shape[1]
    nblk = tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    k = k_pool[tables]  # (B, nblk, bs, KH, D)
    v = v_pool[tables]
    k = k.reshape(b, nblk * bs, kh, d)
    v = v.reshape(b, nblk * bs, kh, d)
    s = jnp.einsum("bkgd,bskd->bkgs", q, k,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(nblk * bs)[None, :]  # logical positions
    valid = pos < lengths[:, None]  # (B, S)
    if num_live_blocks is not None:
        live = jnp.asarray(num_live_blocks, jnp.int32)
        valid = valid & (pos < (live * bs)[:, None])
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w.astype(jnp.float32),
                     v.astype(jnp.float32))
    return out.astype(q.dtype)
