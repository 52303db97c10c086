"""Symmetric per-(block, kv-head) int8 quantization for the paged KV pool.

The storage scheme behind ``init_pools(kv_dtype="int8")``: every K/V pool
page stores int8 codes plus ONE fp32 scale per (pool block, kv head) —
``k_scale``/``v_scale`` arrays shaped ``(n_blocks, KH)`` riding next to
the pools.  Dequantization is ``code.astype(f32) * scale``; the paged
kernels fuse it right after the VMEM load (the scale arrives as an extra
scalar-prefetch operand indexed through the block table, exactly like
``num_live_blocks``), so K/V stream from HBM at 1 byte/element instead
of 4 and the flash accumulator math downstream is unchanged.

Writes keep a RUNNING absmax per block: appending a token may only GROW
a block's scale (``scales.at[blk].max``), and when it does, the block's
already-stored rows are re-scaled ``round(code * old/new)`` — old tokens
are re-read only at their stored int8 precision, never from a stale
higher-precision copy (there is none; the int8 pool is the only storage).
A block's quantization error is therefore bounded by HALF the largest
absmax any of its tokens ever reached: ``|deq - true| <= scale / 2``
per element, with ``scale = running_absmax / 127``.

All helpers are pure jnp so the serve steps jit them in place and the
oracles in ``ref`` mirror the arithmetic exactly.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["QMAX", "dequantize_pool", "quantize_rows", "requantize_blocks",
           "scatter_quantized"]

#: symmetric int8 code range: [-127, 127], -128 unused (keeps the scheme
#: symmetric so negating a value negates its code)
QMAX = 127.0


def _safe(scales: jnp.ndarray) -> jnp.ndarray:
    """Division-safe scales: an all-zero (never-written) block has scale 0
    and every code 0 — substituting 1.0 keeps 0/1 = 0 without NaN."""
    return jnp.where(scales > 0, scales, 1.0)


def dequantize_pool(pool: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """int8 pool (N, bs, KH, D) + scales (N, KH) -> fp32 (N, bs, KH, D).

    A lane-packed pool (N, bs, KH*D) dequantizes to the same packed shape.

    EXACT mirror of the kernel's fused in-register dequant
    (``k.astype(f32) * k_scale[block, head]``): int8 -> f32 is exact and
    the scalar multiply is one f32 rounding, so materializing this array
    and running the fp kernel is bitwise-identical to the fused path.
    """
    n, bs, kh = pool.shape[0], pool.shape[1], scales.shape[-1]
    out = (pool.reshape(n, bs, kh, -1).astype(jnp.float32)
           * jnp.asarray(scales, jnp.float32)[:, None, :, None])
    return out.reshape(pool.shape)


def quantize_rows(x: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """Quantize fp rows (..., KH, D) under per-(row, head) scales (..., KH).

    ``round(x / scale)`` clipped to the symmetric code range; callers pass
    scales >= absmax(x) / QMAX so the clip only trims float round-off.
    """
    q = jnp.round(x.astype(jnp.float32) / _safe(scales)[..., None])
    return jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)


def requantize_blocks(blocks: jnp.ndarray, old_scales: jnp.ndarray,
                      new_scales: jnp.ndarray) -> jnp.ndarray:
    """Re-code stored int8 rows (..., bs, KH, D) from old to new scales.

    ``round(code * old/new)``: the monotone-scale invariant guarantees
    new >= old, so the ratio is <= 1 and never overflows the code range.
    When the scale did not change the ratio is exactly 1.0 and the round
    trip is the identity — untouched blocks are bitwise stable.
    """
    ratio = jnp.where(new_scales > 0, old_scales / _safe(new_scales), 0.0)
    q = jnp.round(blocks.astype(jnp.float32) * ratio[..., None, :, None])
    return jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)


def scatter_quantized(pool: jnp.ndarray, scales: jnp.ndarray,
                      blk: jnp.ndarray, off: jnp.ndarray,
                      toks: jnp.ndarray, drop_block, layer=None) -> tuple:
    """Scatter fp K/V rows into an int8 pool under running absmax scales.

    pool (N, bs, KH, D) or lane-packed (N, bs, KH*D) int8; scales (N, KH)
    f32; blk/off (B, C) i32 destination block/offset per token
    (``blk == drop_block`` marks a padded row: it updates nothing); toks
    (B, C, KH, D) fp.  With ``layer`` the pool and scales are the
    layer-stacked (L, N, ...) / (L, N, KH) arrays and only that layer is
    written, in place.  Returns (pool, scales) updated.

    Three scatters, in an order that keeps duplicates idempotent:

    1. ``scales.at[blk].max(absmax / QMAX)`` — the running absmax; max is
       associative, so several chunk tokens landing in one block commute;
    2. re-scale each DESTINATION block's existing rows old -> new scale
       (gathered from the pre-update pool, coded under the post-update
       scale: duplicate destinations write identical bytes);
    3. quantize the new tokens under the post-update scale and write them
       at their offsets (overwriting step 2's re-coding of those rows).

    Prefix-cache-shared pages never appear in ``blk`` (consumers start
    past the cached boundary — the scatter skip is structural), so a
    cached block's codes AND scale are written by its producer only.
    """
    pre = () if layer is None else (layer,)
    n = pool.shape[len(pre)]
    b, c, kh, d = toks.shape
    valid = blk != drop_block
    amax = jnp.max(jnp.abs(toks.astype(jnp.float32)), axis=-1)  # (B, C, KH)
    amax = jnp.where(valid[..., None], amax, 0.0)
    new_scales = scales.at[pre + (blk,)].max(amax / QMAX, mode="drop")
    blk_g = jnp.minimum(blk, n - 1)  # in-bounds gather index for pad rows
    old_s = scales[pre + (blk_g,)]   # (B, C, KH)
    new_s = new_scales[pre + (blk_g,)]
    blocks = pool[pre + (blk_g,)]    # (B, C, bs, ...)
    blocks = requantize_blocks(blocks.reshape(b, c, -1, kh, d), old_s, new_s)
    pool = pool.at[pre + (blk,)].set(
        blocks.reshape(b, c, *pool.shape[len(pre) + 1:]), mode="drop")
    rows = quantize_rows(toks, new_s).reshape(
        b, c, *pool.shape[len(pre) + 2:])
    pool = pool.at[pre + (blk, off)].set(rows, mode="drop")
    return pool, new_scales
