"""Jit'd public wrappers around the Pallas kernels.

``use_kernel`` selects the Pallas path or the pure-jnp reference; the
default ``None`` decides by platform (``kernels/platform.py``): the kernel
on TPU, the reference elsewhere, where it is the test oracle.  A kernel
asked for off-TPU runs in the Pallas interpreter.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .era_scan import era_scan, era_scan_interval
from .paged_attention import paged_attention, paged_attention_chunk
from .platform import resolve_use_kernel

__all__ = ["can_delete_blocks", "can_delete_blocks_interval",
           "paged_decode_attention", "paged_chunk_attention"]


def can_delete_blocks(alloc_eras, retire_eras, reservations, *,
                      use_kernel: bool | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Vectorized WFE can_delete over R retired blocks.  Returns (R,) bool."""
    alloc_eras = jnp.asarray(alloc_eras, jnp.int32)
    retire_eras = jnp.asarray(retire_eras, jnp.int32)
    reservations = jnp.asarray(reservations, jnp.int32)
    if resolve_use_kernel(use_kernel):
        return era_scan(alloc_eras, retire_eras, reservations,
                        interpret=interpret)
    return ref.era_scan_ref(alloc_eras, retire_eras, reservations)


def can_delete_blocks_interval(alloc_eras, retire_eras, res_lo, res_hi, *,
                               interpret: bool | None = None) -> jax.Array:
    """Generalized interval form used by ``core.era_table``'s pallas backend.

    Always takes the Pallas kernel (``interpret=None`` auto-selects compiled
    vs interpreter by backend); the jnp oracle lives in ``ref``.
    """
    return era_scan_interval(
        jnp.asarray(alloc_eras, jnp.int32),
        jnp.asarray(retire_eras, jnp.int32),
        jnp.asarray(res_lo, jnp.int32),
        jnp.asarray(res_hi, jnp.int32),
        interpret=interpret)


def _layer_of(pools, layer):
    """The reference reads one layer's (N, bs, KH*D) pool (or scales)."""
    return pools if layer is None or pools is None else pools[layer]


def paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                           num_live_blocks=None, k_scales=None,
                           v_scales=None, *, layer=None,
                           scale: Optional[float] = None,
                           use_kernel: bool | None = None) -> jax.Array:
    """Decode attention over the paged pool.  q (B,KH,G,D) -> (B,KH,G,D).

    Pools are (N,bs,KH,D), or the lane-packed, layer-stacked
    (L,N,bs,KH*D) with ``layer`` naming the one to read.
    ``num_live_blocks`` (B,) i32 bounds each request's table walk (the
    kernel path fetches and folds in only the live pages; the ref masks
    the rest).  ``k_scales``/``v_scales`` (N, KH)
    f32 (or (L, N, KH)) select the int8 pool mode: the kernel dequantizes
    in-register after the VMEM load; the ref path materializes the
    identical dequant.
    """
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if num_live_blocks is not None:
        num_live_blocks = jnp.asarray(num_live_blocks, jnp.int32)
    if resolve_use_kernel(use_kernel):
        return paged_attention(q, k_pool, v_pool, tables, lengths,
                               num_live_blocks, k_scales, v_scales,
                               layer=layer, scale=scale)
    k_pool, v_pool = _layer_of(k_pool, layer), _layer_of(v_pool, layer)
    if k_scales is not None:
        return ref.paged_attention_int8_ref(
            q, k_pool, v_pool, _layer_of(k_scales, layer),
            _layer_of(v_scales, layer), tables, lengths, num_live_blocks,
            scale=scale)
    return ref.paged_attention_ref(q, k_pool, v_pool, tables, lengths,
                                   num_live_blocks, scale=scale)


def paged_chunk_attention(q, k_pool, v_pool, tables, q_positions,
                          num_live_blocks=None, k_scales=None,
                          v_scales=None, *, layer=None,
                          scale: Optional[float] = None,
                          use_kernel: bool | None = None) -> jax.Array:
    """Chunked-prefill attention over the paged pool.

    q (B,C,KH,G,D) -> (B,C,KH,G,D); each query at absolute position p sees
    pool tokens at positions <= p (prior context + intra-chunk causal).
    ``layer`` / ``num_live_blocks`` / ``k_scales``/``v_scales`` /
    ``use_kernel`` as in ``paged_decode_attention``.
    """
    tables = jnp.asarray(tables, jnp.int32)
    q_positions = jnp.asarray(q_positions, jnp.int32)
    if num_live_blocks is not None:
        num_live_blocks = jnp.asarray(num_live_blocks, jnp.int32)
    if resolve_use_kernel(use_kernel):
        return paged_attention_chunk(q, k_pool, v_pool, tables, q_positions,
                                     num_live_blocks, k_scales, v_scales,
                                     layer=layer, scale=scale)
    k_pool, v_pool = _layer_of(k_pool, layer), _layer_of(v_pool, layer)
    if k_scales is not None:
        return ref.paged_attention_chunk_int8_ref(
            q, k_pool, v_pool, _layer_of(k_scales, layer),
            _layer_of(v_scales, layer), tables, q_positions,
            num_live_blocks, scale=scale)
    return ref.paged_attention_chunk_ref(q, k_pool, v_pool, tables,
                                         q_positions, num_live_blocks,
                                         scale=scale)
