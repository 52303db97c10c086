"""Pallas TPU kernel: attention through WFE-managed block tables.

This is the consumer of the era-reclaimed block pool: query tokens attend
over K/V scattered across pool blocks named by the request's block table.
The GPU version of this idea (vLLM PagedAttention) walks the table with
per-warp gathers; the TPU adaptation keeps the pools in HBM and has the
kernel copy each page the table names into VMEM itself, reading the page
ids from the scalar-prefetched table — so a step's DMAs and grid work
grow with the live pages, not with rows x table width.

Pool layout: ``(L, N, bs, KH*D)`` — layer, pool block, in-block token,
and the token's K (or V) for every kv head packed along the lanes.  For
stablelm-3b that is 32 heads x 80 = 2,560 lanes, a multiple of 128: the
default TPU layout of the pool is then plain row-major with no padding,
and one page is one dense ``(bs, KH*D)`` tile whose last two dims equal
the array's (Mosaic's (8, 128) rule).  A head-major ``(..., KH, bs, D)``
pool would have to pad each 80-wide page to 128 lanes in HBM (1.6x the
bytes), and the layout XLA picks for it instead is one the kernel cannot
read without a whole-pool relayout copy.  The kernel takes the whole
layer-stacked pool plus a scalar-prefetched layer index, so a step never
slices (copies) one layer's pool out of the stack.

The kernel is written for a (C, ...) **query chunk** per request (chunked
prefill); single-token decode is the C == 1 specialization:

* grid = (B,): one grid step walks one row's LIVE pages, the first
  ``num_live_blocks[b]`` table slots, carrying a flash-style (m, l, acc)
  accumulator per (head, query row) in VMEM scratch;
* the walk goes by page GROUPS of P pages (``pages_per_group``: enough
  pages for 128 keys, within a VMEM budget, from static shapes): each
  page of a group is one ``make_async_copy`` of a ``(bs, KH*D)`` K and V
  tile, HBM -> VMEM, page id ``tables[b, j]``, and two buffer slots
  alternate so the next group streams in while this one is folded in.  A
  full group is folded in with one score and one value matmul per head
  over its ``P*bs`` keys; the last, partial group page by page, so a
  short row (a padded row has one page) pays for what it holds.  A slot
  ``j >= num_live_blocks[b]`` is never named, so never fetched, and no
  buffer row it would have filled ever reaches the accumulators;
* queries arrive lane-packed the same way, ``(B, C*G, KH*D)``: head
  ``h``'s rows are a 2-D ``(C*G, D)`` tile, so scores and the value
  update are two 2-D matmuls per head; heads go in blocks whose lanes
  fill whole 128-lane tiles (``heads_per_block``: 8 heads of 80 lanes),
  one aligned load of q, K and V per block and a static lane slice per
  head;
* masking is causal by ABSOLUTE position: a chunk query at position p sees
  every pool token at positions <= p — the table's prior context AND the
  chunk's own earlier tokens (which the caller scattered into the pool
  before attention), so one mask covers history + intra-chunk causality;
  the walk ends at the causal bound where the caller's bound is wider, so
  a wider bound walks the same pages in the same groups;
* QUANTIZED pools (``kv_dtype="int8"``): the layer's per-(block, kv-head)
  scales ride along as one more scalar-prefetch operand (flattened to
  ``(N*KH,)`` in scalar memory), read through the same table entry as the
  page they scale, and the kernel dequantizes each K/V tile in-register
  right after the VMEM load (``k.astype(f32) * k_scales[tables[b, j] * KH
  + h]``) — K/V stream from HBM at 1 byte/element and the flash
  accumulator math is UNCHANGED: the fused path does the arithmetic of
  materializing the dequantized fp32 pools and running the unquantized
  kernel (bitwise equal in the interpreter wherever its f32 dots sum both
  in the same order; at 128-key groups of 80-lane heads they may not,
  with every scale 1.0 as much as with real scales).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import resolve_interpret

NEG_INF = -1e30
#: scalar memory of one TensorCore: 1 MiB on v5e (the compiler's refusal
#: names this size).  The int8 mode keeps one layer's (N*KH) K and V
#: scales there; ``SMEM_RESERVE`` is left for the tables and bounds.
SMEM_BYTES = 1 << 20
SMEM_RESERVE = 64 << 10
#: lane width of the (m, l) accumulators: each row's value is replicated
#: across one 128-lane vreg row, so every load and store is lane-dense
LANES = 128
#: keys one page group aims for: one MXU-wide score tile per head
GROUP_KEYS = 128
#: VMEM for the page groups, two slots (double buffering) of K and V,
#: counted at 4 bytes an element (a bf16 or int8 pool takes less)
KV_BUFFER_BYTES = 8 << 20


def check_int8_scales_fit(n_blocks: int, kv_heads: int) -> None:
    """Raise if an int8 pool of ``n_blocks`` has more scales than the
    compiled kernel can hold in scalar memory (``SMEM_BYTES``)."""
    need = 2 * n_blocks * kv_heads * 4
    if need > SMEM_BYTES - SMEM_RESERVE:
        raise ValueError(
            f"kv_dtype='int8' with {n_blocks} blocks x {kv_heads} kv heads: "
            f"the kernel keeps a layer's K and V scales in scalar memory, "
            f"{need} bytes of the {SMEM_BYTES - SMEM_RESERVE} available; "
            f"use at most {(SMEM_BYTES - SMEM_RESERVE) // (8 * kv_heads)} "
            f"blocks or a floating-point kv_dtype")


def pages_per_group(bs: int, lanes: int, nblk: int) -> int:
    """P, the pages one DMA group carries, from static shapes alone:
    enough that ``P * bs`` reaches ``GROUP_KEYS`` keys, no more than the
    table holds, and at most what two slots of K and V take within
    ``KV_BUFFER_BYTES``.  A page is counted as f32 rows (the math is f32
    whatever the pool holds), so the pool's dtype never changes the
    grouping: an int8 pool folds its pages exactly as the dequantized f32
    pool would."""
    page = -(-bs // 8) * 8 * lanes * 4
    return max(1, min(-(-GROUP_KEYS // bs), nblk,
                      KV_BUFFER_BYTES // (4 * page)))


def heads_per_block(kh: int, d: int) -> int:
    """The fewest heads whose lanes fill whole 128-lane tiles (8 heads of
    80 lanes, one head of 128), so that a loop over head blocks reads
    aligned lane windows; all ``kh`` heads where no divisor of ``kh``
    does."""
    return next((n for n in range(1, kh + 1)
                 if kh % n == 0 and n * d % LANES == 0), kh)


def _chunk_kernel_body(layer, tables, live, k_scales, v_scales, q_ref,
                       qpos_ref, k_hbm, v_hbm, out_ref, k_buf, v_buf, sems,
                       m_s, l_s, acc_s, *, bs: int, kh: int, d: int,
                       scale: float):
    """One grid step walks one row's live pages: P pages (one group) per
    DMA, a full group folded in at once, the last partial group page by
    page.  ``k_scales``/``v_scales`` None selects the unquantized load (the
    branch resolves at trace time)."""
    bi = pl.program_id(0)
    n_pages = k_buf.shape[1]
    hb = heads_per_block(kh, d)
    n_live = live[bi]
    n_full = n_live // n_pages
    n_groups = (n_live + n_pages - 1) // n_pages

    def live_pages(g, slot, act):
        """``act`` on the copy of each of group ``g``'s pages below
        ``n_live`` into ``slot`` (K and V; started or awaited).  A table
        slot at or past ``n_live`` is never named, so never fetched."""
        for p in range(n_pages):
            j = g * n_pages + p

            @pl.when(j < n_live)
            def _():
                for kv, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf))):
                    act(pltpu.make_async_copy(
                        hbm.at[layer[0], tables[bi, j]], buf.at[slot, p],
                        sems.at[kv, slot]))

    def fold(slot, first, npg, j0):
        """Fold the fetched pages ``first .. first + npg - 1`` of buffer
        ``slot`` (table slots ``j0 ..``) into the flash accumulators."""
        keys = npg * bs
        qp = qpos_ref[0]                                   # (R, 1) positions
        kvpos = j0 * bs + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        valid = kvpos <= qp                                # (R, keys): causal
        if k_scales is not None:
            # the scale of each page is named through the SAME protected
            # table entry as the page itself
            blks = [tables[bi, j0 + p] for p in range(npg)]
        width = hb * d

        def head_block(i, carry):
            # heads i*hb .. i*hb + hb - 1 fill the 128-lane-aligned window
            # [i*width, (i+1)*width): one load each of q, K and V, and each
            # head a static lane slice of it
            lanes = pl.ds(pl.multiple_of(i * width, LANES), width)
            pages = pl.ds(first, npg)
            qb = q_ref[0, :, lanes].astype(jnp.float32)    # (R, width)
            kb = k_buf[slot, pages, :, lanes].astype(jnp.float32).reshape(
                keys, width)
            vb = v_buf[slot, pages, :, lanes].astype(jnp.float32).reshape(
                keys, width)
            for t in range(hb):
                h = i * hb + t
                q, k, v = (x[:, t * d:(t + 1) * d] for x in (qb, kb, vb))
                if k_scales is not None:
                    # fused dequant: each page times its own (page, kv
                    # head) scalar — int8 -> f32 is exact and the multiply
                    # one f32 rounding, the arithmetic of materializing
                    # the dequantized pool (kernels/quant.dequantize_pool)
                    k, v = (jnp.concatenate(
                        [x[p * bs:(p + 1) * bs] * sc[blk * kh + h]
                         for p, blk in enumerate(blks)])
                        for x, sc in ((k, k_scales), (v, v_scales)))
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                s = jnp.where(valid, s * scale, NEG_INF)   # (R, keys)
                m_prev = m_s[h]                            # (R, LANES)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                p = jnp.where(valid, jnp.exp(s - m_new[:, :1]), 0.0)
                corr = jnp.exp(m_prev - m_new)
                l_s[h] = l_s[h] * corr + jnp.sum(p, axis=1, keepdims=True)
                acc_s[h] = acc_s[h] * corr[:, :1] + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_s[h] = m_new
            return carry

        jax.lax.fori_loop(0, kh // hb, head_block, 0)

    m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
    l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
    acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)
    live_pages(0, 0, lambda c: c.start())

    def group(g, carry):
        slot = g % 2

        # double buffering: the next group's pages stream in while this
        # group's math runs
        @pl.when(g + 1 < n_groups)
        def _prefetch():
            live_pages(g + 1, 1 - slot, lambda c: c.start())

        live_pages(g, slot, lambda c: c.wait())

        @pl.when(g < n_full)
        def _whole():
            fold(slot, 0, n_pages, g * n_pages)

        # the last, partial group: only its fetched pages, one at a time,
        # so a short row (a pad row has one page) pays for what it holds
        @pl.when(g == n_full)
        def _tail():
            def page(t, c):
                fold(slot, t, 1, g * n_pages + t)
                return c
            jax.lax.fori_loop(0, n_live - n_full * n_pages, page, 0)
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)

    # the max(l, eps) guard keeps an all-masked row at a defined 0 output
    for h in range(kh):
        out_ref[0, :, h * d:(h + 1) * d] = (
            acc_s[h] / jnp.maximum(l_s[h][:, :1], 1e-30)
        ).astype(out_ref.dtype)


def _paged_chunk_kernel(layer, tables, live, *refs, **kw):
    _chunk_kernel_body(layer, tables, live, None, None, *refs, **kw)


def _paged_chunk_kernel_q8(layer, tables, live, k_scales, v_scales, *refs,
                           **kw):
    _chunk_kernel_body(layer, tables, live, k_scales, v_scales, *refs, **kw)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_chunk_call(q, k_pool, v_pool, tables, q_positions,
                      num_live_blocks, layer, k_scales, v_scales, *,
                      scale: float, interpret: bool):
    """The pallas_call over lane-packed, layer-stacked pools
    ``(L, N, bs, KH*D)``; see ``paged_attention_chunk`` for the contract."""
    b, c, kh, g, d = q.shape
    _, n, bs, _ = k_pool.shape
    nblk = tables.shape[1]
    r = c * g
    # the walk ends at the last block holding any causally visible
    # position (the default bound), or earlier where the caller bounds it:
    # blocks past the causal range would fold in nothing, so a wider
    # bound gives the same walk, page groups and result
    causal = jnp.max(q_positions, axis=1).astype(jnp.int32) // bs + 1
    if num_live_blocks is not None:
        causal = jnp.minimum(jnp.asarray(num_live_blocks, jnp.int32), causal)
    num_live_blocks = jnp.minimum(causal, nblk)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # lane-packed queries: row (chunk position, group member), lanes (kv
    # head, head dim) — head h of every row is the 2-D tile [:, h*D:(h+1)*D]
    qt = q.transpose(0, 1, 3, 2, 4).reshape(b, r, kh * d)
    qpos = jnp.repeat(q_positions.astype(jnp.int32), g, axis=1)[..., None]

    n_pages = pages_per_group(bs, kh * d, nblk)
    # the *pf tail absorbs the int8 mode's extra scale operands — index
    # maps see every scalar-prefetch ref, however many ride along
    q_index = lambda bi, *pf: (bi, 0, 0)
    kw = dict(bs=bs, kh=kh, d=d, scale=scale)
    if k_scales is None:
        kernel = functools.partial(_paged_chunk_kernel, **kw)
        prefetch = (layer, tables, num_live_blocks)
    else:
        kernel = functools.partial(_paged_chunk_kernel_q8, **kw)
        # only this layer's scales ride in scalar memory, flattened to 1-D
        # (a 2-D scalar-memory array pads its minor dim)
        prefetch = (layer, tables, num_live_blocks,
                    jnp.asarray(k_scales[layer[0]], jnp.float32).reshape(-1),
                    jnp.asarray(v_scales[layer[0]], jnp.float32).reshape(-1))
    page_buf = pltpu.VMEM((2, n_pages, bs, kh * d), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, r, kh * d), q_index),
            pl.BlockSpec((1, r, 1), q_index),
            # the pools stay in HBM: the body copies each live page itself
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, r, kh * d), q_index),
        scratch_shapes=[page_buf, page_buf,                   # K, V groups
                        pltpu.SemaphoreType.DMA((2, 2)),      # (K|V, slot)
                        pltpu.VMEM((kh, r, LANES), jnp.float32),  # m
                        pltpu.VMEM((kh, r, LANES), jnp.float32),  # l
                        pltpu.VMEM((kh, r, d), jnp.float32)],     # acc
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, r, kh * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_chunk",
    )(*prefetch, qt, qpos, k_pool, v_pool)
    return out.reshape(b, c, g, kh, d).transpose(0, 1, 3, 2, 4)


def paged_attention_chunk(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                          tables: jax.Array, q_positions: jax.Array,
                          num_live_blocks: jax.Array | None = None,
                          k_scales: jax.Array | None = None,
                          v_scales: jax.Array | None = None, *,
                          layer: jax.Array | int | None = None,
                          scale: float | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """q (B,C,KH,G,D); pools (N,bs,KH,D) or lane-packed (N,bs,KH*D);
    tables (B,nblk) i32; q_positions (B,C) i32 absolute positions.
    Returns (B,C,KH,G,D).

    Each query row attends to every pool token the table names at an
    absolute position <= its own (prior context + intra-chunk causal).

    ``layer`` given: the pools are the lane-packed, layer-stacked
    ``(L,N,bs,KH*D)`` arrays the serving steps keep (scales ``(L,N,KH)``),
    and the kernel reads layer ``layer`` straight from the stack — no
    per-layer copy.

    ``num_live_blocks`` (B,) i32 bounds each request's walk: table slots
    ``j >= num_live_blocks[b]`` are neither fetched nor computed.  Values
    must be >= 1 and cover every causally visible position (the default —
    derived from the highest query position — is the exact bound; a wider
    bound walks the same pages).
    ``interpret=None`` auto-selects compiled Mosaic on TPU backends and
    the interpreter elsewhere (CPU CI), like ``era_scan``.

    ``k_scales``/``v_scales`` (N, KH) f32 select the int8 pool mode: pools
    hold symmetric per-(block, kv-head) codes and the kernel dequantizes
    each tile in-register after the load (see module docstring).  Both or
    neither must be given.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    if k_pool.dtype == jnp.int8 and k_scales is None:
        raise ValueError("int8 pools need k_scales/v_scales "
                         "(init_pools(kv_dtype='int8') provides them)")
    interpret = resolve_interpret(interpret)
    if k_scales is not None and not interpret:
        check_int8_scales_fit(k_scales.shape[-2], k_scales.shape[-1])
    if layer is None:
        n, bs = k_pool.shape[:2]
        k_pool = k_pool.reshape(1, n, bs, -1)
        v_pool = v_pool.reshape(1, n, bs, -1)
        layer = 0
        if k_scales is not None:
            k_scales, v_scales = k_scales[None], v_scales[None]
    return _paged_chunk_call(q, k_pool, v_pool, tables, q_positions,
                             num_live_blocks, layer, k_scales, v_scales,
                             scale=float(scale), interpret=interpret)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    tables: jax.Array, lengths: jax.Array,
                    num_live_blocks: jax.Array | None = None,
                    k_scales: jax.Array | None = None,
                    v_scales: jax.Array | None = None, *,
                    layer: jax.Array | int | None = None,
                    scale: float | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Single-token decode attention: the C == 1 chunk specialization.

    q (B,KH,G,D); pools (N,bs,KH,D) (or layer-stacked with ``layer``);
    tables (B,nblk) i32; lengths (B,) i32 (context length INCLUDING the
    query token).  Returns (B, KH, G, D).  ``num_live_blocks`` defaults to
    the exact per-request bound ``ceil(lengths / bs)``;
    ``k_scales``/``v_scales`` select the int8 pool mode — see
    ``paged_attention_chunk``.
    """
    # a decode token at position lengths-1 sees kv positions < lengths —
    # exactly the chunk kernel's causal-by-position mask with C == 1
    q_positions = (jnp.asarray(lengths) - 1).astype(jnp.int32)[:, None]
    out = paged_attention_chunk(q[:, None], k_pool, v_pool, tables,
                                q_positions, num_live_blocks, k_scales,
                                v_scales, layer=layer, scale=scale,
                                interpret=interpret)
    return out[:, 0]
