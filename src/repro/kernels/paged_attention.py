"""Pallas TPU kernel: attention through WFE-managed block tables.

This is the consumer of the era-reclaimed block pool: query tokens attend
over K/V scattered across pool blocks named by the request's block table.
The GPU version of this idea (vLLM PagedAttention) walks the table with
per-warp gathers; the TPU adaptation uses ``PrefetchScalarGridSpec`` so
the *block table itself drives the BlockSpec index_map* — the pipeline
prefetches exactly the pool blocks the table names, and the kernel body
never sees a gather.

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

* grid = (B, nblk); one grid step streams one pool block for ALL kv heads
  (a ``(bs, KH*D)`` tile of K and of V) and walks the heads in the body,
  each a static lane slice ``[h*D, (h+1)*D)``.  The block-index dimension
  is sequential, carrying a flash-style (m, l, acc) accumulator per
  (head, query row) in VMEM scratch;
* queries arrive lane-packed the same way, ``(B, C*G, KH*D)``: head
  ``h``'s rows are a 2-D ``(C*G, D)`` tile, so scores and the value
  update are two 2-D matmuls per head;
* K/V index_map ``(layer, tables[b, j], 0, 0)`` — scalar-prefetched
  table entries select the HBM tile to stream, so only live blocks are
  ever read;
* masking is causal by ABSOLUTE position: a chunk query at position p sees
  every pool token at positions <= p — the table's prior context AND the
  chunk's own earlier tokens (which the caller scattered into the pool
  before attention), so one mask covers history + intra-chunk causality;
* the grid is LENGTH-BOUNDED per request: a scalar-prefetched
  ``num_live_blocks`` vector rides next to the tables, the K/V index_maps
  clamp dead slots ``j >= num_live_blocks[b]`` to the request's last live
  block (a repeated block index, so the pipeline elides the HBM copy),
  and the kernel body skips the score/accumulate math for them — padded
  table slots cost neither DMA nor FLOPs, only their grid steps (the
  grid stays ``(rows, W)``).  Finalization happens on the
  last grid step regardless, reading the accumulator state a short row
  stopped updating at its own boundary;
* QUANTIZED pools (``kv_dtype="int8"``): the layer's per-(block, kv-head)
  scales ride along as one more scalar-prefetch operand (flattened to
  ``(N*KH,)`` in scalar memory, indexed through the block table exactly
  like ``num_live_blocks``), and the kernel dequantizes each K/V tile
  in-register right after the VMEM load
  (``k.astype(f32) * k_scales[tables[b, j] * KH + h]``) — K/V stream from
  HBM at 1 byte/element and the flash accumulator math below is
  UNCHANGED, so the fused path is bitwise-identical to materializing the
  dequantized fp32 pools and running the unquantized kernel.
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


def _chunk_kernel_body(layer, tables, live, k_scales, v_scales, q_ref,
                       qpos_ref, k_ref, v_ref, out_ref, m_s, l_s, acc_s, *,
                       bs: int, kh: int, d: int, scale: float):
    """Shared flash-walk body; ``k_scales``/``v_scales`` None selects the
    unquantized load (the branch resolves at trace time)."""
    del layer  # consumed by the index_maps only
    bi = pl.program_id(0)
    j = pl.program_id(1)
    nblk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    # dead iterations (j beyond this request's live blocks) update nothing:
    # their K/V tile was never fetched (the index_map clamps to the last
    # live block, and a repeated index elides the copy), and with every
    # position causally masked the flash update would be an exact no-op
    # (p = 0, corr = exp(0) = 1) — skipping it is bitwise equivalent
    @pl.when(j < live[bi])
    def _update():
        qp = qpos_ref[0]                                   # (R, 1) positions
        kvpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        valid = kvpos <= qp                                # (R, bs): causal
        blk = tables[bi, j]
        kt = k_ref[0, 0].astype(jnp.float32)               # (bs, KH*D)
        vt = v_ref[0, 0].astype(jnp.float32)
        for h in range(kh):  # static: head h owns lanes [h*D, (h+1)*D)
            lanes = slice(h * d, (h + 1) * d)
            q = q_ref[0, :, lanes].astype(jnp.float32)     # (R, D)
            k, v = kt[:, lanes], vt[:, lanes]              # (bs, D)
            if k_scales is not None:
                # fused dequant: one scalar per (pool block, kv head), named
                # through the SAME protected table snapshot as the page it
                # scales — int8 -> f32 is exact, the scalar multiply is one
                # f32 rounding, so this equals materializing the dequantized
                # pool bitwise (see kernels/quant.dequantize_pool)
                k = k * k_scales[blk * kh + h]
                v = v * v_scales[blk * kh + h]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, NEG_INF)       # (R, bs)
            m_prev = m_s[h]                                # (R, LANES)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new[:, :1]), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_s[h] = l_s[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_s[h] = acc_s[h] * corr[:, :1] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_s[h] = m_new

    # finalize on the LAST grid step (which may be dead for this request):
    # the accumulators hold their values from iteration live[bi]-1, and the
    # max(l, eps) guard keeps an all-masked row at a defined 0 output
    @pl.when(j == nblk - 1)
    def _finalize():
        for h in range(kh):
            out_ref[0, :, h * d:(h + 1) * d] = (
                acc_s[h] / jnp.maximum(l_s[h][:, :1], 1e-30)
            ).astype(out_ref.dtype)


def _paged_chunk_kernel(layer, tables, live, q_ref, qpos_ref, k_ref, v_ref,
                        out_ref, m_s, l_s, acc_s, **kw):
    _chunk_kernel_body(layer, tables, live, None, None, q_ref, qpos_ref,
                       k_ref, v_ref, out_ref, m_s, l_s, acc_s, **kw)


def _paged_chunk_kernel_q8(layer, tables, live, k_scales, v_scales, q_ref,
                           qpos_ref, k_ref, v_ref, out_ref, m_s, l_s, acc_s,
                           **kw):
    _chunk_kernel_body(layer, tables, live, k_scales, v_scales, q_ref,
                       qpos_ref, k_ref, v_ref, out_ref, m_s, l_s, acc_s, **kw)


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
    if num_live_blocks is None:
        # exact bound: the last block holding any causally visible position
        num_live_blocks = jnp.max(q_positions, axis=1) // bs + 1
    num_live_blocks = jnp.minimum(
        jnp.asarray(num_live_blocks, jnp.int32), nblk)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # lane-packed queries: row (chunk position, group member), lanes (kv
    # head, head dim) — head h of every row is the 2-D tile [:, h*D:(h+1)*D]
    qt = q.transpose(0, 1, 3, 2, 4).reshape(b, r, kh * d)
    qpos = jnp.repeat(q_positions.astype(jnp.int32), g, axis=1)[..., None]

    # dead-slot clamp: j >= live[b] repeats the LAST live block's index, so
    # the pipeline sees an unchanged (non-decreasing run of equal) index
    # and skips the HBM->VMEM copy for every dead iteration.  The *pf tail
    # absorbs the int8 mode's extra scale operands — index maps see every
    # scalar-prefetch ref, however many ride along.
    kv_index = lambda bi, j, lay, tbl, live, *pf: (
        lay[0], tbl[bi, jnp.minimum(j, jnp.maximum(live[bi] - 1, 0))], 0, 0)
    q_index = lambda bi, j, *pf: (bi, 0, 0)
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
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, r, kh * d), q_index),
            pl.BlockSpec((1, r, 1), q_index),
            pl.BlockSpec((1, 1, bs, kh * d), kv_index),
            pl.BlockSpec((1, 1, bs, kh * d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, r, kh * d), q_index),
        scratch_shapes=[pltpu.VMEM((kh, r, LANES), jnp.float32),  # m
                        pltpu.VMEM((kh, r, LANES), jnp.float32),  # l
                        pltpu.VMEM((kh, r, d), jnp.float32)],     # acc
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, r, kh * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
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

    ``num_live_blocks`` (B,) i32 bounds the per-request grid: table slots
    ``j >= num_live_blocks[b]`` are neither fetched nor computed.  Values
    must be >= 1 and cover every causally visible position (the default —
    derived from the highest query position — is the exact bound).
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
