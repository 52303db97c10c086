"""Decode/prefill steps that read and write the PAGED KV pool.

This is the device side of the WFE adaptation: the host scheduler names
blocks via tables; the device step scatters each new token's K/V into the
block the table names and attends through the tables (the Pallas kernel
on TPU, the jnp reference elsewhere — ``kernels/platform.py``).

Supported stacks: dense GQA full-attention archs (every layer ``"attn"``,
no MLA).  Windowed layers (``"swa"``/``"local_attn"``) are rejected by
``_check_paged_support``: the kernel has no window yet.  Recurrent archs
keep O(1) state and need no paging; MLA pages 576-wide latents through
``paged_mla_decode_step`` below.

Pools are layer-stacked and lane-packed, ``(L, N, bs, KH*D)`` (see
``kernels/paged_attention``).  Both steps scan over the layers carrying
the pools: each layer scatters into ``pools[l, blk, off]`` of the carried
(donated) arrays, so the update is in place and a step's temporaries do
not grow with the pool.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.kernels import paged_chunk_attention, paged_decode_attention
from repro.kernels.quant import scatter_quantized
from repro.models import transformer
from repro.models.attention import _qkv
from repro.models.layers import (apply_mlp, apply_norm, embed_tokens, matmul,
                                 unembed)
from repro.models import moe as moe_mod

Params = Dict[str, Any]


#: ``kv_dtype=`` strings -> pool storage dtype (None = follow cfg.dtype)
KV_DTYPES = {"fp32": jnp.float32, "fp16": jnp.float16,
             "bf16": jnp.bfloat16, "int8": jnp.int8}


def pool_shape(cfg, n_blocks: int, block_size: int) -> tuple:
    """``(L, N, bs, KH*D)``: one token's K (or V) for every kv head packed
    along the lanes of one pool row."""
    return (cfg.n_groups * len(cfg.block_pattern), n_blocks, block_size,
            cfg.n_kv_heads * cfg.resolved_head_dim)


def live_blocks(last_positions, block_size: int):
    """Each row's live table slots, ``num_live_blocks``: through the block
    holding the row's last query position (NumPy or JAX arrays alike)."""
    return last_positions // block_size + 1


def init_pools(cfg, n_blocks: int, block_size: int, kv_dtype=None):
    """One K and one V pool, layer-stacked: ``pool_shape`` above.

    ``kv_dtype`` overrides the pool storage dtype (``KV_DTYPES`` keys;
    None follows ``cfg.dtype``).  ``"int8"`` stores symmetric
    per-(block, kv-head) codes and additionally allocates ``k_scale`` /
    ``v_scale`` arrays shaped (L, N, KH) f32 — see ``kernels.quant``.

    The scale slots are POOL-SLOT-INDEXED: scale row ``[l, n]`` belongs to
    pool block ``n`` forever, exactly like the page bytes at ``pool[l, n]``.
    Allocation, retirement, sharing, and era-reclamation all operate on
    block IDS and never dereference pool storage, so the blocks layer
    (BlockPool / PrefixCache / era tables) needs ZERO changes for int8
    mode: a scale is only ever read through a request's protected table
    snapshot — the same snapshot that names the page it scales — so the
    WFE era-safety argument covers scales for free.  Reallocation of a
    reclaimed block needs no reset either: a prior tenant's stale CODES
    are causally dead (a new tenant's queries only see offsets its own
    scatters wrote), and its stale SCALE can only make the running absmax
    start higher — codes and dequant always use the same per-slot scale,
    so a recycled slot is merely quantized a notch coarser (bounded by
    the largest absmax the slot ever held), never incorrectly.
    """
    if kv_dtype is not None and kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype={kv_dtype!r}: expected one of "
                         f"{sorted(KV_DTYPES)} or None")
    dtype = cfg.dtype if kv_dtype is None else KV_DTYPES[kv_dtype]
    shape = pool_shape(cfg, n_blocks, block_size)
    pools = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if dtype == jnp.int8:
        sshape = shape[:2] + (cfg.n_kv_heads,)
        pools["k_scale"] = jnp.zeros(sshape, jnp.float32)
        pools["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return pools


def _check_paged_support(cfg):
    # full-attention GQA only: windowed archs would need window masking in
    # the paged kernel (ROADMAP R2), and MLA pages 576-wide latents
    # instead of K/V (paged_mla_decode_step)
    assert not cfg.use_mla and not cfg.is_encoder_decoder, cfg.name
    assert all(k == "attn" for k in cfg.block_pattern), cfg.block_pattern


#: out-of-bounds scatter sentinel: with ``mode="drop"`` a block id this
#: large drops the update entirely (padded chunk rows write nothing; note
#: NEGATIVE ids would wrap, so the sentinel must be a large positive)
_DROP_BLOCK = jnp.int32(2**30)


def _scatter_kv(pools, layer, blk, off, k1, v1):
    """Write a step's K/V rows (B, C, KH, D) into layer ``layer`` at
    ``[blk, off]`` of the carried pools (``blk == _DROP_BLOCK`` rows write
    nothing).  int8 pools quantize under the block's running absmax
    (``kernels.quant.scatter_quantized``)."""
    pools = dict(pools)
    if "k_scale" in pools:
        pools["k"], pools["k_scale"] = scatter_quantized(
            pools["k"], pools["k_scale"], blk, off, k1, _DROP_BLOCK,
            layer=layer)
        pools["v"], pools["v_scale"] = scatter_quantized(
            pools["v"], pools["v_scale"], blk, off, v1, _DROP_BLOCK,
            layer=layer)
        return pools
    b, c = blk.shape
    for name, rows in (("k", k1), ("v", v1)):
        pools[name] = pools[name].at[layer, blk, off].set(
            rows.reshape(b, c, -1).astype(pools[name].dtype), mode="drop")
    return pools


def _run_layers(cfg, params, pools, x, positions, blk, off, attend):
    """The layer stack over the paged pools, as one scan over layer groups.

    x (B, C, d); positions/blk/off (B, C).  ``attend(q, pools, layer)``
    maps the grouped queries (B, C, KH, G, D) to attention outputs of the
    same shape, reading the pools AFTER this layer's scatter (so a chunk
    sees its own earlier tokens).  Returns (x, pools).
    """
    b, c = positions.shape
    kh, hd, h = cfg.n_kv_heads, cfg.resolved_head_dim, cfg.n_heads
    n_pat = len(cfg.block_pattern)

    def group(carry, xs):
        x, pools = carry
        gp, g_i = xs
        for j, kind in enumerate(cfg.block_pattern):
            bp = gp[f"b{j}_{kind}"]
            layer = g_i * n_pat + j
            hn = apply_norm(cfg, bp["norm_mix"], x)
            q, k1, v1 = _qkv(cfg, bp["mix"], hn, positions)
            pools = _scatter_kv(pools, layer, blk, off, k1, v1)
            out = attend(q.reshape(b, c, kh, h // kh, hd), pools, layer)
            out = out.reshape(b, c, h * hd).astype(x.dtype)
            x = x + matmul(out, bp["mix"]["wo"])
            if transformer._has_mlp(cfg):
                hn = apply_norm(cfg, bp["norm_mlp"], x)
                ff = moe_mod.apply_moe(cfg, bp["mlp"], hn) if cfg.is_moe \
                    else apply_mlp(cfg, bp["mlp"], hn)
                x = x + ff
        return (x, pools), None

    (x, pools), _ = jax.lax.scan(
        group, (x, pools), (params["groups"], jnp.arange(cfg.n_groups)))
    return x, pools


def paged_decode_step(cfg, params, pools, tables, lengths, tokens, positions,
                      *, use_kernel: bool | None = None):
    """One token for a batch of requests against the paged pool.

    tables (B, nblk) i32; lengths (B,) i32 (INCLUDING the new token);
    tokens (B,) i32; positions (B,) i32 (= lengths - 1).
    Returns (logits (B, V) f32, updated pools).  ``use_kernel=None``
    selects attention by platform (``kernels/platform.py``).

    int8 pools (``init_pools(kv_dtype="int8")`` — ``k_scale``/``v_scale``
    present): the scatter quantizes the new token under the block's
    running absmax (``kernels.quant.scatter_quantized``) and attention
    dequantizes through the scales.
    """
    _check_paged_support(cfg)
    b = tokens.shape[0]
    bs = pools["k"].shape[2]
    hd = cfg.resolved_head_dim
    x = embed_tokens(cfg, params["embed"], tokens[:, None])
    # the pool block and in-block offset receiving this token's K/V
    blk_of_tok = tables[jnp.arange(b), positions // bs]  # (B,)
    off = positions % bs
    # per-request LIVE table slots: the decode token's own block is the
    # last one holding context, so slots beyond it are dead — the kernel
    # fetches and folds in only the live pages
    num_live = live_blocks(positions, bs).astype(jnp.int32)  # (B,)

    def attend(q, pools, layer):
        return paged_decode_attention(
            q[:, 0], pools["k"], pools["v"], tables, lengths, num_live,
            pools.get("k_scale"), pools.get("v_scale"), layer=layer,
            scale=1.0 / math.sqrt(hd), use_kernel=use_kernel)[:, None]

    x, pools = _run_layers(cfg, params, pools, x, positions[:, None],
                           blk_of_tok[:, None], off[:, None], attend)
    x = apply_norm(cfg, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = unembed(cfg, head, x)[:, 0]
    return logits, pools


def paged_prefill_chunk(cfg, params, pools, tables, tokens, positions,
                        chunk_lens=None, *, use_kernel: bool | None = None):
    """Run a C-token prompt CHUNK against already-materialized pages.

    The chunked-prefill device step: the chunk's K/V rows scatter into the
    pool blocks the table names, then every chunk query attends over the
    table's prior context PLUS the chunk's own earlier tokens — one paged
    causal-by-position attention covers both (the scatter runs first, so
    the pool holds every kv position <= the last query's).  No whole-prompt
    or ``S % block_size == 0`` restriction: any ragged tail of any prompt
    can be a chunk.

    Prefix-cache hits never reach this function: the scheduler starts the
    chunk at the cached boundary (positions >= the shared prefix), so a
    cached page is read through the table like any prior-context page but
    its K/V are NEVER re-scattered — the scatter skip is structural, not
    masked.

    tables (B, nblk) i32; tokens/positions (B, C) i32 (positions are
    absolute: ``ctx + i`` for a chunk starting at context length ctx);
    chunk_lens (B,) i32 — valid tokens per row (None = all C; padded rows
    scatter nothing and their outputs are never read).
    Returns (logits of each row's LAST VALID token (B, V), updated pools).
    """
    _check_paged_support(cfg)
    b, c = tokens.shape
    bs = pools["k"].shape[2]
    nblk = tables.shape[1]
    hd = cfg.resolved_head_dim
    if chunk_lens is None:
        chunk_lens = jnp.full((b,), c, jnp.int32)
    valid = jnp.arange(c)[None, :] < chunk_lens[:, None]  # (B, C)
    # destination block/offset per chunk token; padded rows drop their write
    col = jnp.minimum(positions // bs, nblk - 1)
    blk = jnp.where(valid, tables[jnp.arange(b)[:, None], col], _DROP_BLOCK)
    off = positions % bs
    # per-request LIVE table slots: the chunk's last valid token sits in
    # the deepest block any of its queries can see, so the bounded kernel
    # walks exactly that many slots (padded rows clamp to the row's last
    # valid position, so they derive the same bound)
    last_pos = positions[jnp.arange(b), jnp.maximum(chunk_lens - 1, 0)]
    num_live = live_blocks(last_pos, bs).astype(jnp.int32)  # (B,)
    x = embed_tokens(cfg, params["embed"], tokens)

    def attend(q, pools, layer):
        return paged_chunk_attention(
            q, pools["k"], pools["v"], tables, positions, num_live,
            pools.get("k_scale"), pools.get("v_scale"), layer=layer,
            scale=1.0 / math.sqrt(hd), use_kernel=use_kernel)

    x, pools = _run_layers(cfg, params, pools, x, positions, blk, off,
                           attend)
    # unembed ONLY each row's last valid token — the chunk that consumes
    # the final prompt token yields the first generated token from it
    last = x[jnp.arange(b), chunk_lens - 1][:, None]  # (B, 1, d)
    last = apply_norm(cfg, params["final_norm"], last)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = unembed(cfg, head, last)[:, 0]
    return logits, pools


# ===================================================================== MLA
def init_mla_pools(cfg, n_blocks: int, block_size: int, kv_dtype=None):
    """Paged MLA latent pool: pages store (c_kv ‖ k_rope) rows — 576 B/token
    for deepseek-v2 instead of 2·KH·D; the same WFE block lifecycle applies.

    ``kv_dtype="int8"`` is rejected up front: a latent page row is the
    FUSED ``(c_kv ‖ k_rope)`` vector, not per-head K/V, so the dense-GQA
    per-(block, kv-head) symmetric scale layout doesn't apply — the
    low-rank ``c_kv`` half and the rope'd ``k_rope`` half have different
    dynamic ranges and would need a split (per-half or per-column) scale
    scheme plus a latent-space dequant in ``paged_mla_decode_step``.
    Failing here beats the silent fp allocation that used to surface only
    as a dtype error deep inside the jitted step.
    """
    if kv_dtype == "int8":
        raise NotImplementedError(
            "kv_dtype='int8' is not supported for paged MLA: latent pages "
            "store fused (c_kv ‖ k_rope) rows whose two halves need "
            "separate scale ranges — the per-(block, kv-head) scheme of "
            "the dense pools does not map onto the latent cache")
    if kv_dtype is not None and kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype={kv_dtype!r}: expected one of "
                         f"{sorted(KV_DTYPES)} or None")
    dtype = cfg.dtype if kv_dtype is None else KV_DTYPES[kv_dtype]
    width = cfg.kv_lora_rank + cfg.rope_head_dim
    shape = (cfg.n_groups * len(cfg.block_pattern), n_blocks, block_size,
             width)
    return {"lat": jnp.zeros(shape, dtype)}


def paged_mla_decode_step(cfg, params, pools, tables, lengths, tokens,
                          positions):
    """One decode token through the paged LATENT pool (absorbed-form MLA).

    Mirrors paged_decode_step for cfg.use_mla archs: the new token's latent
    row scatters into the table's tail block; attention runs in the latent
    space against the gathered pages (jnp ref; the Pallas paged kernel
    generalizes by treating the latent width as head_dim with KH=1).
    """
    import math as _math

    from repro.models.attention import _mla_qkv
    from repro.models.layers import apply_norm as _norm

    assert cfg.use_mla
    if pools["lat"].dtype == jnp.int8:
        raise NotImplementedError(
            "paged_mla_decode_step has no int8 latent path — see "
            "init_mla_pools (fused (c_kv ‖ k_rope) rows need a split "
            "scale scheme)")
    b = tokens.shape[0]
    bs = pools["lat"].shape[2]
    h = cfg.n_heads
    r, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    dn, dvh = cfg.nope_head_dim, cfg.v_head_dim
    x = embed_tokens(cfg, params["embed"], tokens[:, None])
    blk_of_tok = tables[jnp.arange(b), positions // bs]
    off = positions % bs
    n_pat = len(cfg.block_pattern)

    def layer_param(l):
        g_i, j = divmod(l, n_pat)
        kind = cfg.block_pattern[j]
        return jax.tree.map(lambda a: a[g_i],
                            params["groups"][f"b{j}_{kind}"])

    n_layers = cfg.n_groups * n_pat
    new_lat = []
    nblk = tables.shape[1]
    for l in range(n_layers):
        bp = layer_param(l)
        hn = apply_norm(cfg, bp["norm_mix"], x)
        q_nope, q_rope, c_kv1, k_rope1 = _mla_qkv(
            cfg, bp["mix"], hn, positions[:, None])
        row = jnp.concatenate([c_kv1[:, 0], k_rope1[:, 0, 0]], -1)  # (B, r+dr)
        lat = pools["lat"][l].at[blk_of_tok, off].set(row)
        pages = lat[tables].reshape(b, nblk * bs, r + dr)  # (B, S, r+dr)
        c_kv, k_rope = pages[..., :r], pages[..., r:]
        wk_b = bp["mix"]["wk_b"].astype(x.dtype).reshape(r, h, dn)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, wk_b,
                           preferred_element_type=jnp.float32).astype(x.dtype)
        s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, c_kv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope,
                          preferred_element_type=jnp.float32)
             ) / _math.sqrt(dn + dr)
        valid = jnp.arange(nblk * bs)[None, :] < lengths[:, None]
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", w.astype(c_kv.dtype), c_kv,
                           preferred_element_type=jnp.float32).astype(x.dtype)
        wv_b = bp["mix"]["wv_b"].astype(x.dtype).reshape(r, h, dvh)
        o = jnp.einsum("bqhr,rhd->bqhd", o_lat, wv_b,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        x = x + matmul(o.reshape(b, 1, h * dvh), bp["mix"]["wo"])
        if transformer._has_mlp(cfg):
            hn = apply_norm(cfg, bp["norm_mlp"], x)
            ff = moe_mod.apply_moe(cfg, bp["mlp"], hn) if cfg.is_moe \
                else apply_mlp(cfg, bp["mlp"], hn)
            x = x + ff
        new_lat.append(lat)
    pools = {"lat": jnp.stack(new_lat)}
    x = apply_norm(cfg, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = unembed(cfg, head, x)[:, 0]
    return logits, pools
