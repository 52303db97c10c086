"""Int8 KV-pool correctness suite (quantized pages + fused dequant).

Layers, bottom-up:

* **quant helpers** (`kernels.quant`): round-trip error within scale/2,
  requantize is the identity when the scale is unchanged, scatter keeps a
  MONOTONE running absmax and re-codes existing rows, drop-sentinel rows
  are no-ops;
* **pool init**: ``init_pools(kv_dtype=...)`` validation, int8 scale-array
  shapes, MLA pools reject int8 up front (fused latent rows have no
  per-(block, kv-head) scale layout);
* **accuracy**: int8 decode attention vs the fp32 oracle under an
  ANALYTIC bound derived from the per-block scales (documented in the
  test — not a tuned tolerance);
* **prefix cache** (satellite): cached-page logits are BITWISE identical
  to self-scattered pages in int8 mode, the cached consumer never writes
  the producer's scale slots, and host-side sharer ops never touch scale
  arrays;
* **engine**: int8 end-to-end with full reclamation, cached == uncached
  token-for-token, and the int8-vs-fp32 greedy token-match rate reported
  (loosely floored, not asserted exact — quantization may legitimately
  flip near-tie argmaxes).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.blocks import BlockPool
from repro.configs import get_smoke_config
from repro.kernels import ref
from repro.kernels.quant import (QMAX, dequantize_pool, quantize_rows,
                                 requantize_blocks, scatter_quantized)
from repro.models import build_model
from repro.serve import ServeEngine
from repro.serve.paged_model import (_DROP_BLOCK, init_mla_pools, init_pools,
                                     paged_mla_decode_step,
                                     paged_prefill_chunk)

BS = 4
SHARED = [1 + j % 13 for j in range(8)]  # block-aligned shared prefix


@pytest.fixture(scope="module")
def dense_model():
    cfg = get_smoke_config("stablelm-3b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


# ==================================================== quant helpers
def test_quant_round_trip_within_half_scale():
    """|dequant(quant(x)) - x| <= scale/2 when scale >= absmax/QMAX."""
    x = jax.random.normal(jax.random.key(0), (6, 4, 2, 32), jnp.float32) * 3
    scales = jnp.max(jnp.abs(x), axis=(1, 3)) / QMAX  # (6, 2)
    q = quantize_rows(x, scales[:, None, :])
    assert q.dtype == jnp.int8
    err = jnp.abs(q.astype(jnp.float32) * scales[:, None, :, None] - x)
    assert float(jnp.max(err - scales[:, None, :, None] / 2)) <= 1e-6


def test_requantize_identity_when_scale_unchanged():
    """old == new scale -> ratio exactly 1.0 -> bitwise-stable codes."""
    codes = jax.random.randint(jax.random.key(1), (5, 4, 2, 16), -127, 128,
                               jnp.int8)
    s = jax.random.uniform(jax.random.key(2), (5, 2), jnp.float32, 0.01, 0.1)
    np.testing.assert_array_equal(np.asarray(requantize_blocks(codes, s, s)),
                                  np.asarray(codes))
    # and a zero (never-written) scale stays all-zero codes, no NaN
    z = requantize_blocks(jnp.zeros((1, 4, 2, 16), jnp.int8),
                          jnp.zeros((1, 2)), jnp.zeros((1, 2)))
    np.testing.assert_array_equal(np.asarray(z), 0)


def test_scatter_monotone_scale_and_requantize():
    """A louder later token GROWS the block scale and re-codes the rows
    already stored; earlier tokens stay within the NEW scale/2 of truth."""
    n, bs, kh, d = 3, 4, 2, 8
    pool = jnp.zeros((n, bs, kh, d), jnp.int8)
    scales = jnp.zeros((n, kh), jnp.float32)
    t0 = jax.random.normal(jax.random.key(3), (1, 1, kh, d), jnp.float32)
    t1 = 4.0 * jax.random.normal(jax.random.key(4), (1, 1, kh, d))
    blk = jnp.zeros((1, 1), jnp.int32)
    pool, scales = scatter_quantized(pool, scales, blk,
                                     jnp.zeros((1, 1), jnp.int32), t0,
                                     _DROP_BLOCK)
    s_after_t0 = np.asarray(scales).copy()
    np.testing.assert_allclose(s_after_t0[0],
                               np.abs(np.asarray(t0[0, 0])).max(-1) / 127.0,
                               rtol=1e-6)
    pool, scales = scatter_quantized(pool, scales, blk,
                                     jnp.ones((1, 1), jnp.int32), t1,
                                     _DROP_BLOCK)
    assert np.all(np.asarray(scales)[0] >= s_after_t0[0] - 1e-9)
    assert np.asarray(scales)[1:].sum() == 0  # untouched blocks stay zero
    # token 0 was re-coded under the grown scale: still within scale/2
    # of truth PLUS the half-code it already lost at the old scale
    deq = np.asarray(dequantize_pool(pool, scales))
    tol = (np.asarray(scales)[0] + s_after_t0[0]) / 2 + 1e-6
    assert np.all(np.abs(deq[0, 0] - np.asarray(t0[0, 0])) <= tol[:, None])
    assert np.all(np.abs(deq[0, 1] - np.asarray(t1[0, 0]))
                  <= np.asarray(scales)[0][:, None] / 2 + 1e-6)


def test_scatter_drop_rows_are_noops():
    """blk == drop sentinel (padded chunk rows) writes nothing anywhere."""
    pool = jax.random.randint(jax.random.key(5), (2, 4, 2, 8), -127, 128,
                              jnp.int8)
    scales = jax.random.uniform(jax.random.key(6), (2, 2), jnp.float32,
                                0.01, 0.1)
    toks = 100.0 * jax.random.normal(jax.random.key(7), (1, 3, 2, 8))
    blk = jnp.full((1, 3), _DROP_BLOCK, jnp.int32)
    off = jnp.array([[0, 1, 2]], jnp.int32)
    p2, s2 = scatter_quantized(pool, scales, blk, off, toks, _DROP_BLOCK)
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(pool))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(scales))


# ======================================================== pool init
def test_init_pools_kv_dtype_validation(dense_model):
    cfg, _, _ = dense_model
    n_layers = cfg.n_groups * len(cfg.block_pattern)
    kh = cfg.n_kv_heads
    pools = init_pools(cfg, n_blocks=6, block_size=BS, kv_dtype="int8")
    assert pools["k"].dtype == jnp.int8 and pools["v"].dtype == jnp.int8
    for s in ("k_scale", "v_scale"):
        assert pools[s].shape == (n_layers, 6, kh)
        assert pools[s].dtype == jnp.float32
    fp16 = init_pools(cfg, n_blocks=6, block_size=BS, kv_dtype="fp16")
    assert fp16["k"].dtype == jnp.float16 and "k_scale" not in fp16
    default = init_pools(cfg, n_blocks=6, block_size=BS)
    assert default["k"].dtype == cfg.dtype and "k_scale" not in default
    with pytest.raises(ValueError, match="kv_dtype"):
        init_pools(cfg, n_blocks=6, block_size=BS, kv_dtype="int4")


def test_engine_rejects_unknown_kv_dtype(dense_model):
    cfg, _, params = dense_model
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeEngine(cfg, params, n_blocks=8, block_size=BS, max_batch=2,
                    kv_dtype="int4")


def test_mla_pools_reject_int8():
    """Latent pages fuse (c_kv || k_rope) rows — no per-(block, kv-head)
    scale layout exists, so int8 MLA fails FAST at both entry points."""
    cfg = get_smoke_config("deepseek-v2-236b")
    with pytest.raises(NotImplementedError, match="latent"):
        init_mla_pools(cfg, n_blocks=4, block_size=BS, kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        init_mla_pools(cfg, n_blocks=4, block_size=BS, kv_dtype="int4")
    # a hand-built int8 latent pool is rejected by the decode step too
    lat = init_mla_pools(cfg, n_blocks=4, block_size=BS)["lat"]
    with pytest.raises(NotImplementedError, match="int8 latent"):
        paged_mla_decode_step(cfg, None, {"lat": lat.astype(jnp.int8)},
                              None, None, jnp.zeros((1,), jnp.int32), None)


# ========================================================== accuracy
def test_int8_attention_error_under_analytic_bound():
    """int8 decode attention vs the fp32 oracle, bounded ANALYTICALLY.

    With per-element dequant errors |eK| <= s_k/2 and |eV| <= s_v/2:
    every score moves by at most d = sm_scale * ||q||_1 * s_k/2, so the
    softmax weights move by at most e^{2d} - 1 in L1 (each weight's
    log-odds shifts by <= 2d), and

        |out_q8 - out_fp| <= s_v/2 + (e^{2d} - 1) * (max|V| + s_v/2).

    The assert uses exactly that bound — no tuned tolerance.
    """
    b, kh, g, d, bs, nblk = 2, 2, 2, 32, 4, 4
    ks = jax.random.split(jax.random.key(8), 3)
    n = b * nblk + 2
    q = jax.random.normal(ks[0], (b, kh, g, d), jnp.float32)
    kp = jax.random.normal(ks[1], (n, bs, kh, d), jnp.float32)
    vp = jax.random.normal(ks[2], (n, bs, kh, d), jnp.float32)
    k_sc = jnp.max(jnp.abs(kp), axis=(1, 3)) / QMAX  # (n, kh)
    v_sc = jnp.max(jnp.abs(vp), axis=(1, 3)) / QMAX
    kq = quantize_rows(kp, k_sc[:, None, :])
    vq = quantize_rows(vp, v_sc[:, None, :])
    tables = jnp.arange(b * nblk, dtype=jnp.int32).reshape(b, nblk)
    lengths = jnp.full((b,), nblk * bs, jnp.int32)
    out_fp = np.asarray(ref.paged_attention_ref(q, kp, vp, tables, lengths))
    out_q8 = np.asarray(ref.paged_attention_int8_ref(
        q, kq, vq, k_sc, v_sc, tables, lengths))
    sm = 1.0 / math.sqrt(d)
    delta = sm * float(jnp.abs(q).sum(-1).max()) * float(k_sc.max()) / 2
    sv = float(v_sc.max())
    bound = sv / 2 + math.expm1(2 * delta) * (float(jnp.abs(vp).max())
                                              + sv / 2)
    err = float(np.abs(out_q8 - out_fp).max())
    assert err <= bound, (err, bound)
    assert err > 0  # quantization really happened (bound isn't vacuous)


@pytest.mark.parametrize("kh,g,d", [(32, 1, 80), (2, 12, 128)])
def test_int8_kernel_walk_matches_int8_oracle(kh, g, d):
    """The fused-dequant kernel's live-page walk at both serving lane
    packings (stablelm-3b's 32 x 80 MHA, starcoder2-3b's 2 x 128 GQA with
    G = 12), on 16-token pages (page groups of 8): ragged rows of two
    groups and a tail, one group and a tail, a tail alone, and one live
    page as a pad row has, against the int8 oracle."""
    from repro.kernels.paged_attention import paged_attention_chunk

    bs, nblk, c = 16, 12, 2
    live = np.array([11, 9, 3, 1], np.int32)
    b, n = len(live), len(live) * nblk
    ks = jax.random.split(jax.random.key(kh + g), 5)
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    kq = jax.random.randint(ks[1], (n, bs, kh, d), -127, 128, jnp.int8)
    vq = jax.random.randint(ks[2], (n, bs, kh, d), -127, 128, jnp.int8)
    k_sc = jax.random.uniform(ks[3], (n, kh), jnp.float32, 0.005, 0.05)
    v_sc = jax.random.uniform(ks[4], (n, kh), jnp.float32, 0.005, 0.05)
    tables = jnp.arange(n, dtype=jnp.int32).reshape(b, nblk)
    qpos = jnp.asarray(np.maximum(
        live[:, None] * bs - 1 - np.arange(c)[::-1], 0), jnp.int32)
    nl = jnp.asarray(live)
    got = paged_attention_chunk(q, kq, vq, tables, qpos, nl, k_sc, v_sc,
                                interpret=True)
    want = ref.paged_attention_chunk_int8_ref(q, kq, vq, k_sc, v_sc, tables,
                                              qpos, nl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ============================================ prefix cache (satellite)
def test_int8_cached_prefill_logits_exact(dense_model):
    """test_cached_prefill_logits_exact, int8 mode: a tail chunk over
    CACHED int8 pages == the same chunk over self-scattered pages,
    BITWISE — same tokens quantize to the same codes under the same
    running absmax, and aliased pages are read through the same scales.
    Also: the cached consumer never writes the producer's scale slots
    (the scatter skip is structural — consumers start past the cached
    boundary)."""
    cfg, model, params = dense_model
    prompt = SHARED + [3, 7, 2, 9, 4]
    hit = len(SHARED)
    nblk = -(-len(prompt) // BS)

    def prefill(pools, tables, tokens, ctx):
        toks = jnp.asarray([tokens], jnp.int32)
        pos = jnp.arange(ctx, ctx + len(tokens), dtype=jnp.int32)[None, :]
        return paged_prefill_chunk(cfg, params, pools, tables, toks, pos)

    n_tail = nblk - hit // BS
    pools = init_pools(cfg, n_blocks=2 * nblk + n_tail, block_size=BS,
                       kv_dtype="int8")
    prod_tbl = jnp.arange(nblk, dtype=jnp.int32)[None, :]
    _, pools = prefill(pools, prod_tbl, prompt[:hit], 0)

    own_tbl = jnp.arange(nblk, 2 * nblk, dtype=jnp.int32)[None, :]
    _, pools = prefill(pools, own_tbl, prompt[:hit], 0)
    lg_own, pools = prefill(pools, own_tbl, prompt[hit:], hit)

    shared_tbl = jnp.concatenate(
        [prod_tbl[0, :hit // BS],
         jnp.arange(2 * nblk, 2 * nblk + n_tail, dtype=jnp.int32)])[None, :]
    prod_scales = np.asarray(pools["k_scale"][:, :hit // BS]).copy()
    lg_cached, pools2 = prefill(pools, shared_tbl, prompt[hit:], hit)

    np.testing.assert_array_equal(np.asarray(lg_cached), np.asarray(lg_own))
    # producer's scale rows are untouched by the cached consumer's chunk
    np.testing.assert_array_equal(
        np.asarray(pools2["k_scale"][:, :hit // BS]), prod_scales)
    # and the re-scattered prefix coded IDENTICALLY in the consumer's own
    # pages: same tokens -> same absmax -> same scales and codes
    np.testing.assert_array_equal(
        np.asarray(pools2["k_scale"][:, nblk:nblk + hit // BS]),
        prod_scales)


def test_sharer_ops_never_touch_scale_slots(dense_model):
    """add_sharer / release_block are HOST block-ID refcount ops: they
    hold no reference to device pools, so scale arrays are bitwise inert
    across a full share/release/reclaim cycle (the design the int8 pools
    rely on — the blocks layer needed zero changes)."""
    cfg, _, _ = dense_model
    pools = init_pools(cfg, n_blocks=8, block_size=BS, kv_dtype="int8")
    toks = jax.random.normal(jax.random.key(9),
                             (1, 2, cfg.n_kv_heads, cfg.resolved_head_dim))
    k_pool, k_sc = scatter_quantized(
        pools["k"][0], pools["k_scale"][0], jnp.array([[0, 1]], jnp.int32),
        jnp.array([[0, 0]], jnp.int32), toks, _DROP_BLOCK)
    snap_pool, snap_sc = np.asarray(k_pool).copy(), np.asarray(k_sc).copy()

    pool = BlockPool(8, era_freq=1, cleanup_freq=10_000)
    tid = pool.register_thread()
    blocks = pool.alloc_blocks(4, tid)
    for blk in blocks:
        pool.add_sharer(blk)
        pool.release_block(blk, tid)
        pool.release_block(blk, tid)  # last sharer -> retire
    pool.cleanup(tid)
    assert pool.free_blocks == 8
    np.testing.assert_array_equal(np.asarray(k_pool), snap_pool)
    np.testing.assert_array_equal(np.asarray(k_sc), snap_sc)


# ============================================================ engine
def _run_engine(cfg, params, prompts, n_new, **kw):
    engine = ServeEngine(cfg, params, n_blocks=48, block_size=BS,
                         max_batch=4, chunk_size=4, era_freq=2,
                         cleanup_freq=2, **kw)
    tid = engine.pool.register_thread()
    reqs = [engine.submit(p, n_new) for p in prompts]
    stats = engine.run(tid)
    assert stats["completed"] == len(prompts)
    assert engine.pool.unreclaimed() == 0
    assert engine.pool.free_blocks == 48
    return [r.generated for r in reqs], stats


def test_engine_int8_end_to_end_token_match_rate(dense_model):
    """int8 engine completes, reclaims fully, and greedy tokens match the
    fp32 engine at a high rate.  The rate is REPORTED, not asserted
    exact: near-tie argmaxes may flip under quantization (that is the
    accuracy trade, bounded upstream); the floor only catches a broken
    dequant path, which would decohere almost every token."""
    cfg, _, params = dense_model
    n_new = 6
    prompts = [[2 + (i * 5 + j) % 11 for j in range(3 + i % 4)]
               for i in range(4)]
    toks_fp, _ = _run_engine(cfg, params, prompts, n_new)
    toks_q8, _ = _run_engine(cfg, params, prompts, n_new, kv_dtype="int8")
    total = n_new * len(prompts)
    match = sum(a == b for fp, q8 in zip(toks_fp, toks_q8)
                for a, b in zip(fp, q8))
    print(f"\nint8 vs fp32 greedy token match: {match}/{total} "
          f"({match / total:.2f})")
    assert match / total >= 0.5, (toks_fp, toks_q8)


def test_engine_int8_cached_equals_uncached(dense_model):
    """Prefix caching in int8 mode: cached == uncached token-for-token
    (aliased pages hold the SAME codes the consumer would have written),
    with real hits and full reclamation."""
    cfg, _, params = dense_model
    prompts = [SHARED + [2 + (i * 5 + j) % 11 for j in range(5)]
               for i in range(4)]
    toks_off, _ = _run_engine(cfg, params, prompts, 4, kv_dtype="int8",
                              prefix_caching=False)
    toks_on, stats = _run_engine(cfg, params, prompts, 4, kv_dtype="int8")
    assert toks_on == toks_off
    assert stats["prefix_hits"] == 3, stats  # all but the first request
    assert stats["prefix_hit_tokens"] == 3 * len(SHARED)
