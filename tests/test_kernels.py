"""Pallas kernel validation: interpret=True vs pure-jnp oracle, shape sweeps.

Also property tests (hypothesis) for era_scan against the scalar WFE
can_delete logic — the kernel must agree with the paper's scan exactly.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional dep: only the property test below needs it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ref
from repro.kernels.era_scan import INF_ERA32, era_scan
from repro.kernels.paged_attention import (heads_per_block, pages_per_group,
                                           paged_attention,
                                           paged_attention_chunk)

jax.config.update("jax_enable_x64", False)


# ================================================================ era_scan
def _scalar_can_delete(alloc, retire, reservations):
    """Paper Fig. 1/4 can_delete, literal scalar transcription."""
    out = []
    for a, r in zip(alloc, retire):
        ok = True
        for row in reservations:
            for era in row:
                if era != INF_ERA32 and a <= era <= r:
                    ok = False
        out.append(ok)
    return np.array(out)


@pytest.mark.parametrize("r", [1, 7, 256, 300, 1000])
@pytest.mark.parametrize("t,h", [(4, 2), (64, 10), (512, 10)])
def test_era_scan_matches_ref_shapes(r, t, h):
    key = jax.random.key(r * 1000 + t + h)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    alloc = jax.random.randint(k1, (r,), 0, 100, jnp.int32)
    retire = alloc + jax.random.randint(k2, (r,), 0, 50, jnp.int32)
    res = jax.random.randint(k3, (t, h), 0, 160, jnp.int32)
    empty = jax.random.bernoulli(k4, 0.5, (t, h))
    res = jnp.where(empty, INF_ERA32, res)

    got = era_scan(alloc, retire, res, interpret=True)
    want = ref.era_scan_ref(alloc, retire, res)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


if not HAVE_HYPOTHESIS:
    @pytest.mark.skip(reason="hypothesis not installed "
                             "(pip install -r requirements-dev.txt)")
    def test_era_scan_property_vs_scalar():
        pass
else:
    @settings(max_examples=50, deadline=None)
    @given(st.data())
    def test_era_scan_property_vs_scalar(data):
        r = data.draw(st.integers(1, 40))
        t = data.draw(st.integers(1, 8))
        h = data.draw(st.integers(1, 6))
        alloc = np.array(data.draw(st.lists(
            st.integers(0, 30), min_size=r, max_size=r)), np.int32)
        retire = alloc + np.array(data.draw(st.lists(
            st.integers(0, 10), min_size=r, max_size=r)), np.int32)
        res = np.array(data.draw(st.lists(
            st.lists(st.one_of(st.integers(0, 40), st.just(INF_ERA32)),
                     min_size=h, max_size=h),
            min_size=t, max_size=t)), np.int32)
        got = np.asarray(era_scan(jnp.asarray(alloc), jnp.asarray(retire),
                                  jnp.asarray(res), interpret=True))
        want = _scalar_can_delete(alloc, retire, res)
        np.testing.assert_array_equal(got, want)


def test_era_scan_never_frees_protected():
    """Safety invariant: any reservation inside [alloc, retire] blocks it."""
    alloc = jnp.array([5, 5, 5], jnp.int32)
    retire = jnp.array([10, 10, 10], jnp.int32)
    res = jnp.array([[7, INF_ERA32]], jnp.int32)  # era 7 within all intervals
    out = era_scan(alloc, retire, res, interpret=True)
    assert not bool(out.any())
    # boundary eras count as protected (paper: alloc <= era <= retire)
    for era in (5, 10):
        res = jnp.array([[era]], jnp.int32)
        assert not bool(era_scan(alloc, retire, res, interpret=True).any())
    # outside the interval -> reclaimable
    for era in (4, 11):
        res = jnp.array([[era]], jnp.int32)
        assert bool(era_scan(alloc, retire, res, interpret=True).all())


# ========================================================== paged_attention
def _contiguous_oracle(q, k, v, lengths, scale):
    """Dense decode attention on the gathered cache (independent oracle)."""
    b, kh, g, d = q.shape
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(k.shape[1])[None, :]
    s = jnp.where((pos < lengths[:, None])[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgs,bskd->bkgd", w, v.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,kh,g,d,bs,nblk", [
    (2, 1, 4, 64, 16, 4),
    (3, 2, 2, 128, 16, 3),
    (1, 4, 1, 128, 32, 2),
    (4, 2, 8, 64, 8, 8),
])
def test_paged_attention_matches_ref(b, kh, g, d, bs, nblk, dtype):
    key = jax.random.key(b * 100 + d)
    ks = jax.random.split(key, 5)
    n = b * nblk + 3  # pool larger than needed
    q = jax.random.normal(ks[0], (b, kh, g, d), dtype)
    k_pool = jax.random.normal(ks[1], (n, bs, kh, d), dtype)
    v_pool = jax.random.normal(ks[2], (n, bs, kh, d), dtype)
    # distinct random tables; padding entries use block 0 (masked anyway)
    perm = jax.random.permutation(ks[3], n)[: b * nblk].reshape(b, nblk)
    tables = perm.astype(jnp.int32)
    lengths = jax.random.randint(ks[4], (b,), 1, nblk * bs + 1, jnp.int32)

    got = paged_attention(q, k_pool, v_pool, tables, lengths, interpret=True)
    want = ref.paged_attention_ref(q, k_pool, v_pool, tables, lengths)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)

    # the jnp ref itself must match a dense gather oracle
    k = k_pool[tables].reshape(b, nblk * bs, kh, d)
    v = v_pool[tables].reshape(b, nblk * bs, kh, d)
    dense = _contiguous_oracle(q, k, v, lengths, 1.0 / math.sqrt(d))
    np.testing.assert_allclose(
        np.asarray(want, np.float32), np.asarray(dense, np.float32),
        rtol=tol, atol=tol)


def test_paged_attention_table_permutation_invariance():
    """Attention output must not depend on *which* pool slots blocks occupy."""
    b, kh, g, d, bs, nblk = 2, 2, 2, 64, 8, 4
    key = jax.random.key(0)
    ks = jax.random.split(key, 4)
    n = 16
    q = jax.random.normal(ks[0], (b, kh, g, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n, bs, kh, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n, bs, kh, d), jnp.float32)
    tables = jnp.arange(b * nblk, dtype=jnp.int32).reshape(b, nblk)
    lengths = jnp.full((b,), nblk * bs, jnp.int32)
    out1 = paged_attention(q, k_pool, v_pool, tables, lengths, interpret=True)

    # move every block to a different pool slot, rewrite tables accordingly
    perm = jax.random.permutation(ks[3], n)
    inv = jnp.argsort(perm)
    k2, v2 = k_pool[inv], v_pool[inv]
    tables2 = perm[tables].astype(jnp.int32)
    out2 = paged_attention(q, k2, v2, tables2, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)


# ===================================================== paged chunk attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,c,kh,g,d,bs,nblk", [
    (2, 4, 1, 4, 64, 16, 4),    # prefill chunk mid-prompt
    (1, 8, 2, 2, 128, 8, 3),    # chunk crossing block boundaries
    (3, 1, 2, 8, 64, 8, 4),     # C == 1 (the decode specialization)
    (2, 5, 4, 1, 128, 32, 2),   # ragged C vs bs
])
def test_paged_chunk_attention_matches_ref(b, c, kh, g, d, bs, nblk, dtype):
    key = jax.random.key(b * 1000 + c * 10 + d)
    ks = jax.random.split(key, 5)
    n = b * nblk + 3
    q = jax.random.normal(ks[0], (b, c, kh, g, d), dtype)
    k_pool = jax.random.normal(ks[1], (n, bs, kh, d), dtype)
    v_pool = jax.random.normal(ks[2], (n, bs, kh, d), dtype)
    perm = jax.random.permutation(ks[3], n)[: b * nblk].reshape(b, nblk)
    tables = perm.astype(jnp.int32)
    # chunk starts at a random context; queries at consecutive positions
    ctx = jax.random.randint(ks[4], (b, 1), 0, nblk * bs - c + 1, jnp.int32)
    qpos = ctx + jnp.arange(c, dtype=jnp.int32)[None, :]

    got = paged_attention_chunk(q, k_pool, v_pool, tables, qpos,
                                interpret=True)
    want = ref.paged_attention_chunk_ref(q, k_pool, v_pool, tables, qpos)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_paged_chunk_attention_is_causal():
    """Row i of a chunk must ignore pool tokens at positions > qpos[i]:
    mutating those slots cannot change row i's output."""
    b, c, kh, g, d, bs, nblk = 1, 4, 2, 2, 64, 8, 2
    ks = jax.random.split(jax.random.key(3), 3)
    n = nblk
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n, bs, kh, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n, bs, kh, d), jnp.float32)
    tables = jnp.arange(nblk, dtype=jnp.int32)[None, :]
    ctx = 5
    qpos = (ctx + jnp.arange(c, dtype=jnp.int32))[None, :]
    out1 = ref.paged_attention_chunk_ref(q, k_pool, v_pool, tables, qpos)
    # scribble over every pool position AFTER the last query's
    flat_pos = jnp.arange(nblk * bs)
    future = (flat_pos > ctx + c - 1).reshape(nblk, bs)
    k2 = jnp.where(future[..., None, None], 1e3, k_pool)
    v2 = jnp.where(future[..., None, None], -1e3, v_pool)
    out2 = ref.paged_attention_chunk_ref(q, k2, v2, tables, qpos)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=0, atol=0)
    out3 = paged_attention_chunk(q, k2, v2, tables, qpos, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out3),
                               rtol=1e-5, atol=1e-5)
    # and the decode wrapper equals the chunk's last row
    dec = paged_attention(q[:, -1], k_pool, v_pool, tables,
                          jnp.asarray([ctx + c], jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(out1[:, -1]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_chunk_attention_reads_the_named_layer(quantized):
    """Layer-stacked, lane-packed pools (L, N, bs, KH*D) — the serving
    layout: with ``layer=l`` the kernel must read exactly layer l (the
    scalar-prefetched layer index names the pages it copies) and match
    the oracle on that layer's pool."""
    n_layers, b, c, kh, g, d, bs, nblk = 3, 2, 3, 2, 2, 64, 4, 4
    ks = jax.random.split(jax.random.key(41), 6)
    n = b * nblk + 1
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    shape = (n_layers, n, bs, kh * d)
    if quantized:
        k = jax.random.randint(ks[1], shape, -127, 128, jnp.int8)
        v = jax.random.randint(ks[2], shape, -127, 128, jnp.int8)
        ksc = jax.random.uniform(ks[4], (n_layers, n, kh), jnp.float32,
                                 0.005, 0.05)
        vsc = jax.random.uniform(ks[5], (n_layers, n, kh), jnp.float32,
                                 0.005, 0.05)
    else:
        k = jax.random.normal(ks[1], shape, jnp.float32)
        v = jax.random.normal(ks[2], shape, jnp.float32)
        ksc = vsc = None
    tables = jax.random.permutation(ks[3], n)[: b * nblk].reshape(
        b, nblk).astype(jnp.int32)
    qpos = (nblk * bs - c + jnp.arange(c, dtype=jnp.int32))[None, :].repeat(
        b, axis=0)
    for layer in range(n_layers):
        got = paged_attention_chunk(q, k, v, tables, qpos, None, ksc, vsc,
                                    layer=layer, interpret=True)
        unstacked = (k[layer].reshape(n, bs, kh, d),
                     v[layer].reshape(n, bs, kh, d))
        if quantized:
            want = ref.paged_attention_chunk_int8_ref(
                q, *unstacked, ksc[layer], vsc[layer], tables, qpos)
        else:
            want = ref.paged_attention_chunk_ref(q, *unstacked, tables, qpos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"{layer=}")


# ================================================ length-bounded walks
@pytest.mark.parametrize("b,c,kh,g,d,bs,nblk", [
    (3, 4, 2, 2, 64, 8, 5),     # ragged contexts mid-prompt
    (2, 1, 1, 4, 64, 16, 4),    # C == 1 (decode-as-chunk)
    (1, 8, 2, 1, 128, 4, 7),    # chunk wider than a block
    (3, 2, 2, 1, 64, 32, 11),   # page groups of 4: full groups + a tail
    (2, 1, 32, 1, 80, 16, 11),  # stablelm-3b's MHA packing, groups of 8
    (2, 3, 2, 12, 128, 16, 11),  # starcoder2-3b's GQA packing (G = 12)
])
def test_bounded_grid_bitwise_equals_unbounded(b, c, kh, g, d, bs, nblk):
    """Pages past the causal range would fold in nothing (every position
    masked: p = 0, corr = exp(0) = 1), and the walk ends at the causal
    bound where the caller's is wider, so bounding must be BITWISE
    equivalent — kernel vs kernel, oracle vs oracle — whenever the bound
    covers the causal range, whole page groups and partial ones alike.
    """
    ks = jax.random.split(jax.random.key(b * 77 + c + nblk), 5)
    n = b * nblk + 2
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n, bs, kh, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n, bs, kh, d), jnp.float32)
    perm = jax.random.permutation(ks[3], n)[: b * nblk].reshape(b, nblk)
    tables = perm.astype(jnp.int32)
    # ragged per-request contexts: every row gets a different live depth
    ctx = jax.random.randint(ks[4], (b, 1), 0, nblk * bs - c + 1, jnp.int32)
    qpos = ctx + jnp.arange(c, dtype=jnp.int32)[None, :]
    exact = jnp.max(qpos, axis=1) // bs + 1  # the derived exact bound
    full = jnp.full((b,), nblk, jnp.int32)   # degenerate: walk everything

    bounded = paged_attention_chunk(q, k_pool, v_pool, tables, qpos,
                                    exact, interpret=True)
    unbounded = paged_attention_chunk(q, k_pool, v_pool, tables, qpos,
                                      full, interpret=True)
    np.testing.assert_array_equal(np.asarray(bounded),
                                  np.asarray(unbounded))
    # the default (num_live_blocks=None) IS the exact bound
    derived = paged_attention_chunk(q, k_pool, v_pool, tables, qpos,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(bounded), np.asarray(derived))
    # same bitwise claim for the jnp oracle...
    r_bounded = ref.paged_attention_chunk_ref(q, k_pool, v_pool, tables,
                                              qpos, exact)
    r_unbounded = ref.paged_attention_chunk_ref(q, k_pool, v_pool, tables,
                                                qpos)
    np.testing.assert_array_equal(np.asarray(r_bounded),
                                  np.asarray(r_unbounded))
    # ...and the kernel still matches the oracle numerically
    np.testing.assert_allclose(np.asarray(bounded), np.asarray(r_bounded),
                               rtol=2e-5, atol=2e-5)


def test_num_live_blocks_spans_one_to_nblk():
    """Sweep the bound through every depth 1..nblk (incl. the all-padded
    tail where only one block of a wide table is live): the kernel must
    agree with the oracle under the SAME bound, even when the bound cuts
    below the causal range (extra slots = garbage the request must never
    read — the walk copies only slots below the bound)."""
    b, c, kh, g, d, bs, nblk = 2, 3, 2, 2, 64, 4, 6
    ks = jax.random.split(jax.random.key(11), 4)
    n = b * nblk + 1
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n, bs, kh, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n, bs, kh, d), jnp.float32)
    perm = jax.random.permutation(ks[3], n)[: b * nblk].reshape(b, nblk)
    tables = perm.astype(jnp.int32)
    # queries see the WHOLE table causally; only num_live bounds the walk
    qpos = (nblk * bs - c + jnp.arange(c, dtype=jnp.int32))[None, :].repeat(
        b, axis=0)
    for live in range(1, nblk + 1):
        nl = jnp.full((b,), live, jnp.int32)
        got = paged_attention_chunk(q, k_pool, v_pool, tables, qpos, nl,
                                    interpret=True)
        want = ref.paged_attention_chunk_ref(q, k_pool, v_pool, tables,
                                             qpos, nl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"{live=}")


def test_bounded_walk_never_reads_dead_slots():
    """Scribbling NaN over every pool block a request's bound excludes
    must not change its output — the dead slots are truly never read
    (the safety argument: the walk copies only table slots below the
    bound)."""
    b, c, kh, g, d, bs, nblk = 1, 2, 2, 2, 64, 4, 5
    ks = jax.random.split(jax.random.key(29), 3)
    n = nblk + 2
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n, bs, kh, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n, bs, kh, d), jnp.float32)
    tables = jnp.arange(nblk, dtype=jnp.int32)[None, :]
    live = 2
    qpos = (live * bs - c + jnp.arange(c, dtype=jnp.int32))[None, :]
    nl = jnp.full((b,), live, jnp.int32)
    out1 = paged_attention_chunk(q, k_pool, v_pool, tables, qpos, nl,
                                 interpret=True)
    dead = jnp.arange(n)[:, None, None, None] >= live  # blocks 2.. poisoned
    k2 = jnp.where(dead, jnp.nan, k_pool)
    v2 = jnp.where(dead, jnp.nan, v_pool)
    out2 = paged_attention_chunk(q, k2, v2, tables, qpos, nl,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert np.isfinite(np.asarray(out2)).all()


def test_decode_wrapper_bounded_matches_chunk():
    """The C == 1 decode specialization derives ceil(lengths/bs) and must
    equal the explicit decode-as-chunk call under the same bound."""
    b, kh, g, d, bs, nblk = 3, 2, 2, 64, 4, 4
    ks = jax.random.split(jax.random.key(5), 5)
    n = b * nblk
    q = jax.random.normal(ks[0], (b, kh, g, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n, bs, kh, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n, bs, kh, d), jnp.float32)
    perm = jax.random.permutation(ks[3], n)[: b * nblk].reshape(b, nblk)
    tables = perm.astype(jnp.int32)
    lengths = jax.random.randint(ks[4], (b,), 1, nblk * bs + 1, jnp.int32)
    live = (lengths - 1) // bs + 1
    dec = paged_attention(q, k_pool, v_pool, tables, lengths, live,
                          interpret=True)
    chunk = paged_attention_chunk(q[:, None], k_pool, v_pool, tables,
                                  (lengths - 1)[:, None], live,
                                  interpret=True)[:, 0]
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(chunk))
    want = ref.paged_attention_ref(q, k_pool, v_pool, tables, lengths, live)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ==================================================== live-page walks
#: lane packings (kh, g, d, bs): small heads on 32-token pages (groups of 4
#: pages), stablelm-3b's MHA (32 x 80 lanes) and starcoder2-3b's GQA (2 x
#: 128 lanes, G = 12) on 16-token pages (groups of 8)
PACKINGS = {"small": (2, 1, 64, 32), "mha-32x80": (32, 1, 80, 16),
            "gqa-2x128-g12": (2, 12, 128, 16)}
#: live pages of the real rows: two groups and a tail, one group and a
#: tail, a tail alone; every serving-shaped case adds a pad row
LIVE = (11, 6, 3)
NBLK = 12


@pytest.mark.parametrize("bs,lanes,nblk,p", [
    (16, 2560, 256, 8),   # stablelm-3b: 128 keys, whatever the pool dtype
    (16, 256, 5, 5),      # never more than the table holds
    (4, 64, 64, 32),
    (16, 16384, 256, 2),  # two slots of K and V within the VMEM budget
    (16, 65536, 256, 1),  # at least one page, whatever its size
])
def test_pages_per_group(bs, lanes, nblk, p):
    assert pages_per_group(bs, lanes, nblk) == p


@pytest.mark.parametrize("kh,d,hb", [(32, 80, 8), (2, 128, 1), (2, 64, 2),
                                     (4, 48, 4), (3, 64, 3)])
def test_heads_per_block(kh, d, hb):
    assert heads_per_block(kh, d) == hb


def _serving_case(key, c, kh, g, d, bs, quantized=False):
    """Rows of ``LIVE`` live pages (ragged against the page group) whose
    last query sits inside their last page, then a pad row as the engine
    builds it: every slot names the scratch block, one query at position
    0, ``num_live_blocks`` 1.  Every slot of a real row names its own
    pool block, live or not."""
    b = len(LIVE) + 1
    n = len(LIVE) * NBLK + 1
    scratch = n - 1
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    if quantized:
        kp, vp, ksc, vsc = _int8_pools(ks[1], n, bs, kh, d)
    else:
        kp = jax.random.normal(ks[1], (n, bs, kh, d), jnp.float32)
        vp = jax.random.normal(ks[2], (n, bs, kh, d), jnp.float32)
        ksc = vsc = None
    tables = np.full((b, NBLK), scratch, np.int32)
    tables[:-1] = np.asarray(jax.random.permutation(ks[3], n - 1)).reshape(
        len(LIVE), NBLK)
    live = np.array(LIVE + (1,), np.int32)
    last = live * bs - 1 - np.arange(b) % 3
    last[-1] = 0
    qpos = np.maximum(last[:, None] - c + 1 + np.arange(c), 0)
    qpos[-1] = 0
    return (q, kp, vp, ksc, vsc, jnp.asarray(tables),
            jnp.asarray(qpos, jnp.int32), jnp.asarray(live))


@pytest.mark.parametrize("c", [1, 3])
@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_live_page_walk_matches_ref(packing, c):
    """Ragged rows whose live pages are not a multiple of the page group
    (full groups, then a partial one page by page) and a pad row, at each
    lane packing: the kernel matches the oracle, and the pad row attends
    to its one visible key, row 0 of the scratch page."""
    kh, g, d, bs = PACKINGS[packing]
    p = pages_per_group(bs, kh * d, NBLK)
    assert any(n > p and n % p for n in LIVE) and p > 1
    q, kp, vp, _, _, tables, qpos, live = _serving_case(
        jax.random.key(kh * d + c), c, kh, g, d, bs)
    got = paged_attention_chunk(q, kp, vp, tables, qpos, live,
                                interpret=True)
    want = ref.paged_attention_chunk_ref(q, kp, vp, tables, qpos, live)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    scratch = int(tables[-1, 0])
    np.testing.assert_allclose(
        np.asarray(got[-1]),
        np.broadcast_to(np.asarray(vp[scratch, 0])[None, :, None, :],
                        got.shape[1:]), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_live_page_walk_never_reads_past_the_bound(packing, quantized):
    """NaN over every pool block a row's table names at or past its live
    bound — inside the last, partial page group and beyond it — and, in
    the int8 mode, NaN over those blocks' scales and extreme codes in
    their pages: the output is bitwise unchanged and finite.  No page the
    bound excludes is fetched, and no buffer row it would have filled is
    folded in."""
    kh, g, d, bs = PACKINGS[packing]
    q, kp, vp, ksc, vsc, tables, qpos, live = _serving_case(
        jax.random.key(7 * kh + d), 2, kh, g, d, bs, quantized)
    out = paged_attention_chunk(q, kp, vp, tables, qpos, live, ksc, vsc,
                                interpret=True)
    t = np.asarray(tables)
    dead = np.ones(kp.shape[0], bool)
    for row, n in zip(t, np.asarray(live)):
        dead[row[:n]] = False
    assert dead.sum() == sum(NBLK - n for n in LIVE)
    page = jnp.asarray(dead)[:, None, None, None]
    if quantized:
        kp, vp = (jnp.where(page, jnp.int8(127), x) for x in (kp, vp))
        ksc, vsc = (jnp.where(jnp.asarray(dead)[:, None], jnp.nan, x)
                    for x in (ksc, vsc))
    else:
        kp, vp = (jnp.where(page, jnp.nan, x) for x in (kp, vp))
    poisoned = paged_attention_chunk(q, kp, vp, tables, qpos, live, ksc,
                                     vsc, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(poisoned))
    assert np.isfinite(np.asarray(poisoned)).all()


# ==================================================== int8 quantized pools
def _int8_pools(key, n, bs, kh, d):
    """Random int8 code pools + per-(block, kv-head) scales."""
    ks = jax.random.split(key, 4)
    kq = jax.random.randint(ks[0], (n, bs, kh, d), -127, 128, jnp.int8)
    vq = jax.random.randint(ks[1], (n, bs, kh, d), -127, 128, jnp.int8)
    ksc = jax.random.uniform(ks[2], (n, kh), jnp.float32, 0.005, 0.05)
    vsc = jax.random.uniform(ks[3], (n, kh), jnp.float32, 0.005, 0.05)
    return kq, vq, ksc, vsc


@pytest.mark.parametrize("b,c,kh,g,d,bs,nblk", [
    (3, 4, 2, 2, 64, 8, 5),     # ragged contexts mid-prompt
    (2, 1, 1, 4, 64, 16, 4),    # C == 1 (decode-as-chunk)
    (1, 8, 2, 1, 128, 4, 7),    # chunk wider than a block
    (3, 2, 2, 1, 64, 32, 11),   # page groups of 4: full groups + a tail
])
def test_int8_kernel_bitwise_matches_materialized_dequant(b, c, kh, g, d,
                                                          bs, nblk):
    """The fused in-register dequant's anchor claim: int8 codes through
    the quantized kernel must be BITWISE identical to materializing the
    dequantized fp32 pools and running the unquantized kernel (int8 ->
    f32 is exact; the scalar multiply is the same single f32 rounding in
    both paths) — across the small-packing cases of the bounded-walk
    tests.  (At 32 x 80 lanes the interpreter's f32 dots may sum an int8
    and an f32 pool in different orders, with every scale 1.0 too;
    test_kv_int8 checks that packing against the int8 oracle.)  Against
    the int8 ORACLE (plain softmax vs flash walk) the standard numeric
    tolerance applies."""
    from repro.kernels.quant import dequantize_pool

    ks = jax.random.split(jax.random.key(b * 31 + c + nblk), 3)
    n = b * nblk + 2
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    kq, vq, ksc, vsc = _int8_pools(ks[1], n, bs, kh, d)
    perm = jax.random.permutation(ks[2], n)[: b * nblk].reshape(b, nblk)
    tables = perm.astype(jnp.int32)
    ctx = jax.random.randint(ks[0], (b, 1), 0, nblk * bs - c + 1, jnp.int32)
    qpos = ctx + jnp.arange(c, dtype=jnp.int32)[None, :]
    live = jnp.max(qpos, axis=1) // bs + 1

    fused = paged_attention_chunk(q, kq, vq, tables, qpos, live, ksc, vsc,
                                  interpret=True)
    mat = paged_attention_chunk(q, dequantize_pool(kq, ksc),
                                dequantize_pool(vq, vsc), tables, qpos,
                                live, interpret=True)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(mat))
    want = ref.paged_attention_chunk_int8_ref(q, kq, vq, ksc, vsc, tables,
                                              qpos, live)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_int8_num_live_blocks_spans_one_to_nblk():
    """The int8 twin of the fp num_live sweep: kernel vs int8 oracle under
    every bound depth 1..nblk, including bounds cutting below the causal
    range (dead slots hold garbage codes AND garbage scales)."""
    b, c, kh, g, d, bs, nblk = 2, 3, 2, 2, 64, 4, 6
    ks = jax.random.split(jax.random.key(13), 3)
    n = b * nblk + 1
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    kq, vq, ksc, vsc = _int8_pools(ks[1], n, bs, kh, d)
    perm = jax.random.permutation(ks[2], n)[: b * nblk].reshape(b, nblk)
    tables = perm.astype(jnp.int32)
    qpos = (nblk * bs - c + jnp.arange(c, dtype=jnp.int32))[None, :].repeat(
        b, axis=0)
    for live in range(1, nblk + 1):
        nl = jnp.full((b,), live, jnp.int32)
        got = paged_attention_chunk(q, kq, vq, tables, qpos, nl, ksc, vsc,
                                    interpret=True)
        want = ref.paged_attention_chunk_int8_ref(q, kq, vq, ksc, vsc,
                                                  tables, qpos, nl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"{live=}")


def test_int8_dead_slot_scales_never_read():
    """NaN-poisoning the scales of every block beyond a request's bound
    must not change the output: the kernel reads a page's scale through
    the SAME table entry as the page it copies, so a dead slot's scale is
    as unreachable as its bytes (the int8 extension of the safety
    argument — and the exact property that makes a freed block's stale
    scale harmless)."""
    b, c, kh, g, d, bs, nblk = 1, 2, 2, 2, 64, 4, 5
    ks = jax.random.split(jax.random.key(31), 2)
    n = nblk + 2
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    kq, vq, ksc, vsc = _int8_pools(ks[1], n, bs, kh, d)
    tables = jnp.arange(nblk, dtype=jnp.int32)[None, :]
    live = 2
    qpos = (live * bs - c + jnp.arange(c, dtype=jnp.int32))[None, :]
    nl = jnp.full((b,), live, jnp.int32)
    out1 = paged_attention_chunk(q, kq, vq, tables, qpos, nl, ksc, vsc,
                                 interpret=True)
    dead = jnp.arange(n)[:, None] >= live  # blocks 2.. poisoned
    ksc2 = jnp.where(dead, jnp.nan, ksc)
    vsc2 = jnp.where(dead, jnp.nan, vsc)
    out2 = paged_attention_chunk(q, kq, vq, tables, qpos, nl, ksc2, vsc2,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert np.isfinite(np.asarray(out2)).all()


def test_int8_pool_requires_scales():
    """An int8 pool without scale operands must fail loudly at the kernel
    boundary, and giving only one of the two scales is rejected too."""
    b, c, kh, g, d, bs, nblk = 1, 1, 1, 1, 64, 4, 2
    ks = jax.random.split(jax.random.key(7), 2)
    q = jax.random.normal(ks[0], (b, c, kh, g, d), jnp.float32)
    kq, vq, ksc, _ = _int8_pools(ks[1], nblk, bs, kh, d)
    tables = jnp.arange(nblk, dtype=jnp.int32)[None, :]
    qpos = jnp.zeros((b, c), jnp.int32)
    with pytest.raises(ValueError, match="int8 pools need"):
        paged_attention_chunk(q, kq, vq, tables, qpos, interpret=True)
    with pytest.raises(ValueError, match="given together"):
        paged_attention_chunk(q, kq, vq, tables, qpos, None, ksc, None,
                              interpret=True)


# ========================================================== flash_attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,kh,d,cq,ck", [
    (2, 256, 4, 4, 64, 128, 128),   # MHA
    (1, 256, 4, 2, 64, 64, 128),    # GQA g=2
    (2, 128, 8, 1, 128, 128, 64),   # MQA
])
def test_flash_attention_kernel_matches_ref(b, t, h, kh, d, cq, ck, dtype):
    from repro.kernels.flash_attention import (flash_attention_ref,
                                               flash_attention_tpu)

    ks = jax.random.split(jax.random.key(t + h), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), dtype)
    k = jax.random.normal(ks[1], (b, t, kh, d), dtype)
    v = jax.random.normal(ks[2], (b, t, kh, d), dtype)
    got = flash_attention_tpu(q, k, v, causal=True, cq=cq, ck=ck,
                              interpret=True)
    want = flash_attention_ref(q, k, v, causal=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_kernel_noncausal():
    from repro.kernels.flash_attention import (flash_attention_ref,
                                               flash_attention_tpu)

    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (2, 128, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 64), jnp.float32)
    got = flash_attention_tpu(q, k, v, causal=False, cq=64, ck=64,
                              interpret=True)
    want = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
