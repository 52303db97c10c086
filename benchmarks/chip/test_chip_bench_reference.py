"""CPU tests of the float32 reference and the benchmark's weights at the
smoke size: the weight tree has the layout the engine takes, and the
engine's chunked prefill followed by paged decode reproduces the
reference's logits."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import reference, testing, weights  # noqa: E402
from chipbench.harness import arch_config  # noqa: E402


def _f32(gqa):
    cfg = testing.smoke_config(gqa)
    cfg["model"] = dict(cfg["model"], dtype="float32")
    return cfg


@pytest.mark.parametrize("gqa", [False, True], ids=["mha", "gqa"])
def test_weight_tree_matches_the_programs_layout(gqa):
    import jax

    from repro.models import build_model

    cfg = _f32(gqa)
    arch = arch_config(cfg)
    want = jax.eval_shape(build_model(arch).init, jax.random.key(0))
    got = weights.make_params(cfg["model"], 3)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [a.shape for a in jax.tree.leaves(got)] == \
        [a.shape for a in jax.tree.leaves(want)]


def test_weights_repeat_for_a_seed_and_take_seeds_past_32_bits():
    import jax

    m = testing.smoke_model()
    a = weights.make_params(m, 2**33 + 5)
    b = weights.make_params(m, 2**33 + 5)
    c = weights.make_params(m, 2**33 + 6)
    la, lb, lc = (jax.tree.leaves(t)[3] for t in (a, b, c))
    assert la.dtype == np.dtype("bfloat16")
    assert np.array_equal(np.asarray(la), np.asarray(lb))
    assert not np.array_equal(np.asarray(la), np.asarray(lc))


@pytest.mark.parametrize("gqa", [False, True], ids=["mha", "gqa"])
def test_engine_prefill_then_decode_matches_the_reference(gqa):
    import jax
    import jax.numpy as jnp

    from repro.serve.paged_model import (init_pools, paged_decode_step,
                                         paged_prefill_chunk)

    cfg = _f32(gqa)
    m = cfg["model"]
    arch = arch_config(cfg)
    params = weights.make_params(m, 11)
    bs, chunk, n_prompt, n_decode = 4, 8, 19, 6
    rng = np.random.default_rng(0)
    seq = rng.integers(0, m["vocab_size"], n_prompt + n_decode)
    n_blk = -(-(n_prompt + n_decode) // bs)
    tables = jnp.asarray(np.arange(1, n_blk + 1, dtype=np.int32)[None])
    pools = init_pools(arch, n_blk + 2, bs)
    got = []
    for c0 in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - c0)
        toks = jnp.asarray(seq[None, c0:c0 + n], jnp.int32)
        pos = jnp.arange(c0, c0 + n, dtype=jnp.int32)[None]
        logits, pools = paged_prefill_chunk(arch, params, pools, tables,
                                            toks, pos, use_kernel=False)
    got.append(np.asarray(logits[0]))
    for t in range(n_prompt, n_prompt + n_decode - 1):
        logits, pools = paged_decode_step(
            arch, params, pools, tables, jnp.asarray([t + 1], jnp.int32),
            jnp.asarray([seq[t]], jnp.int32), jnp.asarray([t], jnp.int32),
            use_kernel=False)
        got.append(np.asarray(logits[0]))
    at = np.arange(n_prompt - 1, n_prompt + n_decode - 1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference._logits_at(
            m, "f32", params, jnp.asarray(seq, jnp.int32), at))
    got = np.stack(got)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) <= 1e-4 * scale


def test_served_gaps_are_zero_for_the_references_own_choices():
    import jax.numpy as jnp

    m = dict(testing.smoke_model(), dtype="float32")
    params = weights.make_params(m, 5)
    prompt = list(range(3, 20))
    seq = list(prompt)
    for _ in range(5):  # greedy decode with the reference itself
        logits = reference._logits_at(m, "f32", params,
                                      jnp.asarray(seq, jnp.int32),
                                      jnp.asarray([len(seq) - 1]))
        seq.append(int(np.argmax(np.asarray(logits)[0])))
    gen = seq[len(prompt):]
    gap, low = reference.served_gaps(m, params, prompt, gen, control=True)
    assert gap.shape == (5,) and np.max(gap) < 1e-5
    assert low.shape == (5,) and np.all(low >= 0)
    wrong = [(t + 1) % m["vocab_size"] for t in gen]
    gap_wrong, _ = reference.served_gaps(m, params, prompt, wrong)
    assert np.min(gap_wrong) > 0
