"""CPU tests of the starcoder2-3b cell: its files resolve to a configuration
the program runs as stated, and at starcoder2's grouping (12 query heads
on each 128-lane KV head, GELU-tanh, rope theta about 1e6) the engine's
chunked prefill followed by paged decode reproduces the float32
reference's logits, through the jnp attention and through the paged
kernel (in the Pallas interpreter)."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness, reference, weights  # noqa: E402

CELL = "starcoder2-3b.codegen-long"


def test_the_codegen_cell_resolves_to_a_configuration_the_program_runs():
    from repro.configs import get_config

    cell = harness.load_cell(CELL)
    m = cell.config["model"]
    arch = harness.arch_config(cell.config)
    assert (arch.n_heads, arch.n_kv_heads, arch.resolved_head_dim) == \
        (24, 2, 128)
    assert arch.mlp_kind == "gelu" and cell.config["reduced"] == []
    # the published theta, in the file and in the program's own entry
    assert m["rope_theta"] == cell.config["published"]["rope_theta"]
    assert get_config("starcoder2-3b").rope_theta == m["rope_theta"]
    assert "rope_theta" not in cell.config["departures"]
    limit = cell.config["limits"]["max_logit_gap"]
    assert isinstance(limit, float) and limit > 0
    assert cell.traffic["loop"] == "closed"
    assert cell.config["serve"]["max_batch"] == 32
    # itl_p50_ms is left out: the cell's runs split it into two modes
    assert {e["name"] for e in cell.end_to_end} == {"tpot_ms", "setup_s"}
    moved = {e["name"] for e in cell.end_to_end}
    assert cell.per_layer
    for e in cell.per_layer:
        assert e["name"].endswith(".codegen") and e["moves"] in moved
        assert callable(harness.metric_reader(e["name"]))


def _grouped_config():
    """Two layers at starcoder2's head grouping and head width, float32."""
    return {"name": "starcoder2-grouping", "repo_arch": "starcoder2-3b",
            "model": {"n_layers": 2, "d_model": 128, "n_heads": 12,
                      "n_kv_heads": 1, "head_dim": 128, "d_ff": 256,
                      "vocab_size": 256, "mlp": "gelu_tanh",
                      "norm": "layernorm", "norm_eps": 1e-6,
                      "rope_theta": 999999.4420358813, "rotary_dim": 128,
                      "dtype": "float32"}}


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "paged_kernel"])
def test_grouped_prefill_then_decode_matches_the_reference(use_kernel):
    import jax
    import jax.numpy as jnp

    from repro.serve.paged_model import (init_pools, paged_decode_step,
                                         paged_prefill_chunk)

    cfg = _grouped_config()
    m = cfg["model"]
    arch = harness.arch_config(cfg)
    params = weights.make_params(m, 17)
    # two chunks, the second ragged, then decode across a page boundary
    bs, chunk, n_prompt, n_decode = 16, 16, 27, 8
    rng = np.random.default_rng(1)
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
                                            toks, pos, use_kernel=use_kernel)
    got.append(np.asarray(logits[0]))
    for t in range(n_prompt, n_prompt + n_decode - 1):
        logits, pools = paged_decode_step(
            arch, params, pools, tables, jnp.asarray([t + 1], jnp.int32),
            jnp.asarray([seq[t]], jnp.int32), jnp.asarray([t], jnp.int32),
            use_kernel=use_kernel)
        got.append(np.asarray(logits[0]))
    at = np.arange(n_prompt - 1, n_prompt + n_decode - 1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference._logits_at(
            m, "f32", params, jnp.asarray(seq, jnp.int32), at))
    got = np.stack(got)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) <= 1e-4 * scale
    # theta matters at these positions: at 1e5 the reference reads
    # otherwise, so the check above would catch a theta left behind
    with jax.default_matmul_precision("highest"):
        other = np.asarray(reference._logits_at(
            dict(m, rope_theta=100000.0), "f32", params,
            jnp.asarray(seq, jnp.int32), at))
    assert np.max(np.abs(other - want)) > 1e-3 * scale
