"""Compile-only checks of the served path for a described TPU v5e chip.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
that is only described, and refuses what the chip would refuse — a block
that breaks the (8, 128) tiling rule, more scalar or vector memory than a
kernel may use, a step that does not fit the device's memory.  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and each test worker imports every test
file.  The persistent compilation cache is off around these compiles — a
compile for a described chip is written to it but cannot be read back.
The platform selectors see the CPU backend here, so the tests steer them
to the TPU branch (``kernels.platform.on_tpu``).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import platform
from repro.kernels.era_scan import _era_scan_call
from repro.kernels.paged_attention import _paged_chunk_call
from repro.launch.roofline import PEAKS
from repro.models import build_model
from repro.serve.engine import (_jit_decode, _jit_prefill, pool_block_bytes,
                                pool_blocks_that_fit)
from repro.serve.paged_model import init_pools

#: the memory a v5e chip gives one program (its compiler's limit)
V5E_BYTES_LIMIT = int(15.75 * 2**30)
#: the smoke's shapes (chip_smoke.py): 16-token blocks, max_batch 8,
#: 128-token chunks, requests up to 1,024 + 64 tokens -> 128-slot tables
BS, MAX_BATCH, CHUNK, WIDTH = 16, 8, 128, 128
#: chip_smoke's step shapes: (rows, query tokens per row)
SHAPES = {"decode": (MAX_BATCH, 1), "prefill": (1, CHUNK),
          "mixed": (MAX_BATCH + 1, CHUNK)}
#: the paged kernel's cases, (arch, rows, query tokens per row, table
#: width): chip_smoke's shapes at stablelm-3b widths (MHA, KH=32, D=80),
#: and the starcoder2-3b codegen cell's at its GQA packing (KH=2, D=128,
#: 12 query rows per KV head): 32 rows, requests up to 1,536 + 2,560
#: tokens -> 256-slot tables
KERNEL_CASES = {kind: ("stablelm-3b", b, c, WIDTH)
                for kind, (b, c) in SHAPES.items()}
KERNEL_CASES.update({
    "codegen-decode": ("starcoder2-3b", 32, 1, 256),
    "codegen-prefill": ("starcoder2-3b", 1, CHUNK, 256),
    "codegen-mixed": ("starcoder2-3b", 33, CHUNK, 256)})


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@functools.cache
def _served(arch):
    """An architecture at its published widths with bf16 weights, and the
    pool a v5e holds beside them: weights resident, 10% headroom (step
    temporaries are below a megabyte — see the memory test)."""
    cfg = get_config(arch)
    cfg = cfg.scaled(param_dtype=cfg.dtype)
    params = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    return cfg, pool_blocks_that_fit(V5E_BYTES_LIMIT, weights, 1 << 20,
                                     pool_block_bytes(cfg, BS))


@pytest.fixture(scope="module")
def published():
    return _served("stablelm-3b")[0]


@pytest.fixture(scope="module")
def smoke_pool_blocks():
    """The pool the smoke sizes on a v5e."""
    return _served("stablelm-3b")[1]


@pytest.fixture
def for_tpu(monkeypatch):
    """Steer the platform selectors to TPU and keep the persistent
    compilation cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kind", sorted(KERNEL_CASES))
def test_paged_kernel_compiles_for_v5e(kind, kv_dtype, one_chip, for_tpu):
    """The paged kernel (bs=16) reading the layer-stacked pool a v5e holds
    beside the weights of each case's architecture: compiled by Mosaic,
    and without any copy of the pool (the pool's default layout is the
    one the kernel reads)."""
    arch, b, c, width = KERNEL_CASES[kind]
    cfg, n_blocks = _served(arch)
    kh, d = cfg.n_kv_heads, cfg.resolved_head_dim
    pools = _on(one_chip, jax.eval_shape(lambda: init_pools(
        cfg, n_blocks + 1, BS, kv_dtype)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((b, c, kh, cfg.n_heads // kh, d), jnp.bfloat16,
                             sharding=one_chip)
    scales = (pools["k_scale"], pools["v_scale"]) if kv_dtype == "int8" \
        else (None, None)
    fn = jax.jit(lambda q, k, v, t, p, n, layer, *sc: _paged_chunk_call(
        q, k, v, t, p, n, layer, *(sc or (None, None)), scale=d ** -0.5,
        interpret=False))
    args = (q, pools["k"], pools["v"], i32(b, width), i32(b, c), i32(b),
            i32()) + tuple(s for s in scales if s is not None)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    pool_bytes = pools["k"].size * pools["k"].dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 100


def test_era_scan_compiles_for_v5e(one_chip, for_tpu):
    """The reclamation scan at a serving-size retire list (4,096 blocks x
    1,024 reservation slots)."""
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda a, t, lo, hi: _era_scan_call(
        a, t, lo, hi, interpret=False)).lower(
        i32(4096, 1), i32(4096, 1), i32(1, 1024), i32(1, 1024)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_full_width_step_fits_one_v5e(kind, one_chip, published,
                                      smoke_pool_blocks, for_tpu):
    """A whole stablelm-3b step at its published widths, bf16 weights and
    the smoke's device-sized pool: it compiles with the kernel in it, the
    donated pools alias input to output (temporaries do not grow with the
    pool), and everything fits in a v5e's 16 GB."""
    assert smoke_pool_blocks * BS >= 8192
    params = _on(one_chip, jax.eval_shape(build_model(published).init,
                                          jax.random.key(0)))
    pools = _on(one_chip, jax.eval_shape(
        lambda: init_pools(published, smoke_pool_blocks + 1, BS)))
    b, c = SHAPES[kind]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    if kind == "decode":
        step, args = _jit_decode(published, True), (
            i32(b, WIDTH), i32(b), i32(b), i32(b))
    else:
        step, args = _jit_prefill(published, True), (
            i32(b, WIDTH), i32(b, c), i32(b, c), i32(b))
    compiled = step.lower(params, pools, *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= PEAKS["TPU v5 lite"]["hbm_bytes"], total
