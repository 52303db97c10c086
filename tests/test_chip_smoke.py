"""``chip_smoke.py`` on the CPU: its phases, its platform guard, and the
platform and compile-cache selection it relies on.

The phases run on the smoke config with the Pallas kernels interpreted, so
tier-1 covers the script's control flow and every check it makes; only
the chip itself is missing (the ``tpu_custom_call`` check and the device
memory numbers need it).
"""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.kernels import platform
from repro.kernels.paged_attention import (check_int8_scales_fit,
                                           paged_attention_chunk)
from repro.launch import compile_cache
from repro.models import build_model
from repro.serve import ServeEngine

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_on_smoke_config(chip_smoke):
    """Build, kernel-vs-reference, serve (all three plan kinds), drain and
    the era_scan cleanup — every check of the chip run, at toy size."""
    lines = []
    out = chip_smoke.run(published=False, n_requests=4,
                         prompt_range=(20, 60), new_tokens=6, max_batch=4,
                         chunk_size=16, block_size=4, n_blocks=96,
                         use_kernel=True, log=lines.append)
    assert out["tokens"] == 4 * 6
    assert all(n > 0 for n in out["kinds"].values()), out["kinds"]
    assert out["max_dlogit"] <= 1e-4
    assert out["max_dattn"] <= 1e-5
    assert sum("attention kernel vs reference" in line
               for line in lines) == 4, lines
    assert any("after drain: unreclaimed 0, free blocks 96 of 96" in line
               for line in lines), lines


def test_chip_smoke_checks_fail_loudly(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="leaked"):
        chip_smoke.check(False, "pool blocks leaked")


def test_chip_smoke_refuses_a_cpu_backend(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.setup_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.setup_compile_cache() == path  # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("stablelm-3b")
    return cfg, build_model(cfg).init(jax.random.key(0))


def test_attention_is_chosen_by_platform(smoke_model, monkeypatch):
    cfg, params = smoke_model
    assert ServeEngine(cfg, params, n_blocks=8).use_kernel is False
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert ServeEngine(cfg, params, n_blocks=8).use_kernel is True
    assert platform.resolve_interpret(None) is False
    with pytest.raises(ValueError, match="on TPU"):
        ServeEngine(cfg, params, n_blocks=8, use_kernel=False)


def test_int8_scales_must_fit_scalar_memory():
    check_int8_scales_fit(3000, 32)  # a device-sized stablelm int8 pool
    with pytest.raises(ValueError, match="scalar memory"):
        check_int8_scales_fit(4000, 32)
    n, kh = 4000, 32
    q = jnp.zeros((1, 1, kh, 1, 8), jnp.float32)
    pool = jnp.zeros((n, 4, kh, 8), jnp.int8)
    scales = jnp.ones((n, kh), jnp.float32)
    tables = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="scalar memory"):
        paged_attention_chunk(q, pool, pool, tables,
                              jnp.zeros((1, 1), jnp.int32), None, scales,
                              scales, interpret=False)


def test_chip_smoke_last_line_contract(chip_smoke, monkeypatch, capsys,
                                      tmp_path):
    """On a TPU the last stdout line is the ok/device JSON object."""

    class FakeTPU:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(chip_smoke, "tpu_device", lambda: FakeTPU())
    monkeypatch.setattr(chip_smoke, "run", lambda **kw: {})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite",
        "count": len(jax.devices())}}
