"""CPU tests of the harness: the traffic generator, a whole run at the
smoke size (correct, and not correct with a token altered where it is
produced, or with the fp8 control in the program's place), a cell, mix
and metric found from new files alone, and the refusal to run without an
accelerator."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import harness, testing, traffic  # noqa: E402

ROOT = HERE.parents[1]


@pytest.fixture(autouse=True)
def smoke_pool(monkeypatch):
    """A CPU reports no memory for ``device_pool_blocks`` to size a pool
    from: the smoke cells take ``testing.SMOKE_BLOCKS`` blocks."""
    import repro.serve.engine as engine

    monkeypatch.setattr(engine, "device_pool_blocks",
                        lambda *a, **kw: (testing.SMOKE_BLOCKS, None))


# ---------------------------------------------------------------- traffic
def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    spec = json.loads((HERE / "traffic" / "chat.json").read_text())
    a = traffic.generate(spec, 1, 1000, 30.0)
    b = traffic.generate(spec, 2**40 + 7, 1000, 30.0)
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt)
                                                      for i in b)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in b)
    gaps = lambda items: sorted(np.diff([0.0] + [i.due for i in items]))  # noqa: E731
    assert np.allclose(gaps(a), gaps(b), atol=1e-9)
    # every request is due inside the window, whatever the seed
    assert len(a) == len(b) == int(spec["rate_per_s"] * 30.0)
    assert max(i.due for i in a + b) < 30.0
    # one fixed schedule: the same lengths at the same times, other tokens
    assert [(len(i.prompt), i.max_new, i.due) for i in a] == \
        [(len(i.prompt), i.max_new, i.due) for i in b]
    assert [i.prompt for i in a] != [i.prompt for i in b]
    again = traffic.generate(spec, 1, 1000, 30.0)
    assert [i.prompt for i in again] == [i.prompt for i in a]


def test_lengths_stay_in_range_and_follow_the_median():
    spec = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64,
            "max": 3072}
    q = traffic.quantiles(spec, 1001)
    assert q.min() >= 64 and q.max() <= 3072 and q[500] == 512
    u = traffic.quantiles({"dist": "uniform", "min": 10, "max": 20}, 5)
    assert list(u) == [11, 13, 15, 17, 19]


def test_prefix_mix_shares_block_aligned_contexts_by_zipf():
    spec = json.loads((HERE / "traffic" / "repo-prefix.json").read_text())
    items = traffic.generate(spec, 9, 1000, 40.0)
    ctx = traffic.contexts(spec, 9, 1000)
    assert len(ctx) == 32 and all(len(c) == 2048 for c in ctx)
    counts = np.bincount([i.context for i in items], minlength=32)
    assert counts[0] > counts[1] > counts[31] >= 0
    for it in items[:5]:
        assert it.prompt[:2048] == ctx[it.context]
    assert traffic.max_len(spec) == 2048 + 512 + 256


def test_warm_shapes_cover_the_mix():
    cell = harness.load_cell("stablelm-3b.chat")
    shapes = harness.warm_shapes(cell)
    assert shapes["widths"] == [8, 16, 32, 64, 128, 256]
    assert shapes["chunks"] == [1, 2, 4, 8, 16, 32, 64, 128]


# -------------------------------------------------------------- whole runs
def _run(cell, seed, **kw):
    return harness.run(cell, seed, 2.0, False, require_accelerator=False,
                       **kw)


@pytest.mark.parametrize("gqa,loop,prefix", [
    (False, "open", False), (True, "closed", False), (True, "open", True)],
    ids=["mha-open", "gqa-closed", "gqa-prefix"])
def test_a_smoke_run_is_correct_and_reclaims_every_block(gqa, loop, prefix):
    out = _run(testing.smoke_cell(gqa, loop, prefix), 2**35 + 3)
    assert out.result["correct"], out.checks
    assert out.checks["unreclaimed"]["value"] == 0
    assert out.info["checked_tokens"] > 0
    assert out.info["compiles_in_window"] == 0
    names = set(out.result["metrics"])
    assert "setup_s" in names and "tokens_per_s" in names
    if prefix:
        assert out.info["counters"]["prefix_hit_tokens"] > 0
    # the engine's pools were freed before the reference ran
    import jax

    pool = tuple(jax.eval_shape(lambda: __import__(
        "repro.serve.paged_model", fromlist=["x"]).init_pools(
        harness.arch_config(testing.smoke_config(gqa)),
        testing.SMOKE_BLOCKS + 1, 4))["k"].shape)
    assert not [a for a in jax.live_arrays() if a.shape == pool]


def test_the_fp8_control_fails_the_limit_that_the_program_meets():
    cell = testing.smoke_cell()
    limit = cell.config["limits"]["max_logit_gap"]
    for seed in (21, 22, 23):
        out = _run(cell, seed, control=True)
        assert not out.result["correct"], out.checks
        assert out.checks["max_logit_gap"]["value"] > limit, out.info
        assert out.info["program_gap"] <= limit, out.info


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.serve.engine import ServeEngine

    real = ServeEngine._dispatch_decode

    def altered(self, plan):
        out = np.array(real(self, plan))
        out[0] = (out[0] + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(ServeEngine, "_dispatch_decode", altered)
    out = _run(testing.smoke_cell(), 31)
    assert not out.result["correct"]
    assert out.checks["max_logit_gap"]["value"] > \
        out.checks["max_logit_gap"]["limit"]


def test_a_cell_mix_and_metric_from_new_files_alone(tmp_path):
    """A later change adds a cell by adding a configuration file, a mix
    file, a metric reader and entries: nothing that exists is edited."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE / "metrics", bench_dir / "metrics")
    (bench_dir / "configs").mkdir()
    (bench_dir / "traffic").mkdir()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.burst", "config": "tiny",
                               "traffic": "burst", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({
        "name": "tokens_per_s.burst", "unit": "tokens/s", "better": "higher",
        "bound": 0.1, "source": "host_clock", "workloads": ["tiny.burst"]})
    bench["per_layer"].append({
        "name": "sched.steps_per_s.burst", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tokens_per_s.burst", "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (bench_dir / "configs" / "tiny.json").write_text(
        json.dumps(testing.smoke_config()))
    (bench_dir / "traffic" / "burst.json").write_text(
        json.dumps(testing.smoke_traffic("open")))
    (bench_dir / "metrics" / "sched.steps_per_s.py").write_text(
        "def read(ctx):\n"
        "    return ctx['counters']['steps'] / ctx['seconds']\n")
    cell = harness.load_cell("tiny.burst", root=tmp_path,
                             bench_dir=bench_dir)
    assert [m["name"] for m in cell.per_layer] == ["sched.steps_per_s.burst"]
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s.burst",
                                                    "setup_s"}
    out = harness.run(cell, 41, 2.0, True, require_accelerator=False)
    assert out.result["correct"], out.checks
    assert out.result["metrics"]["sched.steps_per_s.burst"]["value"] > 0
    out = harness.run(cell, 42, 2.0, False, require_accelerator=False)
    assert out.result["metrics"]["tokens_per_s.burst"]["value"] > 0


def test_without_an_accelerator_no_result_is_printed(tmp_path):
    """On a CPU the guard refuses the run; in a directory that holds only
    the benchmark, without the program, the run fails too."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "benchmarks/chip/run_cell.py", "--workload",
           "stablelm-3b.chat", "--seed", "1", "--seconds", "1"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout, p.stderr[-2000:]
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env.pop("PYTHONPATH", None)
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout, p.stderr[-2000:]


# ------------------------------------------------------- useful work only
def test_a_plans_counted_rows_ignore_its_padding():
    from repro.blocks.scheduler import StepPlan

    class R:
        prompt = [0] * 20

    tok = np.zeros((3, 8), np.int32)  # the chunk row pads 5 tokens to 8
    plan = StepPlan(0, [R(), R(), R()], tok, tok, np.zeros((3, 4), np.int32),
                    np.array([11, 31, 5], np.int32), kind="mixed",
                    n_tokens=7, n_decode=2,
                    chunk_lens=np.array([1, 1, 5], np.int32))
    rows = harness.PlanRec.of(plan).rows
    assert [(r.ctx, r.n, r.emits) for r in rows] == [
        (10, 1, False), (30, 1, True), (0, 5, False)]


def test_padding_the_engine_adds_leaves_the_counted_work_unchanged():
    """The same requests through an engine that pads every step to its
    buckets and one that dispatches exact shapes: the steps' counted
    operations are the same, though the padded engine runs more rows."""
    from repro.serve import ServeEngine

    from chipbench import flops, weights

    cfg = testing.smoke_config()
    arch = harness.arch_config(cfg)
    params = weights.make_params(cfg["model"], 5)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, int(n)).tolist() for n in (5, 13, 9, 17)]
    counted, rows_run = {}, {}
    for pad in (True, False):
        engine = ServeEngine(arch, params, n_blocks=64, block_size=4,
                             max_batch=4, chunk_size=8, pad_shapes=pad)
        rec = harness.Recorder(engine, annotate=True)
        rec.record = True
        real = engine._prefill
        seen = []

        def prefill(p, pools, tables, tokens, *a, _real=real, _seen=seen):
            _seen.append(int(np.prod(tokens.shape)))
            return _real(p, pools, tables, tokens, *a)

        engine._prefill = prefill
        for p in prompts:
            engine.submit(p, 3)
        engine.run(engine.pool.register_thread())
        counted[pad] = sum(flops.step_flops(cfg["model"], p.rows)
                           for p in rec.plans.values())
        rows_run[pad] = sum(seen)
    assert counted[True] == counted[False] > 0
    assert rows_run[True] > rows_run[False]
