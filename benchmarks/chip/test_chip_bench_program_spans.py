"""CPU tests of what the benchmark reads from the program's own
instrumentation: the readers of the program's step counters on a smoke
run (and on counters without them), and the trace reduction unchanged by
the program's ``serve.*`` host spans, which name the idle gaps they
cover."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import flops, harness, testing, trace  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402

#: the readers of the program's step counters, with the range (inclusive)
#: a smoke run's reading must fall in
PROGRAM_READERS = {
    "sched.decode_rows_per_step": (1.0, 4.0),   # max_batch 4
    "sched.rows_held_share": (0.0, 1.0),
    "kernel.live_block_share": (0.0, 1.0),
    "host.step_ms": (0.0, None),
    "sched.lock_wait_us_per_step": (0.0, None),
    "reclaim.us_per_step": (0.0, None),
}


@pytest.fixture(autouse=True)
def smoke_pool(monkeypatch):
    """A CPU reports no memory for ``device_pool_blocks`` to size a pool
    from: the smoke cells take ``testing.SMOKE_BLOCKS`` blocks."""
    import repro.serve.engine as engine

    monkeypatch.setattr(engine, "device_pool_blocks",
                        lambda *a, **kw: (testing.SMOKE_BLOCKS, None))


# ------------------------------------------------------ counter readers
def test_the_step_counter_readers_on_a_smoke_run():
    out = harness.run(testing.smoke_cell(), 2**33 + 11, 2.0, False,
                      require_accelerator=False)
    assert out.result["correct"], out.checks
    c = out.info["counters"]
    assert c["steps"] > 0 and c["decode_rows"] > 0
    for name, (lo, hi) in PROGRAM_READERS.items():
        v = harness.metric_reader(f"{name}.serve")({"counters": c})
        assert v is not None, name
        assert lo <= v and (hi is None or v <= hi), (name, v)
    assert c["rows_dispatched"] >= c["decode_rows"]
    assert c["kv_blocks_walked"] > c["kv_blocks_live"] > 0


def test_the_step_counter_readers_read_nothing_without_the_counters():
    """Counters of a program that keeps none of the step counts (a
    window's differences of its older stats keys): every reader returns
    None and raises nothing."""
    old = {k: 3 for k in ("admitted", "completed", "evictions", "steps",
                          "mixed_steps", "prefill_chunks", "reclaimed",
                          "prefix_hit_tokens")}
    for name in PROGRAM_READERS:
        assert harness.metric_reader(name)({"counters": old}) is None


def test_the_step_counter_readers_by_hand():
    c = {"steps": 10, "prefill_chunks": 4, "mixed_steps": 2,
         "decode_rows": 16, "decode_rows_held": 4,
         "kv_blocks_live": 30, "kv_blocks_walked": 600,
         "tick_ns": 4_000_000, "dispatch_ns": 5_000_000,
         "complete_ns": 3_000_000, "lock_wait_ns": 250_000,
         "reclaim_ns": 120_000}
    read = lambda n: harness.metric_reader(n)({"counters": c})  # noqa: E731
    assert read("sched.decode_rows_per_step") == pytest.approx(2.0)
    assert read("sched.rows_held_share") == pytest.approx(0.2)
    assert read("kernel.live_block_share") == pytest.approx(0.05)
    assert read("host.step_ms") == pytest.approx(1.2)
    assert read("sched.lock_wait_us_per_step") == pytest.approx(25.0)
    assert read("reclaim.us_per_step") == pytest.approx(12.0)


# ---------------------------------------------- traces with program spans
DEV = "/device:TPU:0"
M = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
     "head_dim": 4, "d_ff": 16, "vocab_size": 10, "mlp": "swiglu",
     "dtype": "bfloat16"}


def _op(name, a, b, **st):
    return trace.Event(DEV, trace.OPS_LINE, name, a, b - a, st)


def _mod(name, a, b):
    return trace.Event(DEV, trace.MODULE_LINE, name, a, b - a)


def _host(name, a, b):
    return trace.Event("/host:CPU", "python", name, a, b - a)


class _Plan:
    def __init__(self, kind, rows):
        self.kind = kind
        self.rows = rows


PLANS = {0: _Plan("decode", [flops.Row(40, 1, True),
                             flops.Row(9, 1, True)]),
         1: _Plan("mixed", [flops.Row(41, 1, True),
                            flops.Row(0, 8, False)]),
         2: _Plan("decode", [flops.Row(42, 1, True)])}

#: a traced window of three steps and the benchmark's own spans
BENCH_EVENTS = [
    _host("bench.trace_window", 0, 1000),
    _host("bench.tick", 5, 20), _host("bench.plan.0", 20, 30),
    _mod("jit__decode(1)", 25, 120),
    _op("paged_attention_chunk.6", 30, 90), _op("fusion.1", 90, 115),
    _host("bench.complete", 120, 200),
    _host("bench.tick", 200, 215), _host("bench.plan.1", 215, 230),
    _mod("jit__prefill(2)", 230, 500),
    _op("custom-call", 240, 400, kernel="paged_attention_chunk"),
    _op("fusion.2", 400, 490),
    _host("bench.complete", 500, 560),
    _host("bench.tick", 560, 600), _host("bench.plan.2", 600, 610),
    _mod("jit__decode(1)", 610, 700),
    _op("paged_attention_chunk.6", 615, 680),
    _host("bench.complete", 700, 720),
]

#: the program's spans of the same steps, on the workers' threads
SERVE_EVENTS = [
    _host("serve.tick", 6, 19), _host("serve.lock_wait", 6, 8),
    _host("serve.dispatch", 21, 29), _host("serve.device_wait", 29, 119),
    _host("serve.complete", 121, 199), _host("serve.lock_wait", 121, 125),
    _host("serve.reclaim", 150, 198),
    _host("serve.tick", 201, 214), _host("serve.dispatch", 216, 229),
    _host("serve.device_wait", 229, 499), _host("serve.complete", 501, 559),
    _host("serve.reclaim", 530, 558),
    _host("serve.idle", 560, 575), _host("serve.tick", 576, 599),
    _host("serve.dispatch", 601, 609), _host("serve.device_wait", 609, 699),
    _host("serve.complete", 701, 719), _host("serve.idle", 720, 1000),
]


def _ctx(events):
    """What a traced run's readers see, reduced as the harness reduces a
    loaded trace."""
    lo, hi = trace.span(events, "bench.trace_window")
    return {"events": events, "lo": lo, "hi": hi,
            "pairs": trace.pair_steps(events, PLANS, lo, hi),
            "peaks": peaks_for("TPU v5 lite"),
            "busy_s": trace.busy_ns(events, lo, hi) / 1e9,
            "window_s": (hi - lo) / 1e9, "model": M, "block_size": 16,
            "counters": {"steps": 3, "mixed_steps": 1, "completed": 1,
                         "evictions": 0, "prefix_hit_tokens": 0},
            "prompt_tokens_sent": 8, "recs": [], "t0": 0.0, "t_end": 1.0,
            "seconds": 1.0, "setup_s": 1.0}


def test_every_reader_reads_the_same_with_the_programs_spans():
    interleaved = sorted(BENCH_EVENTS + SERVE_EVENTS,
                         key=lambda e: e.start_ns)
    readers = sorted(p.stem for p in (HERE / "metrics").glob("*.py"))
    assert len(readers) >= 15
    plain, both = _ctx(BENCH_EVENTS), _ctx(interleaved)
    assert [p.kind for p, _ in both["pairs"]] == ["decode", "mixed",
                                                  "decode"]
    traced = 0
    for name in readers:
        read = harness.metric_reader(name)
        a, b = read(plain), read(both)
        assert a == b, name
        traced += a is not None and name in (
            "step.decode_ms", "step.mixed_ms", "step_mfu",
            "paged_attn_roofline", "device.idle_share")
    assert traced == 5
    assert trace.top_ops(interleaved, 0, 1000) == \
        trace.top_ops(BENCH_EVENTS, 0, 1000)


def test_idle_gaps_take_the_innermost_program_span():
    interleaved = BENCH_EVENTS + SERVE_EVENTS
    gaps = dict(trace.idle_gaps(interleaved, 0, 1000))
    # (115, 240) and (490, 615): the complete's reclamation, nested in
    # the benchmark's bench.complete
    assert "serve.reclaim" in gaps and "bench.complete" not in gaps
    # (680, 1000): the worker parks after the last complete
    assert gaps["serve.idle"] == pytest.approx(320e-9)
    assert dict(trace.idle_gaps(BENCH_EVENTS, 0, 1000))[
        "bench.complete"] > 0
