"""CPU tests of the benchmark's arithmetic: percentiles and censoring,
trace reduction on hand-built events and on a small recorded trace, and
the shape-based operation counts."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import flops, stats, trace  # noqa: E402
from chipbench.harness import Rec  # noqa: E402
from chipbench.traffic import Item  # noqa: E402


def _rec(due, times):
    r = Rec(Item([1], 4), due=due)
    r.token_times = list(times)
    return r


# ------------------------------------------------------------ latencies
def test_ttft_counts_from_due_time_and_censors_at_window_end():
    recs = [_rec(1.0, [1.5, 1.6]),   # 0.5 s
            _rec(2.0, []),           # no token by t_end = 10: waited 8 s
            _rec(3.0, [12.0]),       # first token after t_end: waited 7 s
            _rec(11.0, [11.1]),      # due after the window: not counted
            _rec(0.5, [0.7])]        # due before the window: not counted
    assert stats.ttfts(recs, 1.0, 10.0) == [0.5, 8.0, 7.0]


def test_itl_uses_first_deliveries_inside_the_window():
    recs = [_rec(0.0, [0.5, 1.0, 3.0, 11.0]), _rec(0.0, [2.0, 2.25])]
    assert sorted(stats.itls(recs, 1.0, 10.0)) == [0.25, 0.5, 2.0]
    assert stats.tokens_in(recs, 1.0, 10.0) == 4


@pytest.mark.parametrize("q,want", [(50, 3.0), (90, 4.6), (100, 5.0)])
def test_percentile_interpolates(q, want):
    assert stats.pct([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_percentile_of_nothing_is_none():
    assert stats.pct([], 90) is None


def test_token_hook_dedupes_replays_by_index():
    from chipbench.harness import Client

    c = Client(None, [Item([1], 3)], {"loop": "open"}, annotate=False)
    rec = c.recs[0]
    for idx, tok in ((0, 7), (1, 8), (0, 7), (1, 9), (2, 5)):
        c._on_token(rec, None, idx, tok)
    assert rec.tokens == [7, 8, 5] and len(rec.token_times) == 3
    assert rec.replay_mismatch == 1


# ------------------------------------------------------- trace reduction
DEV = "/device:TPU:0"


def _op(name, a, b, **st):
    return trace.Event(DEV, trace.OPS_LINE, name, a, b - a, st)


def _mod(name, a, b):
    return trace.Event(DEV, trace.MODULE_LINE, name, a, b - a)


def _host(name, a, b):
    return trace.Event("/host:CPU", "python", name, a, b - a)


def test_union_and_busy_clip_to_the_window():
    assert trace.union([(0, 10), (5, 20), (30, 40)], 2, 35) == [(2, 20),
                                                               (30, 35)]
    evs = [_op("a", 0, 10), _op("b", 5, 20), _op("c", 30, 40)]
    assert trace.busy_ns(evs, 0, 100) == 30
    assert trace.busy_ns(evs, 15, 35) == 10


def test_idle_gaps_are_named_by_the_covering_host_span():
    evs = [_op("a", 0, 10), _op("b", 50, 60), _host("bench.tick", 10, 45),
           _host("bench.client_wait", 0, 100),
           _host("bench.trace_window", 0, 100)]
    gaps = sorted(trace.idle_gaps(evs, 0, 100))
    # (10, 50) lies inside the tick; (60, 100) only inside the client's wait
    assert gaps == [("bench.client_wait", pytest.approx(40e-9)),
                    ("bench.tick", pytest.approx(40e-9))]


def test_idle_gaps_are_named_by_the_programs_spans_too():
    """A gap under a worker's ``serve.idle`` is named by it; the program's
    spans change no device reading."""
    bench = [_op("a", 0, 10), _op("b", 50, 60),
             _host("bench.trace_window", 0, 100)]
    serve = [_host("serve.tick", 10, 20), _host("serve.idle", 20, 48),
             _host("serve.idle", 60, 100)]
    named = dict(trace.idle_gaps(bench + serve, 0, 100))
    assert named == {"serve.idle": pytest.approx(40e-9)}
    assert dict(trace.idle_gaps(bench, 0, 100)) == {
        "no span": pytest.approx(40e-9)}
    assert trace.busy_ns(bench + serve, 0, 100) == \
        trace.busy_ns(bench, 0, 100) == 20


def test_top_ops_sum_self_time_by_short_name():
    evs = [_op("%fusion.1 = bf16[8] fusion(x)", 0, 10),
           _op("%fusion.1 = bf16[8] fusion(x)", 20, 25),
           _op("%while.3 = (s32[]) while(t)", 30, 60),  # a loop over:
           _op("%dot.2 = f32[4] dot(a, b)", 31, 41),
           _op("%dot.2 = f32[4] dot(a, b)", 45, 50)]
    assert dict(trace.top_ops(evs, 0, 100)) == pytest.approx(
        {"fusion.1": 15e-9, "while.3": 15e-9, "dot.2": 15e-9})


class _Plan:
    def __init__(self, kind):
        self.kind = kind
        self.rows = []


def test_pairing_matches_plans_to_their_modules_in_order():
    plans = {3: _Plan("decode"), 4: _Plan("mixed"), 5: _Plan("decode")}
    evs = [_host("bench.plan.3", 10, 12), _host("bench.plan.4", 20, 22),
           _host("bench.plan.5", 30, 32),
           _mod("jit__decode(1)", 5, 9),      # a plan before the window's
           _mod("jit__decode(1)", 13, 18),
           _mod("jit__prefill(2)", 23, 29),
           _mod("jit__decode(1)", 33, 38),
           _op("paged_attention_chunk", 24, 26),
           _op("fusion", 26, 28, hlo_op="x"),
           _op("custom-call", 34, 35, kernel="paged_attention_chunk")]
    pairs = trace.pair_steps(evs, plans, 10, 100)
    assert [(p.kind, m.start_ns) for p, m in pairs] == [
        ("decode", 13), ("mixed", 23), ("decode", 33)]
    assert trace.kernel_ns_within(evs, [m for _, m in pairs]) == 3


def test_a_recorded_cpu_trace_yields_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        with jax.profiler.TraceAnnotation("bench.plan.0"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("serve.idle"):
            pass
        with jax.profiler.TraceAnnotation("other.span"):
            pass
    jax.profiler.stop_trace()
    events = trace.load(trace.find_xplane(str(tmp_path)))
    lo, hi = trace.span(events, "bench.trace_window")
    plan = trace.span(events, "bench.plan.0")
    assert lo <= plan[0] < plan[1] <= hi
    # the program's spans are kept beside the benchmark's, no other host
    # event is
    idle = trace.span(events, "serve.idle")
    assert idle is not None and lo <= idle[0] <= idle[1] <= hi
    assert trace.span(events, "other.span") is None
    assert all(e.name.startswith(trace.HOST_SPANS) for e in events
               if not trace.is_device(e))
    # the CPU has no device plane: nothing is counted as device time
    assert trace.busy_ns(events, lo, hi) == 0.0


# ------------------------------------------------------------ op counts
M = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
     "head_dim": 4, "d_ff": 16, "vocab_size": 10, "mlp": "swiglu",
     "dtype": "bfloat16"}


def test_step_flops_by_hand():
    # one decode row at context 5 (6 keys) that emits a token
    rows = [flops.Row(5, 1, True)]
    proj = 8 * 8 + 2 * 8 * 4 + 8 * 8
    mlp = 3 * 8 * 16
    want = 2 * 2 * (proj + mlp) + 4 * 2 * 2 * 4 * 6 + 2 * 8 * 10
    assert flops.step_flops(M, rows) == want


def test_chunk_attention_counts_each_query_causal_context():
    assert flops.attention_keys([flops.Row(10, 3, False)]) == 11 + 12 + 13


def test_kernel_cost_reads_whole_live_pages():
    ops, byts = flops.kernel_call_cost(M, [flops.Row(5, 1, True)], 4)
    assert ops == 4 * 2 * 4 * 6
    assert byts == 2 * 2 * 4 * 1 * 4 * 2 + 2 * 1 * 2 * 4 * 2


def test_kernel_bound_takes_the_larger_of_compute_and_bandwidth():
    rows = [flops.Row(100, 1, True)]
    ops, byts = flops.kernel_call_cost(M, rows, 16)
    assert flops.kernel_step_bound_s(M, rows, 16, 1e12, 1e15) == \
        pytest.approx(2 * ops / 1e12)
    assert flops.kernel_step_bound_s(M, rows, 16, 1e20, 1e9) == \
        pytest.approx(2 * byts / 1e9)


def test_peaks_refuse_an_unknown_device():
    from chipbench.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")
