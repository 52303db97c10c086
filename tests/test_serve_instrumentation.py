"""The serving loop's own spans and counters.

* a tick counts the decode-phase rows another worker's step holds
  (``decode_rows_held``) and leaves them out of its plan; an idle tick
  that finds only held rows counts nothing;
* the engine's per-step counts match the shapes it hands the device:
  ``rows_dispatched`` is ``max_batch`` per decode step,
  ``kv_blocks_walked`` is the ``num_live_blocks`` that ``paged_model``
  derives for every dispatched row (the pages the kernel fetches; a pad
  row's is 1), ``kv_blocks_live`` the same for the real rows;
  the phase timers run, and the idle loop's drain is counted;
* a profiler trace of ``ServeRuntime`` workers holds the ``serve.*``
  spans, each step's dispatch before its complete on the worker's thread.
"""

import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.blocks import BlockPool, Scheduler
from repro.blocks.scheduler import STAT_KEYS
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import ServeEngine, ServeRuntime

PROMPTS = [[5, 9, 2], [11, 3, 8, 1, 4, 6], [7], [2, 4, 8, 16, 3],
           [9, 9, 1], [13, 2]]


@pytest.fixture(scope="module")
def dense_model():
    cfg = get_smoke_config("stablelm-3b")
    params = build_model(cfg).init(jax.random.key(0))
    return cfg, params


def _complete(sched, plan, tid):
    sched.complete(plan, np.full((len(plan.requests),), 5, np.int64), tid)


# ------------------------------------------------------------- scheduler
def test_rows_held_by_another_step_are_counted_and_left_out():
    pool = BlockPool(64, max_threads=2, era_freq=1, cleanup_freq=1)
    tid = pool.register_thread()
    sched = Scheduler(pool, block_size=4, max_batch=8, chunk_size=8)
    reqs = [sched.submit([1, 2, 3], 6) for _ in range(4)]
    while any(r.phase == "prefill" for r in reqs):
        _complete(sched, sched.tick(tid), tid)
    assert all(r.phase == "decode" and r.state == "active" for r in reqs)
    assert sched.stats["decode_rows_held"] == 0
    held = reqs[1:3]
    for r in held:  # another worker's step holds these two rows
        r.inflight = True
    plan = sched.tick(tid)
    assert plan.kind == "decode"
    assert plan.requests == [reqs[0], reqs[3]]
    assert sched.stats["decode_rows_held"] == 2
    _complete(sched, plan, tid)
    # a tick that finds nothing but held rows plans no step and counts
    # nothing: re-finding the same rows while idle leaves no row out
    for r in reqs:
        r.inflight = True
    assert sched.tick(tid) is None
    assert sched.stats["decode_rows_held"] == 2
    for r in reqs:
        r.inflight = False
    while sched.active:
        _complete(sched, sched.tick(tid), tid)
    assert sched.stats["completed"] == 4


def test_tick_and_complete_time_their_phases():
    pool = BlockPool(64, max_threads=2, era_freq=1, cleanup_freq=1)
    tid = pool.register_thread()
    sched = Scheduler(pool, block_size=4, max_batch=8, chunk_size=8)
    sched.submit([1, 2, 3], 2)
    while sched.active or sched.pending():
        _complete(sched, sched.tick(tid), tid)
    st = sched.stats
    assert st["completed"] == 1 and st["steps"] == 2
    for k in ("tick_ns", "lock_wait_ns", "complete_ns", "reclaim_ns"):
        assert st[k] > 0, k
    assert st["lock_wait_ns"] < st["tick_ns"] + st["complete_ns"]
    assert st["reclaim_ns"] < st["complete_ns"]
    assert set(st) == set(STAT_KEYS)


# ---------------------------------------------------------------- engine
def test_engine_counts_match_the_shapes_it_dispatches(dense_model):
    cfg, params = dense_model
    bs, max_batch = 4, 4
    engine = ServeEngine(cfg, params, n_blocks=64, block_size=bs,
                         max_batch=max_batch, chunk_size=4)
    pad_slot = engine._shard_sizes[0]
    calls = []  # (kind, tables, last query position per row)

    def capture(kind, real):
        def step(params, pools, tables, *a):
            if kind == "decode":
                last = np.asarray(a[2])  # positions (B,)
            else:  # tokens, positions (B, C), chunk_lens (B,)
                pos, lens = np.asarray(a[1]), np.asarray(a[2])
                last = pos[np.arange(len(lens)), np.maximum(lens - 1, 0)]
            calls.append((kind, np.asarray(tables), last))
            return real(params, pools, tables, *a)
        return step

    engine._decode = capture("decode", engine._decode)
    engine._prefill = capture("prefill", engine._prefill)
    plans = []
    tick = engine.sched.tick

    def tick_(tid):
        plan = tick(tid)
        if plan is not None:
            plans.append(plan)
        return plan

    engine.sched.tick = tick_
    tid = engine.pool.register_thread()
    reqs = [engine.submit(p, 7) for p in PROMPTS]
    engine.run_worker(tid)
    assert all(r.done for r in reqs)
    st = engine.sched.stats
    assert len(calls) == len(plans) == st["steps"]
    decode_calls = [c for c in calls if c[0] == "decode"]
    assert decode_calls
    assert all(t.shape[0] == max_batch for _, t, _ in decode_calls)
    assert st["rows_dispatched"] == sum(t.shape[0] for _, t, _ in calls)
    # paged_model's num_live_blocks for every dispatched row: through the
    # block of the row's last query position — the pages the kernel
    # fetches for it in each layer.  A pad row names only the scratch
    # slot and fetches its one page.
    pad = [t[:, 0] == pad_slot for _, t, _ in calls]
    derived = [last // bs + 1 for _, _, last in calls]
    assert all((n[p] == 1).all() for n, p in zip(derived, pad))
    assert st["kv_blocks_walked"] == sum(int(n.sum()) for n in derived)
    live = sum(int(n[~p].sum()) for n, p in zip(derived, pad))
    assert st["kv_blocks_live"] == live
    assert 0 < st["kv_blocks_live"] < st["kv_blocks_walked"]
    # far below the (rows x width) grid the kernel no longer walks
    assert st["kv_blocks_walked"] < sum(t.size for _, t, _ in calls)
    assert st["decode_rows"] == sum(
        len(p.requests) if p.kind == "decode" else p.n_decode
        for p in plans)
    assert st["decode_rows_held"] == 0  # one worker holds nothing back
    for k in ("tick_ns", "dispatch_ns", "device_wait_ns", "complete_ns",
              "reclaim_ns"):
        assert st[k] > 0, k
    assert st["reclaimed"] > 0
    engine.drain(tid)
    assert engine.pool.unreclaimed() == 0


def test_the_idle_loops_drain_is_counted(dense_model):
    """An idle worker's fused drain (``cleanup_all``) frees blocks a
    stalled thread retired; they add to the idle worker's ``reclaimed``
    and the pass to its ``reclaim_ns``."""
    cfg, params = dense_model
    engine = ServeEngine(cfg, params, n_blocks=16, block_size=4,
                         max_batch=4, era_freq=1, cleanup_freq=10**9)
    pool = engine.pool
    stalled = pool.register_thread()
    for blk in [pool.alloc(stalled) for _ in range(5)]:
        pool.retire(blk, stalled)
    pool.advance_eras(stalled)
    retired = pool.unreclaimed()
    assert retired > 0
    tid = pool.register_thread()
    stop = threading.Event()
    worker = threading.Thread(target=engine.run_worker, args=(tid,),
                              kwargs=dict(stop=stop, exit_when_idle=False))
    worker.start()
    deadline = time.monotonic() + 30
    while pool.unreclaimed() and time.monotonic() < deadline:
        time.sleep(0.005)
    stop.set()
    worker.join(timeout=30)
    assert not worker.is_alive()
    assert pool.unreclaimed() == 0 and pool.free_blocks == 16
    st = engine.sched.worker_stats(tid)
    assert st["reclaimed"] == retired and st["reclaim_ns"] > 0
    assert st["steps"] == 0


# ------------------------------------------------------------------ trace
SPANS = ("serve.tick", "serve.lock_wait", "serve.dispatch",
         "serve.device_wait", "serve.complete", "serve.reclaim")


def _host_spans(trace_dir):
    """``(thread line, name, start_ns, end_ns, stats)`` of every host span
    named ``serve.*`` or ``window`` in the trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("serve.") or ev.name == "window":
                    out.append((i, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return out


def test_a_recorded_trace_of_the_runtime_holds_the_serving_spans(
        dense_model, tmp_path):
    cfg, params = dense_model
    engine = ServeEngine(cfg, params, n_blocks=64, block_size=4,
                         max_batch=4, chunk_size=4)
    runtime = ServeRuntime(engine, n_workers=2)
    for p in PROMPTS:  # compile every step shape before the trace
        runtime.submit(p, 4)
    runtime.serve()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for p in PROMPTS:
            runtime.submit(p, 4)
        stats = runtime.serve()
    jax.profiler.stop_trace()
    assert stats["unreclaimed"] == 0
    spans = _host_spans(str(tmp_path))
    names = {n for _, n, *_ in spans}
    assert set(SPANS) <= names, names
    (lo, hi), = [(a, b) for _, n, a, b, _ in spans
                 if n == "window"]
    dispatch = [s for s in spans if s[1] == "serve.dispatch"]
    complete = [s for s in spans if s[1] == "serve.complete"]
    assert len(dispatch) == len(complete) > 0
    for line, _, a, b, meta in dispatch:
        assert lo <= a < b <= hi
        assert meta["kind"] in ("decode", "prefill", "mixed")
        assert 0 < meta["rows"] <= meta["dispatched"] and meta["width"] > 0
        # this step's complete follows on the same worker thread
        assert any(ln == line and c >= b for ln, _, c, _, _ in complete)
    # the workers' spans sit on their own threads' lines, not the line
    # of the thread that opened the window
    (main,) = [ln for ln, n, *_ in spans if n == "window"]
    assert main not in {line for line, *_ in dispatch}
