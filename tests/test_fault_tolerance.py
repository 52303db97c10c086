"""Crash-tolerant serving tests (ISSUE-10).

A worker thread that dies holding an era reservation is the one failure
mode the wait-free guarantees say nothing about: the reservation is
never released, every block whose lifetime intersects it is pinned, and
``unreclaimed == 0`` becomes unreachable.  These tests drive the full
recovery pipeline — deterministic fault injection (``serve/faults.py``),
the ``ServeRuntime`` supervisor (quarantine + reap + requeue + respawn),
and ``SMRScheme.reap_thread`` — and assert the end state the robustness
doc promises (docs/robustness.md):

* every submitted request completes-or-fails **exactly once** (counted
  through ``on_finish``), across every scheme and sharding, with ≥ 3
  injected crashes covering all three crash points;
* survivors are **token-identical** to a fault-free run (greedy decode +
  the eviction rewind replay make recovery deterministic);
* a reaped tid's freed pages are never read again (NaN/1e30 scribble
  proof, same mechanism as the cancellation poison test);
* the reap alone unblocks a drain a dead reservation was pinning, for
  every scheme — including WFE's slow-path counter rebalancing;
* the ``serve()`` error path drains before raising (``partial_stats``).

Reclamation is always asserted through the shared ``quiescence_check``
fixture — blocks flow through the refcount/era path, never force-retire.
"""

import asyncio

import jax
import pytest

from repro.configs import get_smoke_config
from repro.core.atomics import INF_ERA, INVPTR
from repro.models import build_model
from repro.serve import (FaultInjector, FaultSpec, Frontend, InjectedCrash,
                         ServeEngine, ServeRuntime)
from repro.serve import frontend as frontend_mod

POOL_SCHEMES = ("WFE", "Crystalline", "HE", "EBR", "2GEIBR")

#: the matrix workload: prompts + budgets are fixed so every scheme and
#: the fault-free reference generate over identical requests
N_REQS = 10
MAX_NEW = 6


def _prompts(vocab):
    return [[1 + (i * 7 + j) % 29 for j in range(1 + i % 5)]
            for i in range(N_REQS)]


@pytest.fixture(scope="module")
def dense_model():
    cfg = get_smoke_config("stablelm-3b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, params


def _engine(dense_model, **kw):
    cfg, params = dense_model
    kw.setdefault("n_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch", 4)
    kw.setdefault("chunk_size", 4)
    kw.setdefault("max_threads", 16)  # respawns burn fresh tids
    kw.setdefault("max_inflight", 4)
    kw.setdefault("era_freq", 2)
    kw.setdefault("cleanup_freq", 2)
    return ServeEngine(cfg, params, **kw)


@pytest.fixture(scope="module")
def reference_tokens(dense_model):
    """Fault-free greedy reference for the shared workload (tokens are
    scheme-independent: the SMR layer never touches sampling)."""
    cfg, _ = dense_model
    engine = _engine(dense_model)
    reqs = [engine.submit(p, MAX_NEW) for p in _prompts(cfg.vocab_size)]
    tid = engine.pool.register_thread()
    stats = engine.run(tid)
    assert stats["completed"] == N_REQS and engine.pool.unreclaimed() == 0
    return [list(r.generated) for r in reqs]


# ========================================================== spec + injector
def test_fault_spec_parse_roundtrip():
    spec = FaultSpec.parse(
        "seed=7,crash_rate=0.25,max_crashes=3,"
        "crash_at=after_dispatch:5|before_tick:9,"
        "points=before_tick|after_dispatch,"
        "alloc_fail_at=3|11,poison_at=4,poison_rate=0.5")
    assert spec.seed == 7 and spec.crash_rate == 0.25
    assert spec.max_crashes == 3
    assert spec.crash_at == (("after_dispatch", 5), ("before_tick", 9))
    assert spec.crash_points == ("before_tick", "after_dispatch")
    assert spec.alloc_fail_at == (3, 11) and spec.poison_at == (4,)
    assert spec.poison_rate == 0.5
    with pytest.raises(ValueError, match="unknown fault-spec key"):
        FaultSpec.parse("frobnicate=1")
    with pytest.raises(ValueError, match="unknown crash point"):
        FaultSpec.parse("points=mid_tick")
    with pytest.raises(ValueError, match="outside"):
        FaultSpec(crash_rate=1.5)


def test_injector_deterministic_across_interleavings():
    """Decisions key on per-site event ordinals, not thread identity: the
    same event sequence yields the same crash set whichever tid observes
    a given ordinal."""

    def decisions(tids):
        inj = FaultInjector(FaultSpec(seed=11, crash_rate=0.3))
        out = []
        for k, tid in enumerate(tids):
            try:
                inj.crash_point("before_tick", tid)
                out.append(False)
            except InjectedCrash as e:
                assert e.ordinal == k and e.point == "before_tick"
                out.append(True)
        return out, inj.n_crashes

    a, na = decisions([0] * 40)
    b, nb = decisions([i % 3 for i in range(40)])  # different "threads"
    assert a == b and na == nb and na > 0


def test_injector_max_crashes_cap():
    inj = FaultInjector(FaultSpec(crash_rate=1.0, max_crashes=2))
    crashed = 0
    for _ in range(10):
        try:
            inj.crash_point("after_dispatch", 0)
        except InjectedCrash:
            crashed += 1
    assert crashed == 2 and inj.n_crashes == 2
    assert inj.stats()["events"]["after_dispatch"] == 10


# ============================================== crash matrix, all 5 schemes
@pytest.mark.parametrize("scheme", POOL_SCHEMES)
@pytest.mark.parametrize("shards", (1, 4))
def test_crash_matrix_all_schemes(dense_model, reference_tokens, scheme,
                                  shards, quiescence_check):
    """Three seeded crashes — one per crash point — under the supervised
    multi-worker runtime: every request completes exactly once, tokens
    match the fault-free reference, and the pool drains to zero."""
    cfg, _ = dense_model
    engine = _engine(dense_model, scheme=scheme, n_shards=shards)
    inj = FaultInjector(FaultSpec(crash_at=(
        ("before_tick", 2), ("after_reservation", 1), ("after_dispatch", 3))))
    engine.set_fault_injector(inj)
    finishes = {}

    def on_finish(req):  # runs under the scheduler lock: exactly-once proof
        finishes[req.rid] = finishes.get(req.rid, 0) + 1

    reqs = [engine.submit(p, MAX_NEW, on_finish=on_finish)
            for p in _prompts(cfg.vocab_size)]
    runtime = ServeRuntime(engine, n_workers=2)
    stats = runtime.serve()

    assert inj.n_crashes == 3, inj.stats()
    assert dict(inj.crashes) == {"before_tick": 1, "after_reservation": 1,
                                 "after_dispatch": 1}
    assert runtime.n_respawns == 3
    assert len(runtime.crashed_tids) == 3
    assert len(set(runtime.crashed_tids)) == 3, "a dead tid was reused"
    assert len(runtime.recovery_latencies) <= runtime.n_respawns
    assert stats["n_respawns"] == 3 and stats["worker_crashes"] == 3
    # exactly-once: every request finished once, none failed, none lost
    assert sorted(finishes) == sorted(r.rid for r in reqs)
    assert all(n == 1 for n in finishes.values()), finishes
    assert stats["completed"] == N_REQS and stats["failed"] == 0
    for r, want in zip(reqs, reference_tokens):
        assert r.state == "done", (r.rid, r.state)
        assert list(r.generated) == want, \
            (r.rid, "crash-requeued request replayed differently")
    assert stats["unreclaimed"] == 0
    quiescence_check(engine.pool, label=f"{scheme}/s{shards}", rounds=0)


def test_crash_requeue_accounting(dense_model, quiescence_check):
    """A crash in the reservation-held window rewinds its rows through the
    eviction path and charges the wasted tokens to the crash counters."""
    engine = _engine(dense_model)
    inj = FaultInjector(FaultSpec(crash_at=(("after_dispatch", 4),)))
    engine.set_fault_injector(inj)
    for i in range(6):
        engine.submit([2 + (i + j) % 13 for j in range(3)], MAX_NEW)
    runtime = ServeRuntime(engine, n_workers=2)
    stats = runtime.serve()
    assert inj.n_crashes == 1 and runtime.n_respawns == 1
    assert stats["crash_requeues"] >= 1
    assert stats["evictions"] >= stats["crash_requeues"]
    assert stats["completed"] == 6 and stats["unreclaimed"] == 0
    quiescence_check(engine.pool, label="requeue-accounting", rounds=0)


# ============================================ graceful degradation (poison)
def test_poison_fails_single_request(dense_model, reference_tokens,
                                     quiescence_check):
    """A NaN-poisoned sampled row fails THAT request (terminal ``failed``
    state) and leaves every other stream token-exact — the batch, and the
    worker, survive."""
    cfg, _ = dense_model
    engine = _engine(dense_model)
    engine.set_fault_injector(FaultInjector(FaultSpec(poison_at=(6,))))
    finishes = {}

    def on_finish(req):
        finishes[req.rid] = finishes.get(req.rid, 0) + 1

    reqs = [engine.submit(p, MAX_NEW, on_finish=on_finish)
            for p in _prompts(cfg.vocab_size)]
    tid = engine.pool.register_thread()
    stats = engine.run(tid)
    failed = [r for r in reqs if r.state == "failed"]
    assert len(failed) == 1, [r.state for r in reqs]
    assert stats["failed"] == 1 and stats["completed"] == N_REQS - 1
    assert stats["failed_tokens"] == len(failed[0].generated)
    assert len(failed[0].table) == 0, "failed request still holds pages"
    assert all(n == 1 for n in finishes.values())
    for r, want in zip(reqs, reference_tokens):
        if r.state == "done":
            assert list(r.generated) == want, \
                (r.rid, "a survivor diverged after a sibling was poisoned")
    quiescence_check(engine.pool, label="poison-degradation", rounds=0)


# =========================================== reaped pages never read again
def test_reaped_tid_pages_never_read_poison(dense_model, quiescence_check):
    """Deterministic single-threaded replay of the supervisor pipeline:
    crash a worker mid-window, reap + requeue, then scribble NaN/1e30
    over every pool slot the rewind freed — the finished run must be
    token-identical to a fault-free one (nothing reads a freed page)."""
    import jax.numpy as jnp
    import numpy as np

    def build():
        # no prefix cache: salvage inserts would legitimately keep freed
        # pages alive for future readers
        return _engine(dense_model, n_blocks=32, prefix_caching=False)

    prompts = [[3, 1, 4, 1, 5], [8, 7, 1, 9], [2, 6, 5]]

    ref_engine = build()
    ref = [ref_engine.submit(p, MAX_NEW) for p in prompts]
    ref_engine.run(ref_engine.pool.register_thread())
    want = [list(r.generated) for r in ref]

    engine = build()
    engine.set_fault_injector(FaultInjector(FaultSpec(
        crash_at=(("after_reservation", 3),))))
    reqs = [engine.submit(p, MAX_NEW) for p in prompts]
    dead = engine.pool.register_thread()
    with pytest.raises(InjectedCrash):
        for _ in range(10_000):
            if not engine.step(dead) and not engine.sched.pending() \
                    and not engine.sched.active:
                raise AssertionError("quiesced before the injected crash")
    # the supervisor pipeline, replayed inline (the "worker" is this very
    # thread, returned from the call stack — as joined as it gets)
    engine.pool.reap_thread(dead)
    plan = engine.take_orphaned_plan(dead)
    assert plan is not None, "crash in the reservation window left no plan"
    sup = engine.pool.register_thread()
    engine.sched.requeue_crashed(plan, sup)
    assert all(not r.inflight for r in reqs)
    # scribble every slot NOT owned by a live request: freed-by-rewind
    # slots are poisoned, so any read of them changes tokens
    live = {i for r in reqs if r.table is not None
            for i in r.table.current().block_ids}
    pools = engine.pools
    dead_slots = np.ones(pools["k"].shape[1], dtype=bool)
    dead_slots[sorted(live)] = False
    assert dead_slots.any(), "the rewind freed no slots to poison"
    mask = jnp.asarray(dead_slots)[None, :, None, None]
    engine.pools = {**pools,
                    "k": jnp.where(mask, jnp.nan, pools["k"]),
                    "v": jnp.where(mask, 1e30, pools["v"])}
    engine.set_fault_injector(None)  # recovery run is fault-free
    stats = engine.run(sup)
    assert stats["completed"] == len(prompts)
    for r, w in zip(reqs, want):
        assert r.state == "done"
        assert list(r.generated) == w, \
            (r.rid, "a replayed request read a reaped/poisoned page")
    quiescence_check(engine.pool, label="reap-poison", rounds=0)


# ======================================================== serve error path
def test_serve_error_path_drains_and_reports(dense_model, quiescence_check):
    """With the respawn budget at zero every crash is unrecoverable —
    but serve() must STILL drain (unreclaimed == 0) and park the merged
    stats in ``partial_stats`` before re-raising (satellite fix: the old
    path raised first and leaked the whole run)."""
    cfg, _ = dense_model
    engine = _engine(dense_model)
    engine.set_fault_injector(FaultInjector(FaultSpec(
        crash_at=(("after_dispatch", 2),))))
    reqs = [engine.submit(p, MAX_NEW) for p in _prompts(cfg.vocab_size)]
    runtime = ServeRuntime(engine, n_workers=2, max_respawns=0)
    with pytest.raises(InjectedCrash):
        runtime.serve()
    assert runtime.n_respawns == 0 and len(runtime.crashed_tids) == 1
    assert runtime.partial_stats is not None
    assert runtime.partial_stats["unreclaimed"] == 0, \
        "the error path left the pool pinned"
    assert runtime.partial_stats["worker_crashes"] == 1
    # no request half-finalized: nothing is still marked in flight, and
    # nothing reached a terminal state it shouldn't have
    for r in reqs:
        assert not r.inflight
        assert r.state in ("done", "queued", "active"), (r.rid, r.state)
    # non-finalized requests legitimately still OWN pages (the aborted
    # run never finished them) — release those, then the pool must drain
    # to every-slot-free: nothing beyond live ownership leaked
    tid = engine.pool.register_thread()
    for r in reqs:
        if r.table is not None and len(r.table) > 0:
            r.table.release_all(tid)
    quiescence_check(engine.pool, label="error-path", tid=tid)


# ==================================================== reap_thread unit layer
@pytest.mark.parametrize("scheme", POOL_SCHEMES)
def test_reap_unblocks_pinned_drain(scheme, quiescence_check):
    """A dead tid's reservation pins retired blocks forever; reap_thread
    alone must unpin them — for every scheme."""
    from repro.blocks import BlockPool

    pool = BlockPool(8, scheme=scheme, max_threads=4,
                     era_freq=1, cleanup_freq=1)
    live = pool.register_thread()
    dead = pool.register_thread()
    blk = pool.alloc(live)
    # publish the dead thread's protection covering the block's lifetime
    if hasattr(pool.smr, "reservations"):
        pool.protect_step(0, dead)  # WFE / Crystalline / HE era slot
    else:
        pool.smr.start_op(dead)  # EBR announce / 2GEIBR interval
    pool.retire(blk, live)

    def drain_pool(p, tid, rounds):  # mirrors conftest.drain_pool
        for _ in range(rounds):
            if p.unreclaimed() == 0:
                return 0
            p.cleanup_all()
            p.advance_eras(tid)
        return p.unreclaimed()

    assert drain_pool(pool, tid=live, rounds=10) > 0, \
        f"{scheme}: a live reservation did not pin the block — the reap " \
        f"test below would be vacuous"
    pool.reap_thread(dead)
    quiescence_check(pool, label=f"reap/{scheme}", tid=live)


def test_wfe_reap_cancels_orphaned_slow_path():
    """A thread that died after publishing a slow-path request (result.ptr
    == INVPTR, counter_start bumped) would leave the counters imbalanced
    forever — every future increment_era takes the help scan.  reap_thread
    must cancel the request exactly as the dead requester would have."""
    from repro.core import make_scheme

    smr = make_scheme("WFE", max_threads=2, era_freq=1, cleanup_freq=1)
    dead = 0
    # forge the orphan: the publish half of WFE's slow path (line 30-33
    # of the paper's Figure), abandoned before any helper served it
    tag = smr.reservations[dead][0].load_b()
    smr.state[dead][0].result.store((INVPTR, tag))
    smr.counter_start.fa_add(1)
    assert smr.counter_start.load() != smr.counter_end.load()
    smr.reap_thread(dead)
    assert smr.counter_start.load() == smr.counter_end.load(), \
        "orphaned slow-path request left the help counters imbalanced"
    assert smr.state[dead][0].result.load() == (None, INF_ERA)
    # every reservation slot — including the two special slots clear()
    # misses — must read empty
    for j in range(smr.max_hes + 2):
        assert smr.reservations[dead][j].load_a() == INF_ERA


def test_crystalline_reap_seals_open_batch(quiescence_check):
    """Crystalline parks retires on a per-tid open batch; a dead tid's
    unsealed batch is invisible to every scan.  reap_thread must seal it
    or up to batch_size - 1 blocks leak."""
    from repro.blocks import BlockPool

    pool = BlockPool(8, scheme="Crystalline", max_threads=2,
                     era_freq=1, cleanup_freq=1, batch_size=8)
    dead = pool.register_thread()
    blk = pool.alloc(dead)
    pool.retire(blk, dead)  # parks on the open batch (batch_size=8 ≫ 1)
    assert pool.smr.pending() == 1
    pool.reap_thread(dead)
    assert pool.smr.pending() == 0, "reap left the dead tid's batch open"
    quiescence_check(pool, label="crystalline-reap", tid=1)


# ===================================================== front-end integration
def test_frontend_error_frame_and_healthz(dense_model):
    """End-to-end over sockets: a poisoned request's SSE stream ends with
    an ``error`` frame (state == failed); /healthz reports per-worker
    liveness, respawn counts, and the fault counters."""
    engine = _engine(dense_model)
    engine.set_fault_injector(FaultInjector(FaultSpec(poison_at=(0,))))
    runtime = ServeRuntime(engine, n_workers=2,
                           max_steps_per_worker=1_000_000)
    frontend = Frontend(runtime, host="127.0.0.1", port=0)

    async def scenario():
        port = await frontend.start()
        status, reader, writer = await frontend_mod._post_generate(
            port, {"prompt": [7, 3, 9, 1], "max_new_tokens": 5})
        assert "200" in status, status
        events = await frontend_mod._read_sse(reader)
        writer.close()
        err = [d for e, d in events if e == "error"]
        assert err and err[0]["state"] == "failed", events
        assert not any(e == "done" for e, _ in events), events
        # a second request on the same runtime streams normally
        status, reader, writer = await frontend_mod._post_generate(
            port, {"prompt": [2, 8, 5], "max_new_tokens": 4})
        events = await frontend_mod._read_sse(reader)
        writer.close()
        done = [d for e, d in events if e == "done"]
        assert done and done[0]["state"] == "done", events
        status, health = await frontend_mod._http_json(
            port, "GET", "/healthz")
        assert "200" in status
        assert len(health["workers"]) == 2
        assert all(w["alive"] for w in health["workers"]), health
        assert health["n_respawns"] == 0
        assert health["faults"]["n_poisoned"] == 1, health
        return await frontend.shutdown(deadline_s=15.0)

    stats = asyncio.run(scenario())
    assert stats["failed"] == 1 and stats["completed"] >= 1
    assert stats["unreclaimed"] == 0
