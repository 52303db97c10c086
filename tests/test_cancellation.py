"""Era-safe mid-flight cancellation tests (ISSUE-9).

The adversarial reclamation pattern the serving front-end introduces:
blocks die because the CLIENT left, not because generation finished.
Covers every cancellation point in a request's lifecycle:

* **queued** — no pages owned yet: finalized in place at ``cancel()``;
* **mid-prefill** — the prompt is partially materialized: pages release
  at the next planning tick, and whatever prefix fully materialized is
  still inserted into the prefix cache (salvage);
* **mid-decode** — the request has live generated context;
* **in-flight / mixed-batch row** — cancel lands BETWEEN ``tick`` and
  ``execute_plan``: the dispatched step still reads the request's pages
  under its era reservation, so ``release_all`` must run only after
  ``complete`` releases that reservation — the exact use-after-free
  window WFE (arXiv 2001.01999) closes;
* **NaN/huge poisoning** — after cancellation finalizes, every pool slot
  NOT referenced by a survivor's table is scribbled with K=NaN and
  V=1e30; surviving requests must still produce bitwise-identical tokens
  (the masked-score path neutralizes K-NaN; V uses a huge FINITE value
  because masked-but-multiplied positions contribute ``0 * v`` — NaN
  there would poison even a correct kernel);
* **salvaged prefix reuse** — a later request must hit the cancelled
  request's inserted prefix and decode bitwise-identically to a
  cache-less engine;
* **drain/submit race** — submitting after ``ServeRuntime.drain`` has
  begun raises instead of silently stranding (both orderings);
* the **scheme x shard stress matrix** and an end-to-end HTTP front-end
  stream/cancel/shutdown pass.

Reclamation is always asserted through the shared ``quiescence_check``
fixture (blocks flow through the refcount/era path — never force-retire).
"""

import asyncio
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import Frontend, ServeEngine, ServeRuntime
from repro.serve import frontend as frontend_mod

POOL_SCHEMES = ("WFE", "Crystalline", "HE", "EBR", "2GEIBR")


@pytest.fixture(scope="module")
def dense_model():
    cfg = get_smoke_config("stablelm-3b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, params


def _engine(dense_model, **kw):
    cfg, params = dense_model
    kw.setdefault("n_blocks", 48)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch", 4)
    kw.setdefault("chunk_size", 4)
    kw.setdefault("era_freq", 2)
    kw.setdefault("cleanup_freq", 2)
    return ServeEngine(cfg, params, **kw)


def _run_with(engine, tid, on_step=None, max_steps=10_000):
    """Drive the engine to quiescence, invoking ``on_step`` between a
    completed step and the next tick (the deterministic cancel hook)."""
    for _ in range(max_steps):
        stepped = engine.step(tid)
        if on_step is not None:
            on_step()
        if not stepped and not engine.sched.pending() \
                and not engine.sched.active:
            return
    raise AssertionError("engine did not quiesce")


# ================================================== lifecycle cancel points
def test_cancel_queued_request(dense_model, quiescence_check):
    """A queued cancel finalizes in place: no pages, no device steps."""
    engine = _engine(dense_model, max_batch=2)
    tid = engine.pool.register_thread()
    keep = [engine.submit([1 + i, 2, 3], 4) for i in range(2)]
    victim = engine.submit([9, 9, 9], 4)  # queued behind the full batch
    assert engine.cancel(victim) is True
    assert victim.state == "cancelled"
    assert victim.t_released is not None and victim.cancel_latency >= 0
    assert engine.cancel(victim) is False, "second cancel must be a no-op"
    engine.run(tid)
    assert all(r.done for r in keep)
    assert victim.generated == [] and victim.table is None
    stats = engine.sched.stats
    assert stats["cancelled"] == 1 and stats["cancelled_blocks"] == 0
    quiescence_check(engine.pool, label="queued-cancel", rounds=0)


@pytest.mark.parametrize("phase", ("prefill", "decode"))
def test_cancel_mid_phase_releases_blocks(dense_model, phase,
                                          quiescence_check):
    """Cancelling mid-prefill / mid-decode releases every page through
    release_all at the next tick; survivors are unaffected."""
    engine = _engine(dense_model)
    tid = engine.pool.register_thread()
    survivor = engine.submit([3, 1, 4, 1, 5], 6)
    victim = engine.submit([2 + i % 7 for i in range(12)], 8)
    done_cancel = []

    def maybe_cancel():
        if done_cancel:
            return
        mid = (0 < victim.length < len(victim.prompt)) \
            if phase == "prefill" else len(victim.generated) >= 2
        if mid:
            assert len(victim.table) > 0, "victim holds no pages yet"
            assert engine.cancel(victim)
            done_cancel.append(True)

    _run_with(engine, tid, on_step=maybe_cancel)
    assert done_cancel, f"never observed the victim mid-{phase}"
    assert victim.state == "cancelled"
    assert len(victim.table) == 0, "cancelled table still holds blocks"
    assert victim.cancel_latency is not None
    assert survivor.done and survivor.state == "done"
    assert engine.sched.stats["cancelled_blocks"] > 0
    engine.drain(tid)
    quiescence_check(engine.pool, label=f"mid-{phase}", rounds=0)


def test_cancel_inflight_row_defers_release(dense_model, quiescence_check):
    """Cancel landing between tick and execute_plan: pages must survive
    until the dispatched step's reservation clears (no release before
    complete), then release through the refcount/era path."""
    engine = _engine(dense_model)
    tid = engine.pool.register_thread()
    reqs = [engine.submit([1 + i, 2, 3], 6) for i in range(3)]
    # advance until a decode plan carries at least one row
    victim = None
    for _ in range(100):
        plan = engine.sched.tick(tid)
        if plan is None:
            continue
        row = next((r for r in plan.requests if not r.cancelled), None)
        if row is not None and row.phase == "decode":
            victim = row
            break
        engine.execute_plan(plan, tid)
    assert victim is not None, "no decode plan materialized"
    blocks_before = victim.table.current().blocks
    assert blocks_before, "victim owns no pages at dispatch time"
    assert engine.cancel(victim)  # mid-flight: plan already snapshotted
    assert victim.inflight, "victim must still be in flight"
    # the mark alone must NOT release pages: the dispatched step's era
    # reservation still covers them
    assert victim.t_released is None
    assert len(victim.table) == len(blocks_before)
    assert all(not b.freed for b in blocks_before), \
        "page freed under a live era reservation"
    engine.execute_plan(plan, tid)  # complete() finalizes the cancel
    assert victim.state == "cancelled" and not victim.inflight
    assert victim.t_released is not None
    assert len(victim.table) == 0
    _run_with(engine, tid)
    assert all(r.done for r in reqs if r is not victim)
    engine.drain(tid)
    quiescence_check(engine.pool, label="inflight-cancel", rounds=0)


def test_cancel_mixed_batch_row(dense_model, quiescence_check):
    """Cancelling one decode row of an in-flight MIXED plan (decode rows +
    prefill chunk in one dispatch) must not disturb the other rows."""
    engine = _engine(dense_model, sched_policy="mixed", token_budget=8)
    tid = engine.pool.register_thread()
    decoders = [engine.submit([1 + i, 2], 8) for i in range(2)]
    late = None
    victim = None
    for _ in range(200):
        if late is None and all(len(r.generated) >= 1 for r in decoders):
            late = engine.submit([5 + i % 7 for i in range(10)], 4)
        plan = engine.sched.tick(tid)
        if plan is None:
            if not engine.sched.pending() and not engine.sched.active:
                break
            continue
        if plan.kind == "mixed" and victim is None:
            victim = next(r for r in plan.requests if r.phase == "decode")
            assert engine.cancel(victim)
            assert victim.inflight and victim.t_released is None
        engine.execute_plan(plan, tid)
    assert victim is not None, "no mixed plan materialized"
    assert victim.state == "cancelled" and len(victim.table) == 0
    for r in [r for r in decoders + [late] if r is not None]:
        if r is not victim:
            assert r.done, (r.rid, r.state)
    engine.drain(tid)
    quiescence_check(engine.pool, label="mixed-row-cancel", rounds=0)


# =============================================== scheme x shard stress matrix
@pytest.mark.parametrize("scheme", POOL_SCHEMES)
@pytest.mark.parametrize("shards", (1, 4))
def test_cancellation_matrix_all_schemes(dense_model, scheme, shards,
                                         quiescence_check):
    """Multi-worker runtime under a half-abandoning workload: every scheme
    and sharding must reclaim all abandoned pages at quiescence."""
    engine = _engine(dense_model, scheme=scheme, n_shards=shards,
                     n_blocks=64, max_threads=8, max_inflight=4)
    n, cancel_after = 12, 2

    def cancel_hook(req, index, tok):
        if index + 1 >= cancel_after:  # runs under the scheduler lock
            engine.cancel(req)

    reqs = []
    for i in range(n):
        hook = cancel_hook if i % 2 else None
        reqs.append(engine.submit([1 + (i * 7 + j) % 29
                                   for j in range(1 + i % 6)], 6,
                                  on_token=hook))
    engine.cancel(reqs[0])  # and one queued cancel before any tick
    runtime = ServeRuntime(engine, n_workers=2)
    stats = runtime.serve()
    assert stats["unreclaimed"] == 0
    assert stats["cancelled"] == n // 2 + 1, stats["cancelled"]
    assert stats["completed"] == n - stats["cancelled"]
    for r in reqs:
        assert r.state in ("done", "cancelled"), (r.rid, r.state)
        assert len(r.table) == 0 if r.table is not None else True
    assert all(r.cancel_latency is not None
               for r in reqs if r.state == "cancelled")
    quiescence_check(engine.pool, label=f"{scheme}/s{shards}", rounds=0)


# ========================================================= poisoned reclaim
def test_cancelled_pages_never_read_poison(dense_model, quiescence_check):
    """Scribble K=NaN / V=1e30 over every pool slot NOT owned by a
    survivor after cancellation finalizes: survivors must decode
    bitwise-identically to a clean run.  Any read of a freed page —
    including one REALLOCATED from the cancelled requests' slots — would
    drag a NaN score or a 1e30 value into the softmax and change tokens.
    """
    cfg, params = dense_model
    n_new = 8

    def build():
        # no prefix cache: salvage inserts would legitimately keep
        # cancelled pages alive for future readers — separate test below
        return _engine(dense_model, n_blocks=32, prefix_caching=False)

    survivors_prompts = [[3, 1, 4, 1, 5], [2, 7, 1]]
    victim_prompts = [[8 + j % 11 for j in range(10)], [9, 9, 2, 6]]

    # clean reference: survivors alone
    ref_engine = build()
    tid = ref_engine.pool.register_thread()
    ref = [ref_engine.submit(p, n_new) for p in survivors_prompts]
    ref_engine.run(tid)
    want = [list(r.generated) for r in ref]

    engine = build()
    tid = engine.pool.register_thread()
    survivors = [engine.submit(p, n_new) for p in survivors_prompts]
    victims = [engine.submit(p, n_new) for p in victim_prompts]
    poisoned = []

    def maybe_poison():
        if poisoned:
            return
        if all(len(v.generated) >= 2 for v in victims):
            for v in victims:
                engine.cancel(v)
        if all(v.state == "cancelled" for v in victims):
            live = {i for s in survivors
                    for i in s.table.current().block_ids}
            pools = engine.pools
            dead = np.ones(pools["k"].shape[1], dtype=bool)
            dead[sorted(live)] = False
            mask = jnp.asarray(dead)[None, :, None, None]
            engine.pools = {**pools,
                            "k": jnp.where(mask, jnp.nan, pools["k"]),
                            "v": jnp.where(mask, 1e30, pools["v"])}
            poisoned.append(int(dead.sum()))

    _run_with(engine, tid, on_step=maybe_poison)
    assert poisoned and poisoned[0] > 0, "poison never applied"
    for s, w in zip(survivors, want):
        assert s.done
        assert list(s.generated) == w, \
            (s.rid, "a survivor read a freed/poisoned page")
    engine.drain(tid)
    quiescence_check(engine.pool, label="poison", rounds=0)


# ===================================================== salvaged prefix reuse
def test_cancelled_prefix_salvage_reused_bitwise(dense_model,
                                                 quiescence_check):
    """A cancelled request's fully-materialized prefix stays in the cache;
    a later identical prompt must HIT it and decode bitwise-identically
    to a cache-less engine (aliased pages hold exactly the right KV)."""
    bs = 4
    prompt = [1 + j % 13 for j in range(3 * bs)]  # block-aligned prefix
    n_new = 6

    # ground truth: no cache at all
    ref_engine = _engine(dense_model, prefix_caching=False)
    tid = ref_engine.pool.register_thread()
    ref = ref_engine.submit(prompt, n_new)
    ref_engine.run(tid)

    engine = _engine(dense_model, block_size=bs)
    tid = engine.pool.register_thread()
    victim = engine.submit(prompt, n_new)
    cancelled = []

    def maybe_cancel():  # cancel mid-decode: the full prompt materialized
        if not cancelled and len(victim.generated) >= 2:
            assert engine.cancel(victim)
            cancelled.append(True)

    _run_with(engine, tid, on_step=maybe_cancel)
    assert victim.state == "cancelled"
    before = dict(engine.sched.stats)
    reader = engine.submit(prompt, n_new)
    _run_with(engine, tid)
    after = engine.sched.stats
    assert reader.done
    assert after["prefix_hits"] - before["prefix_hits"] >= 1, \
        "the cancelled request's salvaged prefix was never hit"
    # consumer hits cap at (P-1)//bs blocks: the final prompt token must
    # prefill (its logits yield the first generated token)
    assert after["prefix_hit_tokens"] - before["prefix_hit_tokens"] \
        >= (len(prompt) - 1) // bs * bs
    assert list(reader.generated) == list(ref.generated), \
        "aliased salvage blocks decoded differently from a fresh scatter"
    engine.drain(tid)
    quiescence_check(engine.pool, label="salvage", rounds=0)


# ======================================================= drain/submit race
def test_submit_after_drain_rejected(dense_model, quiescence_check):
    """ISSUE-9 bugfix: submit after drain-begin must raise, not strand."""
    engine = _engine(dense_model, max_threads=8)
    runtime = ServeRuntime(engine, n_workers=2,
                           max_steps_per_worker=1_000_000)
    runtime.start()
    # ordering 1: submit BEFORE drain — must be served by the drain
    req = runtime.submit([5, 2, 8], 4)
    stats = runtime.drain(deadline_s=30.0)
    assert req.done and req.state == "done"
    assert stats["unreclaimed"] == 0
    assert stats["cancelled_at_deadline"] == 0
    # ordering 2: submit AFTER drain — must reject loudly
    with pytest.raises(RuntimeError, match="draining"):
        runtime.submit([1, 2, 3], 4)
    quiescence_check(engine.pool, label="drain-race", rounds=0)


def test_submit_during_drain_rejected_and_deadline_cancels(dense_model):
    """Concurrent ordering: a submit racing an in-progress drain either
    lands before the gate (served/cancelled) or raises — never strands.
    The drain deadline must cancel stragglers through the era path."""
    engine = _engine(dense_model, max_threads=8)
    runtime = ServeRuntime(engine, n_workers=2,
                           max_steps_per_worker=1_000_000)
    runtime.start()
    slow = runtime.submit([4, 4, 4], 500)  # far beyond the drain deadline
    results = {}

    def drainer():
        results["stats"] = runtime.drain(deadline_s=0.3)

    th = threading.Thread(target=drainer)
    th.start()
    outcomes = []
    for _ in range(50):  # hammer submit while the drain progresses
        try:
            outcomes.append(runtime.submit([1, 2], 2))
        except RuntimeError:
            outcomes.append(None)
            break
    th.join(timeout=60.0)
    assert not th.is_alive(), "drain wedged"
    stats = results["stats"]
    assert outcomes and outcomes[-1] is None, \
        "submit never observed the drain gate"
    assert slow.state == "cancelled", slow.state
    assert stats["cancelled_at_deadline"] >= 1
    assert stats["unreclaimed"] == 0
    # every submit that got in before the gate was served or cancelled
    for r in outcomes[:-1]:
        assert r is not None and r.state in ("done", "cancelled"), \
            (r.rid, r.state, "stranded request")


# ===================================================== HTTP front-end e2e
def test_http_frontend_stream_cancel_drain(dense_model):
    """End-to-end over real sockets: SSE stream to completion, explicit
    DELETE mid-stream, rolling drain with unreclaimed == 0."""
    engine = _engine(dense_model, max_threads=8)
    runtime = ServeRuntime(engine, n_workers=2,
                           max_steps_per_worker=1_000_000)
    frontend = Frontend(runtime, host="127.0.0.1", port=0)

    async def scenario():
        port = await frontend.start()
        # full stream
        status, reader, writer = await frontend_mod._post_generate(
            port, {"prompt": [7, 3, 9, 1], "max_new_tokens": 5})
        assert "200" in status, status
        events = await frontend_mod._read_sse(reader)
        writer.close()
        toks = [d for e, d in events if e == "token"]
        assert [t["index"] for t in toks] == list(range(5)), events
        done = next(d for e, d in events if e == "done")
        assert done["state"] == "done"
        # DELETE mid-stream
        status, reader, writer = await frontend_mod._post_generate(
            port, {"prompt": [2, 8, 5], "max_new_tokens": 64})
        events = await frontend_mod._read_sse(reader, until_tokens=1)
        rid = next(d["id"] for e, d in events if e == "start")
        status, body = await frontend_mod._http_json(
            port, "DELETE", f"/v1/requests/{rid}")
        assert "200" in status and body["cancelled"], (status, body)
        tail = await frontend_mod._read_sse(reader)
        writer.close()
        fin = next(d for e, d in tail if e == "done")
        assert fin["state"] == "cancelled", tail
        # malformed + unknown-id routes stay well-behaved
        status, _ = await frontend_mod._http_json(
            port, "DELETE", "/v1/requests/99999")
        assert "404" in status, status
        status, health = await frontend_mod._http_json(
            port, "GET", "/healthz")
        assert "200" in status and health["draining"] is False
        return await frontend.shutdown(deadline_s=15.0)

    stats = asyncio.run(scenario())
    assert stats["unreclaimed"] == 0
    assert stats["completed"] >= 1 and stats["cancelled"] >= 1
    assert json.dumps(stats["cancelled"])  # stats stay JSON-serializable


def test_frontend_backpressure_and_drain_reject(dense_model):
    """Admission control: 429 + Retry-After when the queue is past
    max_pending; 503 once the rolling drain begins."""
    engine = _engine(dense_model, max_threads=8)
    runtime = ServeRuntime(engine, n_workers=2,
                           max_steps_per_worker=1_000_000)
    frontend = Frontend(runtime, host="127.0.0.1", port=0, max_pending=0)

    async def scenario():
        port = await frontend.start()
        status, body = await frontend_mod._http_json(port, "GET", "/healthz")
        assert "200" in status
        # max_pending=0: pending() >= 0 holds vacuously only when a
        # request is queued — park one that can't admit... simplest: the
        # threshold compares pending >= 0, so ANY generate is refused
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        payload = json.dumps({"prompt": [1, 2, 3],
                              "max_new_tokens": 4}).encode()
        writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: l\r\n"
                      f"Content-Length: {len(payload)}\r\n\r\n").encode()
                     + payload)
        await writer.drain()
        status = (await reader.readline()).decode()
        headers = {}
        while True:
            line = (await reader.readline()).decode()
            if not line.strip():
                break
            k, _, v = line.partition(":")
            headers[k.strip().lower()] = v.strip()
        writer.close()
        assert "429" in status, status
        assert headers.get("retry-after") == "1", headers
        stats = await frontend.shutdown(deadline_s=5.0)
        # post-drain: generate must be refused with 503... the listener is
        # closed by shutdown, so assert the runtime-level gate instead
        with pytest.raises(RuntimeError, match="draining"):
            runtime.submit([1], 1)
        return stats

    stats = asyncio.run(scenario())
    assert stats["unreclaimed"] == 0
