"""Serving-engine benchmark: WFE pool vs other SMR schemes under the
continuous-batching engine (the paper's technique in its integrated home).

Modes:

* ``run()`` — the original single-worker scheme comparison: scheduler-side
  tail latencies of tick() (admission+alloc+protect) — the operations the
  paper makes wait-free — plus end-to-end tokens/s on a reduced dense model.
* ``run_scaling()`` / CLI — the sharded multi-worker matrix: throughput for
  workers x shards x scheme, with speedup over the single-worker
  single-shard baseline.  This is the configuration the sharded runtime
  exists for: K worker threads pipelining device steps over N per-shard SMR
  instances joined by the distributed era clock.

  PYTHONPATH=src python benchmarks/serve_bench.py --workers 4 --shards 4

* ``run_prefill_heavy()`` / ``--prefill-heavy`` — the chunked-prefill
  scenario: long prompts, few generated tokens.  Reports TTFT and TPOT
  (definitions in docs/benchmarks.md) for token-at-a-time prompt
  processing (chunk_size=1 — one device dispatch per prompt token) vs
  chunked prefill (``--chunk-size`` tokens per dispatch), plus the TTFT
  speedup.

  PYTHONPATH=src python benchmarks/serve_bench.py --prefill-heavy --chunk-size 32

* ``run_prefix_heavy()`` / ``--prefix-heavy`` — the prefix-caching
  scenario: every prompt shares a system-prompt prefix and diverges in
  its tail.  Reports the cache **hit-rate** (cached prompt tokens /
  submitted prompt tokens — definition in docs/benchmarks.md), TTFT with
  and without caching, and the prefill dispatches saved.

  PYTHONPATH=src python benchmarks/serve_bench.py --prefix-heavy

* ``run_decode_heavy()`` / ``--decode-heavy`` — the bucket-policy
  scenario: a few LONG-generation requests pin the batch's table width
  over many short ones (heavily skewed context lengths).  Compares the
  legacy ``pow2`` current-width buckets (growing contexts recompile at
  every doubling, mid-decode) against the coarse ``maxlen`` policy (one
  final-width bucket per request lifetime; affordable because the
  length-bounded kernel skips dead padded slots).  Reports TPOT p50/p95
  plus per-shape compile counts.

  PYTHONPATH=src python benchmarks/serve_bench.py --decode-heavy

* ``run_kv_dtype()`` / ``--kv-dtype`` — the quantized-KV A/B: int8 pool
  pages (fused in-kernel dequant) vs fp32 on a decode-heavy workload.
  Reports TPOT p50/p95 per mode plus the ANALYTIC KV bytes streamed per
  decode step (see docs/benchmarks.md); headlines are ``tpot_ratio``
  (int8/fp32 p50 — gated as a <= 1.05 no-harm bound in
  ``check_regression``) and ``kv_bytes_saved_frac`` (> 0 invariant).

  PYTHONPATH=src python benchmarks/serve_bench.py --kv-dtype

* ``run_open_loop()`` / ``--open-loop`` — the decode-starvation scenario:
  requests ARRIVE on a Poisson clock (``--arrival-rate`` req/s) instead
  of all-at-once, the load every closed-loop scenario above cannot
  produce — sustained prompt arrival WHILE earlier requests decode.
  Reports goodput-under-SLO (fraction of requests meeting their TTFT and
  TPOT targets, split by SLO class: ``--batch-frac`` of arrivals are
  batch-class), TPOT p95/p99, and the worst per-token gap percentiles —
  the starvation symptom the TPOT *mean* hides.  SLO targets default to
  runner-independent multiples of an unloaded calibration pass.

  PYTHONPATH=src python benchmarks/serve_bench.py --open-loop --arrival-rate 8

* ``run_cancellation()`` / ``--cancel-frac`` — the mid-flight abandonment
  scenario: open-loop arrivals where a fraction of clients cancel after a
  few generated tokens (the serving front-end's disconnect path), some
  while still queued.  Reports the wasted-tokens fraction,
  cancel-latency percentiles (cancel -> blocks released), and
  ``unreclaimed`` — which must be 0: every abandoned page reclaims
  through the refcount/era path.

  PYTHONPATH=src python benchmarks/serve_bench.py --cancel-frac 0.5

* ``--smoke`` — a seconds-scale tiny-config pass over ALL scenarios for
  CI, emitting the TTFT/TPOT JSON schema (``--json PATH``) the bench
  trajectory and the perf-regression gate consume.  The bench validates
  its own output (schema + required keys) and exits nonzero on a
  mismatch — CI does not need to re-parse the JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

if __name__ == "__main__":
    # Scaling mode (script invocation only — importing benchmarks.run keeps
    # the ambient XLA config): one XLA compute thread per step, so decode
    # parallelism comes from the shard chains — the per-device picture of a
    # production host, measurable on a 2-vCPU CI box.
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_cpu_multi_thread_eigen=false"
                               " intra_op_parallelism_threads=1")

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build_model
from repro.serve import (FaultInjector, FaultSpec, ServeEngine,
                         ServeRuntime)


def _build_base(arch: str = "stablelm-3b"):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, params


def _build_bench(arch: str = "stablelm-3b"):
    """Scaled-up smoke model for the scaling matrix: the device step must
    cost more than the Python scheduling around it, or the measurement
    reads the interpreter, not the runtime."""
    cfg = get_smoke_config(arch).scaled(
        n_layers=4, d_model=256, n_heads=8, n_kv_heads=8, d_ff=768,
        vocab_size=512)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, params


def _pct(xs) -> dict:
    """p50/p95/p99/mean (ms) of a list of latencies in seconds."""
    if not xs:
        return {"p50_ms": None, "p95_ms": None, "p99_ms": None,
                "mean_ms": None}
    a = np.asarray(xs) * 1e3
    return {"p50_ms": float(np.percentile(a, 50)),
            "p95_ms": float(np.percentile(a, 95)),
            "p99_ms": float(np.percentile(a, 99)),
            "mean_ms": float(a.mean())}


def latency_summary(reqs) -> dict:
    """TTFT/TPOT percentiles (ms) over finished requests.

    TTFT = submit -> first generated token; TPOT = mean per-token gap over
    the remaining generated tokens (see docs/benchmarks.md).
    """
    return {"ttft": _pct([r.ttft for r in reqs if r.ttft is not None]),
            "tpot": _pct([r.tpot for r in reqs if r.tpot is not None]),
            "n_requests": len(reqs)}


def run(n_requests: int = 12, new_tokens: int = 8):
    cfg, params = _build_base()
    out = {}
    print("\n### Serving engine: scheduler-op latency + throughput by scheme")
    print(f"{'scheme':>8s} {'tok/s':>8s} {'tick p50 us':>12s} "
          f"{'tick p99 us':>12s} {'unreclaimed':>12s} {'slow paths':>11s}")
    for scheme in ("WFE", "Crystalline", "HE", "EBR", "2GEIBR"):
        engine = ServeEngine(cfg, params, n_blocks=64, block_size=4,
                             max_batch=8, scheme=scheme,
                             era_freq=4, cleanup_freq=4)
        tid = engine.pool.register_thread()
        for i in range(n_requests):
            engine.submit([1 + i % 7, 2, 3], new_tokens)
        tick_times = []
        t0 = time.perf_counter()
        while True:
            t1 = time.perf_counter()
            plan = engine.sched.tick(tid)
            tick_times.append(time.perf_counter() - t1)
            if plan is None:
                if not engine.sched.active and not engine.sched.queue:
                    break
                continue
            engine.execute_plan(plan, tid)
        dt = time.perf_counter() - t0
        tokens = engine.sched.stats["completed"] * new_tokens
        engine.drain(tid)
        ticks_us = np.array(tick_times) * 1e6
        stats = engine.pool.stats()
        row = {
            "tok_s": tokens / dt,
            "tick_p50_us": float(np.percentile(ticks_us, 50)),
            "tick_p99_us": float(np.percentile(ticks_us, 99)),
            "unreclaimed": stats["unreclaimed"],
            "slow_paths": stats.get("slow_paths", 0),
        }
        out[scheme] = row
        print(f"{scheme:>8s} {row['tok_s']:>8.1f} "
              f"{row['tick_p50_us']:>12.1f} {row['tick_p99_us']:>12.1f} "
              f"{row['unreclaimed']:>12d} {row['slow_paths']:>11d}")
    return out


# ------------------------------------------------------- prefill-heavy TTFT
def run_prefill_heavy(chunk_size: int = 32, prompt_len: int = 96,
                      n_requests: int = 8, new_tokens: int = 4,
                      block_size: int = 8, scheme: str = "WFE",
                      build=_build_base) -> dict:
    """Chunked prefill vs token-at-a-time on a prefill-heavy workload.

    Long prompts + short generations make prompt materialization the
    dominant latency term: token-at-a-time costs P device dispatches
    before the first token, chunked prefill ceil(P/C).  Each engine gets
    one untimed warmup pass (compiles every chunk/table-width bucket) and
    one timed pass; TTFT/TPOT come from the requests' monotonic stamps.
    """
    cfg, params = build()
    n_blocks = n_requests * (-(-(prompt_len + new_tokens) // block_size)) + 8
    out: dict = {"prompt_len": prompt_len, "new_tokens": new_tokens,
                 "chunk_size": chunk_size, "scheme": scheme}
    print(f"\n### Prefill-heavy serving: P={prompt_len} prompt tokens, "
          f"{new_tokens} generated, chunk C={chunk_size} ({scheme})")
    print(f"{'mode':>18s} {'ttft p50 ms':>12s} {'ttft p95 ms':>12s} "
          f"{'tpot p50 ms':>12s} {'tok/s':>8s} {'dispatches':>11s}")
    for label, c in (("token_at_a_time", 1), ("chunked", chunk_size)):
        engine = ServeEngine(cfg, params, n_blocks=n_blocks,
                             block_size=block_size, max_batch=4,
                             scheme=scheme, chunk_size=c,
                             era_freq=8, cleanup_freq=8)
        tid = engine.pool.register_thread()

        def prompts():
            return [[1 + (i * 7 + j) % 31 for j in range(prompt_len)]
                    for i in range(n_requests)]

        for p in prompts():  # warmup: compiles every shape bucket
            engine.submit(p, new_tokens)
        engine.run(tid)
        before = dict(engine.sched.stats)  # counters are cumulative
        reqs = [engine.submit(p, new_tokens) for p in prompts()]
        t0 = time.perf_counter()
        engine.run(tid)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        after = engine.sched.stats
        row = latency_summary(reqs)
        row["tok_s"] = n_requests * new_tokens / dt
        row["dispatches"] = after["steps"] - before["steps"]
        row["prefill_chunks"] = (after["prefill_chunks"]
                                 - before["prefill_chunks"])

        def fmt(x):  # tpot is None when new_tokens < 2
            return f"{x:>12.1f}" if x is not None else f"{'-':>12s}"

        out[label] = row
        print(f"{label:>18s} {fmt(row['ttft']['p50_ms'])} "
              f"{fmt(row['ttft']['p95_ms'])} {fmt(row['tpot']['p50_ms'])} "
              f"{row['tok_s']:>8.1f} {row['dispatches']:>11d}")
    base, chunked = out["token_at_a_time"], out["chunked"]
    out["ttft_speedup"] = base["ttft"]["p50_ms"] / chunked["ttft"]["p50_ms"]
    print(f"TTFT speedup (p50): {out['ttft_speedup']:.2f}x  "
          f"[{'PASS' if out['ttft_speedup'] > 1 else 'FAIL'}: chunked "
          f"prefill must cut time-to-first-token]")
    return out


# ------------------------------------------------------ prefix-heavy TTFT
def run_prefix_heavy(chunk_size: int = 16, shared_len: int = 64,
                     tail_len: int = 16, n_requests: int = 8,
                     new_tokens: int = 4, block_size: int = 8,
                     scheme: str = "WFE", build=_build_base) -> dict:
    """Prefix caching on a shared-system-prompt workload.

    Every prompt is ``shared_len`` identical system tokens plus a
    divergent ``tail_len``-token user tail — the canonical serving shape
    prefix caching exists for.  With caching, the first request prefills
    the shared run and inserts it; every later request aliases those
    pool blocks and prefills ONLY its tail (zero dispatches for the
    cached chunks).  Reports hit-rate = cached prompt tokens / submitted
    prompt tokens, TTFT/TPOT with and without caching, and the prefill
    dispatch saving.  Each engine gets one untimed warmup pass (compiles
    the shape buckets; the drain clears its cache) and one timed pass.
    """
    cfg, params = build()
    prompt_len = shared_len + tail_len
    n_blocks = n_requests * (-(-(prompt_len + new_tokens) // block_size)) + 8
    shared = [1 + j % 29 for j in range(shared_len)]

    def prompts():
        return [shared + [2 + (i * 7 + j) % 23 for j in range(tail_len)]
                for i in range(n_requests)]

    total_prompt_tokens = n_requests * prompt_len
    out: dict = {"shared_len": shared_len, "tail_len": tail_len,
                 "new_tokens": new_tokens, "chunk_size": chunk_size,
                 "scheme": scheme, "n_requests": n_requests}
    print(f"\n### Prefix-heavy serving: {shared_len} shared + {tail_len} "
          f"tail prompt tokens, {new_tokens} generated, chunk "
          f"C={chunk_size} ({scheme})")
    print(f"{'mode':>10s} {'ttft p50 ms':>12s} {'ttft p95 ms':>12s} "
          f"{'tpot p50 ms':>12s} {'hit-rate':>9s} {'dispatches':>11s}")
    for label, enabled in (("uncached", False), ("cached", True)):
        engine = ServeEngine(cfg, params, n_blocks=n_blocks,
                             block_size=block_size, max_batch=4,
                             scheme=scheme, chunk_size=chunk_size,
                             prefix_caching=enabled,
                             era_freq=8, cleanup_freq=8)
        tid = engine.pool.register_thread()
        for p in prompts():  # warmup: compiles every shape bucket
            engine.submit(p, new_tokens)
        engine.run(tid)  # the final drain clears the warmup's cache
        before = dict(engine.sched.stats)  # counters are cumulative
        reqs = [engine.submit(p, new_tokens) for p in prompts()]
        t0 = time.perf_counter()
        engine.run(tid)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        after = engine.sched.stats
        row = latency_summary(reqs)
        row["tok_s"] = n_requests * new_tokens / dt
        row["dispatches"] = after["steps"] - before["steps"]
        row["prefill_chunks"] = (after["prefill_chunks"]
                                 - before["prefill_chunks"])
        row["prefix_hits"] = after["prefix_hits"] - before["prefix_hits"]
        hit_tokens = (after["prefix_hit_tokens"]
                      - before["prefix_hit_tokens"])
        row["hit_tokens"] = hit_tokens
        row["hit_rate"] = hit_tokens / total_prompt_tokens

        def fmt(x):  # tpot is None when new_tokens < 2
            return f"{x:>12.1f}" if x is not None else f"{'-':>12s}"

        out[label] = row
        print(f"{label:>10s} {fmt(row['ttft']['p50_ms'])} "
              f"{fmt(row['ttft']['p95_ms'])} {fmt(row['tpot']['p50_ms'])} "
              f"{row['hit_rate']:>9.2f} {row['dispatches']:>11d}")
    base, cached = out["uncached"], out["cached"]
    out["hit_rate"] = cached["hit_rate"]
    out["chunks_saved"] = base["prefill_chunks"] - cached["prefill_chunks"]
    out["ttft_speedup"] = (base["ttft"]["p50_ms"]
                           / cached["ttft"]["p50_ms"])
    ok = cached["hit_rate"] > 0 and out["chunks_saved"] > 0
    print(f"hit-rate {cached['hit_rate']:.2f}, {out['chunks_saved']} "
          f"prefill dispatches saved, TTFT speedup (p50) "
          f"{out['ttft_speedup']:.2f}x  "
          f"[{'PASS' if ok else 'FAIL'}: cached prompts must share "
          f"blocks and skip prefill work]")
    return out


# ------------------------------------------------------ decode-heavy TPOT
def run_decode_heavy(chunk_size: int = 8, short_len: int = 4,
                     long_len: int = 4, n_short: int = 10, n_long: int = 2,
                     short_new: int = 8, long_new: int = 190,
                     block_size: int = 2, scheme: str = "WFE",
                     build=_build_base) -> dict:
    """Bucket-policy comparison on a decode-heavy skewed-length workload.

    A few requests generate LONG tails while many short ones continuously
    cycle through the batch — the mixed-length steady state of a serving
    fleet.  The long requests pin the batch's table width; under the
    legacy ``pow2`` policy their growing contexts re-cross a bucket
    boundary at every doubling, paying a recompile MID-DECODE each time —
    and every such gap lands in the TPOT of whichever short requests are
    in flight at that moment.  The ``maxlen`` policy pads to the batch's
    final width up front (known at admission), so a request compiles its
    bucket once at entry and never again — affordable because the
    length-bounded kernel skips the dead padded slots (no DMA, no FLOPs;
    see docs/benchmarks.md "dead DMA").

    Each engine warms up on SHORT traffic only (the steady state a long
    request arrives into), with the shared jit caches cleared per mode so
    compile counts measure the policy, not the run order.  Reports
    TTFT/TPOT p50/p95, dispatches, and the per-shape compile count; the
    headlines are ``tpot_speedup`` (pow2 p50 / maxlen p50) and
    ``compile_savings`` (pow2 compiles - maxlen compiles).
    """
    cfg, params = build()
    # TPOT needs >= 2 generated tokens per request (it is the mean
    # INTER-token gap) — below that the scenario has no headline
    short_new, long_new = max(2, short_new), max(2, long_new)
    short_total = short_len + short_new
    long_total = long_len + long_new
    n_blocks = (n_long * (-(-long_total // block_size))
                + n_short * (-(-short_total // block_size)) + 8)
    out: dict = {"short_len": short_len, "long_len": long_len,
                 "short_new": short_new, "long_new": long_new,
                 "n_short": n_short, "n_long": n_long,
                 "chunk_size": chunk_size, "scheme": scheme}
    print(f"\n### Decode-heavy serving: {n_short} short (+{short_new} tok) "
          f"vs {n_long} long (+{long_new} tok) requests, bs={block_size} "
          f"({scheme})")
    print(f"{'policy':>8s} {'ttft p50 ms':>12s} {'tpot p50 ms':>12s} "
          f"{'tpot p95 ms':>12s} {'dispatches':>11s} {'compiles':>9s}")

    def prompts():
        # longs first: they admit immediately and stay in the batch for
        # the whole run, so every pow2 width crossing has shorts in flight
        longs = [([2 + (i * 7 + j) % 23 for j in range(long_len)],
                  long_new) for i in range(n_long)]
        shorts = [([1 + (i * 5 + j) % 29 for j in range(short_len)],
                   short_new) for i in range(n_short)]
        return longs + shorts

    for label, policy in (("pow2", "pow2"), ("coarse", "maxlen")):
        engine = ServeEngine(cfg, params, n_blocks=n_blocks,
                             block_size=block_size, max_batch=4,
                             scheme=scheme, chunk_size=chunk_size,
                             bucket_policy=policy,
                             era_freq=8, cleanup_freq=8)
        tid = engine.pool.register_thread()
        # the jitted steps are lru-shared across engines over one config:
        # clear so compile counts measure the POLICY, not the run order
        engine.clear_compile_caches()
        # warmup: SHORT traffic only — the long requests' width buckets
        # arrive cold in the timed pass, exactly as in live serving
        for p, nt in prompts()[n_long:n_long + 2]:
            engine.submit(p, nt)
        engine.run(tid)
        before = dict(engine.sched.stats)  # counters are cumulative
        compiles0 = engine.compile_cache_size()
        reqs = [engine.submit(p, nt) for p, nt in prompts()]
        t0 = time.perf_counter()
        engine.run(tid)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        after = engine.sched.stats
        row = latency_summary(reqs)
        row["tok_s"] = sum(nt for _, nt in prompts()) / dt
        row["dispatches"] = after["steps"] - before["steps"]
        compiles1 = engine.compile_cache_size()
        row["compiles"] = compiles1 - compiles0
        out[label] = row
        print(f"{label:>8s} {row['ttft']['p50_ms']:>12.1f} "
              f"{row['tpot']['p50_ms']:>12.1f} "
              f"{row['tpot']['p95_ms']:>12.1f} {row['dispatches']:>11d} "
              f"{row['compiles']:>9}")
    base, coarse = out["pow2"], out["coarse"]
    out["tpot_speedup"] = base["tpot"]["p50_ms"] / coarse["tpot"]["p50_ms"]
    out["compile_savings"] = base["compiles"] - coarse["compiles"]
    ok = out["tpot_speedup"] > 1.0 and out["compile_savings"] > 0
    print(f"TPOT speedup (p50): {out['tpot_speedup']:.2f}x, "
          f"{out['compile_savings']} recompiles saved  "
          f"[{'PASS' if ok else 'FAIL'}: coarse buckets must cut "
          f"mid-decode recompiles]")
    return out


# ------------------------------------------------------- int8 KV pool A/B
def _kv_bytes_per_step(cfg, kv_dtype: str, prompt_len: int,
                       new_tokens: int, block_size: int) -> float:
    """Analytic K/V bytes one decode token streams from the pool, averaged
    over the request's decode steps (see docs/benchmarks.md "what KV
    bytes/step measures").  The length-bounded kernel reads
    ``ceil(ctx / bs)`` whole blocks per layer for K and V; int8 adds one
    fp32 scale per (block, kv-head) read — the 8 extra bytes per
    ``bs x D`` page that buy the 4x page shrink.
    """
    import numpy as _np

    kh, d = cfg.n_kv_heads, cfg.resolved_head_dim
    n_layers = cfg.n_groups * len(cfg.block_pattern)
    itemsize = 1 if kv_dtype == "int8" else _np.dtype(cfg.dtype).itemsize
    ctx = _np.arange(prompt_len + 1, prompt_len + new_tokens + 1)
    blocks = _np.ceil(ctx / block_size)  # live blocks per decode step
    page = block_size * kh * d * itemsize
    scale = kh * 4 if kv_dtype == "int8" else 0
    return float(n_layers * 2 * (blocks * (page + scale)).mean())


def run_kv_dtype(n_requests: int = 8, prompt_len: int = 4,
                 new_tokens: int = 16, block_size: int = 4,
                 chunk_size: int = 8, scheme: str = "WFE",
                 build=_build_base) -> dict:
    """int8 vs fp32 KV pools on a decode-heavy workload.

    Short prompts + long generations put the measurement where the
    quantized pools pay off: the decode steady state, where paged
    attention streams every live K/V page per token.  Both engines run
    the SAME workload (one untimed warmup pass, one timed); the rows
    report TPOT percentiles plus the ANALYTIC KV bytes/step (the CPU
    interpreter cannot observe HBM traffic — the byte model is exact for
    the length-bounded kernel's block walk, see ``_kv_bytes_per_step``).
    Headlines: ``tpot_ratio`` (int8 p50 / fp32 p50 — the no-harm bound
    ``check_regression`` gates at 1.05) and ``kv_bytes_saved_frac``
    (> 0 invariant: int8 must stream fewer bytes).
    """
    cfg, params = build()
    n_blocks = n_requests * (-(-(prompt_len + new_tokens) // block_size)) + 8
    out: dict = {"n_requests": n_requests, "prompt_len": prompt_len,
                 "new_tokens": new_tokens, "block_size": block_size,
                 "chunk_size": chunk_size, "scheme": scheme}
    print(f"\n### KV-dtype A/B: {n_requests} requests x {new_tokens} "
          f"generated tokens, bs={block_size} ({scheme})")
    print(f"{'kv_dtype':>9s} {'ttft p50 ms':>12s} {'tpot p50 ms':>12s} "
          f"{'tpot p95 ms':>12s} {'kv bytes/step':>14s} {'tok/s':>8s}")

    def prompts():
        return [[1 + (i * 7 + j) % 29 for j in range(prompt_len)]
                for i in range(n_requests)]

    for label in ("fp32", "int8"):
        engine = ServeEngine(cfg, params, n_blocks=n_blocks,
                             block_size=block_size, max_batch=4,
                             scheme=scheme, chunk_size=chunk_size,
                             kv_dtype=label, era_freq=8, cleanup_freq=8)
        tid = engine.pool.register_thread()
        for p in prompts():  # warmup: compiles every shape bucket
            engine.submit(p, new_tokens)
        engine.run(tid)
        before = dict(engine.sched.stats)  # counters are cumulative
        reqs = [engine.submit(p, new_tokens) for p in prompts()]
        t0 = time.perf_counter()
        engine.run(tid)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        after = engine.sched.stats
        row = latency_summary(reqs)
        row["tok_s"] = n_requests * new_tokens / dt
        row["dispatches"] = after["steps"] - before["steps"]
        row["kv_bytes_per_step"] = _kv_bytes_per_step(
            cfg, label, prompt_len, new_tokens, block_size)
        out[label] = row
        print(f"{label:>9s} {row['ttft']['p50_ms']:>12.1f} "
              f"{row['tpot']['p50_ms']:>12.1f} "
              f"{row['tpot']['p95_ms']:>12.1f} "
              f"{row['kv_bytes_per_step']:>14.0f} {row['tok_s']:>8.1f}")
    base, q8 = out["fp32"], out["int8"]
    out["tpot_ratio"] = q8["tpot"]["p50_ms"] / base["tpot"]["p50_ms"]
    out["kv_bytes_saved_frac"] = (
        1.0 - q8["kv_bytes_per_step"] / base["kv_bytes_per_step"])
    ok = out["kv_bytes_saved_frac"] > 0
    print(f"int8/fp32 TPOT ratio (p50): {out['tpot_ratio']:.2f}x, "
          f"KV bytes/step saved: {out['kv_bytes_saved_frac']:.0%}  "
          f"[{'PASS' if ok else 'FAIL'}: int8 pages must stream fewer "
          f"bytes]")
    return out


# ------------------------------------------------------ SMR scheme matrix
def run_scheme_matrix(schemes=("WFE", "Crystalline", "HE", "EBR", "2GEIBR"),
                      n_requests: int = 8, prompt_len: int = 4,
                      new_tokens: int = 16, block_size: int = 2,
                      chunk_size: int = 8, build=_build_base) -> dict:
    """Decode-path SMR scheme comparison under one fixed workload.

    Every engine runs the SAME short-prompt / long-generation workload —
    the decode steady state where per-step reclamation work (retire
    stamping, era advances, interval scans) is the term the schemes
    actually differ on.  One untimed warmup pass compiles the shape
    buckets; the timed pass reports TTFT/TPOT percentiles, throughput,
    and the scheme's reclamation telemetry.  The headline is
    ``crystalline_vs_wfe`` — WFE TPOT p50 / Crystalline TPOT p50 (> 1
    means the batched retire path wins on this runner).  The ratio is
    reported, not gated: CI asserts the structural keys and the
    machine-independent ``unreclaimed == 0``, never a timing race.
    """
    cfg, params = build()
    n_blocks = n_requests * (-(-(prompt_len + new_tokens) // block_size)) + 8
    out: dict = {"n_requests": n_requests, "prompt_len": prompt_len,
                 "new_tokens": new_tokens, "schemes": {}}
    print(f"\n### SMR scheme matrix: decode-path serving, "
          f"{n_requests} requests x {new_tokens} generated tokens")
    print(f"{'scheme':>12s} {'ttft p50 ms':>12s} {'tpot p50 ms':>12s} "
          f"{'tok/s':>8s} {'retires':>8s} {'unreclaimed':>12s}")

    def prompts():
        return [[1 + (i * 7 + j) % 29 for j in range(prompt_len)]
                for i in range(n_requests)]

    for scheme in schemes:
        engine = ServeEngine(cfg, params, n_blocks=n_blocks,
                             block_size=block_size, max_batch=4,
                             scheme=scheme, chunk_size=chunk_size,
                             era_freq=4, cleanup_freq=4)
        tid = engine.pool.register_thread()
        for p in prompts():  # warmup: compiles every shape bucket
            engine.submit(p, new_tokens)
        engine.run(tid)
        before = dict(engine.sched.stats)  # counters are cumulative
        reqs = [engine.submit(p, new_tokens) for p in prompts()]
        t0 = time.perf_counter()
        engine.run(tid)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        after = engine.sched.stats
        row = latency_summary(reqs)
        pool_stats = engine.pool.stats()
        row["tok_s"] = n_requests * new_tokens / dt
        row["dispatches"] = after["steps"] - before["steps"]
        row["unreclaimed"] = pool_stats["unreclaimed"]
        row["retires"] = pool_stats["retires"]
        row["frees"] = pool_stats["frees"]
        row["slow_paths"] = pool_stats.get("slow_paths", 0)
        if "batches_sealed" in pool_stats:  # Crystalline telemetry
            row["batches_sealed"] = pool_stats["batches_sealed"]
            row["batches_freed"] = pool_stats["batches_freed"]
        out["schemes"][scheme] = row
        print(f"{scheme:>12s} {row['ttft']['p50_ms']:>12.1f} "
              f"{row['tpot']['p50_ms']:>12.1f} {row['tok_s']:>8.1f} "
              f"{row['retires']:>8d} {row['unreclaimed']:>12d}")
    rows = out["schemes"]
    if "WFE" in rows and "Crystalline" in rows:
        out["crystalline_vs_wfe"] = (rows["WFE"]["tpot"]["p50_ms"]
                                     / rows["Crystalline"]["tpot"]["p50_ms"])
        verdict = ("beats" if out["crystalline_vs_wfe"] > 1 else "trails")
        print(f"Crystalline vs WFE decode TPOT (p50): "
              f"{out['crystalline_vs_wfe']:.2f}x — batched retirement "
              f"{verdict} per-block retirement on this runner "
              f"(informational, not gated)")
    return out


# ---------------------------------------------------- open-loop goodput
def run_open_loop(arrival_rate: float = None, n_requests: int = 24,
                  prompt_len: int = 24, new_tokens: int = 8,
                  chunk_size: int = 8, block_size: int = 4,
                  batch_frac: float = 0.5, scheme: str = "WFE",
                  sched_policy: str = "mixed", seed: int = 0,
                  ttft_slo_mult: float = 10.0, tpot_slo_mult: float = 5.0,
                  ttft_slo_ms: float = None, tpot_slo_ms: float = None,
                  build=_build_base) -> dict:
    """Open-loop Poisson arrivals: goodput-under-SLO + per-token gaps.

    Closed-loop scenarios submit everything up front and measure a
    DRAINING queue — sustained prompt arrival concurrent with live decode
    (the load that starves a TTFT-first planner) never occurs.  Here a
    feeder thread submits ``n_requests`` requests on a Poisson clock
    (exponential inter-arrivals at ``arrival_rate`` req/s; default = the
    warmup pass's measured service rate, i.e. AT capacity, so queueing
    pressure builds stochastically) while the main thread serves.
    ``batch_frac`` of arrivals are batch-class; the rest interactive.

    A request meets its SLO when TTFT <= target AND TPOT <= target.
    Targets default to runner-independent MULTIPLES of an unloaded
    calibration pass (requests served one at a time after warmup):
    ``ttft_slo_mult`` x unloaded TTFT p50, ``tpot_slo_mult`` x unloaded
    TPOT p50 — override with absolute ``*_slo_ms``.  Goodput = fraction
    of finished requests meeting SLO, reported overall and per class.
    ``gap`` percentiles summarize each request's WORST inter-token gap —
    the starvation symptom the TPOT mean hides.
    """
    cfg, params = build()
    n_blocks = n_requests * (-(-(prompt_len + new_tokens) // block_size)) + 8
    engine = ServeEngine(cfg, params, n_blocks=n_blocks,
                         block_size=block_size, max_batch=4,
                         scheme=scheme, chunk_size=chunk_size,
                         sched_policy=sched_policy,
                         era_freq=8, cleanup_freq=8)
    tid = engine.pool.register_thread()
    rng = np.random.default_rng(seed)

    def prompts():
        return [[1 + (i * 7 + j) % 31 for j in range(prompt_len)]
                for i in range(n_requests)]

    # warmup: compiles every shape bucket AND measures the service rate
    t0 = time.perf_counter()
    for p in prompts():
        engine.submit(p, new_tokens)
    engine.run(tid)
    service_rate = n_requests / (time.perf_counter() - t0)
    # unloaded calibration: one request at a time — no queueing in TTFT
    calib = []
    for p in prompts()[:4]:
        calib.append(engine.submit(p, new_tokens))
        engine.run(tid)
    unloaded = latency_summary(calib)
    if ttft_slo_ms is None:
        ttft_slo_ms = ttft_slo_mult * unloaded["ttft"]["p50_ms"]
    if tpot_slo_ms is None and unloaded["tpot"]["p50_ms"] is not None:
        tpot_slo_ms = tpot_slo_mult * unloaded["tpot"]["p50_ms"]
    if arrival_rate is None:
        arrival_rate = service_rate  # AT capacity: pressure builds

    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate, n_requests))
    slos = ["batch" if rng.random() < batch_frac else "interactive"
            for _ in range(n_requests)]
    reqs: list = []
    done = threading.Event()

    def feeder():
        start = time.perf_counter()
        for p, at, slo in zip(prompts(), arrivals, slos):
            lag = start + at - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            reqs.append(engine.submit(p, new_tokens, slo=slo))
        done.set()

    before = dict(engine.sched.stats)  # counters are cumulative
    t0 = time.perf_counter()
    th = threading.Thread(target=feeder, daemon=True)
    th.start()
    while (not done.is_set() or engine.sched.pending()
           or engine.sched.active):
        if not engine.step(tid):
            engine.sched.wait_for_work(0.001)
    th.join()
    wall = time.perf_counter() - t0
    engine.drain(tid)
    after = engine.sched.stats
    assert all(r.done for r in reqs)

    def meets_slo(r) -> bool:
        if r.ttft is None or r.ttft * 1e3 > ttft_slo_ms:
            return False
        if tpot_slo_ms is not None and r.tpot is not None \
                and r.tpot * 1e3 > tpot_slo_ms:
            return False
        return True

    def goodput(rs) -> float:
        return sum(meets_slo(r) for r in rs) / len(rs) if rs else None

    inter = [r for r in reqs if r.slo == "interactive"]
    batch = [r for r in reqs if r.slo == "batch"]
    out = latency_summary(reqs)
    out.update({
        "arrival_rate": float(arrival_rate),
        "service_rate": float(service_rate),
        "batch_frac": batch_frac, "sched_policy": sched_policy,
        "scheme": scheme, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "ttft_slo_ms": float(ttft_slo_ms),
        "tpot_slo_ms": None if tpot_slo_ms is None else float(tpot_slo_ms),
        "unloaded": unloaded,
        "goodput": goodput(reqs),
        "goodput_interactive": goodput(inter),
        "goodput_batch": goodput(batch),
        "n_interactive": len(inter), "n_batch": len(batch),
        "gap": _pct([r.max_gap for r in reqs if r.t_last is not None]),
        "tok_s": n_requests * new_tokens / wall,
        "mixed_steps": after["mixed_steps"] - before["mixed_steps"],
        "evictions": after["evictions"] - before["evictions"],
        "batch_evictions": (after["batch_evictions"]
                            - before["batch_evictions"]),
        "deadline_cutoffs": (after["deadline_cutoffs"]
                             - before["deadline_cutoffs"]),
    })
    print(f"\n### Open-loop serving: Poisson arrivals at "
          f"{arrival_rate:.1f} req/s (service rate {service_rate:.1f}), "
          f"{n_requests} requests, {batch_frac:.0%} batch-class, "
          f"policy={sched_policy} ({scheme})")
    print(f"SLO targets: TTFT <= {ttft_slo_ms:.1f} ms, TPOT <= "
          + (f"{tpot_slo_ms:.1f} ms" if tpot_slo_ms is not None else "-"))

    def fmt(x, d=1):
        return f"{x:.{d}f}" if x is not None else "-"

    print(f"goodput {fmt(out['goodput'], 2)} (interactive "
          f"{fmt(out['goodput_interactive'], 2)} [{len(inter)}], batch "
          f"{fmt(out['goodput_batch'], 2)} [{len(batch)}]) | "
          f"TPOT p95 {fmt(out['tpot']['p95_ms'])} p99 "
          f"{fmt(out['tpot']['p99_ms'])} ms | worst-gap p95 "
          f"{fmt(out['gap']['p95_ms'])} p99 {fmt(out['gap']['p99_ms'])} ms")
    print(f"mixed steps {out['mixed_steps']}, evictions "
          f"{out['evictions']} ({out['batch_evictions']} batch-class), "
          f"deadline cutoffs {out['deadline_cutoffs']}")
    return out


# ----------------------------------------------------- cancellation scenario
def run_cancellation(cancel_frac: float = 0.5, cancel_after: int = 3,
                     arrival_rate: float = None, n_requests: int = 16,
                     prompt_len: int = 8, new_tokens: int = 16,
                     block_size: int = 4, chunk_size: int = 8,
                     scheme: str = "WFE", seed: int = 0,
                     build=_build_base) -> dict:
    """Open-loop arrivals where a fraction of clients ABANDON mid-flight.

    The adversarial reclamation pattern the serving front-end introduces:
    blocks die because the client left, not because generation finished.
    A feeder thread submits requests on a Poisson clock; ``cancel_frac``
    of them carry an ``on_token`` hook that cancels after
    ``cancel_after`` generated tokens (the disconnect path — the hook
    runs under the scheduler lock, exactly like the edge's
    ``call_soon_threadsafe`` handoff), and every fourth cancelled request
    is instead cancelled by the FEEDER right after submit — a genuine
    cross-thread race against admission (the queued-cancel path).

    Reports (definitions in docs/benchmarks.md):

    * ``wasted_frac`` — tokens generated for cancelled requests / all
      generated tokens: the compute the server spent on clients that left;
    * ``cancel_latency`` — percentiles of ``Request.cancel_latency``
      (cancel() -> blocks released): how long an abandoned request kept
      its pages referenced;
    * ``unreclaimed`` — MUST be 0 after the drain: every abandoned page
      flowed through the refcount/era path back to the free list.
    """
    cfg, params = build()
    n_blocks = n_requests * (-(-(prompt_len + new_tokens) // block_size)) + 8
    engine = ServeEngine(cfg, params, n_blocks=n_blocks,
                         block_size=block_size, max_batch=4,
                         scheme=scheme, chunk_size=chunk_size,
                         era_freq=4, cleanup_freq=4)
    tid = engine.pool.register_thread()
    rng = np.random.default_rng(seed)

    def prompts():
        return [[1 + (i * 7 + j) % 31 for j in range(prompt_len)]
                for i in range(n_requests)]

    # warmup: compiles every shape bucket AND measures the service rate
    t0 = time.perf_counter()
    for p in prompts():
        engine.submit(p, new_tokens)
    engine.run(tid)
    service_rate = n_requests / (time.perf_counter() - t0)
    if arrival_rate is None:
        arrival_rate = service_rate  # AT capacity: queues actually form

    # Bresenham spread: floor((i+1)f) > floor(if) picks ~frac of indices
    cancel_set = {i for i in range(n_requests)
                  if int((i + 1) * cancel_frac) > int(i * cancel_frac)}
    queued_set = {i for k, i in enumerate(sorted(cancel_set)) if k % 4 == 3}

    def cancel_hook(req, index, tok, k=cancel_after):
        if index + 1 >= k:  # runs under the scheduler lock (RLock): safe
            engine.cancel(req)

    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate, n_requests))
    reqs: list = []
    done = threading.Event()

    def feeder():
        start = time.perf_counter()
        for i, (p, at) in enumerate(zip(prompts(), arrivals)):
            lag = start + at - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            hook = cancel_hook if i in cancel_set \
                and i not in queued_set else None
            r = engine.submit(p, new_tokens, on_token=hook)
            reqs.append(r)
            if i in queued_set:  # cross-thread race against admission
                engine.cancel(r)
        done.set()

    before = dict(engine.sched.stats)  # counters are cumulative
    t0 = time.perf_counter()
    th = threading.Thread(target=feeder, daemon=True)
    th.start()
    while (not done.is_set() or engine.sched.pending()
           or engine.sched.active):
        if not engine.step(tid):
            engine.sched.wait_for_work(0.001)
    th.join()
    wall = time.perf_counter() - t0
    unreclaimed = engine.drain(tid)
    after = engine.sched.stats

    survivors = [r for r in reqs if r.state == "done"]
    assert all(r.done for r in survivors)
    n_cancelled = after["cancelled"] - before["cancelled"]
    wasted = after["cancelled_tokens"] - before["cancelled_tokens"]
    total_generated = wasted + len(survivors) * new_tokens
    out = latency_summary(survivors)
    out.update({
        "cancel_frac": cancel_frac, "cancel_after": cancel_after,
        "arrival_rate": float(arrival_rate),
        "service_rate": float(service_rate),
        "scheme": scheme, "n_requests": n_requests,
        "n_cancelled": n_cancelled,
        "n_cancelled_queued": len(queued_set),
        "cancelled_blocks": (after["cancelled_blocks"]
                             - before["cancelled_blocks"]),
        "wasted_tokens": wasted,
        "wasted_frac": (wasted / total_generated if total_generated else 0.0),
        "cancel_latency": _pct([r.cancel_latency for r in reqs
                                if r.cancel_latency is not None]),
        "unreclaimed": unreclaimed,
        "tok_s": total_generated / wall,
    })
    print(f"\n### Cancellation: open-loop at {arrival_rate:.1f} req/s, "
          f"{cancel_frac:.0%} of {n_requests} clients abandon after "
          f"{cancel_after} tokens ({len(queued_set)} while queued) "
          f"({scheme})")

    def fmt(x, d=2):
        return f"{x:.{d}f}" if x is not None else "-"

    print(f"cancelled {n_cancelled} requests ({out['cancelled_blocks']} "
          f"blocks released), wasted-tokens fraction "
          f"{out['wasted_frac']:.2f} | cancel latency p50 "
          f"{fmt(out['cancel_latency']['p50_ms'], 1)} p95 "
          f"{fmt(out['cancel_latency']['p95_ms'], 1)} ms | "
          f"unreclaimed {unreclaimed}")
    ok = (unreclaimed == 0 and n_cancelled > 0
          and 0.0 <= out["wasted_frac"] <= 1.0)
    print(f"[{'PASS' if ok else 'FAIL'}: abandoned pages must reclaim "
          f"through the refcount/era path]")
    return out


# ------------------------------------------------- fault-tolerance scenario
def run_fault_tolerance(schemes=("WFE", "Crystalline", "HE", "EBR",
                                 "2GEIBR"),
                        fault_rate: float = None, seed: int = 0,
                        n_requests: int = 10, new_tokens: int = 6,
                        chunk_size: int = 8, n_workers: int = 2,
                        build=_build_base) -> dict:
    """Chaos scenario: seeded worker crashes under the supervised runtime.

    Every scheme's engine runs the same workload with the fault injector
    armed: one deterministic crash per named crash point (before_tick /
    after_reservation / after_dispatch — at least 3 crashes per scheme),
    plus an optional ``fault_rate`` of extra per-event crashes drawn from
    the seeded per-site streams (``--fault-rate``).  The supervisor must
    reap each dead tid's era reservation, requeue its in-flight rows
    through the eviction-rewind path, and respawn a replacement on a
    fresh tid.

    Reports per scheme (definitions in docs/robustness.md):

    * ``completed_despite_faults`` — completed / submitted: MUST be 1.0
      (crash-requeued requests replay to completion, none lost or
      double-finished);
    * ``token_exact`` — survivors' generated tokens match a fault-free
      single-worker reference run (greedy decode replays exactly);
    * ``recovery_latency`` — percentiles of crash-detected -> the
      replacement worker's first productive step;
    * ``crash_wasted_frac`` — tokens generated then discarded by the
      requeue rewind / all generated tokens (the compute a crash costs);
    * ``unreclaimed`` — MUST be 0 after the drain: reaping the dead tids
      unpinned every era reservation they held.
    """
    cfg, params = build()

    def prompts():
        return [[1 + (i * 7 + j) % 29 for j in range(1 + i % 5)]
                for i in range(n_requests)]

    def make_engine(scheme):
        # max_threads: workers + supervisor + one fresh tid per respawn
        return ServeEngine(cfg, params, n_blocks=64, block_size=4,
                           max_batch=4, scheme=scheme,
                           chunk_size=min(chunk_size, 8), max_threads=16,
                           max_inflight=4, era_freq=2, cleanup_freq=2)

    # fault-free greedy reference (tokens are scheme-independent: the SMR
    # layer never touches sampling)
    ref_engine = make_engine(schemes[0])
    ref_reqs = [ref_engine.submit(p, new_tokens) for p in prompts()]
    ref_engine.run(ref_engine.pool.register_thread())
    reference = [list(r.generated) for r in ref_reqs]

    # one deterministic crash per point (the >= 3 floor the CI gate
    # needs), plus the rate-drawn chaos stream when --fault-rate is set
    spec_kw = dict(seed=seed, crash_at=(
        ("before_tick", 2), ("after_reservation", 1), ("after_dispatch", 3)))
    if fault_rate:
        spec_kw.update(crash_rate=fault_rate, max_crashes=6)

    rows: dict = {}
    print(f"\n### Fault tolerance: 3 seeded crashes/scheme"
          + (f" + crash_rate={fault_rate}" if fault_rate else "")
          + f", {n_workers} workers, {n_requests} requests")
    print(f"{'scheme':>12s} {'crashes':>8s} {'respawns':>9s} "
          f"{'completed':>10s} {'exact':>6s} {'recov p50':>10s} "
          f"{'wasted':>7s} {'unreclaimed':>12s}")
    for scheme in schemes:
        engine = make_engine(scheme)
        inj = FaultInjector(FaultSpec(**spec_kw))
        engine.set_fault_injector(inj)
        reqs = [engine.submit(p, new_tokens) for p in prompts()]
        runtime = ServeRuntime(engine, n_workers=n_workers)
        t0 = time.perf_counter()
        stats = runtime.serve()
        wall = time.perf_counter() - t0
        survivors = [r for r in reqs if r.state == "done"]
        token_exact = all(list(r.generated) == want
                          for r, want in zip(reqs, reference)
                          if r.state == "done")
        wasted = stats.get("crash_wasted_tokens", 0)
        total_generated = wasted + sum(len(r.generated) for r in survivors)
        recovery = _pct(runtime.recovery_latencies)
        row = {
            "scheme": scheme,
            "n_crashes": inj.n_crashes,
            "crashes_by_point": dict(inj.crashes),
            "n_respawns": runtime.n_respawns,
            "completed": stats["completed"],
            "failed": stats.get("failed", 0),
            "completed_despite_faults": (
                stats["completed"] / n_requests if n_requests else 0.0),
            "token_exact": bool(token_exact),
            "recovery_latency": recovery,
            "crash_requeues": stats.get("crash_requeues", 0),
            "crash_wasted_tokens": wasted,
            "crash_wasted_frac": (wasted / total_generated
                                  if total_generated else 0.0),
            "unreclaimed": stats["unreclaimed"],
            "tok_s": total_generated / wall,
        }
        rows[scheme] = row
        p50 = recovery["p50_ms"]
        print(f"{scheme:>12s} {row['n_crashes']:>8d} "
              f"{row['n_respawns']:>9d} "
              f"{row['completed']:>6d}/{n_requests:<3d} "
              f"{'yes' if token_exact else 'NO':>6s} "
              f"{'-' if p50 is None else f'{p50:.1f} ms':>10s} "
              f"{row['crash_wasted_frac']:>7.2f} "
              f"{row['unreclaimed']:>12d}")
    total_crashes = sum(r["n_crashes"] for r in rows.values())
    ok = (total_crashes >= 3 * len(schemes)
          and all(r["n_respawns"] > 0
                  and r["completed_despite_faults"] == 1.0
                  and r["token_exact"] and r["unreclaimed"] == 0
                  for r in rows.values()))
    print(f"[{'PASS' if ok else 'FAIL'}: every request completes despite "
          f"{total_crashes} injected crashes, survivors token-exact, "
          f"post-drain unreclaimed == 0]")
    return {
        "schemes": rows,
        "n_requests": n_requests,
        "new_tokens": new_tokens,
        "n_workers": n_workers,
        "fault_rate": fault_rate,
        "seed": seed,
        "total_crashes": total_crashes,
    }


def run_smoke(chunk_size: int = 8) -> dict:
    """Seconds-scale CI smoke: tiny config, short prompts, same schema."""
    return {
        "schema": "serve_bench/ttft_tpot/v1",
        "mode": "smoke",
        "prefill_heavy": run_prefill_heavy(
            chunk_size=chunk_size, prompt_len=24, n_requests=4,
            new_tokens=3, block_size=4),
        "prefix_heavy": run_prefix_heavy(
            chunk_size=chunk_size, shared_len=16, tail_len=8,
            n_requests=4, new_tokens=3, block_size=4),
        "decode_heavy": run_decode_heavy(
            chunk_size=chunk_size, n_short=6, n_long=2,
            short_new=8, long_new=190, block_size=2),
        # the SCALED model on purpose: on the tiny smoke config the step
        # is all pool arithmetic, so int8's extra quant ops read as a
        # spurious ~1.3x TPOT "regression" — on a model where matmuls
        # carry their real weight the ratio sits under 1.0 and the 1.05
        # no-harm gate has headroom instead of noise
        "kv_dtype": run_kv_dtype(
            chunk_size=chunk_size, n_requests=6, new_tokens=12,
            block_size=4, build=_build_bench),
        "scheme_matrix": run_scheme_matrix(
            schemes=("WFE", "Crystalline"), n_requests=4,
            new_tokens=8, chunk_size=chunk_size),
        "open_loop": run_open_loop(
            n_requests=16, prompt_len=16, new_tokens=6,
            chunk_size=chunk_size, block_size=4),
        "cancellation": run_cancellation(
            cancel_frac=0.5, cancel_after=2, n_requests=12,
            prompt_len=8, new_tokens=8, chunk_size=chunk_size,
            block_size=4),
        # two schemes in smoke (one per reap specialization: WFE's
        # slow-path cancel + the shared end_op path); --fault-rate runs
        # the full five-scheme matrix
        "fault_tolerance": run_fault_tolerance(
            schemes=("WFE", "EBR"), n_requests=8, new_tokens=5,
            chunk_size=chunk_size),
    }


#: required (section, mode, metric) shape of the ttft_tpot schema — the
#: bench validates its OWN output and exits nonzero on a mismatch, so the
#: CI gate never green-lights a silently malformed JSON
_TTFT_SCHEMA_MODES = {"prefill_heavy": ("token_at_a_time", "chunked"),
                      "prefix_heavy": ("uncached", "cached"),
                      "decode_heavy": ("pow2", "coarse"),
                      "kv_dtype": ("fp32", "int8")}

#: per-section headline metric the validator requires to be numeric
_HEADLINES = {"prefill_heavy": "ttft_speedup",
              "prefix_heavy": "hit_rate",
              "decode_heavy": "tpot_speedup",
              "kv_dtype": "tpot_ratio"}

#: schemes the scheme_matrix section must cover when present (--smoke
#: always runs both; the full matrix adds the rest of the registry)
_SCHEME_MATRIX_REQUIRED = ("WFE", "Crystalline")


def validate_results(results: dict) -> list:
    """Schema/shape check of a ttft_tpot results dict -> list of errors."""
    errors = []
    if results.get("schema") != "serve_bench/ttft_tpot/v1":
        errors.append(f"bad schema: {results.get('schema')!r}")
    present = [s for s in _TTFT_SCHEMA_MODES if s in results]
    if not present and not any(
            s in results
            for s in ("scheme_matrix", "open_loop", "cancellation",
                      "fault_tolerance")):
        errors.append("no scenario section "
                      f"({'/'.join(_TTFT_SCHEMA_MODES)}/scheme_matrix/"
                      "open_loop/cancellation/fault_tolerance)")
    for section in present:
        sec = results[section]
        for mode in _TTFT_SCHEMA_MODES[section]:
            if mode not in sec:
                errors.append(f"{section}: missing mode {mode!r}")
                continue
            for metric in ("ttft", "tpot"):
                row = sec[mode].get(metric)
                if not isinstance(row, dict) or "p50_ms" not in row:
                    errors.append(f"{section}.{mode}.{metric}: no p50_ms")
                elif metric == "ttft" and row["p50_ms"] is None:
                    # tpot p50 is legitimately None when < 2 tokens were
                    # generated (--new-tokens 1); ttft never is
                    errors.append(f"{section}.{mode}.ttft: p50_ms is None")
            if "dispatches" not in sec[mode]:
                errors.append(f"{section}.{mode}: missing dispatches")
        headline = _HEADLINES[section]
        if not isinstance(sec.get(headline), (int, float)):
            errors.append(f"{section}: missing {headline}")
    if "kv_dtype" in results:
        sec = results["kv_dtype"]
        for mode in _TTFT_SCHEMA_MODES["kv_dtype"]:
            if mode in sec and not isinstance(
                    sec[mode].get("kv_bytes_per_step"), (int, float)):
                errors.append(f"kv_dtype.{mode}: missing kv_bytes_per_step")
        if not isinstance(sec.get("kv_bytes_saved_frac"), (int, float)):
            errors.append("kv_dtype: missing kv_bytes_saved_frac")
    if "open_loop" in results:
        sec = results["open_loop"]
        for metric in ("ttft", "tpot", "gap"):
            row = sec.get(metric)
            if not isinstance(row, dict) or "p99_ms" not in row:
                errors.append(f"open_loop.{metric}: no p99_ms")
        # goodput-under-SLO is the scenario's headline: overall and the
        # interactive split must be present and numeric (batch goodput may
        # legitimately be None when no batch-class request arrived)
        for key in ("goodput", "goodput_interactive"):
            if not isinstance(sec.get(key), (int, float)):
                errors.append(f"open_loop: missing {key}")
        if not sec.get("n_interactive"):
            errors.append("open_loop: no interactive-class requests "
                          "(the goodput gate would be vacuous)")
        if not isinstance(sec.get("ttft_slo_ms"), (int, float)):
            errors.append("open_loop: missing ttft_slo_ms")
    if "cancellation" in results:
        sec = results["cancellation"]
        wf = sec.get("wasted_frac")
        if not isinstance(wf, (int, float)) or not 0.0 <= wf <= 1.0:
            errors.append(f"cancellation: wasted_frac = {wf!r} "
                          "(must be numeric in [0, 1])")
        if not sec.get("n_cancelled"):
            errors.append("cancellation: n_cancelled == 0 (the scenario "
                          "must actually abandon requests)")
        elif not isinstance(sec.get("cancel_latency", {}).get("p50_ms"),
                            (int, float)):
            errors.append("cancellation: missing cancel_latency.p50_ms")
        # machine-independent: every abandoned page must reclaim
        if sec.get("unreclaimed") != 0:
            errors.append(f"cancellation: unreclaimed = "
                          f"{sec.get('unreclaimed')!r} (drain must reach 0)")
    if "fault_tolerance" in results:
        sec = results["fault_tolerance"]
        rows = sec.get("schemes")
        if not isinstance(rows, dict) or not rows:
            errors.append("fault_tolerance: missing schemes table")
            rows = {}
        for name, row in rows.items():
            # the scenario must actually crash workers and recover them
            if row.get("n_crashes", 0) < 3:
                errors.append(f"fault_tolerance.{name}: n_crashes = "
                              f"{row.get('n_crashes')!r} (< 3 — one "
                              "seeded crash per crash point is the floor)")
            if not row.get("n_respawns"):
                errors.append(f"fault_tolerance.{name}: n_respawns == 0 "
                              "(the supervisor never recovered a worker)")
            cdf = row.get("completed_despite_faults")
            if cdf != 1.0:
                errors.append(f"fault_tolerance.{name}: "
                              f"completed_despite_faults = {cdf!r} "
                              "(every request must complete exactly once)")
            if not row.get("token_exact"):
                errors.append(f"fault_tolerance.{name}: crash-requeued "
                              "requests replayed differently from the "
                              "fault-free reference")
            wf = row.get("crash_wasted_frac")
            if not isinstance(wf, (int, float)) or not 0.0 <= wf <= 1.0:
                errors.append(f"fault_tolerance.{name}: crash_wasted_frac "
                              f"= {wf!r} (must be numeric in [0, 1])")
            # recovery latency is informational (machine-dependent) but
            # the percentile block must be present and well-formed
            rl = row.get("recovery_latency")
            if not isinstance(rl, dict) or "p50_ms" not in rl:
                errors.append(f"fault_tolerance.{name}: missing "
                              "recovery_latency.p50_ms")
            # machine-independent: reaping dead tids must unpin every era
            if row.get("unreclaimed") != 0:
                errors.append(f"fault_tolerance.{name}: unreclaimed = "
                              f"{row.get('unreclaimed')!r} "
                              "(drain must reach 0)")
    if "scheme_matrix" in results:
        sec = results["scheme_matrix"]
        rows = sec.get("schemes")
        if not isinstance(rows, dict):
            errors.append("scheme_matrix: missing schemes table")
            rows = {}
        for name in _SCHEME_MATRIX_REQUIRED:
            if name not in rows:
                errors.append(f"scheme_matrix: missing scheme {name!r}")
                continue
            row = rows[name]
            for metric in ("ttft", "tpot"):
                m = row.get(metric)
                if not isinstance(m, dict) or m.get("p50_ms") is None:
                    errors.append(
                        f"scheme_matrix.{name}.{metric}: no p50_ms")
            # machine-independent: every engine's drain must reclaim all
            if row.get("unreclaimed") != 0:
                errors.append(
                    f"scheme_matrix.{name}: unreclaimed = "
                    f"{row.get('unreclaimed')!r} (drain must reach 0)")
        if not isinstance(sec.get("crystalline_vs_wfe"), (int, float)):
            errors.append("scheme_matrix: missing crystalline_vs_wfe")
    return errors


# ------------------------------------------------------------- scaling matrix
class _Cell:
    """One (scheme, workers, shards) engine + its runtime, reused per rep."""

    def __init__(self, cfg, params, *, scheme, workers, shards, n_requests,
                 new_tokens, n_blocks, max_batch, block_size=4):
        self.workers, self.shards = workers, shards
        self.n_requests, self.new_tokens = n_requests, new_tokens
        self.engine = ServeEngine(
            cfg, params, n_blocks=n_blocks, block_size=block_size,
            max_batch=max_batch, scheme=scheme, n_shards=shards,
            max_threads=workers + 2, max_inflight=max(4, 2 * workers),
            era_freq=16, cleanup_freq=16)
        self.runtime = ServeRuntime(self.engine, n_workers=workers)
        self.tok_s: list = []
        self.last: dict = {}

    def one_pass(self) -> dict:
        for i in range(self.n_requests):
            prompt = [1 + (i + j) % 7 for j in range(1 + i % 4)]
            self.engine.submit(prompt, self.new_tokens)
        return self.runtime.serve()

    def timed_pass(self) -> None:
        done_before = self.engine.sched.stats["completed"]
        stats = self.one_pass()
        completed = stats["completed"] - done_before  # stats are cumulative
        self.tok_s.append(completed * self.new_tokens / stats["wall_s"])
        self.last = stats

    def row(self) -> dict:
        pool_stats = self.engine.pool.stats()
        return {
            "tok_s": float(np.median(self.tok_s)),
            "tok_s_all": list(self.tok_s),
            "completed": self.last["completed"],
            "unreclaimed": self.last["unreclaimed"],
            "worker_steps": self.last["worker_steps"],
            "era_spread": pool_stats.get("era_spread", 0),
            "era_merges": pool_stats.get("era_merges", 0),
        }


def run_scaling(workers: int = 4, shards: int = 4,
                schemes=("WFE", "Crystalline", "HE", "EBR", "2GEIBR"),
                n_requests: int = 64, new_tokens: int = 16,
                n_blocks: int = 512, max_batch: int = 8,
                reps: int = 3, build=_build_bench) -> dict:
    """Throughput matrix: (1,1) baseline vs (workers, shards) per scheme.

    Reps are INTERLEAVED across configs (A/B/A/B...) and the median is
    reported: shared-vCPU hosts drift over seconds, so back-to-back
    per-config timing would fold that drift into the comparison.
    """
    cfg, params = build()
    configs = [(1, 1)]
    if workers > 1:
        configs.append((workers, 1))
    if (workers, shards) not in configs:
        configs.append((workers, shards))
    cells = {(sc, w, s): _Cell(cfg, params, scheme=sc, workers=w, shards=s,
                               n_requests=n_requests, new_tokens=new_tokens,
                               n_blocks=n_blocks, max_batch=max_batch)
             for sc in schemes for (w, s) in configs}
    old_switch = sys.getswitchinterval()
    sys.setswitchinterval(0.0005)  # ms-scale steps need ms-scale GIL slices
    try:
        for cell in cells.values():
            cell.one_pass()  # warmup: compiles every shape bucket
        for _ in range(reps):
            for cell in cells.values():
                cell.timed_pass()
    finally:
        sys.setswitchinterval(old_switch)
    out: dict = {}
    print("\n### Sharded multi-worker serving: throughput by "
          "workers x shards x scheme")
    print(f"{'scheme':>8s} {'workers':>8s} {'shards':>7s} {'tok/s':>9s} "
          f"{'speedup':>8s} {'unreclaimed':>12s} {'era spread':>11s}")
    for sc in schemes:
        base_tok_s = None
        for (w, s) in configs:
            row = cells[(sc, w, s)].row()
            if base_tok_s is None:
                base_tok_s = row["tok_s"]
            row["speedup"] = row["tok_s"] / base_tok_s
            out[(sc, w, s)] = row
            print(f"{sc:>8s} {w:>8d} {s:>7d} {row['tok_s']:>9.1f} "
                  f"{row['speedup']:>7.2f}x {row['unreclaimed']:>12d} "
                  f"{row['era_spread']:>11d}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--schemes", nargs="*",
                    default=["WFE", "Crystalline", "HE", "EBR", "2GEIBR"])
    # None = per-mode default (64/16 for the scaling matrix, 8/4 for the
    # prefill-heavy scenario) — a value-equality sentinel could not tell
    # an explicit 64 from the default
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--new-tokens", type=int, default=None)
    ap.add_argument("--n-blocks", type=int, default=512)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="prefill chunk token budget (C) for the "
                         "prefill-heavy scenario")
    ap.add_argument("--prompt-len", type=int, default=96,
                    help="prompt length (P) for the prefill-heavy scenario")
    ap.add_argument("--prefill-heavy", action="store_true",
                    help="run the chunked-prefill TTFT/TPOT scenario "
                         "instead of the scaling matrix")
    ap.add_argument("--prefix-heavy", action="store_true",
                    help="run the prefix-caching scenario (shared system "
                         "prompt, divergent tails): hit-rate + TTFT "
                         "with/without caching")
    ap.add_argument("--decode-heavy", action="store_true",
                    help="run the bucket-policy scenario (a few long-"
                         "generation requests pin the table width over "
                         "many short ones): TPOT + per-shape compile "
                         "counts for pow2 vs coarse (maxlen) buckets")
    ap.add_argument("--kv-dtype", action="store_true",
                    help="run the int8-vs-fp32 KV pool A/B on a decode-"
                         "heavy workload: TPOT ratio + analytic KV "
                         "bytes/step (fused in-kernel dequant)")
    ap.add_argument("--long-new", type=int, default=190,
                    help="tokens generated by each long request in "
                         "--decode-heavy (the skew driver)")
    ap.add_argument("--shared-len", type=int, default=64,
                    help="shared system-prompt length for --prefix-heavy")
    ap.add_argument("--tail-len", type=int, default=16,
                    help="divergent tail length for --prefix-heavy")
    ap.add_argument("--open-loop", action="store_true",
                    help="run the open-loop Poisson-arrival scenario: "
                         "goodput-under-SLO by class + TPOT/worst-gap "
                         "p95/p99 (the decode-starvation measurement)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="open-loop Poisson arrival rate in req/s "
                         "(default: the warmup pass's measured service "
                         "rate — serving AT capacity)")
    ap.add_argument("--batch-frac", type=float, default=0.5,
                    help="fraction of open-loop arrivals tagged "
                         "batch-class (admit after / shed before "
                         "interactive)")
    ap.add_argument("--sched-policy", default="mixed",
                    choices=("mixed", "prefill_first"),
                    help="planner for --open-loop: 'mixed' token budget "
                         "vs the legacy TTFT-first planner (A/B the "
                         "starvation fix)")
    ap.add_argument("--ttft-slo-ms", type=float, default=None,
                    help="absolute TTFT SLO target (default: 10x the "
                         "unloaded calibration p50)")
    ap.add_argument("--tpot-slo-ms", type=float, default=None,
                    help="absolute TPOT SLO target (default: 5x the "
                         "unloaded calibration p50)")
    ap.add_argument("--cancel-frac", type=float, default=None,
                    help="run the cancellation scenario: this fraction of "
                         "open-loop arrivals abandon mid-flight (reports "
                         "wasted-tokens fraction, cancel-latency "
                         "percentiles, unreclaimed==0)")
    ap.add_argument("--cancel-after", type=int, default=3,
                    help="generated tokens before an abandoning client "
                         "cancels (--cancel-frac scenario)")
    ap.add_argument("--fault-rate", type=float, default=None,
                    help="run the fault-tolerance chaos scenario: every "
                         "--schemes engine gets 3 deterministic seeded "
                         "worker crashes (one per crash point) plus this "
                         "per-event crash rate; gates completed-despite-"
                         "faults == 1.0, token exactness, n_respawns > 0, "
                         "unreclaimed == 0 (0.0 = deterministic crashes "
                         "only)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the --fault-rate per-site fault streams")
    ap.add_argument("--scheme-matrix", action="store_true",
                    help="run the decode-path SMR scheme comparison "
                         "(every --schemes engine on one fixed workload; "
                         "headline: Crystalline vs WFE TPOT)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI pass: tiny config, emits the "
                         "TTFT/TPOT JSON schema")
    ap.add_argument("--json", metavar="PATH",
                    help="write the structured results as JSON")
    ap.add_argument("--smoke-model", action="store_true",
                    help="use the tiny smoke config instead of the scaled "
                         "bench model (interpreter-bound; scaling flattens)")
    ap.add_argument("--latency", action="store_true",
                    help="also run the single-worker tick-latency suite")
    args = ap.parse_args(argv)
    setup_compile_cache()
    if args.smoke:
        results = run_smoke(chunk_size=min(args.chunk_size, 8))
        savings = results["decode_heavy"]["compile_savings"]
        matrix_rows = results["scheme_matrix"]["schemes"]
        ok = (results["prefill_heavy"]["ttft_speedup"] > 1.0
              and results["prefix_heavy"]["hit_rate"] > 0
              and results["prefix_heavy"]["chunks_saved"] > 0
              and results["decode_heavy"]["tpot_speedup"] > 1.0
              and savings > 0
              # int8 pages must stream fewer analytic bytes (the TPOT
              # no-harm band lives in check_regression, not here)
              and results["kv_dtype"]["kv_bytes_saved_frac"] > 0
              and all(r["unreclaimed"] == 0 for r in matrix_rows.values())
              # the starvation fix must hold under open-loop pressure:
              # some interactive request met its SLO, and the worst
              # per-token gap stayed measurable (decode kept moving)
              and results["open_loop"]["goodput_interactive"] > 0
              and results["open_loop"]["gap"]["p95_ms"] is not None
              # abandoned pages must reclaim through the refcount/era path
              and results["cancellation"]["unreclaimed"] == 0
              and results["cancellation"]["n_cancelled"] > 0
              # crashed workers must be reaped + respawned, every request
              # completing exactly once with fault-free tokens
              and all(r["unreclaimed"] == 0 and r["n_respawns"] > 0
                      and r["completed_despite_faults"] == 1.0
                      and r["token_exact"]
                      for r in results["fault_tolerance"]
                                      ["schemes"].values()))
    elif args.prefill_heavy:
        results = {"schema": "serve_bench/ttft_tpot/v1"}
        results["prefill_heavy"] = run_prefill_heavy(
            chunk_size=args.chunk_size, prompt_len=args.prompt_len,
            n_requests=args.requests or 8,
            new_tokens=args.new_tokens or 4)
        ok = results["prefill_heavy"]["ttft_speedup"] > 1.0
    elif args.prefix_heavy:
        results = {"schema": "serve_bench/ttft_tpot/v1"}
        results["prefix_heavy"] = run_prefix_heavy(
            chunk_size=args.chunk_size, shared_len=args.shared_len,
            tail_len=args.tail_len, n_requests=args.requests or 8,
            new_tokens=args.new_tokens or 4)
        ok = (results["prefix_heavy"]["hit_rate"] > 0
              and results["prefix_heavy"]["chunks_saved"] > 0)
    elif args.open_loop:
        results = {"schema": "serve_bench/ttft_tpot/v1"}
        results["open_loop"] = run_open_loop(
            arrival_rate=args.arrival_rate,
            n_requests=args.requests or 24,
            new_tokens=args.new_tokens or 8,
            chunk_size=min(args.chunk_size, 8),
            batch_frac=args.batch_frac,
            sched_policy=args.sched_policy,
            ttft_slo_ms=args.ttft_slo_ms, tpot_slo_ms=args.tpot_slo_ms)
        ok = results["open_loop"]["goodput_interactive"] > 0
    elif args.cancel_frac is not None:
        results = {"schema": "serve_bench/ttft_tpot/v1"}
        results["cancellation"] = run_cancellation(
            cancel_frac=args.cancel_frac, cancel_after=args.cancel_after,
            arrival_rate=args.arrival_rate,
            n_requests=args.requests or 16,
            new_tokens=args.new_tokens or 16,
            chunk_size=min(args.chunk_size, 8))
        sec = results["cancellation"]
        ok = (sec["unreclaimed"] == 0 and sec["n_cancelled"] > 0
              and 0.0 <= sec["wasted_frac"] <= 1.0)
    elif args.fault_rate is not None:
        results = {"schema": "serve_bench/ttft_tpot/v1"}
        results["fault_tolerance"] = run_fault_tolerance(
            schemes=tuple(args.schemes), fault_rate=args.fault_rate or None,
            seed=args.fault_seed, n_requests=args.requests or 10,
            new_tokens=args.new_tokens or 6,
            chunk_size=min(args.chunk_size, 8))
        ft = results["fault_tolerance"]
        ok = (ft["total_crashes"] >= 3 * len(ft["schemes"])
              and all(r["n_respawns"] > 0 and r["unreclaimed"] == 0
                      and r["completed_despite_faults"] == 1.0
                      and r["token_exact"]
                      for r in ft["schemes"].values()))
    elif args.scheme_matrix:
        results = {"schema": "serve_bench/ttft_tpot/v1"}
        results["scheme_matrix"] = run_scheme_matrix(
            schemes=tuple(args.schemes),
            n_requests=args.requests or 8,
            new_tokens=args.new_tokens or 16,
            chunk_size=min(args.chunk_size, 8))
        ok = all(r["unreclaimed"] == 0
                 for r in results["scheme_matrix"]["schemes"].values())
    elif args.kv_dtype:
        results = {"schema": "serve_bench/ttft_tpot/v1"}
        results["kv_dtype"] = run_kv_dtype(
            chunk_size=min(args.chunk_size, 8),
            n_requests=args.requests or 8,
            new_tokens=args.new_tokens or 16)
        ok = results["kv_dtype"]["kv_bytes_saved_frac"] > 0
    elif args.decode_heavy:
        results = {"schema": "serve_bench/ttft_tpot/v1"}
        results["decode_heavy"] = run_decode_heavy(
            chunk_size=args.chunk_size, long_new=args.long_new,
            short_new=args.new_tokens or 8)
        savings = results["decode_heavy"]["compile_savings"]
        ok = results["decode_heavy"]["tpot_speedup"] > 1.0 and savings > 0
    else:
        if args.latency:
            run()
        scaling = run_scaling(
            workers=args.workers, shards=args.shards,
            schemes=tuple(args.schemes), n_requests=args.requests or 64,
            new_tokens=args.new_tokens or 16, n_blocks=args.n_blocks,
            max_batch=args.max_batch, reps=args.reps,
            build=_build_base if args.smoke_model else _build_bench)
        results = {"schema": "serve_bench/scaling/v1", "scaling": {
            f"{sc}_w{w}_s{s}": row for (sc, w, s), row in scaling.items()}}
        ok = True
    if results["schema"] == "serve_bench/ttft_tpot/v1":
        # self-validation: a malformed results dict (schema drift, missing
        # keys, None medians) fails HERE with a nonzero exit — downstream
        # consumers (the CI perf gate) never see a silently bad JSON
        errors = validate_results(results)
        if errors:
            for e in errors:
                print(f"SCHEMA ERROR: {e}")
            ok = False
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {args.json}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
