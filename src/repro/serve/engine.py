"""Serving engine: continuous batching + WFE block pool + paged steps.

The full adaptation loop (docs/serving.md):

  submit() -> scheduler queue -> tick(): admit / allocate blocks (WFE
  alloc_block / bulk alloc_blocks) / protect_step (WFE get_protected, one
  era reservation per in-flight step) -> device step — a DECODE batch or
  a PREFILL chunk (``StepPlan.kind``) — reads K/V through the protected
  block tables -> complete(): append tokens, retire finished requests'
  blocks (WFE retire), release the step reservation, cleanup() reclaims.

Chunked prefill: a prompt materializes ``chunk_size`` tokens per dispatch
(``paged_prefill_chunk``), so a P-token prompt costs ceil(P/C) steps, not
P.  Prefill chunks dispatch through pow2 chunk-length buckets next to the
table-width buckets, and both plan kinds share the per-shard device locks
— multi-worker pipelining overlaps a prefill chunk on one shard with
decode batches on others.

Mixed batches (``sched_policy="mixed"``, the default): the scheduler's
token-budget planner packs decode rows AND one prefill chunk into a
single ``StepPlan(kind="mixed")``, executed as ONE dispatch of the
chunked kernel — decode rows are rows with ``chunk_lens == 1``, the
chunk rides in the last row.  This is the decode-starvation fix: under
sustained prompt arrival the legacy TTFT-first planner
(``sched_policy="prefill_first"``) plans prefill chunks back-to-back and
live decode requests stall unboundedly; the mixed batch funds decode
first every tick, bounding per-token gaps.  ``submit`` takes a per-
request SLO class (``slo="interactive" | "batch"``): interactive intake
admits first, and under pool pressure batch-class requests are shed
before any interactive request is preempted.

Shape buckets (``bucket_policy``): every step pads its block table to a
width bucket so XLA compiles once per bucket.  The default ``"maxlen"``
buckets on the batch's FINAL width (known at admission from prompt +
max_new_tokens): a request stays in one bucket for its whole lifetime, so
growing contexts never recompile mid-decode.  The paged kernels are
LENGTH-BOUNDED: a per-request ``num_live_blocks`` vector (derived in
``paged_model`` from lengths/positions) ends the kernel's table walk at
the last live slot — the kernel fetches and folds in only the live
pages, so a wider bucket costs nothing in the kernel; a padded row still
fetches its one scratch page.  The worker's ``kv_blocks_walked`` (pages
fetched per layer: the real rows' ``num_live_blocks`` plus one a pad
row) against ``kv_blocks_live`` counts how many.  ``"pow2"`` keeps the
legacy current-width ladder.

Prefix caching (``prefix_caching=True``, the default): prompts sharing a
block-aligned token prefix alias the same pool pages via the refcounted
``PrefixCache`` — the prefill cursor starts at the cached boundary, so
cached chunks cost ZERO dispatches and the device step never re-scatters
a cached page.  ``drain`` clears the cache first (cache references must
not pin slots past shutdown), restoring the every-block-freed invariant.

Greedy sampling; each plan kind dispatches through one jitted function.
Attention is chosen by platform (``kernels/platform.py``): the compiled
Pallas paged kernel on TPU — where the jnp reference is refused — and
the reference elsewhere, where ``use_kernel=True`` runs the kernel in the
Pallas interpreter.  Reclamation is chosen separately by
``cleanup_backend``: ``"numpy"`` (default) vectorizes the era scan on the
host, ``"pallas"`` runs the ``era_scan`` kernel.

Concurrency: ``step()`` is safe to call from many worker threads (the
``ServeRuntime`` in ``runtime.py`` does exactly that).  Scheduling and
accounting are serialized inside the scheduler; the device dispatch is
serialized by a short lock (the KV pools are a functional-update chain),
but the *blocking wait* on the result happens outside every lock — while
worker A waits on XLA, worker B plans and dispatches the next step against
a disjoint set of requests (``max_inflight`` era-reservation slots deep).

``n_shards > 1`` splits the pool into per-shard SMR instances joined by
the distributed era clock (``blocks/sharded_pool.py``): per-shard retire
lists and clocks, max-merged on step boundaries.

Shutdown runs ``drain()`` — an era-progress-bounded fleet drain that
provably terminates (every round either frees a block or ticks every era
clock, and at quiescence each scheme frees all blocks within a bounded
number of clock ticks), replacing the old fixed-64-round loop.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.blocks import (BlockPool, PrefixCache, Scheduler,
                          ShardedBlockPool)
from repro.kernels import platform
from repro.kernels.paged_attention import check_int8_scales_fit
from repro.models.common import ArchConfig

from .paged_model import (KV_DTYPES, init_pools, live_blocks,
                          paged_decode_step, paged_prefill_chunk,
                          pool_shape)

__all__ = ["ServeEngine", "device_pool_blocks", "pool_block_bytes",
           "pool_blocks_that_fit"]

#: era ticks a quiescent drain may need before every scheme must have
#: reclaimed everything: EBR's two grace periods + one for the stamp round,
#: +1 slack.  More stalled rounds than this means a reservation is still
#: held (an in-flight step) — drain returns instead of spinning.
DRAIN_ERA_BOUND = 4


@functools.lru_cache(maxsize=None)
def _jit_decode(cfg, use_kernel: bool):
    """Jitted decode step with greedy sampling fused in: the host pulls
    back (B,) sampled ids, not (B, vocab) logits.  Shared across engines
    over one (frozen, hashable) config, so they reuse one compilation
    cache; the donated pools are updated in place."""

    def _decode(params, pools, tables, lengths, tokens, positions):
        logits, pools = paged_decode_step(
            cfg, params, pools, tables, lengths, tokens, positions,
            use_kernel=use_kernel)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pools

    return jax.jit(_decode, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _jit_prefill(cfg, use_kernel: bool):
    """Jitted chunked-prefill step with fused greedy sampling of the
    chunk's last valid token (the first generated token when the chunk
    consumes the final prompt token).  Shares one compilation cache across
    engines like ``_jit_decode``; donated pools write pages in place."""

    def _prefill(params, pools, tables, tokens, positions, chunk_lens):
        logits, pools = paged_prefill_chunk(
            cfg, params, pools, tables, tokens, positions, chunk_lens,
            use_kernel=use_kernel)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pools

    return jax.jit(_prefill, donate_argnums=(1,))


def pool_block_bytes(cfg, block_size: int, kv_dtype=None) -> int:
    """Device bytes of one pool block across all layers: its K and V pages
    (lane-packed, so no tile padding when KH*D is a multiple of 128) plus
    the int8 mode's per-(block, kv-head) f32 scales."""
    dtype = cfg.dtype if kv_dtype is None else KV_DTYPES[kv_dtype]
    n_layers, _, bs, width = pool_shape(cfg, 1, block_size)
    total = 2 * n_layers * bs * width * jnp.dtype(dtype).itemsize
    if dtype == jnp.int8:
        total += 2 * n_layers * cfg.n_kv_heads * 4
    return total


def pool_blocks_that_fit(bytes_limit: int, resident_bytes: int,
                         step_temp_bytes: int, block_bytes: int,
                         headroom: float = 0.10) -> int:
    """Blocks left by ``bytes_limit`` less ``headroom``, the resident bytes
    and a step's temporaries — less the one scratch block the engine adds
    per shard."""
    free = bytes_limit * (1.0 - headroom) - resident_bytes - step_temp_bytes
    return int(free // block_bytes) - 1


def device_pool_blocks(cfg, params, *, block_size: int, max_batch: int,
                       chunk_size: int, max_len: int, kv_dtype=None,
                       headroom: float = 0.10, min_tokens: int = 8192,
                       probe_blocks: int = 64) -> tuple:
    """The largest pool the device's memory leaves room for.

    Compiles the decode step and the widest mixed step (``max_batch``
    decode rows plus one ``chunk_size`` chunk, at the table width of
    ``max_len`` tokens) against a ``probe_blocks`` pool and takes the
    larger temporary footprint — the steps update the pools in place, so
    their temporaries do not grow with the pool.  What is left of
    ``bytes_limit`` after ``headroom``, the resident bytes (the weights)
    and those temporaries is divided into blocks of ``pool_block_bytes``.
    Returns ``(n_blocks, info)``; raises if the device reports no memory
    statistics or the pool would hold fewer than ``min_tokens`` tokens.
    """
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(f"{jax.devices()[0]} reports no memory "
                           "statistics: give n_blocks explicitly")
    use_kernel = platform.resolve_use_kernel(None)
    pools = jax.eval_shape(
        lambda: init_pools(cfg, probe_blocks, block_size, kv_dtype))
    width = 1 << max(0, -(-max_len // block_size) - 1).bit_length()
    cb = 1 << max(0, chunk_size - 1).bit_length()
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    temps = []
    for fn, args in (
            (_jit_decode(cfg, use_kernel),
             (i32(max_batch, width), i32(max_batch), i32(max_batch),
              i32(max_batch))),
            (_jit_prefill(cfg, use_kernel),
             (i32(max_batch + 1, width), i32(max_batch + 1, cb),
              i32(max_batch + 1, cb), i32(max_batch + 1)))):
        mem = fn.lower(params, pools, *args).compile().memory_analysis()
        temps.append(mem.temp_size_in_bytes)
    per_block = pool_block_bytes(cfg, block_size, kv_dtype)
    n_blocks = pool_blocks_that_fit(stats["bytes_limit"],
                                    stats["bytes_in_use"], max(temps),
                                    per_block, headroom)
    info = dict(bytes_limit=stats["bytes_limit"],
                resident_bytes=stats["bytes_in_use"],
                step_temp_bytes=max(temps), block_bytes=per_block,
                n_blocks=n_blocks, tokens=n_blocks * block_size)
    if n_blocks * block_size < min_tokens:
        raise RuntimeError(f"device pool holds {n_blocks * block_size} "
                           f"tokens, fewer than {min_tokens}: {info}")
    return n_blocks, info


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *, n_blocks: int = 64,
                 block_size: int = 8, max_batch: int = 8,
                 scheme: str = "WFE", use_kernel: Optional[bool] = None,
                 cleanup_backend: str = "numpy",
                 max_threads: int = 8, n_shards: int = 1,
                 max_inflight: int = 4, merge_freq: int = 1,
                 pad_shapes: bool = True, chunk_size: int = 16,
                 token_budget: Optional[int] = None,
                 sched_policy: str = "mixed",
                 bucket_policy: str = "maxlen",
                 prefix_caching: bool = True,
                 prefix_cache_entries: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 **smr_kwargs):
        self.cfg = cfg
        self.params = params
        self.block_size = block_size
        # attention by platform: the Pallas kernel on TPU (never the
        # reference there), the jnp reference elsewhere unless the kernel
        # is asked for (then it runs in the Pallas interpreter)
        self.use_kernel = platform.resolve_use_kernel(use_kernel)
        if platform.on_tpu() and not self.use_kernel:
            raise ValueError("use_kernel=False on TPU: the paged kernel "
                             "serves attention there; the jnp reference "
                             "is the CPU test oracle")
        # quantized KV mode: ``kv_dtype="int8"`` stores pool pages as
        # symmetric per-(block, kv-head) int8 codes with fp32 scale arrays
        # riding in the pools dict (donated alongside the pages by the
        # jitted steps — the pools pytree gains two leaves, so the shared
        # jit caches key on the new structure automatically).  Width
        # bucketing, the scratch pad slot, and ALL blocks-layer logic are
        # unchanged: scales are pool-slot-indexed (see init_pools).
        self.kv_dtype = kv_dtype
        # shape bucketing: pad every step to (max_batch, bucketed table
        # width) so XLA compiles once per bucket instead of once per
        # (B, nblk) — without it the serve loop is recompile-bound
        # (hundreds of ms per shape) and multi-worker pipelining has
        # nothing to overlap.  Width policy (the length-bounded kernel
        # walks only a row's live pages, so a padded slot costs nothing):
        #   "maxlen" (default) — pow2 of the batch's FINAL table width,
        #     known at admission (prompt + max_new_tokens), ratcheted by a
        #     per-shard high-water mark so the width never NARROWS either
        #     (a wide request completing must not push the surviving
        #     narrow batch into a never-compiled smaller shape): a shape
        #     compiles only when a wider-than-ever request class arrives;
        #   "pow2" — the legacy ladder over the CURRENT width: tight
        #     padding, but every growth past a pow2 boundary recompiles.
        if bucket_policy not in ("maxlen", "pow2"):
            raise ValueError(f"bucket_policy {bucket_policy!r}: "
                             "expected 'maxlen' or 'pow2'")
        self.bucket_policy = bucket_policy
        # per-shard width high-water marks (see "maxlen" above).  Updated
        # outside the device locks: a racing lost update merely lets a
        # narrower shape through once (one extra cached compile), never
        # an incorrect table.
        self._width_hwm = [0] * max(1, n_shards)
        self.pad_shapes = pad_shapes
        self.max_batch = max_batch
        pool_kwargs = dict(scheme=scheme, max_threads=max_threads,
                           cleanup_backend=cleanup_backend, **smr_kwargs)
        self.pool: Union[BlockPool, ShardedBlockPool]
        if n_shards > 1:
            self.pool = ShardedBlockPool(n_blocks, n_shards=n_shards,
                                         merge_freq=merge_freq, **pool_kwargs)
        else:
            self.pool = BlockPool(n_blocks, **pool_kwargs)
        # refcounted prefix cache: prompts sharing a block-aligned token
        # prefix alias the same pool pages (zero prefill dispatches for
        # the cached chunks); the LAST sharer retires a block, and the
        # era reservations keep retired pages safe against in-flight
        # readers — see blocks/prefix_cache.py and docs/serving.md
        self.prefix_cache = (
            PrefixCache(self.pool, block_size=block_size,
                        max_entries=prefix_cache_entries)
            if prefix_caching else None)
        self.sched = Scheduler(self.pool, block_size=block_size,
                               max_batch=max_batch,
                               max_inflight=max_inflight,
                               chunk_size=chunk_size,
                               token_budget=token_budget,
                               policy=sched_policy,
                               prefix_cache=self.prefix_cache)
        # ONE device-pool chain per shard: a step's functional KV update
        # depends on the previous value of the pools it touches, so a
        # single chain serializes every step's compute.  Request-level
        # sharding makes each plan touch exactly one shard's pages, giving
        # n_shards independent chains that execute concurrently.
        if n_shards > 1:
            self._shard_bases = [p.first_block for p in self.pool.shards]
            self._shard_sizes = [p.n_blocks for p in self.pool.shards]
        else:
            self._shard_bases = [0]
            self._shard_sizes = [n_blocks]
        # fault injection (serve/faults.py): None = disabled.  The plans a
        # worker has dispatched-but-not-completed are tracked per tid so a
        # supervisor can requeue them after the worker dies (the dispatch
        # is synchronous — a dead worker holds no device read in flight).
        self.faults = None
        self._inflight_plans: Dict[int, object] = {}
        pad = 1 if pad_shapes else 0
        if kv_dtype == "int8" and self.use_kernel and platform.on_tpu():
            # the kernel keeps a layer's scales in scalar memory: refuse a
            # pool too large for it here, not at the first dispatch
            check_int8_scales_fit(max(self._shard_sizes) + pad,
                                  cfg.n_kv_heads)
        # one extra scratch slot per shard absorbs the KV writes of
        # batch-padding rows — it is never handed out by the block pool, so
        # padded steps can't corrupt a live request's pages
        self._shard_pools = [init_pools(cfg, size + pad, block_size,
                                        kv_dtype=kv_dtype)
                             for size in self._shard_sizes]
        # per-shard dispatch locks: each serializes one shard's functional
        # KV-pool chain; the wait on the device result happens outside
        self._device_locks = [threading.Lock() for _ in self._shard_sizes]
        # donated pools: the step's KV scatter writes in place instead of
        # copying every page each token
        self._decode = _jit_decode(cfg, self.use_kernel)
        self._prefill = _jit_prefill(cfg, self.use_kernel)
        # the padded row count of the step this thread dispatched last:
        # ``_dispatching`` sets it, ``execute_plan`` counts from it
        self._dispatched = threading.local()

    # ------------------------------------------- compile-cache introspection
    # the jitted steps are lru-shared across engines over one config; the
    # compile-count gate (benchmarks/serve_bench.py) and the bucket-policy
    # tests read their cache counters here
    def compile_cache_size(self) -> int:
        """Total compiled shape variants of the decode+prefill steps."""
        return sum(int(fn._cache_size())
                   for fn in (self._decode, self._prefill))

    def clear_compile_caches(self) -> None:
        """Drop the compiled decode/prefill variants.  NOTE: shared across
        engines over one config."""
        for fn in (self._decode, self._prefill):
            fn.clear_cache()

    # single-shard view of the device pools (tests poison pages through it)
    @property
    def pools(self):
        return self._shard_pools[0]

    @pools.setter
    def pools(self, value):
        self._shard_pools[0] = value

    def submit(self, prompt: List[int], max_new_tokens: int,
               slo: str = "interactive", on_token=None, on_finish=None):
        return self.sched.submit(prompt, max_new_tokens, slo=slo,
                                 on_token=on_token, on_finish=on_finish)

    # ------------------------------------------------------- fault injection
    def set_fault_injector(self, injector) -> None:
        """Install (or remove, with ``None``) a ``FaultInjector``.

        Wires the allocation gate into every shard pool and arms the
        crash/poison hooks in ``step``/``execute_plan``.  Call before
        workers start; the hooks are read once per step without a lock.
        """
        self.faults = injector
        shards = getattr(self.pool, "shards", None) or [self.pool]
        gate = None if injector is None else injector.alloc_gate
        for p in shards:
            p._fault_alloc = gate

    def take_orphaned_plan(self, tid: int):
        """Pop the plan a (dead) worker dispatched but never completed.

        Returns None when the worker died outside the
        reservation-published window.  Supervisor-only: the worker must be
        joined first, so no race with its own pop in ``step``.
        """
        return self._inflight_plans.pop(tid, None)

    def cancel(self, req) -> bool:
        """Abandon a request (client disconnect / DELETE): marks it; the
        scheduler drops it at the next safe point and releases its pages
        through the normal refcount/era path (see ``Scheduler.cancel``).
        Callable from any thread.  Returns True iff this call marked it."""
        return self.sched.cancel(req)

    def step(self, tid: int) -> bool:
        """One scheduler tick + device step.  Returns False when idle.

        Thread-safe: callable concurrently from several workers (each with
        its own registered ``tid``).
        """
        faults = self.faults
        if faults is not None:
            faults.crash_point("before_tick", tid)
        plan = self.sched.tick(tid)
        if plan is None:
            return False
        # track the plan across the reservation-held window: a crash
        # anywhere between here and complete() leaves the entry behind
        # for the supervisor's requeue (take_orphaned_plan)
        self._inflight_plans[tid] = plan
        if faults is not None:
            faults.crash_point("after_reservation", tid)
        self.execute_plan(plan, tid)
        self._inflight_plans.pop(tid, None)
        return True

    def execute_plan(self, plan, tid: int) -> np.ndarray:
        """Dispatch one typed plan to the device and account the result.

        Callable on its own after ``sched.tick``, so the planner and the
        device step can be driven separately.  The step shows on a
        profiler trace as ``serve.dispatch`` (host padding and the
        enqueue, with the plan's kind, rows, padded rows and table width),
        ``serve.device_wait`` (the blocking read of the sampled ids) and
        ``serve.complete``; worker ``tid``'s stats gain the step's rows,
        KV blocks and phase times (``Scheduler`` ``STAT_KEYS``).
        """
        stats = self.sched.worker_stats(tid)
        b = len(plan.requests)
        t0 = time.perf_counter_ns()
        if plan.kind == "prefill":
            out = self._dispatch_prefill(plan)
        elif plan.kind == "mixed":
            out = self._dispatch_mixed(plan)
        else:
            out = self._dispatch_decode(plan)
        t1 = time.perf_counter_ns()
        # block on the result OUTSIDE the device lock: other workers plan,
        # dispatch and run OTHER shards' chains while this one waits
        with jax.profiler.TraceAnnotation("serve.device_wait"):
            sampled = np.asarray(out)[:b]
        stats["device_wait_ns"] += time.perf_counter_ns() - t1
        stats["dispatch_ns"] += t1 - t0
        rows = self._dispatched.rows
        stats["decode_rows"] += (plan.n_decode if plan.kind == "mixed"
                                 else b if plan.kind == "decode" else 0)
        stats["rows_dispatched"] += rows
        # every real row's last query sits at length - 1: the position
        # ``paged_model`` derives the row's num_live_blocks from, and the
        # pages the kernel fetches for it in each layer; a pad row fetches
        # the scratch slot's one page
        live = int(live_blocks(np.asarray(plan.lengths) - 1,
                               self.block_size).sum())
        stats["kv_blocks_live"] += live
        stats["kv_blocks_walked"] += live + rows - b
        faults = self.faults
        if faults is not None:
            row = faults.poison_row(len(plan.requests))
            if row is not None:
                poisoned = np.asarray(sampled, dtype=np.float64).copy()
                poisoned[row] = np.nan
                sampled = poisoned
            faults.crash_point("after_dispatch", tid)
        failed_rows = None
        arr = np.asarray(sampled)
        if not np.issubdtype(arr.dtype, np.integer):
            # graceful degradation: a non-finite sampled output (device
            # fault, poisoned logits) fails THAT request, not the batch —
            # surviving rows keep their (finite) tokens
            finite = np.isfinite(arr)
            if not finite.all():
                failed_rows = [not bool(f) for f in finite]
            sampled = np.where(finite, arr, 0).astype(np.int32)
        self.sched.complete(plan, sampled, tid, failed_rows=failed_rows)
        return sampled

    def _bucket_width(self, plan, nblk: int, shard: int) -> int:
        """Padded table width for a plan (see ``bucket_policy`` above)."""
        if self.bucket_policy != "maxlen":
            return 1 << max(0, nblk - 1).bit_length()
        # the batch's maximal FINAL table width is known at admission:
        # every request tops out at ceil((prompt + max_new) / bs) pages
        # (eviction rewinds the cursor, never the cap); the pow2 quantizer
        # bounds the shape count across heterogeneous workloads
        final = max(-(-(len(r.prompt) + r.max_new_tokens)
                      // self.block_size) for r in plan.requests)
        nblk = max(nblk, min(final, self._shard_sizes[shard]))
        w = 1 << max(0, nblk - 1).bit_length()
        if plan.kind in ("decode", "mixed"):
            # ratchet DECODE (and mixed-batch) widths: batch membership
            # changes (a wide request completing) must never shrink the
            # width into a never-compiled shape mid-decode — padding wider
            # costs the kernel nothing (it walks only live pages) and the
            # host a wider table, recompiling costs a compile.  Pure prefill
            # needs no ratchet: B == 1, so its width is the one request's
            # own final — stable across all chunks.
            w = max(w, self._width_hwm[shard])
            self._width_hwm[shard] = w
        return w

    @contextlib.contextmanager
    def _dispatching(self, plan, rows: int):
        """The ``serve.dispatch`` span of one step (host padding and the
        enqueue).  Yields the table width the step pads to, and records
        the padded row count for ``execute_plan``."""
        nblk = plan.tables.shape[1]
        width = (self._bucket_width(plan, nblk, plan.shard)
                 if self.pad_shapes else nblk)
        self._dispatched.rows = rows
        with jax.profiler.TraceAnnotation(
                "serve.dispatch", kind=plan.kind, rows=len(plan.requests),
                dispatched=rows, width=width):
            yield width

    def _bucket_tables(self, plan, rows: int, width: Optional[int] = None):
        """Shard-localize + (optionally) pad a plan's table to its width
        bucket (``width``, else the one ``_bucket_width`` picks).  Returns
        (tables (rows, W) i32, pad_slot)."""
        s = plan.shard
        base = self._shard_bases[s]
        pad_slot = self._shard_sizes[s]  # shard-local scratch slot id
        # shard-local slot ids: the plan's tables name global slots; this
        # shard's device pool indexes [0, size + pad).  Column padding (0
        # fill) clamps to local 0 — never fetched: the per-request
        # num_live_blocks bound stops the kernel's table walk at the last
        # live slot (the ref path masks them by length/causal position).
        local = np.maximum(plan.tables.astype(np.int32) - base, 0)
        if not self.pad_shapes:
            return local, pad_slot
        b, nblk = local.shape
        w = self._bucket_width(plan, nblk, s) if width is None else width
        tables = np.full((rows, w), pad_slot, np.int32)
        tables[:b, :] = 0
        tables[:b, :nblk] = local
        return tables, pad_slot

    def _dispatch_decode(self, plan) -> jax.Array:
        """Pad a decode batch to ``max_batch`` rows and its width bucket
        and enqueue it; returns the (padded) sampled ids, not waited on."""
        s = plan.shard
        b = plan.tables.shape[0]
        rows = self.max_batch if self.pad_shapes else b
        with self._dispatching(plan, rows) as width:
            tables, _ = self._bucket_tables(plan, rows, width)
            lengths, tokens, positions = (plan.lengths, plan.tokens,
                                          plan.positions)
            if self.pad_shapes:
                lengths = np.ones((rows,), np.int32)  # pad rows: 1 token
                lengths[:b] = plan.lengths
                tokens = np.zeros((rows,), np.int32)
                tokens[:b] = plan.tokens
                positions = np.zeros((rows,), np.int32)
                positions[:b] = plan.positions
            with self._device_locks[s]:
                out, self._shard_pools[s] = self._decode(
                    self.params, self._shard_pools[s],
                    jnp.asarray(tables), jnp.asarray(lengths),
                    jnp.asarray(tokens), jnp.asarray(positions))
        return out

    def _dispatch_prefill(self, plan) -> jax.Array:
        """One prefill chunk (B == 1): pad the chunk length to its pow2
        bucket next to the table-width buckets (``bucket_policy``), so XLA
        compiles once per (chunk bucket, width bucket) instead of per
        chunk shape.  Returns the sampled id, not waited on."""
        s = plan.shard
        n = plan.n_tokens
        ctx = int(plan.lengths[0]) - n  # context BEFORE the chunk
        cb = 1 << max(0, n - 1).bit_length() if self.pad_shapes else n
        with self._dispatching(plan, 1) as width:
            tables, _ = self._bucket_tables(plan, 1, width)
            tokens = np.zeros((1, cb), np.int32)
            tokens[0, :n] = plan.tokens
            # pad positions clamp to the last valid one: their (discarded)
            # attention rows stay masked to materialized pages — no NaN risk
            positions = (ctx + np.minimum(np.arange(cb), n - 1)
                         ).astype(np.int32)[None, :]
            chunk_lens = np.array([n], np.int32)
            with self._device_locks[s]:
                out, self._shard_pools[s] = self._prefill(
                    self.params, self._shard_pools[s],
                    jnp.asarray(tables), jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray(chunk_lens))
        return out

    def _dispatch_mixed(self, plan) -> jax.Array:
        """Decode rows + one prefill chunk row in ONE dispatch of the
        chunked kernel (ragged rows via ``chunk_lens``; decode rows carry
        1 valid token).  Shape buckets: rows pad to ``max_batch + 1`` (a
        full decode batch plus the chunk row), columns to the pow2 chunk
        bucket — the same two ladders the pure plans use, so the compile
        count stays bounded.  Pad rows write their (masked) token to the
        scratch slot; pad columns clamp to each row's last valid position
        so their discarded attention rows stay within materialized pages.
        Returns the (padded) sampled ids, not waited on.
        """
        s = plan.shard
        b, c = plan.tokens.shape
        rows = (self.max_batch + 1) if self.pad_shapes else b
        cb = 1 << max(0, c - 1).bit_length() if self.pad_shapes else c
        with self._dispatching(plan, rows) as width:
            tables, _ = self._bucket_tables(plan, rows, width)
            tokens = np.zeros((rows, cb), np.int32)
            tokens[:b, :c] = plan.tokens
            positions = np.zeros((rows, cb), np.int32)
            positions[:b, :c] = plan.positions
            if cb > c:
                positions[:b, c:] = plan.positions[:, c - 1:c]
            chunk_lens = np.ones((rows,), np.int32)  # pad rows: 1 token
            chunk_lens[:b] = plan.chunk_lens
            with self._device_locks[s]:
                out, self._shard_pools[s] = self._prefill(
                    self.params, self._shard_pools[s],
                    jnp.asarray(tables), jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray(chunk_lens))
        return out

    # ------------------------------------------------------------- drain
    def drain(self, tid: int) -> int:
        """Era-progress-bounded final drain; returns blocks left unreclaimed.

        Termination proof sketch: each loop iteration either (a) frees at
        least one block — possible at most R times, R the finite number of
        retired blocks, and freeing never retires more — or (b) advances
        every era/epoch clock once, which happens at most DRAIN_ERA_BOUND
        times consecutively before the loop exits.  Total iterations are
        therefore bounded by R * (DRAIN_ERA_BOUND + 1) + DRAIN_ERA_BOUND.
        At quiescence (all reservations released, all brackets closed)
        every scheme reclaims everything within DRAIN_ERA_BOUND clock
        ticks — EBR needs its two grace periods, era schemes one scan — so
        a nonzero return value means a reservation is genuinely still held.
        """
        pool = self.pool
        if self.prefix_cache is not None:
            # the cache's sharer references would otherwise pin cached
            # pool slots past shutdown; dropping them retires every
            # block whose last sharer was the cache
            self.prefix_cache.clear(tid)
        stalled = 0
        while pool.unreclaimed() > 0:
            freed = pool.cleanup_all()
            freed += pool.cleanup(tid)
            if freed > 0:
                stalled = 0
                continue
            if stalled >= DRAIN_ERA_BOUND:
                break  # pinned by a live reservation; caller still holds it
            pool.advance_eras(tid)
            stalled += 1
        return pool.unreclaimed()

    # ------------------------------------------------------------- run loops
    def run_worker(self, tid: int, max_steps: int = 10_000,
                   stop: Optional[threading.Event] = None,
                   exit_when_idle: bool = True,
                   on_first_step=None) -> int:
        """Worker loop: step until the queue AND active set are empty.

        Used by every ``ServeRuntime`` worker thread; does NOT run the
        final drain (the runtime drains once after all workers join).
        ``stop`` aborts promptly (a sibling worker died — its in-flight
        requests would otherwise stall this loop until ``max_steps``).
        ``exit_when_idle=False`` is the PERSISTENT mode for the serving
        front-end: an empty queue parks the worker on the scheduler's
        condition instead of exiting — new submissions (and cancellations)
        wake it — until ``stop`` is set by the runtime's rolling drain.
        ``on_first_step`` fires once, after the first PRODUCTIVE step —
        the supervisor stamps recovery latency with it.
        Returns the number of productive steps taken.
        """
        steps = 0
        productive = 0
        idle = 0
        while steps < max_steps and (stop is None or not stop.is_set()):
            # persistent workers bound PRODUCTIVE steps only: a long-lived
            # server parks through arbitrarily many idle wakeups without
            # burning down its runaway backstop
            steps = steps + 1 if exit_when_idle else productive
            if self.step(tid):
                productive += 1
                if productive == 1 and on_first_step is not None:
                    on_first_step()
                idle = 0
                continue
            if exit_when_idle and not self.sched.pending() \
                    and not self.sched.active:
                break
            # idle tick: another worker's steps are in flight, or blocks
            # need reclaiming before allocation can proceed.  The fused
            # cross-thread drain reclaims blocks retired by workers that
            # are stalled or done ticking.  Back off while idle — a hot
            # spin here starves the working threads of the GIL.
            idle += 1
            if idle % 4 == 1:
                self.sched.reclaim_all(tid)
            else:
                with jax.profiler.TraceAnnotation("serve.idle"):
                    self.sched.wait_for_work(0.002)
        return productive

    def run(self, tid: int, max_steps: int = 10_000) -> Dict[str, int]:
        """Single-threaded serve loop + era-progress-bounded final drain."""
        self.run_worker(tid, max_steps)
        self.drain(tid)
        return dict(self.sched.stats)
