"""One run of one cell: build, warm up, drive the window, check, report.

The cell, its configuration, its traffic mix and its metrics are found by
name: the cell in ``BENCHMARK.json``, the configuration in
``configs/<config>.json``, the mix in ``traffic/<traffic>.json`` and each
metric's reader in ``metrics/<metric>.py`` (or, for a metric named
``<base>.<suffix>``, ``metrics/<base>.py``).  A cell, a mix or a metric is
added by adding files and entries.

The window drives the program's ``ServeRuntime`` in persistent mode
(``start`` / ``submit`` / ``drain``), the mode its SSE front end serves
through.  The benchmark's client thread submits on schedule and records
the first delivery of each token index through the ``on_token`` hook.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import queue
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import flops, stats, traffic, trace

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
#: JAX's persistent compilation cache: one fixed directory in the checkout
CACHE_DIR = ROOT / ".bench_cache" / "jax"
#: the traced run's profile (rewritten by every traced run)
TRACE_DIR = ROOT / ".bench_cache" / "trace"
#: how much of the window a traced run profiles (its middle)
TRACE_SECONDS = 16.0
#: a closed-loop window opens once this many requests have their first
#: token (the batch is full and decoding), or after this many seconds
FILL_TIMEOUT_S = 240.0
#: after the window, in-flight requests may finish for this long before
#: the drain cancels what is left (only finished requests are checked)
DRAIN_S = 30.0
#: served tokens the reference checks at least, per run
CHECK_TOKENS = 384
#: at most this many requests go to the reference, per run
CHECK_REQUESTS = 8

_T_PROCESS = time.perf_counter()


class NoAccelerator(RuntimeError):
    pass


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ lookup
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path = BENCH_DIR


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(name, load_json(bench_dir / "configs" / f"{w['config']}.json"),
                load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
                w["chips"], e2e, layer, bench_dir)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` of ``metrics/<name>.py``, else of
    ``metrics/<base>.py`` for ``<base>.<suffix>``."""
    for stem in (name, name.rsplit(".", 1)[0]):
        path = bench_dir / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"chipbench_metric_{abs(hash(str(path)))}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{bench_dir / 'metrics'}")


# ------------------------------------------------------------------ device
def accelerator(chips: int, require: bool = True):
    """The devices a cell runs on; without ``chips`` accelerators raise
    ``NoAccelerator`` (the run then prints no result)."""
    import jax

    devs = jax.devices()
    if require and (devs[0].platform != "tpu"
                    or len(devs) < chips):
        raise NoAccelerator(f"needs {chips} accelerator chip(s); JAX found "
                            f"{len(devs)} {devs[0].platform!r} device(s) "
                            f"({devs[0].device_kind})")
    return devs[:chips]


def setup_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(dev) -> dict:
    mem = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind, "count": 1,
            "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}


# ------------------------------------------------------------------- build
def arch_config(cfgfile: dict):
    """The program's ArchConfig for the configuration the file states: the
    repo's own entry with the file's sizes applied.  Refuses a file the
    program cannot run as stated."""
    import jax.numpy as jnp

    from repro.configs import get_config

    m = cfgfile["model"]
    base = get_config(cfgfile["repo_arch"])
    kinds = {"swiglu": "swiglu", "gelu_tanh": "gelu"}
    if base.mlp_kind != kinds[m["mlp"]] or base.norm_kind != m["norm"] \
            or m["rotary_dim"] != m["head_dim"] or base.tie_embeddings \
            or m["norm_eps"] != 1e-6:
        raise ValueError(f"{cfgfile['name']}: the program serves "
                         f"{cfgfile['repo_arch']} otherwise than the "
                         "configuration file states")
    dt = jnp.dtype(m["dtype"])
    return base.scaled(n_layers=m["n_layers"], d_model=m["d_model"],
                       n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                       head_dim=m["head_dim"], d_ff=m["d_ff"],
                       vocab_size=m["vocab_size"],
                       rope_theta=float(m["rope_theta"]),
                       dtype=dt, param_dtype=dt)


def build_engine(cell: Cell, params, arch):
    """``launch.serve.build_engine``'s construction with the benchmark's
    weights: a device-sized pool and the engine with the cell's deployment
    settings, all else default."""
    from repro.serve import ServeEngine
    from repro.serve.engine import device_pool_blocks

    s = cell.config["serve"]
    n_blocks, info = device_pool_blocks(
        arch, params, block_size=s["block_size"], max_batch=s["max_batch"],
        chunk_size=s["chunk_size"], max_len=traffic.max_len(cell.traffic))
    engine = ServeEngine(arch, params, n_blocks=n_blocks,
                         block_size=s["block_size"], max_batch=s["max_batch"],
                         chunk_size=s["chunk_size"], scheme=s["scheme"])
    return engine, n_blocks, info


# ------------------------------------------------------------------ warm-up
def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def warm_shapes(cell: Cell) -> Dict[str, list]:
    """Sizes that span the cell's traffic at every power of two: table
    widths in blocks, from its shortest request to its longest, and chunk
    lengths up to the chunk size."""
    s, t = cell.config["serve"], cell.traffic
    bs, chunk = s["block_size"], s["chunk_size"]
    ctx = t.get("prefix", {}).get("length", 0)
    lo = ctx + t["prompt"]["min"] + t["output"]["min"]
    hi = traffic.max_len(t)
    widths, w = [], _pow2(-(-lo // bs))
    while w <= _pow2(-(-hi // bs)):
        widths.append(w)
        w *= 2
    chunks, c = [], 1
    while c <= _pow2(min(chunk, ctx + t["prompt"]["max"])):
        chunks.append(c)
        c *= 2
    return {"widths": widths, "chunks": chunks, "longest": [hi]}


def warm_up(engine, cell: Cell) -> int:
    """Serve synthetic requests through the engine's public entry points
    (``submit``, ``step``, ``cancel``, ``run``) on one thread, so that its
    own bucketing compiles every step shape the cell's traffic can reach:

    * the longest request decodes, and beside it a prompt of each chunk
      length arrives (a mixed step each);
    * then, alone, a prompt of each chunk length at each table width
      (a prefill step each), cancelled after its first token.

    Returns the number of steps taken."""
    bs = cell.config["serve"]["block_size"]
    shapes = warm_shapes(cell)
    tid = engine.pool.register_thread()
    # the shortest chunk length that falls in each power-of-two bucket
    lengths = [c // 2 + 1 for c in shapes["chunks"]]
    rng = np.random.default_rng(0)
    # distinct ids, so that no prompt finds another's pages in the cache
    prompt = lambda k: rng.integers(0, engine.cfg.vocab_size, k).tolist()  # noqa: E731
    n = 0

    def step_until(done) -> None:
        nonlocal n
        while not done():
            if not engine.step(tid):
                raise RuntimeError("the warm-up stalled")
            n += 1

    longest = shapes["longest"][0]
    lead = engine.submit(prompt(bs), longest - bs)
    step_until(lambda: len(lead.generated) > 1)  # a decode step
    for k in lengths:
        req = engine.submit(prompt(k), 1)
        step_until(lambda: req.state == "done")
    engine.cancel(lead)
    for w in shapes["widths"]:
        for k in lengths:
            req = engine.submit(prompt(k), w * bs - k)
            step_until(lambda: req.generated)
            engine.cancel(req)
            engine.run(tid)
    engine.run(tid)
    return n


# ------------------------------------------------------------------ client
@dataclass
class Rec:
    item: traffic.Item
    due: float = 0.0
    sent: Optional[float] = None
    req: object = None
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    replay_mismatch: int = 0
    done_at: Optional[float] = None


class Client:
    """Sends a mix's requests to the runtime and records every token's
    first delivery.  One thread; the hooks it hands the server only
    append to lists (they run under the scheduler's lock)."""

    def __init__(self, runtime, items: List[traffic.Item], spec: dict,
                 annotate: bool):
        self.runtime = runtime
        self.spec = spec
        self.recs = [Rec(it) for it in items]
        self.annotate = annotate
        self.finished: "queue.Queue[int]" = queue.Queue()
        self.stop = threading.Event()
        self.t0 = 0.0
        self.t_end = float("inf")
        self.thread: Optional[threading.Thread] = None
        self.lag: List[float] = []

    def _on_token(self, rec, req, idx, tok):
        if idx == len(rec.token_times):
            rec.token_times.append(time.perf_counter())
            rec.tokens.append(tok)
        elif idx < len(rec.tokens) and rec.tokens[idx] != tok:
            rec.replay_mismatch += 1

    def _on_finish(self, rec, req):
        rec.done_at = time.perf_counter()
        self.finished.put(rec.item.client)

    def _send(self, rec: Rec) -> None:
        rec.sent = time.perf_counter()
        rec.req = self.runtime.submit(
            rec.item.prompt, rec.item.max_new, slo="interactive",
            on_token=functools.partial(self._on_token, rec),
            on_finish=functools.partial(self._on_finish, rec))

    def _span(self, name):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open_loop(self, t0: float) -> None:
        self.t0 = t0
        for rec in self.recs:
            rec.due = t0 + rec.item.due
            if rec.due >= self.t_end:
                break
            with self._span("bench.client_wait"):
                while not self.stop.is_set():
                    dt = rec.due - time.perf_counter()
                    if dt <= 0:
                        break
                    self.stop.wait(min(dt, 0.05))
            if self.stop.is_set():
                break
            with self._span("bench.submit"):
                self._send(rec)
            self.lag.append(rec.sent - rec.due)

    def closed_loop(self) -> None:
        n = self.spec["clients"]
        streams: Dict[int, List[Rec]] = {}
        for rec in self.recs:
            streams.setdefault(rec.item.client, []).append(rec)
        for c in range(n):
            rec = streams[c].pop(0)
            rec.due = time.perf_counter()
            self._send(rec)
        while not self.stop.is_set():
            with self._span("bench.client_wait"):
                try:
                    c = self.finished.get(timeout=0.05)
                except queue.Empty:
                    continue
            if not streams[c]:
                continue
            rec = streams[c].pop(0)
            rec.due = time.perf_counter()
            with self._span("bench.submit"):
                self._send(rec)

    def start(self, t0: Optional[float] = None) -> None:
        target = (functools.partial(self.open_loop, t0)
                  if self.spec["loop"] == "open" else self.closed_loop)
        self.thread = threading.Thread(target=target, name="bench-client",
                                       daemon=True)
        self.thread.start()

    def join(self) -> None:
        self.stop.set()
        if self.thread is not None:
            self.thread.join()
        # the hooks each request keeps reach the client: let go of the
        # runtime, so that the engine's pools can be freed after the drain
        self.runtime = None

    def sent(self) -> List[Rec]:
        return [r for r in self.recs if r.req is not None]


# ---------------------------------------------------------- instrumentation
class Recorder:
    """The benchmark's own record of each plan the engine executes, and
    host spans around the scheduler's tick and completion (traced runs
    only): wrappers set on the engine's instance, calling through."""

    def __init__(self, engine, annotate: bool):
        import jax

        self.plans: Dict[int, object] = {}
        self._n = 0
        self._lock = threading.Lock()
        self.record = False
        engine_exec = engine.execute_plan
        sched = engine.sched
        tick, complete = sched.tick, sched.complete
        Ann = jax.profiler.TraceAnnotation

        def execute_plan(plan, tid):
            with self._lock:
                i = self._n
                self._n += 1
            if self.record:
                self.plans[i] = PlanRec.of(plan)
            with Ann(f"bench.plan.{i}"):
                return engine_exec(plan, tid)

        def tick_(tid):
            with Ann("bench.tick"):
                return tick(tid)

        def complete_(*a, **kw):
            with Ann("bench.complete"):
                return complete(*a, **kw)

        if annotate:
            engine.execute_plan = execute_plan
            sched.tick = tick_
            sched.complete = complete_


@dataclass
class PlanRec:
    kind: str
    rows: List[flops.Row]

    @classmethod
    def of(cls, plan) -> "PlanRec":
        rows = []
        lengths = np.asarray(plan.lengths)
        for i, req in enumerate(plan.requests):
            if plan.kind == "decode":
                n = 1
            elif plan.kind == "prefill":
                n = plan.n_tokens
            else:
                n = int(plan.chunk_lens[i])
            end = int(lengths[i])
            rows.append(flops.Row(end - n, n, end >= len(req.prompt)))
        return cls(plan.kind, rows)


# --------------------------------------------------------------- the check
def pick_sample(recs: List[Rec], seed: int) -> List[Rec]:
    """Finished requests for the reference: the longest served, one that
    was evicted and replayed (if any), then others drawn from the seed,
    until ``CHECK_TOKENS`` served tokens or ``CHECK_REQUESTS`` requests."""
    done = [r for r in recs if r.req is not None and r.req.state == "done"]
    if not done:
        return []
    rng = np.random.default_rng([seed, 2])
    order = [done[i] for i in rng.permutation(len(done))]
    first = [max(done, key=lambda r: (len(r.req.generated),
                                      len(r.req.prompt)))]
    first += [r for r in order if r.req.evictions > 0][:1]
    out: List[Rec] = []
    for r in first + order:
        if r in out:
            continue
        if len(out) >= CHECK_REQUESTS or \
                sum(len(x.req.generated) for x in out) >= CHECK_TOKENS:
            break
        out.append(r)
    return out


def logit_gaps(m: dict, params, sample: List[Rec], control: bool = False):
    """Widest gap of the served tokens (and of the control's picks)."""
    from . import reference

    worst, worst_low = 0.0, 0.0
    for r in sample:
        gap, low = reference.served_gaps(m, params, r.req.prompt,
                                         r.req.generated, control=control)
        worst = max(worst, float(np.max(gap)))
        if control:
            worst_low = max(worst_low, float(np.max(low)))
    return worst, (worst_low if control else None)


# ------------------------------------------------------------------ the run
@dataclass
class Outcome:
    result: dict
    checks: Dict[str, dict]
    info: dict


class Session:
    """The program under test, built and warmed for one cell and seed:
    weights, engine and a started runtime, plus the benchmark's compile
    counter and plan recorder."""

    def __init__(self, cell: Cell, seed: int, traced: bool,
                 require_accelerator: bool = True):
        import jax

        from repro.serve import ServeRuntime

        from . import weights

        self.cell, self.seed, self.traced = cell, seed, traced
        self.dev = accelerator(cell.chips, require_accelerator)[0]
        setup_compile_cache()
        self.compiles: List[str] = []
        self.counting = threading.Event()
        self.listener = self._on_event
        jax.monitoring.register_event_duration_secs_listener(self.listener)
        self.m = cell.config["model"]
        arch = arch_config(cell.config)
        self.params = weights.make_params(self.m, seed)
        jax.block_until_ready(self.params)
        self.engine, self.n_blocks, info = build_engine(cell, self.params,
                                                        arch)
        if info:
            log(f"pool: {self.n_blocks} blocks of "
                f"{cell.config['serve']['block_size']} tokens; {info}")
        t = time.perf_counter()
        n = warm_up(self.engine, cell)
        log(f"warm-up: {n} steps in {time.perf_counter() - t:.1f} s")
        self.rec = Recorder(self.engine, annotate=traced)
        self.runtime = ServeRuntime(self.engine,
                                    max_steps_per_worker=1_000_000)
        self.runtime.start()

    def _on_event(self, event, secs, **kw):
        if self.counting.is_set() and ("/jax/core/compile" in event
                                       or "compilation_cache" in event):
            self.compiles.append(event)

    def warm_prefixes(self, spec: dict) -> None:
        """Serve each shared context of the mix once, so that the prefix
        cache is warm when the window opens."""
        shared = traffic.contexts(spec, self.seed, self.m["vocab_size"])
        if not shared:
            return
        done = threading.Event()
        left = [len(shared)]

        def fin(_req):
            left[0] -= 1
            if left[0] == 0:
                done.set()

        for ctx in shared:
            self.runtime.submit(ctx, 1, on_finish=fin)
        if not done.wait(FILL_TIMEOUT_S):
            raise RuntimeError("the prefix warm-up did not finish")

    def window(self, spec: dict, seconds: float, items) -> dict:
        """Drive one window of ``seconds``: open loop from its start, or
        closed loop from a full batch.  Returns what the metrics read."""
        import jax

        client = Client(self.runtime, items, spec, annotate=self.traced)
        sched = self.engine.sched
        if spec["loop"] == "closed":
            client.start()
            need = self.cell.config["serve"]["max_batch"]
            t_fill = time.perf_counter()
            while sum(1 for r in client.recs if r.token_times) < need:
                if time.perf_counter() - t_fill > FILL_TIMEOUT_S:
                    log(f"window opens with fewer than {need} rows decoding")
                    break
                time.sleep(0.01)
            log(f"closed loop: {need} rows decoding after "
                f"{time.perf_counter() - t_fill:.1f} s")
        before = sched.stats
        n_compiles = len(self.compiles)
        t0 = time.perf_counter()
        client.t0, client.t_end = t0, t0 + seconds
        t_end = t0 + seconds
        self.counting.set()
        self.rec.record = self.traced
        if spec["loop"] == "open":
            client.start(t0)
        if self.traced:
            lo = t0 + max(0.0, seconds / 2 - TRACE_SECONDS / 2)
            time.sleep(max(0.0, lo - time.perf_counter()))
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            with jax.profiler.TraceAnnotation("bench.trace_window"):
                time.sleep(min(TRACE_SECONDS, seconds))
            jax.profiler.stop_trace()
        time.sleep(max(0.0, t_end - time.perf_counter()))
        after = sched.stats
        self.rec.record = False
        self.counting.clear()
        client.join()
        recs = [r for r in client.sent() if r.due < t_end]
        return {"recs": recs, "t0": t0, "t_end": t_end, "seconds": seconds,
                "counters": {k: after[k] - before[k] for k in after},
                "compiles_in_window": len(self.compiles) - n_compiles,
                "lag": client.lag, "backlog": sched.pending(),
                "model": self.m,
                "block_size": self.cell.config["serve"]["block_size"],
                "prompt_tokens_sent": sum(len(r.item.prompt) for r in recs
                                          if t0 <= r.sent < t_end)}

    def wait_idle(self, grace_s: float) -> None:
        """Let the runtime finish what it holds; past ``grace_s`` cancel
        the rest.  Returns once nothing is queued or active."""
        sched = self.engine.sched
        t = time.perf_counter()
        cancelled = False
        while sched.pending() or sched.active:
            if not cancelled and time.perf_counter() - t > grace_s:
                for req in sched.queue + list(sched.active):
                    self.runtime.cancel(req)
                cancelled = True
            time.sleep(0.01)


def describe(w: dict) -> None:
    recs, t0, t_end = w["recs"], w["t0"], w["t_end"]
    done = sum(1 for r in recs if r.req.state == "done")
    log(f"window {w['seconds']} s: {len(recs)} requests sent, {done} "
        f"finished, {stats.tokens_in(recs, t0, t_end)} tokens; "
        f"counters {w['counters']}")
    if w["lag"]:
        log(f"client lag behind schedule: p50 {stats.pct(w['lag'], 50)} s, "
            f"max {max(w['lag'])} s")
    log(f"compiles inside the window: {w['compiles_in_window']}")
    log(f"TTFT s {stats.summary(stats.ttfts(recs, t0, t_end))}")
    log(f"ITL s {stats.summary(stats.itls(recs, t0, t_end))}")


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        require_accelerator: bool = True, control: bool = False,
        t_process: float = _T_PROCESS) -> Outcome:
    """One run of ``cell``; returns the result line's object."""
    import jax

    s = Session(cell, seed, traced, require_accelerator)
    spec = cell.traffic
    s.warm_prefixes(spec)
    items = traffic.generate(spec, seed, s.m["vocab_size"], seconds)
    w = s.window(spec, seconds, items)
    jax.monitoring.unregister_event_duration_listener(s.listener)
    # the closed loop's set-up ends when its batch is full
    w["setup_s"] = w["t0"] - t_process
    t_drain = time.perf_counter()
    drained = s.runtime.drain(deadline_s=DRAIN_S)
    log(f"drain: {time.perf_counter() - t_drain:.1f} s, "
        f"{drained.get('cancelled_at_deadline')} cancelled at its deadline")
    info_dev = device_info(s.dev)
    describe(w)
    recs = w["recs"]
    failed = sum(1 for r in recs if r.req.state == "failed")
    short = sum(1 for r in recs if r.req.state == "done"
                and len(r.req.generated) != r.item.max_new)
    mismatch = sum(r.replay_mismatch for r in recs)
    log(f"replayed tokens that differ from their first delivery: {mismatch}")
    n_blocks, free = s.n_blocks, s.engine.pool.free_blocks
    sample = pick_sample(recs, seed)
    breakdown = None
    if traced:
        w.update(_trace_ctx(s.rec, s.dev, require_accelerator))
        breakdown = w.pop("breakdown")
        info_dev["busy_s"] = w["busy_s"]
        info_dev["window_s"] = w["window_s"]
    # free the program's state before the reference runs
    m, params = s.m, s.params
    s.runtime = s.engine = s.rec = None
    del s
    gc.collect()
    t_ref = time.perf_counter()
    gap, low = logit_gaps(m, params, sample, control=control)
    n_tok = sum(len(r.req.generated) for r in sample)
    log(f"reference over {len(sample)} requests, {n_tok} served tokens, in "
        f"{time.perf_counter() - t_ref:.1f} s")
    if control:
        # the control's picks stand in the program's place
        log(f"served tokens' widest gap {gap}; the fp8 control's {low}")
    limit = cell.config["limits"]["max_logit_gap"]
    checks = {
        "max_logit_gap": {"value": low if control else gap, "limit": limit},
        "unreclaimed": {"value": int(drained["unreclaimed"]), "limit": 0},
        "blocks_not_free": {"value": int(n_blocks - free), "limit": 0},
        "failed": {"value": failed, "limit": 0},
        "short_outputs": {"value": short, "limit": 0},
    }
    correct = (limit is not None and n_tok > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    metrics = {}
    for spec_m in (cell.per_layer if traced else cell.end_to_end):
        val = metric_reader(spec_m["name"], cell.bench_dir)(w)
        if val is not None:
            metrics[spec_m["name"]] = {"value": val, "unit": spec_m["unit"]}
    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": failed, "metrics": metrics, "device": info_dev,
              "compiles_in_window": w["compiles_in_window"]}
    if breakdown is not None:
        result["breakdown"] = breakdown
    info = {"compiles_in_window": w["compiles_in_window"],
            "replay_mismatch": mismatch, "program_gap": gap,
            "control_gap": low,
            "checked_tokens": n_tok, "n_blocks": n_blocks,
            "counters": w["counters"]}
    return Outcome(result, checks, info)


def _trace_ctx(rec: Recorder, dev, strict: bool = True) -> dict:
    path = trace.find_xplane(str(TRACE_DIR))
    if path is None:
        raise RuntimeError(f"no trace written under {TRACE_DIR}")
    t = time.perf_counter()
    events = trace.load(path)
    win = trace.span(events, "bench.trace_window")
    if win is None:
        raise RuntimeError("the trace holds no bench.trace_window span")
    lo, hi = win
    pairs = trace.pair_steps(events, rec.plans, lo, hi)
    busy = trace.busy_ns(events, lo, hi)
    from .peaks import peaks_for

    out = {"events": events, "lo": lo, "hi": hi, "pairs": pairs,
           # off the chip (the CPU tests) there is no peak to divide by
           "peaks": peaks_for(dev.device_kind) if strict else None,
           "busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
           "breakdown": {"device_ops": trace.top_ops(events, lo, hi),
                         "idle_gaps": trace.idle_gaps(events, lo, hi)}}
    n_mod = sum(1 for e in events if trace.is_device(e)
                and e.line == trace.MODULE_LINE and lo <= e.start_ns < hi)
    log(f"trace: {len(events)} events, {n_mod} step modules in the traced "
        f"window, {len(pairs)} paired with recorded plans; read in "
        f"{time.perf_counter() - t:.1f} s")
    return out


def report(out: Outcome) -> None:
    """Print the compared numbers beside their limits as the last lines
    of standard error, and the result as the last line of standard
    output with the same numbers last."""
    for k, c in out.checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    line = dict(out.result)
    line["checks"] = out.checks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
