#!/usr/bin/env python3
"""Chip smoke: serve stablelm-3b at its published widths on one TPU chip.

    python chip_smoke.py            # from the repo root, on a TPU host

Everything runs in this one process (a chip belongs to one process):

1. platform guard — exits non-zero, printing no result, unless JAX's
   first device is a TPU;
2. build — ``launch.serve.build_engine``, the CLI's own constructor: the
   published config (32 layers, d_model 2560, 32 heads x 80, d_ff 6912,
   vocab 50,304) with bf16 weights made from ``--seed``, and a pool of
   16-token blocks sized from the device's memory;
3. kernel vs reference — one prefill chunk and two decode steps at full
   width, the Pallas kernel and the ``kernels/ref.py`` reference reading
   the same pools; the largest logit difference must stay within
   ``LOGIT_RTOL`` of the largest reference logit, and one attention call
   on the pools they filled within ``ATTN_RTOL`` of its largest output;
4. serve — 8 requests with ragged 256-1,024-token prompts and 64 new
   tokens each, under WFE, through ``ServeEngine`` (``max_batch`` 8,
   ``chunk_size`` 128); prefill, mixed and decode plans must all occur;
   after the drain every request is complete, ``unreclaimed == 0`` and
   every block is free again; the compiled decode and prefill steps must
   contain the kernel (``tpu_custom_call``);
5. reclaim — one cleanup through the compiled ``era_scan`` kernel
   (``cleanup_backend="pallas"``), checked against a held reservation.

Any failed check raises, so the script exits non-zero.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Kernel vs reference: the largest |logit difference| may be this share
#: of the largest |reference logit|.  Both paths run the same bf16 model
#: and differ only inside attention (an online softmax over groups of
#: pages against one softmax over the whole table, and different f32
#: summation orders).  Where the two f32 attention outputs fall on
#: opposite sides of a bf16 rounding boundary, the cast back to bf16
#: differs by one ulp (2^-8 relative), and that difference travels
#: through the remaining layers.  2% is a few bf16 ulps of the logit
#: scale: it admits that rounding noise and still fails a kernel that
#: reads a wrong page, a wrong head or a wrong position.
LOGIT_RTOL = 2e-2

#: Kernel vs reference on one attention call over the same full-width
#: pools: the largest |output difference| may be this share of the
#: largest |reference output|.  The queries are f32 holding bf16 values,
#: so neither output is rounded to bf16 and no later layer amplifies
#: anything: what is left is the kernel's own arithmetic (an online
#: softmax over groups of pages, and matmul passes that may round the
#: probabilities to bf16, 2^-9 relative each).  2^-7 admits that; a wrong
#: page, head, position or mask moves the output by a share of order one.
ATTN_RTOL = 2.0 ** -7


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def tpu_device():
    """The first JAX device if it is a TPU; otherwise exit non-zero."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        raise SystemExit(2)
    return dev


def compare_attention(cfg, pools, tables, *, n_tokens: int, chunk: int,
                      seed: int, log=print) -> float:
    """Largest |output| difference, kernel vs reference, of one attention
    call at the first and the last layer of ``pools`` (which hold
    ``n_tokens`` tokens), for a chunk of ``chunk`` queries ending at the
    last token and for one decode query.  Raises beyond ``ATTN_RTOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import paged_chunk_attention

    kh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    attend = {k: jax.jit(functools.partial(
        paged_chunk_attention, scale=hd ** -0.5, use_kernel=k))
        for k in (True, False)}
    key = jax.random.key(seed)
    worst = 0.0
    for name, c in (("chunk", chunk), ("decode", 1)):
        key, sub = jax.random.split(key)
        q = jax.random.normal(sub, (1, c, kh, cfg.n_heads // kh, hd))
        q = q.astype(jnp.bfloat16).astype(jnp.float32)
        qpos = jnp.arange(n_tokens - c, n_tokens, dtype=jnp.int32)[None]
        for layer in (0, cfg.n_layers - 1):
            args = (q, pools["k"], pools["v"], tables, qpos)
            layer = jnp.int32(layer)
            ok = np.asarray(attend[True](*args, layer=layer))
            with jax.default_matmul_precision("highest"):
                want = np.asarray(attend[False](*args, layer=layer))
            diff = float(np.max(np.abs(ok - want)))
            scale = float(np.max(np.abs(want)))
            log(f"attention kernel vs reference, {name} of {c} at layer "
                f"{int(layer)}: max |dout| {diff} (max |out| {scale}, "
                f"tolerance {ATTN_RTOL * scale})")
            check(diff <= ATTN_RTOL * scale,
                  f"attention {name}, layer {int(layer)}: kernel differs "
                  f"from the reference by {diff}")
            worst = max(worst, diff)
    return worst


def compare_kernel_to_reference(cfg, params, *, block_size: int,
                                chunk: int, seed: int, log=print) -> tuple:
    """Largest |logit| difference, kernel vs reference, over one prefill
    chunk of ``chunk`` tokens and two greedy decode steps.  Each step runs
    both paths on the same input pools; the run continues on the kernel's
    pools.  Raises if a difference exceeds ``LOGIT_RTOL``.  Both paths
    run the same bf16 model; only attention differs.  Then compares one
    attention call on the pools those steps filled (``compare_attention``).
    Returns the largest logit and attention-output differences."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.paged_model import (init_pools, paged_decode_step,
                                         paged_prefill_chunk)

    n_blocks = -(-(chunk + 2) // block_size)
    tables = jnp.arange(n_blocks, dtype=jnp.int32)[None, :]
    pools = init_pools(cfg, n_blocks, block_size)
    prefill = {k: jax.jit(functools.partial(paged_prefill_chunk, cfg,
                                            use_kernel=k))
               for k in (True, False)}
    decode = {k: jax.jit(functools.partial(paged_decode_step, cfg,
                                           use_kernel=k))
              for k in (True, False)}
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, chunk)),
                         jnp.int32)
    positions = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    steps = [("prefill", lambda k, pl, nxt: prefill[k](
        params, pl, tables, tokens, positions))]
    for pos in (chunk, chunk + 1):
        steps.append((f"decode at {pos}", lambda k, pl, nxt, pos=pos:
                      decode[k](params, pl, tables,
                                jnp.asarray([pos + 1], jnp.int32), nxt,
                                jnp.asarray([pos], jnp.int32))))
    worst, nxt = 0.0, None
    for name, step in steps:
        lk, new_pools = step(True, pools, nxt)
        # the oracle's f32 matmuls at full f32 precision (TPU's default
        # would round their operands to bf16)
        with jax.default_matmul_precision("highest"):
            lr, _ = step(False, pools, nxt)
        lk, lr = np.asarray(lk, np.float32), np.asarray(lr, np.float32)
        check(np.isfinite(lk).all() and np.isfinite(lr).all(),
              f"{name}: non-finite logits")
        diff = float(np.max(np.abs(lk - lr)))
        scale = float(np.max(np.abs(lr)))
        log(f"kernel vs reference, {name}: max |dlogit| {diff} "
            f"(max |logit| {scale}, tolerance {LOGIT_RTOL * scale})")
        check(diff <= LOGIT_RTOL * scale,
              f"{name}: kernel differs from the reference by {diff}")
        worst = max(worst, diff)
        pools, nxt = new_pools, jnp.asarray(lk.argmax(-1), jnp.int32)
    return worst, compare_attention(cfg, pools, tables, n_tokens=chunk + 2,
                                    chunk=chunk, seed=seed, log=log)


def compiled_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


def serve(engine, cfg, *, n_requests: int, prompt_range, new_tokens: int,
          seed: int, on_tpu: bool, log=print) -> dict:
    """Serve ``n_requests`` ragged prompts to completion, drain, check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    compiles = {}

    def on_event(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.setdefault(kw.get("fun_name", "?"), []).append(secs)

    rng = np.random.default_rng(seed)
    lo, hi = prompt_range
    lengths = rng.integers(lo, hi + 1, n_requests)
    reqs = [engine.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                          new_tokens) for n in lengths]
    tid = engine.pool.register_thread()
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        t0 = time.perf_counter()
        engine.run_worker(tid)
        served_s = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    stats = dict(engine.sched.stats)
    kinds = {"prefill": stats["prefill_chunks"] - stats["mixed_steps"],
             "mixed": stats["mixed_steps"],
             "decode": stats["steps"] - stats["prefill_chunks"]}
    log(f"prompt lengths {lengths.tolist()}; plans {kinds}")
    check(all(n > 0 for n in kinds.values()),
          f"every plan kind must occur: {kinds}")
    check(stats["completed"] == n_requests
          and all(r.done and len(r.generated) == new_tokens for r in reqs),
          f"requests incomplete: {stats}")
    tokens = [t for r in reqs for t in r.generated]
    check(all(0 <= t < cfg.vocab_size for t in tokens),
          "a generated token is outside the vocabulary")
    # the compiled steps must hold the kernel: recompiled at the widest
    # shapes served (the persistent compile cache answers these)
    width = max(engine._width_hwm[0], 1)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    hlo = {"decode": compiled_text(
               engine._decode, engine.params, engine.pools,
               i32(engine.max_batch, width), i32(engine.max_batch),
               i32(engine.max_batch), i32(engine.max_batch)),
           "prefill": compiled_text(
               engine._prefill, engine.params, engine.pools,
               i32(1, width), i32(1, engine.sched.chunk_size),
               i32(1, engine.sched.chunk_size), i32(1))}
    for kind, text in hlo.items():
        found = "tpu_custom_call" in text
        log(f"compiled {kind} step contains tpu_custom_call: {found}")
        if on_tpu:
            check(found, f"the compiled {kind} step holds no Pallas kernel")
    unreclaimed = engine.drain(tid)
    free = engine.pool.free_blocks
    log(f"after drain: unreclaimed {unreclaimed}, free blocks {free} of "
        f"{engine.pool.n_blocks}")
    check(unreclaimed == 0, f"{unreclaimed} blocks left unreclaimed")
    check(free == engine.pool.n_blocks, "pool blocks leaked")
    for name, secs in sorted(compiles.items()):
        if name in ("jit(_decode)", "jit(_prefill)"):
            log(f"compile seconds {name}: {secs}")
    log(f"tokens served {len(tokens)} in {served_s} s (host clock, "
        f"compiles included)")
    return {"kinds": kinds, "tokens": len(tokens), "compiles": compiles}


def reclaim_with_era_scan(*, n_blocks: int = 512, log=print) -> int:
    """One cleanup through the ``era_scan`` kernel: blocks retired under a
    held step reservation must survive it, and all must be freed once the
    reservation is released."""
    from repro.blocks import BlockPool

    pool = BlockPool(n_blocks, scheme="WFE", cleanup_backend="pallas",
                     vectorized_threshold=1, era_freq=1,
                     cleanup_freq=10 ** 9)
    tid = pool.register_thread()
    blocks = pool.alloc_blocks(n_blocks, tid)
    pool.protect_step(0, tid)
    for blk in blocks:
        pool.retire(blk, tid)
    held = pool.cleanup(tid)
    check(held == 0, f"era_scan freed {held} blocks under a reservation")
    pool.release_step(0, tid)
    freed = pool.cleanup(tid)
    log(f"era_scan cleanup: 0 freed under the reservation, {freed} after")
    check(freed == n_blocks and pool.free_blocks == n_blocks,
          f"era_scan freed {freed} of {n_blocks}")
    return freed


def run(*, published: bool = True, seed: int = 0, n_requests: int = 8,
        prompt_range=(256, 1024), new_tokens: int = 64, max_batch: int = 8,
        chunk_size: int = 128, block_size: int = 16, n_blocks=None,
        use_kernel=None, log=print) -> dict:
    """Phases 2-5 (see the module docstring); returns what was measured.
    The CPU tests call this on the smoke config with interpreted kernels."""
    import jax

    from repro.kernels import platform
    from repro.launch.serve import build_engine

    dev = jax.devices()[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    t0 = time.perf_counter()
    cfg, params, engine, info = build_engine(
        "stablelm-3b", published=published, seed=seed, n_blocks=n_blocks,
        block_size=block_size, max_batch=max_batch, chunk_size=chunk_size,
        max_len=prompt_range[1] + new_tokens, use_kernel=use_kernel,
        scheme="WFE")
    weight_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    pool_bytes = sum(a.nbytes for a in jax.tree.leaves(engine.pools))
    log(f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads x {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}, weights {cfg.param_dtype.__name__}")
    log(f"weight bytes {weight_bytes}; pool bytes {pool_bytes} "
        f"({engine.pool.n_blocks} blocks x {block_size} tokens); "
        f"build {time.perf_counter() - t0} s")
    if info is not None:
        log(f"pool sizing {info}")
    check(engine.use_kernel, "the engine serves reference attention")
    dlogit, dattn = compare_kernel_to_reference(
        cfg, params, block_size=block_size, chunk=chunk_size, seed=seed,
        log=log)
    served = serve(engine, cfg, n_requests=n_requests,
                   prompt_range=prompt_range, new_tokens=new_tokens,
                   seed=seed, on_tpu=platform.on_tpu(), log=log)
    reclaim_with_era_scan(log=log)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}"
        f"; bytes_limit {stats.get('bytes_limit', 'not reported')}")
    return {"weight_bytes": weight_bytes, "pool_bytes": pool_bytes,
            "max_dlogit": dlogit, "max_dattn": dattn,
            "tokens": served["tokens"],
            "kinds": served["kinds"], "peak": stats.get("peak_bytes_in_use"),
            "n_blocks": engine.pool.n_blocks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    args = ap.parse_args(argv)
    dev = tpu_device()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's src/ is not beside this script: {e}",
              file=sys.stderr)
        return 2
    log = lambda *a: print(*a, flush=True)
    log(f"compile cache {setup_compile_cache()}")
    run(seed=args.seed, log=log)
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
