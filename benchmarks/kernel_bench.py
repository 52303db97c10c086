"""Kernel benchmarks: era_scan + paged_attention vs their jnp references,
plus the three ``cleanup_batch`` reclamation backends head-to-head.

Wall-clock times the jit'd jnp references on the host it runs on.  The
analytic bytes and operations of each kernel call are computed from its
shapes; they become time estimates only with the published peaks of the
device the bench runs on (``launch.roofline.PEAKS``, keyed by
``device_kind``) — on a device without peaks (a CPU) none are printed.

The backend comparison (``bench_cleanup_backends``) is the serving-relevant
number: scalar is the paper's per-block Python loop, numpy the vectorized
era-table scan, pallas the TPU kernel (jnp fallback timing on CPU hosts).
The batched backends must beat scalar from R ≈ 1k retired blocks.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.era_table import batched_can_delete
from repro.kernels import ref
from repro.kernels.era_scan import INF_ERA32
from repro.launch.roofline import PEAKS


def _device_peaks():
    """Peaks of the device this bench runs on, or None (e.g. a CPU)."""
    return PEAKS.get(jax.devices()[0].device_kind)


def _est_us(amount: float, peak_key: str) -> str:
    p = _device_peaks()
    return "n/a" if p is None else f"{amount / p[peak_key] * 1e6:.3f} us"


def _time(fn, *args, n=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def bench_era_scan(r=4096, t=512, h=10):
    key = jax.random.key(0)
    ks = jax.random.split(key, 3)
    alloc = jax.random.randint(ks[0], (r,), 0, 1000, jnp.int32)
    retire = alloc + jax.random.randint(ks[1], (r,), 0, 100, jnp.int32)
    res = jax.random.randint(ks[2], (t, h), 0, 1100, jnp.int32)
    ref_fn = jax.jit(ref.era_scan_ref)
    dt = _time(ref_fn, alloc, retire, res)
    # analytic TPU cost: (R × T·H) int32 compares, memory-bound on the
    # R×TH broadcast -> bytes = R·4·2 + TH·4 (res resident in VMEM)
    work_bytes = r * 4 * 2 + t * h * 4
    vpu_ops = r * t * h * 3  # 2 compares + and-reduce per pair
    print(f"era_scan R={r} T={t} H={h}: jnp-ref {dt*1e3:.2f} ms; "
          f"{work_bytes} bytes, {vpu_ops} VPU ops; device est: mem "
          f"{_est_us(work_bytes, 'hbm_bytes_per_s')}")
    return {"ref_ms": dt * 1e3, "bytes": work_bytes, "vpu_ops": vpu_ops}


def bench_paged_attention(b=8, kh=2, g=4, d=128, bs=16, nblk=64):
    ks = jax.random.split(jax.random.key(1), 5)
    n = b * nblk + 8
    q = jax.random.normal(ks[0], (b, kh, g, d), jnp.float32)
    kp = jax.random.normal(ks[1], (n, bs, kh, d), jnp.float32)
    vp = jax.random.normal(ks[2], (n, bs, kh, d), jnp.float32)
    tables = jax.random.permutation(ks[3], n)[: b * nblk].reshape(
        b, nblk).astype(jnp.int32)
    lengths = jnp.full((b,), nblk * bs, jnp.int32)
    ref_fn = jax.jit(ref.paged_attention_ref)
    dt = _time(ref_fn, q, kp, vp, tables, lengths)
    # decode attention is memory-bound: traffic = K+V blocks touched
    kv_bytes = b * nblk * bs * kh * d * 2 * 4
    flops = b * kh * g * nblk * bs * d * 4
    print(f"paged_attention B={b} ctx={nblk*bs}: jnp-ref {dt*1e3:.2f} ms;"
          f" {kv_bytes} KV bytes, {flops} FLOPs (AI={flops/kv_bytes:.2f});"
          f" device est: mem {_est_us(kv_bytes, 'hbm_bytes_per_s')}"
          f" vs compute {_est_us(flops, 'flops_bf16')}")
    return {"ref_ms": dt * 1e3, "kv_bytes": kv_bytes, "flops": flops,
            "arith_intensity": flops / kv_bytes}


def bench_paged_attention_int8(b=8, kh=2, g=4, d=128, bs=16, nblk=64):
    """int8 vs fp32 pool pages under the SAME decode-attention shape.

    CPU wall-clock times the jnp refs (int8 = materialized dequant + fp
    oracle — the exact kernel arithmetic); the serving-relevant number is
    the analytic TPU traffic: int8 pages stream 1 byte/element plus one
    fp32 scale per (block, kv-head), so the memory-bound decode step's
    HBM time drops ~4x at this width (the fused in-kernel dequant adds
    VPU multiplies, which the MXU-bound score math hides).
    """
    ks = jax.random.split(jax.random.key(2), 6)
    n = b * nblk + 8
    q = jax.random.normal(ks[0], (b, kh, g, d), jnp.float32)
    kp = jax.random.normal(ks[1], (n, bs, kh, d), jnp.float32)
    vp = jax.random.normal(ks[2], (n, bs, kh, d), jnp.float32)
    kq = jax.random.randint(ks[1], (n, bs, kh, d), -127, 128, jnp.int8)
    vq = jax.random.randint(ks[2], (n, bs, kh, d), -127, 128, jnp.int8)
    ksc = jax.random.uniform(ks[4], (n, kh), jnp.float32, 0.005, 0.03)
    vsc = jax.random.uniform(ks[5], (n, kh), jnp.float32, 0.005, 0.03)
    tables = jax.random.permutation(ks[3], n)[: b * nblk].reshape(
        b, nblk).astype(jnp.int32)
    lengths = jnp.full((b,), nblk * bs, jnp.int32)
    dt_fp = _time(jax.jit(ref.paged_attention_ref), q, kp, vp, tables,
                  lengths)
    dt_q8 = _time(jax.jit(ref.paged_attention_int8_ref), q, kq, vq, ksc,
                  vsc, tables, lengths)
    kv_fp = b * nblk * bs * kh * d * 2 * 4
    kv_q8 = b * nblk * (bs * kh * d + kh * 4) * 2  # codes + fp32 scales
    saved = 1 - kv_q8 / kv_fp
    print(f"paged_attention int8 B={b} ctx={nblk*bs}: jnp-ref "
          f"fp32 {dt_fp*1e3:.2f} ms vs int8 {dt_q8*1e3:.2f} ms; KV bytes "
          f"{kv_fp} -> {kv_q8} ({saved:.0%} saved); device est: mem "
          f"{_est_us(kv_fp, 'hbm_bytes_per_s')} -> "
          f"{_est_us(kv_q8, 'hbm_bytes_per_s')}")
    return {"ref_fp32_ms": dt_fp * 1e3, "ref_int8_ms": dt_q8 * 1e3,
            "kv_bytes_fp32": kv_fp, "kv_bytes_int8": kv_q8,
            "hbm_bytes_saved_frac": saved}


def bench_cleanup_backends(rs=(256, 1024, 4096, 16384), t=64, h=10):
    """The tentpole comparison: one cleanup_batch scan per backend.

    T·H = 640 reservation slots (a 64-thread fleet with WFE's H+2 layout);
    ~half the slots empty, intervals randomized.  Times exclude retire-list
    construction — the era table maintains the arrays incrementally, so the
    scan IS the whole reclamation cost.
    """
    rng = np.random.default_rng(0)
    s = t * h
    lo = rng.integers(0, 1000, s).astype(np.int32)
    hi = (lo + rng.integers(0, 50, s)).astype(np.int32)
    lo[rng.random(s) < 0.5] = INF_ERA32
    out = {}
    print(f"\ncleanup_batch backends (T*H={s} reservation slots)")
    print(f"{'R':>8s} {'scalar ms':>10s} {'numpy ms':>10s} {'pallas ms':>10s}"
          f" {'numpy x':>8s} {'pallas x':>9s}")
    for r in rs:
        alloc = rng.integers(0, 1000, r).astype(np.int32)
        retire = (alloc + rng.integers(0, 100, r)).astype(np.int32)
        times = {}
        for backend in ("scalar", "numpy", "pallas"):
            reps = 1 if backend == "scalar" else 5
            batched_can_delete(alloc, retire, lo, hi, backend)  # warm/compile
            t0 = time.perf_counter()
            for _ in range(reps):
                mask = batched_can_delete(alloc, retire, lo, hi, backend)
            times[backend] = (time.perf_counter() - t0) / reps * 1e3
            del mask
        sp_np = times["scalar"] / times["numpy"]
        sp_pl = times["scalar"] / times["pallas"]
        print(f"{r:>8d} {times['scalar']:>10.2f} {times['numpy']:>10.3f} "
              f"{times['pallas']:>10.3f} {sp_np:>7.1f}x {sp_pl:>8.1f}x")
        out[r] = {**times, "numpy_speedup": sp_np, "pallas_speedup": sp_pl}
    beat = all(out[r]["numpy_speedup"] > 1 and out[r]["pallas_speedup"] > 1
               for r in rs if r >= 1024)
    print("batched backends beat scalar at R >= 1k:",
          "PASS" if beat else "FAIL")
    out["batched_beats_scalar_at_1k"] = beat
    return out


def run():
    print(f"\n### Kernel benchmarks on {jax.devices()[0].device_kind} "
          "(jnp references timed; bytes and operations analytic)")
    return {"era_scan": bench_era_scan(),
            "cleanup_backends": bench_cleanup_backends(),
            "paged_attention": bench_paged_attention(),
            "paged_attention_int8": bench_paged_attention_int8()}


if __name__ == "__main__":
    run()
