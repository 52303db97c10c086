"""Roofline analysis from the compiled dry-run artifact.

Three terms per (arch × shape × mesh), in seconds, from the peaks of the
target chip (``PEAKS``, keyed by ``jax.Device.device_kind``):

    compute    = HLO_FLOPs_per_device / peak_FLOPs        (v5e: 197 TFLOP/s)
    memory     = HLO_bytes_per_device / HBM_bw            (v5e: 819 GB/s)
    collective = wire_bytes_per_device / link_bw          (v5e: 50 GB/s/link)

``cost_analysis()`` yields per-device FLOPs/bytes (the compiled module is
the per-device SPMD program).  Collective bytes are NOT in cost_analysis —
``parse_collectives`` scans the optimized HLO text, summing result-shape
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, with per-op group sizes from replica_groups, and
converts to per-device wire bytes with the standard ring model:

    all-reduce      2·S·(g-1)/g        all-gather     S·(g-1)/g
    reduce-scatter  S_out·(g-1)        all-to-all     S·(g-1)/g
    collective-permute  S

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE), D = tokens in the step;
the ratio MODEL_FLOPS / (HLO_FLOPs × chips) flags remat/redundancy waste
(remat recompute, masked-out flash-attention blocks, dispatch overhead).
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

# ------------------------------------------------------- hardware constants
#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
#: interconnect (four links: 50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "ici_link_bytes_per_s": 50e9},
}
#: the chip the dry run's production mesh is built from
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown device is an error."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}: add them to PEAKS with their "
                         f"source (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_ITOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _line_max_bytes(line: str) -> int:
    """Largest single shape on an HLO instruction line.

    Robust across sync/async (-start tuple) forms and operand shape refs:
    for all-reduce the result == operand (max = S); for all-gather the
    gathered result is the max; for reduce-scatter the input is the max —
    each matches the S the ring formulas below expect.
    """
    rhs = line.split("=", 1)[1][:400]
    best = 0
    for m in _SHAPE_RE.finditer(rhs):
        best = max(best, _shape_bytes(m.group(1), m.group(2)))
    return best


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_ITOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        entries = [e for e in m.group(1).split(",") if e]
        return max(1, len(entries))
    return default


def parse_collectives(hlo_text: str, n_devices: int) -> Dict[str, Dict]:
    """Per-kind result-shape bytes + ring-model wire bytes per device."""
    out: Dict[str, Dict] = {
        k: {"count": 0, "result_bytes": 0, "wire_bytes": 0.0}
        for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        for kind in _COLLECTIVES:
            # match sync and async-start forms; skip -done (the transfer is
            # accounted at its -start)
            if f" {kind}(" in line or f" {kind}-start(" in line:
                break
        else:
            continue
        s = _line_max_bytes(line)
        g = _group_size(line, n_devices)
        rec = out[kind]
        rec["count"] += 1
        rec["result_bytes"] += s
        if kind == "all-reduce":
            rec["wire_bytes"] += 2 * s * (g - 1) / max(g, 1)
        elif kind == "collective-permute":
            rec["wire_bytes"] += s
        else:  # all-gather / reduce-scatter / all-to-all
            rec["wire_bytes"] += s * (g - 1) / max(g, 1)
    return out


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   collectives: Dict[str, Dict],
                   device_kind: str = DRYRUN_DEVICE_KIND) -> Dict[str, float]:
    p = peaks(device_kind)
    wire = sum(r["wire_bytes"] for r in collectives.values())
    return {
        "compute_s": flops_per_dev / p["flops_bf16"],
        "memory_s": bytes_per_dev / p["hbm_bytes_per_s"],
        "collective_s": wire / p["ici_link_bytes_per_s"],
        "wire_bytes_per_dev": wire,
    }


def dominant_term(terms: Dict[str, float]) -> str:
    kinds = {k: terms[k] for k in ("compute_s", "memory_s", "collective_s")}
    return max(kinds, key=kinds.get)


def model_flops(cfg, shape, n_active: Optional[int] = None) -> float:
    """6·N·D with D = tokens processed by the step."""
    n = n_active if n_active is not None else cfg.param_count()
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n * d
    if shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        return 2.0 * n * d  # forward only
    return 2.0 * n * shape.global_batch  # decode: 1 token/request, fwd only


def mfu_fraction(model_fl: float, flops_per_dev: float, chips: int,
                 terms: Dict[str, float],
                 device_kind: str = DRYRUN_DEVICE_KIND) -> Dict[str, float]:
    """Useful-FLOPs fraction of the roofline-limited step time."""
    peak = peaks(device_kind)["flops_bf16"]
    step_time = max(terms["compute_s"], terms["memory_s"],
                    terms["collective_s"])
    hlo_global = flops_per_dev * chips
    return {
        "useful_flops_ratio": model_fl / hlo_global if hlo_global else 0.0,
        "bound_step_time_s": step_time,
        "model_flops_time_s": model_fl / (chips * peak),
        "roofline_fraction": (model_fl / (chips * peak)) / step_time
        if step_time else 0.0,
    }
