"""Perf-regression gate: fresh serve_bench smoke JSON vs committed baseline.

Pure-stdlib on purpose (no jax/numpy import): CI runs it right after the
bench in the same job, and a broken runtime environment must fail in the
BENCH step, not mask itself as a checker crash here.

Usage (CI runs exactly this):

    PYTHONPATH=src python benchmarks/serve_bench.py --smoke --json serve_smoke.json
    python benchmarks/check_regression.py serve_smoke.json

Compares the headline latency medians (TTFT/TPOT p50 of the chunked
prefill mode, the cached prefix mode, and the coarse-bucket decode-heavy
mode) against ``benchmarks/baselines/serve_smoke.json`` with a
multiplicative tolerance band: ``fresh <= baseline * tolerance`` per
metric.  The band absorbs runner-to-runner variance; a genuine hot-path
regression (recompiles in the serve loop, a lock where none belongs,
reclamation stalling planning) blows through it.  Improvements always
pass; a large one (beyond 1/tolerance) prints a hint to refresh the
committed baseline:

    PYTHONPATH=src python benchmarks/serve_bench.py --smoke \
        --json benchmarks/baselines/serve_smoke.json

The relative invariants (chunked TTFT speedup > 1, prefix hit-rate > 0,
coarse buckets saving recompiles and staying within a fixed per-shape
compile budget, open-loop interactive goodput > 0 under Poisson arrival
pressure) are also re-asserted from the fresh JSON — they are
machine-independent and have NO tolerance.  The compile-count bounds are
the bucket-policy gate: a regression that reintroduces per-shape
recompiles (e.g. bucketing on the current width again) shows up as a
compile count the budget rejects, regardless of runner speed.
"""

from __future__ import annotations

import argparse
import json
import sys

#: (section, mode, metric-path) medians gated against the baseline
GATED_METRICS = (
    ("prefill_heavy", "chunked", "ttft"),
    ("prefill_heavy", "chunked", "tpot"),
    ("prefix_heavy", "cached", "ttft"),
    ("prefix_heavy", "cached", "tpot"),
    ("decode_heavy", "coarse", "ttft"),
    ("decode_heavy", "coarse", "tpot"),
)

#: machine-independent invariants: (section, key, exclusive lower bound,
#: description) — the bound lives HERE so a new invariant cannot silently
#: inherit the wrong threshold
INVARIANTS = (
    ("prefill_heavy", "ttft_speedup", 1.0, "chunked prefill must win"),
    ("prefix_heavy", "hit_rate", 0.0, "prefix cache must hit"),
)

#: compile-count budget for the coarse bucket policy in the decode-heavy
#: scenario: one decode bucket + one prefill bucket per request size
#: class that arrives cold, plus slack for a prefix-shrunken chunk shape.
#: Counted via the jitted steps' per-shape cache sizes — a bucket-policy
#: regression that recompiles per CURRENT width walks the whole pow2
#: ladder (4+ shapes in the smoke scenario, measured 2 for coarse) and
#: blows this budget even on an arbitrarily fast runner.
MAX_COARSE_COMPILES = 3

#: no-harm bound on the quantized-KV mode: int8 decode TPOT p50 must stay
#: within this factor of the SAME run's fp32 p50.  On the CPU interpreter
#: int8 buys no bandwidth (the dequant and requantizing scatter are extra
#: work), so this is a regression tripwire — a blowup here means the int8
#: step graph grew something expensive — not a speedup claim; the byte
#: saving is asserted separately as an exact analytic invariant.
KV_TPOT_NO_HARM = 1.05

#: absolute slack on the open-loop interactive goodput band: goodput is a
#: FRACTION of (16) smoke requests meeting SLO, so one request flipping
#: across the line moves it by ~0.1 on a noisy shared runner — the band
#: catches collapses (starvation regressions push it toward 0), not
#: single-request jitter
GOODPUT_SLACK = 0.35


def _p50(results: dict, section: str, mode: str, metric: str):
    try:
        return results[section][mode][metric]["p50_ms"]
    except (KeyError, TypeError):
        return None


def check(fresh: dict, baseline: dict, tolerance: float) -> list:
    """Returns a list of failure strings (empty = gate passes)."""
    failures = []
    for blob, name in ((fresh, "fresh"), (baseline, "baseline")):
        if blob.get("schema") != "serve_bench/ttft_tpot/v1":
            failures.append(f"{name}: bad schema {blob.get('schema')!r}")
    if failures:
        return failures

    print(f"{'metric':>32s} {'baseline':>9s} {'fresh':>9s} {'ratio':>6s} "
          f"{'limit':>6s} {'status':>7s}")
    for section, mode, metric in GATED_METRICS:
        base = _p50(baseline, section, mode, metric)
        new = _p50(fresh, section, mode, metric)
        label = f"{section}.{mode}.{metric}.p50_ms"
        if base is None:
            failures.append(f"{label}: missing from baseline")
            continue
        if new is None:
            failures.append(f"{label}: missing from fresh results")
            continue
        ratio = new / base
        ok = ratio <= tolerance
        print(f"{label:>32s} {base:>9.2f} {new:>9.2f} {ratio:>5.2f}x "
              f"{tolerance:>5.2f}x {'ok' if ok else 'FAIL':>7s}")
        if not ok:
            failures.append(
                f"{label}: {new:.2f} ms vs baseline {base:.2f} ms "
                f"({ratio:.2f}x > {tolerance:.2f}x tolerance)")
        elif ratio < 1.0 / tolerance:
            print(f"  note: {label} improved {1 / ratio:.2f}x — consider "
                  f"refreshing benchmarks/baselines/serve_smoke.json")

    for section, key, bound, why in INVARIANTS:
        val = fresh.get(section, {}).get(key)
        if val is None:
            failures.append(f"{section}.{key}: missing from fresh results")
        elif not val > bound:
            failures.append(
                f"{section}.{key} = {val}: must be > {bound} ({why})")

    # the compile gates: absolute and runner-speed-independent
    dh = fresh.get("decode_heavy", {})
    compiles = dh.get("coarse", {}).get("compiles")
    savings = dh.get("compile_savings")
    if "decode_heavy" not in fresh:
        failures.append("decode_heavy: section missing from fresh results")
    else:
        if savings <= 0:
            failures.append(
                f"decode_heavy.compile_savings = {savings}: must be > 0 "
                f"(coarse buckets must save recompiles vs the pow2 ladder)")
        if compiles > MAX_COARSE_COMPILES:
            failures.append(
                f"decode_heavy.coarse.compiles = {compiles}: exceeds the "
                f"{MAX_COARSE_COMPILES}-shape budget (per-shape recompiles "
                f"are back in the serve loop — check the bucket policy)")
        else:
            print(f"coarse bucket compiles: {compiles} "
                  f"(budget {MAX_COARSE_COMPILES}), "
                  f"savings vs pow2: {savings}")

    # scheme-matrix reclamation invariant (machine-independent): every
    # scheme's engine drain must reclaim all retired blocks.  The section
    # only exists in JSONs produced since the Crystalline port, so it is
    # checked on the FRESH results alone — an older committed baseline
    # without it neither gates nor fails.
    sm = fresh.get("scheme_matrix")
    if sm is not None:
        for name, row in sorted(sm.get("schemes", {}).items()):
            left = row.get("unreclaimed")
            if left != 0:
                failures.append(
                    f"scheme_matrix.{name}.unreclaimed = {left!r}: engine "
                    f"drain must reclaim every retired block")
        ratio = sm.get("crystalline_vs_wfe")
        if isinstance(ratio, (int, float)):
            print(f"scheme matrix: Crystalline vs WFE decode TPOT "
                  f"{ratio:.2f}x (informational, not gated)")

    # quantized-KV gates, all on the FRESH results (the A/B's fp32 leg is
    # the same-run control, so no cross-machine baseline is needed and an
    # older committed baseline without the section neither gates nor
    # fails — the scheme-matrix precedent):
    #   tpot_ratio <= KV_TPOT_NO_HARM — int8 decode must not slow the
    #     interpreter-path step beyond noise (no-harm, not a speedup);
    #   kv_bytes_saved_frac > 0 — the analytic byte model must show int8
    #     pages streaming fewer bytes (machine-independent, exact).
    kv = fresh.get("kv_dtype")
    if kv is None:
        failures.append("kv_dtype: section missing from fresh results")
    else:
        ratio = kv.get("tpot_ratio")
        if not isinstance(ratio, (int, float)):
            failures.append("kv_dtype.tpot_ratio: missing")
        elif ratio > KV_TPOT_NO_HARM:
            failures.append(
                f"kv_dtype.tpot_ratio = {ratio:.2f}: int8 decode TPOT p50 "
                f"exceeds fp32's x {KV_TPOT_NO_HARM} no-harm bound (the "
                f"quantized step graph grew something expensive)")
        else:
            print(f"kv_dtype: int8/fp32 TPOT ratio {ratio:.2f} "
                  f"(no-harm bound {KV_TPOT_NO_HARM})")
        saved = kv.get("kv_bytes_saved_frac")
        if not isinstance(saved, (int, float)):
            failures.append("kv_dtype.kv_bytes_saved_frac: missing")
        elif not saved > 0:
            failures.append(
                f"kv_dtype.kv_bytes_saved_frac = {saved}: int8 pages must "
                f"stream fewer bytes per decode step than fp32")
        else:
            print(f"kv_dtype: KV bytes/step saved {saved:.0%}")

    # cancellation gates, all on the FRESH results (the section only
    # exists in JSONs produced since the serving front-end landed — an
    # older committed baseline without it neither gates nor fails, the
    # scheme_matrix precedent).  All three are machine-independent:
    #   unreclaimed == 0 — every page a cancelled client abandoned must
    #     reclaim through the refcount/era path by the end of the drain;
    #   n_cancelled > 0 — the scenario must actually abandon requests
    #     (a vacuous run must not green-light the gate);
    #   wasted_frac in [0, 1] — the wasted-tokens accounting must be a
    #     well-formed fraction of generated tokens.
    ca = fresh.get("cancellation")
    if ca is not None:
        left = ca.get("unreclaimed")
        if left != 0:
            failures.append(
                f"cancellation.unreclaimed = {left!r}: abandoned pages "
                f"must reclaim through the refcount/era path")
        if not ca.get("n_cancelled"):
            failures.append(
                "cancellation.n_cancelled = 0: the scenario must actually "
                "abandon requests mid-flight")
        wf = ca.get("wasted_frac")
        if not isinstance(wf, (int, float)) or not 0.0 <= wf <= 1.0:
            failures.append(
                f"cancellation.wasted_frac = {wf!r}: must be a fraction "
                f"in [0, 1]")
        else:
            lat = ca.get("cancel_latency", {}).get("p50_ms")
            print(f"cancellation: {ca.get('n_cancelled')} abandoned, "
                  f"wasted-tokens fraction {wf:.2f}, cancel latency p50 "
                  + (f"{lat:.1f} ms" if isinstance(lat, (int, float))
                     else "-")
                  + " (latency informational, not gated)")

    # fault-tolerance gates, all on the FRESH results (the section only
    # exists in JSONs produced since the crash-tolerance work — an older
    # committed baseline without it neither gates nor fails, the
    # scheme_matrix precedent).  All machine-independent:
    #   n_respawns > 0 — the supervisor must actually recover a crashed
    #     worker (a vacuous run must not green-light the gate);
    #   completed_despite_faults == 1.0 — every request completes
    #     exactly once; crash-requeued rows replay, none are lost;
    #   token_exact — survivors match the fault-free greedy reference;
    #   unreclaimed == 0 — reaping the dead tids unpinned every era
    #     reservation they held.
    # Recovery latency is informational: it measures crash-detected ->
    # the replacement worker's first productive step, which is dominated
    # by thread spawn + poll interval on a shared runner.
    ft = fresh.get("fault_tolerance")
    if ft is not None:
        for name, row in sorted(ft.get("schemes", {}).items()):
            if not row.get("n_respawns"):
                failures.append(
                    f"fault_tolerance.{name}.n_respawns = 0: the "
                    f"supervisor never recovered a crashed worker")
            cdf = row.get("completed_despite_faults")
            if cdf != 1.0:
                failures.append(
                    f"fault_tolerance.{name}.completed_despite_faults = "
                    f"{cdf!r}: every request must complete exactly once "
                    f"despite injected crashes")
            if not row.get("token_exact"):
                failures.append(
                    f"fault_tolerance.{name}: crash-requeued requests "
                    f"replayed differently from the fault-free reference")
            left = row.get("unreclaimed")
            if left != 0:
                failures.append(
                    f"fault_tolerance.{name}.unreclaimed = {left!r}: "
                    f"reaping dead tids must unpin every era reservation")
        n_rows = len(ft.get("schemes", {}))
        if n_rows:
            lats = [r.get("recovery_latency", {}).get("p50_ms")
                    for r in ft["schemes"].values()]
            lats = [x for x in lats if isinstance(x, (int, float))]
            print(f"fault tolerance: {ft.get('total_crashes')} injected "
                  f"crashes over {n_rows} scheme(s), all requests "
                  f"completed token-exact; recovery p50 "
                  + (f"{max(lats):.1f} ms worst-scheme" if lats else "-")
                  + " (informational, not gated)")

    # open-loop goodput gate: interactive-class requests must keep
    # meeting their SLO under Poisson arrival pressure.  The invariant
    # (goodput_interactive > 0 with interactive arrivals present) is
    # machine-independent — the SLO targets are multiples of the runner's
    # OWN unloaded calibration, so a slow runner gets a proportionally
    # slower target, not a free pass.  The band against the committed
    # baseline only applies when the baseline HAS the section (older
    # baselines neither gate nor fail, like scheme_matrix above).
    ol = fresh.get("open_loop")
    if ol is None:
        failures.append("open_loop: section missing from fresh results")
    else:
        gi = ol.get("goodput_interactive")
        if not isinstance(gi, (int, float)):
            failures.append("open_loop.goodput_interactive: missing")
        elif not gi > 0:
            failures.append(
                f"open_loop.goodput_interactive = {gi}: no interactive "
                f"request met its SLO under open-loop arrival (decode "
                f"starvation or admission failure)")
        if not ol.get("n_interactive"):
            failures.append("open_loop.n_interactive = 0: the goodput "
                            "gate is vacuous without interactive arrivals")
        base_ol = baseline.get("open_loop")
        if (base_ol is not None and isinstance(gi, (int, float))
                and isinstance(base_ol.get("goodput_interactive"),
                               (int, float))):
            floor = base_ol["goodput_interactive"] - GOODPUT_SLACK
            ok = gi >= floor
            print(f"open loop: interactive goodput {gi:.2f} "
                  f"(baseline {base_ol['goodput_interactive']:.2f}, "
                  f"floor {floor:.2f}) {'ok' if ok else 'FAIL'}")
            if not ok:
                failures.append(
                    f"open_loop.goodput_interactive = {gi:.2f}: below "
                    f"baseline {base_ol['goodput_interactive']:.2f} - "
                    f"{GOODPUT_SLACK} slack")
        gap = ol.get("gap", {})
        if isinstance(gap, dict) and gap.get("p95_ms") is not None:
            print(f"open loop: worst per-token gap p95 "
                  f"{gap['p95_ms']:.1f} ms / p99 {gap['p99_ms']:.1f} ms "
                  f"(informational, not gated)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("fresh", help="JSON written by serve_bench --smoke --json")
    ap.add_argument("--baseline",
                    default="benchmarks/baselines/serve_smoke.json")
    ap.add_argument("--tolerance", type=float, default=3.0,
                    help="max allowed fresh/baseline latency ratio "
                         "(default 3.0: wide enough for runner variance, "
                         "tight enough to catch recompile-bound loops)")
    args = ap.parse_args(argv)
    with open(args.fresh) as f:
        fresh = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)
    failures = check(fresh, baseline, args.tolerance)
    if failures:
        for msg in failures:
            print(f"REGRESSION: {msg}", file=sys.stderr)
        return 1
    print("perf gate: all metrics within tolerance")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
