#!/usr/bin/env python3
"""Find an open-loop cell's highest sustained rate: one process, one
set-up, one window at each of several fixed arrival rates.

    python3 benchmarks/chip/sweep.py --workload stablelm-3b.chat \
        --rates 0.3,0.45,0.6 --seconds 100 --seeds 5,6

Prints one JSON line per rate and seed: requests sent and finished in the
window, the requests in the system (sent and not finished) at each
quarter of the window, time to first token of the requests due in its
first and second halves, inter-token gap (median and tail, ms), tokens
per second and how late the client ran; then one line per rate over all
its seeds.  Between windows the runtime finishes (or, past a grace
period, cancels) what it holds.  A rate is sustained when the requests
in the system do not grow through the window and the second half's TTFT
stays at the first half's; the cell runs at four fifths of the highest
such rate, written as a number into its traffic file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def in_system(recs, t: float) -> int:
    """Requests sent by ``t`` and not finished by then."""
    return sum(1 for r in recs if r.sent is not None and r.sent <= t
               and (r.done_at is None or r.done_at > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, requests/s")
    ap.add_argument("--seconds", type=float, default=100.0)
    ap.add_argument("--seeds", default="0",
                    help="comma-separated seeds: one window per rate each")
    args = ap.parse_args(argv)

    from chipbench import harness, stats, traffic

    cell = harness.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{args.workload}: a sweep needs an open loop")
    seeds = [int(x) for x in args.seeds.split(",")]
    try:
        s = harness.Session(cell, seeds[0], traced=False)
    except harness.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    s.warm_prefixes(cell.traffic)
    ms = lambda v: None if v is None else v * 1e3  # noqa: E731
    for rate in (float(r) for r in args.rates.split(",")):
        spec = dict(cell.traffic, rate_per_s=rate)
        pooled = {"tt": [], "it": [], "tokens": 0, "sent": 0, "done": 0}
        for seed in seeds:
            items = traffic.generate(spec, seed, s.m["vocab_size"],
                                     args.seconds)
            w = s.window(spec, args.seconds, items)
            recs, t0, t1 = w["recs"], w["t0"], w["t_end"]
            mid = t0 + args.seconds / 2
            tt, it = stats.ttfts(recs, t0, t1), stats.itls(recs, t0, t1)
            done = sum(1 for r in recs
                       if r.done_at is not None and r.done_at < t1)
            tokens = stats.tokens_in(recs, t0, t1)
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "sent": len(recs),
                "finished": done,
                "in_system": [in_system(recs, t0 + k * args.seconds / 4)
                              for k in (1, 2, 3, 4)],
                "ttft_p50_ms": ms(stats.pct(tt, 50)),
                "ttft_p90_ms": ms(stats.pct(tt, 90)),
                "ttft_p90_ms_halves": [
                    ms(stats.pct(stats.ttfts(recs, t0, mid), 90)),
                    ms(stats.pct(stats.ttfts(recs, mid, t1), 90))],
                "itl_p50_ms": ms(stats.pct(it, 50)),
                "itl_p95_ms": ms(stats.pct(it, 95)),
                "tokens_per_s": tokens / args.seconds,
                "lag_max_s": max(w["lag"]) if w["lag"] else None,
                "compiles_in_window": w["compiles_in_window"],
                "counters": w["counters"]}), flush=True)
            pooled["tt"] += tt
            pooled["it"] += it
            pooled["tokens"] += tokens
            pooled["sent"] += len(recs)
            pooled["done"] += done
            s.wait_idle(grace_s=30.0)
        print(json.dumps({
            "rate_per_s": rate, "seeds": seeds, "sent": pooled["sent"],
            "finished": pooled["done"],
            "ttft_p50_ms": ms(stats.pct(pooled["tt"], 50)),
            "ttft_p90_ms": ms(stats.pct(pooled["tt"], 90)),
            "itl_p95_ms": ms(stats.pct(pooled["it"], 95)),
            "tokens_per_s": pooled["tokens"] / (args.seconds * len(seeds))}),
            flush=True)
    s.runtime.drain(deadline_s=0.0)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
