#!/usr/bin/env python3
"""Readings from which a cell's limit is set: the program's widest logit
gap on each of many seeds, and the control's (the reference computed in
fp8, put in the program's place) on some of them, in one process.

    python3 benchmarks/chip/calibrate.py --workload stablelm-3b.chat \
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 20

Each seed is a whole run at the cell's own load and sizes (weights, pool,
window, drain, reference), as ``run_cell.py`` makes it; compiled steps
are shared between seeds.  Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    control = {int(x) for x in args.control_seeds.split(",") if x}
    for seed in (int(x) for x in args.seeds.split(",")):
        try:
            out = harness.run(cell, seed, args.seconds, False,
                              control=seed in control,
                              t_process=time.perf_counter())
        except harness.NoAccelerator as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 3
        print(json.dumps({"seed": seed, "gap": out.info["program_gap"],
                          "control_gap": out.info["control_gap"],
                          "checks": out.checks, "info": out.info,
                          "metrics": out.result["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
