#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 benchmarks/chip/run_cell.py --workload stablelm-3b.chat \
        --seed 7 --seconds 30 --trace 0

From the root of a checkout.  Exits non-zero, printing no result, when
JAX finds no accelerator or fewer chips than the cell asks for.  The last
line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; the numbers compared with the reference come last, under
``checks``, and again as the last lines of standard error.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_process=_T0)
    except harness.NoAccelerator as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 3
    harness.report(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
