#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip.

    python3 bench/readings.py --workload s6_study --seeds 101 102 ... \\
        --control-seeds 3 --calls 1

For each seed, in one process: the cell's calls as a run makes them
(``--calls`` timed calls with that ``--seed``), then the run's check
(the program's gaps: the lower readings), and for the first
``--control-seeds`` seeds the control's gaps (the reference computed in
bfloat16 in the program's place: the upper readings). One JSON line per
seed, then a summary: the largest program gap and the smallest control gap
of each number, beside the limit the traffic file sets.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--calls", type=int, default=1)
    args = ap.parse_args(argv)

    from bench import drivers, harness

    p = harness.plan(args.workload)
    try:
        harness.find_devices(p["cell"]["chips"])
    except harness.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    lower: dict = {}
    upper: dict = {}
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        drv = drivers.make(p["config"], p["traffic"], seed)
        for c in range(args.calls):
            drv.call(c)
        line = {"seed": seed, "program": drv.check()}
        if i < args.control_seeds:
            line["control"] = drv.check(control=True)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        for k, v in line["program"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        for k, v in line.get("control", {}).items():
            upper[k] = min(upper.get(k, float("inf")), v)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "lower": lower, "upper": upper,
                      "limits": p["traffic"]["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
