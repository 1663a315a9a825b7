#!/usr/bin/env python3
"""Readings of the output check over many seeds, in one process.

  python3 benchmarks/chip/readings.py --workload police_sweep \\
      --seeds 11,12,13 --seconds 20 [--control]

Runs the cell once per seed with the given window (the program, or with
``--control`` the control of ``control.py`` in its place), and prints one
JSON line per seed with the compared numbers, ``correct`` and the metrics.
These are the readings the limits in the deployment files are set from:
the largest reading of the program's sound runs and the smallest of the
control's.  Not part of the benchmark's runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--check-every", type=int, default=None,
                    help="check every n-th query (a slow control finishes "
                         "fewer queries than the program in one window)")
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    from run import use_compile_cache
    use_compile_cache()
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for seed in (int(s) for s in args.seeds.split(",")):
        engine = None
        if args.control:
            from control import ControlEngine
            engine = ControlEngine()
        overrides = {"check_every": args.check_every} \
            if args.check_every else None
        out = harness.run_cell(bench, args.workload, seed, args.seconds,
                               engine=engine, overrides=overrides)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "checks": out["checks"],
                          "metrics": out["metrics"],
                          "device": out["device"]}), flush=True)


if __name__ == "__main__":
    main()
