#!/usr/bin/env python3
"""Record a short traced window of one cell on the chip, for the tests.

  python3 benchmarks/chip/tests/record_trace.py --workload police_sweep \\
      --seconds 0.3 --out chiprun_out/trace_police_sweep.json

Writes the captured trace (``devtrace.extract``'s dict) as JSON, and prints
a summary of it: each device plane's lines with their event counts and most
frequent event names.  ``tests/data/`` keeps one such recording.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    from run import use_compile_cache
    use_compile_cache()
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    got = []
    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           True, on_trace=got.append)
    trace = got[0]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(trace, f)
    for plane, lines in trace["planes"].items():
        for line, evs in lines.items():
            names = collections.Counter(e[0] for e in evs)
            print(json.dumps({"plane": plane, "line": line, "events": len(evs),
                              "names": names.most_common(12)}))
    print(json.dumps({k: out[k] for k in ("correct", "metrics", "device")}))


if __name__ == "__main__":
    main()
