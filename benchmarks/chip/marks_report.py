#!/usr/bin/env python3
"""Run one cell traced, keeping the program's host annotations, and say
what the device's idle time is made of, from a checkout's root:

  python3 benchmarks/chip/marks_report.py --workload police_probe \\
      --seed 7 --seconds 51 [--out chiprun_out/marks_police_probe.json]

The run is ``run.py --trace 1``'s (``harness.run_cell``) with a capture
that also keeps the ``fdj.*`` annotations of the host planes
(``marks.Capture``).  Printed, one JSON object a line: the run's result;
its end-to-end metrics, traced; the window's idle time by the innermost
annotation covering each gap, on the trace clock (``marks.idle_gaps``),
beside the ``perf_counter`` spans' mapping (``reduce.idle_gaps``), both in
seconds a window and in ms per finished query; each annotation's total
time; and each band-step scope's device time per step.  ``--out`` writes the captured trace with its
``"host"`` events and the band step's ``"scopes"`` (instruction name to
scope) as JSON, for the tests.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import devtrace
    import harness
    import marks
    import reduce
    from run import use_compile_cache, require_chips

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _, _ = harness.load_cell(bench, args.workload)
    use_compile_cache()
    require_chips(int(cell["chips"]))
    devtrace.Capture = marks.Capture         # keep the host annotations
    got, ctx = [], {}
    load_reader = harness.load_reader

    def keep_ctx(name):                      # the spans and queries too
        read = load_reader(name)
        return lambda c: (ctx.setdefault("c", c), read(c))[1]

    harness.load_reader = keep_ctx
    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           True, on_trace=got.append)
    trace, c = got[0], ctx["c"]
    scopes = marks.program_scopes()
    print(json.dumps({k: out[k] for k in ("correct", "metrics", "device")}))
    # the end-to-end metrics of this traced window: against an untraced
    # run's, the cost of tracing
    print(json.dumps({"end_to_end_traced": {
        m["name"]: load_reader(m["name"])(c)
        for m in harness.cell_metrics(bench, args.workload, False)}}))
    done = sum(q.complete for q in c.queries)
    for label, gaps in (
            ("idle_by_annotation", marks.idle_gaps(trace, c.spans,
                                                   c.window[0], n=20)),
            ("idle_by_span", reduce.idle_gaps(trace, c.spans, c.window[0],
                                              n=20))):
        print(json.dumps({label: [[k, v, v / done * 1e3] for k, v in gaps],
                          "queries": done}))
    totals = defaultdict(float)
    for name, _, dur in trace["host"]:
        totals[name] += dur * 1e-9
    print(json.dumps({"annotation_s": sorted(totals.items(),
                                             key=lambda kv: -kv[1])}))
    print(json.dumps({"scope_ms_per_step": {
        s: marks.scope_ms_per_step(trace, s, scopes)
        for s in ("fdj_kernel", "fdj_extract", "fdj_offsets")}}))
    if args.out:
        used = set()
        for lines in trace["planes"].values():
            for evs in lines.values():
                for name, _, _ in evs:
                    m = marks.EVENT.match(name)
                    if m:
                        used.add(m.group(1))
        trace["scopes"] = {k: v for k, v in scopes.items() if k in used}
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(trace, f)


if __name__ == "__main__":
    main()
