#!/usr/bin/env python3
"""Run one cell of FDJ's on-chip benchmark once, from a checkout's root:

  python3 benchmarks/chip/run.py --workload police_sweep --seed 7 \\
      --seconds 35 --trace 0

The cell, its deployment and its traffic mix are read from ``BENCHMARK.json``
and the files it names.  The run needs as many TPU chips as the cell asks
for and exits with a non-zero code, printing no result, on any other
device.  With ``--trace 0`` it reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace of the window.
The numbers the output check compared are printed, each beside its limit,
as the last lines of standard error and under ``checks`` in the result;
the last line of standard output is the result, one JSON object.

JAX's persistent compilation cache is kept at ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else at ``<checkout>/.cache/jax``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def use_compile_cache() -> None:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile, so a cell's second run
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"run.py: no TPU (the first device is {devs[0].platform})")
    if len(devs) < chips:
        sys.exit(f"run.py: the cell needs {chips} chips, JAX sees "
                 f"{len(devs)}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        sys.exit("run.py: --seed must be a non-negative whole number")

    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _, _ = harness.load_cell(bench, args.workload)
    use_compile_cache()
    require_chips(int(cell["chips"]))
    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    for name, check in out["checks"].items():
        print(f"check {name}: {check['value']!r} (limit {check['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
