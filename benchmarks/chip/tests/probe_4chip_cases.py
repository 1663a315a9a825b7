"""The ``police_probe_4chip`` cell at a small size on four CPU devices, in a
process of its own (the device count is fixed when JAX starts):

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
      python benchmarks/chip/tests/probe_4chip_cases.py

Prints one JSON object, which ``test_probe_4chip.py`` reads.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, os.path.join(ROOT, "src")]

import jax  # noqa: E402

import harness  # noqa: E402
import planes  # noqa: E402
from repro.engine.sharded import ShardedEngine  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL, CHIPS, SEED = "police_probe_4chip", 4, 2**33 + 17
ROWS_L = 2044                  # shards of 511 rows: padded per shard


def overrides() -> dict:
    _, config, _ = harness.load_cell(BENCH, CELL)
    feats = [dict(f, width=256) if f["kind"] == "embed" else f
             for f in config["features"]]
    return {"rows_l": ROWS_L, "batch_rows": 64, "distinct_batches": 3,
            "warmup_queries": 1, "check_every": 1, "check_rows": 600,
            "features": feats}


class LostShard(ShardedEngine):
    """The engine with the last shard's candidates lost on the way out, as
    a chip whose rows never reached the host would leave them."""

    def __init__(self, first_row: int, **kw):
        super().__init__(**kw)
        self.first_row = first_row

    def evaluate_stream(self, feats, clauses, thetas):
        for ch in super().evaluate_stream(feats, clauses, thetas):
            yield dataclasses.replace(ch, candidates=[
                p for p in ch.candidates if p[0] < self.first_row])


def run(engine=None, trace=False) -> dict:
    out = harness.run_cell(BENCH, CELL, SEED, 0.5, trace, engine=engine,
                           overrides=overrides(), log=lambda m: None)
    return {"correct": out["correct"], "failed": out["failed"],
            "attempted": out["attempted"], "checks": out["checks"],
            "device": out["device"], "metrics": out["metrics"]}


def main() -> None:
    if len(jax.devices()) < CHIPS:
        sys.exit(f"needs {CHIPS} devices, JAX sees {len(jax.devices())}")
    planes.BLOCK_ROWS = 256
    stride = -(-ROWS_L // CHIPS)
    out = {"program": run(),
           "lost_shard": run(LostShard(3 * stride, prefetch_depth=2)),
           "traced": run(trace=True)}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
