"""Least work of a cell whose resident L side is split over its chips, per
device, from the cell's deployment file (not from the program's counters).

* The staging program (``fdj_stage_shards``, kernels/fused_cnf_join/ops.py)
  has to read each of a device's resident L rows once and write it once in
  the kernel's layout: ``rows_l / chips`` rows of every feature the CNF
  names, an embed at its width plus the two marker columns, a scalar one
  value, 4 bytes each.  Padding is not counted.  Its least time is those
  bytes over HBM bandwidth (``peaks.py``).
* One device's band step is ``kernel_work.band_step_work`` of its
  ``rows_l / chips`` L rows against the band's real columns.
"""

from __future__ import annotations

import os
import re

import harness
import kernel_work
from peaks import peak

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
# the staging program is jax.jit(jax.shard_map(fdj_stage_shards))
STAGE = re.compile(r"^jit_fdj_stage_shards\(")


# the one cell whose per-layer metrics read this module
CELL = "police_probe_4chip"


def deployment(ctx) -> dict | None:
    """``CELL``'s deployment file, where the run ``ctx`` is of it: the
    harness's band-step work of the run (its L rows, a full band's
    columns, the features and clauses) is that of the file.  A run at
    another size, as a test's ``overrides`` make, gives None."""
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    config = harness.load_cell(bench, CELL)[1]
    rows = int(config["rows_l"])
    if not ctx.step_pairs or max(ctx.step_pairs) % rows:
        return None
    work = kernel_work.band_step_work(rows, max(ctx.step_pairs) // rows,
                                      config["features"], config["clauses"])
    return config if work == ctx.work else None


def device_rows(config: dict, chips: int) -> int:
    return -(-int(config["rows_l"]) // chips)


def stage_bytes(config: dict, chips: int) -> int:
    """Bytes one device's staging has to read and write at least."""
    used = sorted({f for c in config["clauses"] for f in c})
    width = sum(int(config["features"][f]["width"]) + 2
                if config["features"][f]["kind"] == "embed" else 1
                for f in used)
    return device_rows(config, chips) * width * 4 * 2


def stage_least_s(config: dict, chips: int, device_kind: str) -> float:
    return stage_bytes(config, chips) / peak(device_kind, "float32")[1]


def band_step_work(config: dict, chips: int, cols: float) -> dict:
    """``kernel_work.band_step_work`` of one device's L rows against
    ``cols`` real columns."""
    return kernel_work.band_step_work(device_rows(config, chips), cols,
                                      config["features"], config["clauses"])
