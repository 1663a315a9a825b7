"""Sharded residency on four CPU devices, in a process of its own (the
device count is fixed when JAX starts):

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
      python benchmarks/chip/tests/sharded_cases.py

Prints one JSON object, which ``test_shards.py`` reads.
"""

from __future__ import annotations

import json
import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import harness  # noqa: E402
import planes  # noqa: E402
import reference  # noqa: E402
from planes import Deployment, Rows, draw  # noqa: E402
from traffic import BATCH, RESIDENT, Traffic, cell_mesh, fetch_rows  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CHIPS, BLOCK, SEED = 4, 256, 2**33 + 7
SIZES = {"police_sweep": {"rows_l": 2048, "rows_r": 2048, "check_rows": 100},
         "police_probe": {"rows_l": 2048, "batch_rows": 64,
                          "distinct_batches": 3}}


def small(workload: str) -> tuple:
    _, config, mix = harness.load_cell(BENCH, workload)
    for key, value in SIZES[workload].items():
        (mix if key in mix else config)[key] = value
    return config, mix


def one_device(config: dict, mix: dict, seed: int) -> dict:
    """The same shards' blocks made one after another on one device, the
    planted rows' partners taken from the whole raw side."""
    dep, dev = Deployment(config), jax.devices()[0]
    blocks = [d for shard in dep.draws(seed, (RESIDENT, 0), config["rows_l"],
                                       [dev] * CHIPS) for d in shard]
    raw = [dep.rows(d) for d in blocks]
    whole = Rows([np.concatenate(v) for v in zip(*(r.values for r in raw))],
                 [np.concatenate(m) for m in zip(*(r.missing for r in raw))])

    def planes(rows: list, side: str) -> list:
        return [np.concatenate(p) for p in zip(*(r.encode(side)
                                                  for r in rows))]

    def planted(d):
        pi = np.asarray(dep.pick(d, config["rows_l"]))
        return dep.rows(d, config["planted_share"], Rows(
            [v[pi] for v in whole.values], [m[pi] for m in whole.missing]))

    out = {"l": planes(raw, "l")}
    if mix["kind"] == "sweep":
        out["r"] = planes([planted(d) for shard in dep.draws(
            seed, (RESIDENT, 1), config["rows_r"], [dev] * CHIPS)
            for d in shard], "r")
    else:
        out["batches"] = [planes([planted(draw(seed, (BATCH, b),
                                               mix["batch_rows"], dev))], "r")
                          for b in range(mix["distinct_batches"])]
    return out


def sharding_case(t: Traffic) -> dict:
    """Per resident plane: its spec, and each shard's device and rows; and
    the shapes of live arrays that sit on one device and hold as many rows
    as a whole side (none should be left once the side is made)."""
    n = min(t.n_l, t.n_r)
    out = {"whole_on_one": [list(a.shape) for a in jax.live_arrays()
                            if len(a.devices()) == 1 and a.ndim
                            and a.shape[0] >= n]}
    for side, planes in t.resident.items():
        out[side] = [{
            "spec": [list(a) if isinstance(a, tuple) else a
                     for a in p.sharding.spec],
            "mesh": dict(p.sharding.mesh.shape),
            "shape": list(p.shape),
            "shards": sorted(
                [sh.device.id, sh.index[0].start or 0,
                 sh.index[0].stop or p.shape[0], list(sh.data.shape)]
                for sh in p.addressable_shards)} for p in planes]
    return out


def same_case(t: Traffic, config: dict, mix: dict) -> dict:
    ref = one_device(config, mix, SEED)
    out = {side: all(np.array_equal(np.asarray(a), b) for a, b in
                     zip(t.resident[side], ref[side]))
           for side in t.resident}
    if "batches" in ref:
        out["batches"] = all(
            np.array_equal(a, b) for got, want in zip(t.batches,
                                                      ref["batches"])
            for a, b in zip(got, want))
    return out


def fetch_case(t: Traffic) -> dict:
    full_l = [np.asarray(p) for p in t.resident["l"]]
    full_r = [np.asarray(p) for p in t.resident["r"]]
    # all of shard 1, some of shard 0 and 2, none of shard 3
    rows = np.r_[5, 17, 512:1024, 1100, 1500]
    picked = all(np.array_equal(fetch_rows(p, rows), f[rows])
                 for p, f in zip(t.resident["l"], full_l))
    n_cols = 1536
    t.fetch(n_cols)
    host_l, host_r = t.planes(0)
    rows_equal = all(np.array_equal(a, f[t.check_rows])
                     for a, f in zip(host_l, full_l)) and \
        all(np.array_equal(a, f[:n_cols]) for a, f in zip(host_r, full_r))
    # the reference's own candidates, one lost, one twice, one outside
    blocks = list(reference.scores(host_l, host_r, t.clauses, t.thetas,
                                   t.check_rows, n_cols))
    good = np.concatenate([np.stack([blk[i], j], 1) for blk, s in blocks
                           for i, j in [np.nonzero(s <= 0)]])
    pairs = np.concatenate([good[1:], good[2:3], [[t.n_l, 0]]])
    args = (t.clauses, t.thetas, t.check_rows, n_cols, t.n_l)
    shard_wise = reference.compare(pairs, host_l, host_r, *args)
    whole = reference.compare(pairs, [f[t.check_rows] for f in full_l],
                              full_r, *args)
    return {"picked": picked, "rows_equal": rows_equal,
            "compare_equal": shard_wise == whole, "compare": shard_wise}


def run_case(workload: str) -> dict:
    """A whole run of the cell, four chips, small and 256 wide."""
    _, config, _ = harness.load_cell(BENCH, workload)
    feats = [dict(f, width=256) if f["kind"] == "embed" else f
             for f in config["features"]]
    overrides = dict(SIZES[workload], chips=CHIPS, features=feats)
    if workload == "police_probe":
        overrides.update(warmup_queries=1, check_every=1)
    seen = {}
    reader = harness.load_reader

    def keep_ctx(name):
        read = reader(name)
        return lambda c: (seen.setdefault("chips", c.chips), read(c))[1]

    harness.load_reader = keep_ctx
    try:
        out = harness.run_cell(BENCH, workload, SEED, 0.5,
                               overrides=overrides, log=lambda m: None)
    finally:
        harness.load_reader = reader
    return {"correct": out["correct"], "attempted": out["attempted"],
            "checks": out["checks"], "device": out["device"],
            "ctx_chips": seen.get("chips")}


def main() -> None:
    if len(jax.devices()) < CHIPS:
        sys.exit(f"needs {CHIPS} devices, JAX sees {len(jax.devices())}")
    planes.BLOCK_ROWS = BLOCK
    mesh = cell_mesh(CHIPS)
    out = {"devices": [d.id for d in mesh.devices.flat]}
    config, mix = small("police_sweep")
    t = Traffic(config, mix, SEED, mesh)
    out["sharding"] = sharding_case(t)
    out["same"] = {"police_sweep": same_case(t, config, mix)}
    out["fetch"] = fetch_case(t)
    del t
    config, mix = small("police_probe")
    t = Traffic(config, mix, SEED, mesh)
    out["same"]["police_probe"] = same_case(t, config, mix)
    del t
    out["run"] = {w: run_case(w) for w in SIZES}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
