"""Resident planes split over a cell's chips: made shard by shard, block by
block, each on its own device; the check fetches only the rows it reads;
the memory read is the fullest device's.  The four-device cases run in a
process of their own (``sharded_cases.py``), since the device count is
fixed when JAX starts."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import harness
from traffic import Traffic

from conftest import CHIP, ROOT

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CHIPS, ROWS = 4, 2048


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, os.path.join(CHIP, "tests", "sharded_cases.py")],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_resident_planes_are_split_over_the_mesh(cases):
    assert len(set(cases["devices"])) == CHIPS
    assert cases["sharding"]["whole_on_one"] == []
    per = ROWS // CHIPS
    for side in ("l", "r"):
        for plane in cases["sharding"][side]:
            assert plane["mesh"] == {"data": CHIPS, "model": 1}
            embed = len(plane["shape"]) == 2
            assert plane["spec"] == (["data", None] if embed else ["data"])
            # each device holds its own rows and no others
            assert [s[0] for s in plane["shards"]] == cases["devices"]
            for s, (_, lo, hi, shape) in enumerate(plane["shards"]):
                assert (lo, hi, shape[0]) == (s * per, (s + 1) * per, per)
                assert shape[1:] == plane["shape"][1:]


@pytest.mark.parametrize("workload", ["police_sweep", "police_probe"])
def test_four_devices_make_what_one_makes_block_by_block(cases, workload):
    same = cases["same"][workload]
    assert same and all(same.values()), same


def test_fetch_sends_only_the_checked_rows(cases):
    got = cases["fetch"]
    assert got["picked"] and got["rows_equal"]
    assert got["compare_equal"]
    # the comparison saw the lost, the doubled and the outside pair
    assert got["compare"]["duplicates"] == 1
    assert got["compare"]["mismatches"] == 2
    assert got["compare"]["gap"] == float("inf")
    assert got["compare"]["reference"] > 10


@pytest.mark.parametrize("workload", ["police_sweep", "police_probe"])
def test_run_on_four_devices(cases, workload):
    run = cases["run"][workload]
    assert run["correct"], run["checks"]
    assert run["attempted"] >= 1
    assert run["ctx_chips"] == CHIPS
    device = run["device"]
    assert device["count"] == CHIPS
    assert len(device["memory_peak_bytes_per_device"]) == CHIPS
    assert device["memory_peak_bytes"] == \
        max(device["memory_peak_bytes_per_device"])


class _Device:
    def __init__(self, peak):
        self.peak = peak

    def memory_stats(self):
        return None if self.peak is None else {"peak_bytes_in_use": self.peak}


def test_memory_is_read_on_every_device(monkeypatch):
    assert harness.device_memory(
        [_Device(7), _Device(None), _Device(42)]) == [7, 0, 42]
    monkeypatch.setattr(harness, "device_memory", lambda devs: [7, 42, 5])
    out = harness.run_cell(BENCH, "police_sweep", 3, 0.1, overrides={
        "rows_l": 512, "rows_r": 384, "check_rows": 100},
        log=lambda m: None)
    assert out["correct"]
    assert out["device"]["memory_peak_bytes"] == 42
    assert out["device"]["memory_peak_bytes_per_device"] == [7, 42, 5]


# sha256 of Traffic(config, mix, seed) with these overrides on one CPU
# device, hashed in this order: the thresholds as float64; each resident
# side's planes, sides in sorted order, features in order; each batch's
# planes; check_rows as int64; check_offset as int64.  Computed with the
# generator as it stood before sharded generation (commit 1054b46, one
# jitted draw a side), under JAX 0.9.0's XLA:CPU.
ONE_CHIP = {
    "police_sweep": {"rows_l": 512, "rows_r": 384, "check_rows": 100},
    "police_probe": {"rows_l": 512, "batch_rows": 64,
                     "distinct_batches": 3},
}
DIGESTS = {
    ("police_sweep", 3):
        "52c5a789e1ad29e5223c7f707a288232af027d991164c9117d9db1d2d964fd89",
    ("police_sweep", 2**40 + 1):
        "ea791e841149f0b2a5255e08dfff0d21c7d2c8f0479e26cee9e22e77633a5b4d",
    ("police_probe", 3):
        "a55415939caabfbf4cf41f61ab1baa4b8974717b58afe9dc6bd3a40d6860c359",
    ("police_probe", 2**40 + 1):
        "43c5d949bfaf56428ffc741c837d9d62ab15a7cffc253de9fac5f975f9a81a9b",
}


@pytest.mark.parametrize("workload,seed", sorted(DIGESTS))
def test_one_chip_inputs_are_the_unsharded_generators(workload, seed):
    """A one-chip cell's planes, batches, thresholds and check rows are
    bit for bit those of the generator before sharding (digests above)."""
    _, config, mix = harness.load_cell(BENCH, workload)
    for key, value in ONE_CHIP[workload].items():
        (mix if key in mix else config)[key] = value
    t = Traffic(config, mix, seed)
    h = hashlib.sha256()
    h.update(np.asarray(t.thetas, np.float64).tobytes())
    for side in sorted(t.resident):
        for p in t.resident[side]:
            h.update(np.asarray(p).tobytes())
    for b in t.batches:
        for p in b:
            h.update(np.asarray(p).tobytes())
    h.update(np.asarray(t.check_rows, np.int64).tobytes())
    h.update(np.int64(t.check_offset).tobytes())
    assert h.hexdigest() == DIGESTS[workload, seed]
