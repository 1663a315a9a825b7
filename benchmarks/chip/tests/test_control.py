"""The control (three bfloat16 passes in the program's place) fails the
output check at a size a test run can hold, where the program passes."""

import os

import pytest

import harness
from control import ControlEngine

from conftest import ROOT

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
SMALL = {
    "police_sweep": {"rows_l": 2048, "rows_r": 1024, "check_rows": 2048},
    "police_probe": {"rows_l": 4096, "batch_rows": 700,
                     "distinct_batches": 2, "check_every": 1},
}


def run(workload, engine=None, seed=11):
    return harness.run_cell(BENCH, workload, seed, 5.0, engine=engine,
                            overrides=SMALL[workload], log=lambda m: None)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_control_fails(workload):
    out = run(workload, ControlEngine())
    assert not out["correct"]
    gap = out["checks"]["gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_program_passes(workload):
    out = run(workload)
    assert out["correct"], out["checks"]
    assert out["checks"]["gap"]["value"] <= out["checks"]["gap"]["limit"]
