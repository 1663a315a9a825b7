"""A run with the timed path broken underneath comes out not correct: once
for each fault a step-2 cell can have.  (One-chip cells have no exchange
between chips; no cell carries training state.)"""

import dataclasses
import os

import pytest

import harness
from repro.engine.sharded import ShardedEngine

from conftest import ROOT
from test_control import SMALL

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Faulty(ShardedEngine):
    """The engine with each chunk's candidates changed where they are
    produced, before the harness sees them."""

    def __init__(self, fault):
        super().__init__()
        self.fault = fault

    def evaluate_stream(self, feats, clauses, thetas):
        for ch in super().evaluate_stream(feats, clauses, thetas):
            yield dataclasses.replace(ch, candidates=self.fault(ch.candidates))


def half_left_out(pairs):
    return [p for p in pairs if p[0] % 2 == 0]


def answer_altered(pairs):
    return [(pairs[0][0], pairs[0][1] + 1)] + pairs[1:] if pairs else pairs


@pytest.mark.parametrize("fault", [half_left_out, answer_altered])
@pytest.mark.parametrize("workload", sorted(SMALL))
def test_fault_is_not_correct(workload, fault):
    out = harness.run_cell(BENCH, workload, 5, 5.0, engine=Faulty(fault),
                           overrides=SMALL[workload], log=lambda m: None)
    assert not out["correct"]
    assert out["failed"] >= 1
