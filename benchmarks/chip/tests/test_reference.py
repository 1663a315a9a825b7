"""The plain reference against the program's own host engine at a tiny
size, the generator's boundary rows, and the comparison's verdicts."""

import copy
import os

import numpy as np
import pytest

import harness
import reference
from planes import bf16_round
from traffic import Traffic

from conftest import ROOT

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def tiny_police(seed=3, rows_l=300, rows_r=700):
    _, config, mix = harness.load_cell(BENCH, "police_sweep")
    config = copy.deepcopy(config)
    config.update(rows_l=rows_l, rows_r=rows_r)
    t = Traffic(config, dict(mix, check_rows=None), seed)
    t.fetch()
    return t


def feature_data(t):
    from repro.core.featurize import FeatureData, FeaturizationSpec
    host_l, host_r = t.planes(0)
    return [FeatureData(FeaturizationSpec(f"f{i}", "", "semantic", "code",
                                          f"f{i}"), k, a, b)
            for i, (k, a, b) in enumerate(zip(t.kinds, host_l, host_r))]


def test_reference_matches_numpy_engine():
    from repro.engine.numpy_engine import NumpyEngine
    t = tiny_police()
    got = NumpyEngine().evaluate(feature_data(t), t.clauses, t.thetas)
    host_l, host_r = t.planes(0)
    out = reference.compare(np.asarray(got.candidates), host_l, host_r,
                            t.clauses, t.thetas, t.check_rows,
                            host_r[0].shape[0], t.n_l)
    assert out["reference"] > 100
    assert out["duplicates"] == 0
    # the numpy engine multiplies in float32: it may differ only on pairs
    # within float32 rounding of a threshold, inside the deployment's limit
    assert out["gap"] <= t.config["limits"]["gap"]


def test_boundary_rows_sit_at_the_margin():
    t = tiny_police(rows_l=2000, rows_r=2000)
    host_l, host_r = t.planes(0)
    margin = t.config["boundary_margin"]
    score = np.concatenate([s for _, s in reference.scores(
        host_l, host_r, t.clauses, t.thetas, t.check_rows,
        host_r[0].shape[0])])
    # placed in float32 on the device: within float32 rounding of it
    at = np.abs(score + margin) < 5e-8
    assert at.sum() >= 10
    # their clause-0 remainders agree in sign component by component
    ii, jj = np.nonzero(at)
    a, b = host_l[0][ii, :-2], host_r[0][jj, :-2]
    ra = np.sign(a.astype(np.float64) - np.asarray(bf16_round(a)))
    rb = np.sign(b.astype(np.float64) - np.asarray(bf16_round(b)))
    assert np.mean(ra == rb) > 0.95


def test_compare_verdicts():
    t = tiny_police()
    host_l, host_r = t.planes(0)
    n_r = host_r[0].shape[0]
    args = (host_l, host_r, t.clauses, t.thetas, t.check_rows, n_r, t.n_l)
    score = np.concatenate([s for _, s in reference.scores(*args[:6])])
    good = np.argwhere(score <= 0)
    assert reference.compare(good, *args)["gap"] == 0.0
    dup = np.concatenate([good, good[:1]])
    assert reference.compare(dup, *args)["duplicates"] == 1
    lost = reference.compare(good[::2], *args)
    assert lost["gap"] > 1e-3 and lost["mismatches"] == len(good) // 2
    far = good.copy()
    far[0, 1] = n_r
    assert reference.compare(far, *args)["gap"] == float("inf")
    moved = good.copy()
    moved[0, 1] = (moved[0, 1] + 1) % n_r
    assert reference.compare(moved, *args)["gap"] > 1e-3


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 1])
def test_same_seed_same_planes(seed):
    a, b = tiny_police(seed), tiny_police(seed)
    for x, y in zip(a.planes(0)[1], b.planes(0)[1]):
        assert np.array_equal(x, y)
    assert a.thetas == tiny_police(seed + 1).thetas
