"""Per-kernel validation: shape/dtype sweeps, interpret-mode vs ref oracle."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.fused_cnf_join import ops as cnf_ops, ref as cnf_ref
from repro.kernels.fused_cnf_join.kernel import SCAL, VEC, cnf_join_block
from repro.kernels.threshold_sweep.ops import (candidate_grid, sweep,
                                               sweep_counts)
from repro.kernels.threshold_sweep.ref import (threshold_sweep_ref,
                                               threshold_sweep_ref_jit)


def _mk_inputs(rng, fv, fs, nl, nr, d, dtype):
    el = rng.normal(size=(fv, nl, d)).astype(dtype)
    er = rng.normal(size=(fv, nr, d)).astype(dtype)
    el /= np.linalg.norm(el, axis=-1, keepdims=True)
    er /= np.linalg.norm(er, axis=-1, keepdims=True)
    sl = rng.uniform(0, 1.5, size=(max(fs, 1), nl)).astype(dtype)
    sr = rng.uniform(0, 1.5, size=(max(fs, 1), nr)).astype(dtype)
    return el, er, sl, sr


@pytest.mark.parametrize("nl,nr,d,tl,tr", [
    (128, 128, 128, 64, 128),
    (256, 512, 128, 128, 256),
    (256, 256, 256, 256, 256),
    (512, 256, 128, 128, 128),
])
def test_cnf_kernel_shapes(nl, nr, d, tl, tr):
    rng = np.random.default_rng(nl + nr)
    el, er, sl, sr = _mk_inputs(rng, 2, 1, nl, nr, d, np.float32)
    clauses = (((VEC, 0), (SCAL, 0)), ((VEC, 1),))
    thetas = (0.45, 0.52)
    packed = cnf_join_block(jnp.asarray(el), jnp.asarray(er), jnp.asarray(sl),
                            jnp.asarray(sr), clauses, thetas, tl=tl, tr=tr,
                            interpret=True)
    expect = cnf_ref.cnf_join_ref(jnp.asarray(el), jnp.asarray(er),
                                  jnp.asarray(sl), jnp.asarray(sr),
                                  clauses, thetas)
    got = cnf_ref.unpack_mask(np.asarray(packed), nr)
    assert np.array_equal(got, np.asarray(expect))


@pytest.mark.parametrize("structure", [
    (((VEC, 0),),),
    (((SCAL, 0),),),
    (((VEC, 0), (VEC, 1)), ((SCAL, 0),)),
    (((VEC, 0),), ((VEC, 1),), ((SCAL, 0), (VEC, 0))),
])
def test_cnf_kernel_clause_structures(structure):
    rng = np.random.default_rng(7)
    el, er, sl, sr = _mk_inputs(rng, 2, 1, 128, 128, 128, np.float32)
    thetas = tuple(0.3 + 0.1 * i for i in range(len(structure)))
    packed = cnf_join_block(jnp.asarray(el), jnp.asarray(er), jnp.asarray(sl),
                            jnp.asarray(sr), structure, thetas, tl=64, tr=64,
                            interpret=True)
    expect = cnf_ref.cnf_join_ref(jnp.asarray(el), jnp.asarray(er),
                                  jnp.asarray(sl), jnp.asarray(sr),
                                  structure, thetas)
    assert np.array_equal(cnf_ref.unpack_mask(np.asarray(packed), 128),
                          np.asarray(expect))


@pytest.mark.parametrize("nl,nr,tl,tr", [
    (24, 96, 8, 96),       # 3 words per tile: fewer than a sublane tile
    (40, 160, 8, 32),      # one word per tile, 5 x 5 grid
    (64, 192, 32, 64),
    (16, 1024, 16, 512),   # 16 words per tile, the pallas R tile
])
@pytest.mark.parametrize("early_reject", [False, True])
def test_cnf_kernel_layout_matches_ref(nl, nr, tl, tr, early_reject):
    """Transposed packed-word output and the grid-summed eval counter on
    ragged tile grids: the mask words are bit-identical to ``pack_mask``
    of the jnp ref, and evals count 1 clause per tile whose first clause
    passes nowhere (early reject) and every clause otherwise."""
    rng = np.random.default_rng(nl * nr + tr)
    el, er, sl, sr = (jnp.asarray(a) for a in
                      _mk_inputs(rng, 2, 1, nl, nr, 128, np.float32))
    clauses = (((VEC, 0),), ((VEC, 1), (SCAL, 0)))
    thetas = (0.37, 0.45)           # a sparse first clause: some dead tiles
    mask, evals = cnf_join_block(el, er, sl, sr, clauses, thetas, tl=tl,
                                 tr=tr, interpret=True,
                                 early_reject=early_reject, with_evals=True)
    want = np.asarray(cnf_ref.pack_mask(
        cnf_ref.cnf_join_ref(el, er, sl, sr, clauses, thetas)))
    assert mask.dtype == jnp.uint32 and mask.shape == (nl, nr // 32)
    assert np.array_equal(np.asarray(mask), want)
    ok0 = np.asarray(cnf_ref.cnf_join_ref(el, er, sl, sr, clauses[:1],
                                          thetas[:1]))
    live = ok0.reshape(nl // tl, tl, nr // tr, tr).any(axis=(1, 3))
    n_c = len(clauses)
    want_evals = np.where(live, n_c, 1).sum() if early_reject \
        else n_c * live.size
    assert int(evals) == want_evals


def test_cnf_corpus_vs_numpy_join_path():
    """evaluate_corpus (padding, packing, missing encoding) == numpy engine."""
    from repro.core.costs import CostLedger
    from repro.core.featurize import FeaturizationSpec
    from repro.data.simulated_llm import SimulatedExtractor
    from repro.data.synth import police_records

    ds = police_records(n_incidents=40, reports_per_incident=2)
    ext = SimulatedExtractor(ds)
    led = CostLedger()
    specs = [FeaturizationSpec("incident_date", "", "arithmetic", "llm", "incident_date"),
             FeaturizationSpec("officer_names", "", "word_overlap", "llm", "officer_names"),
             FeaturizationSpec("location", "", "semantic", "llm", "location")]
    feats = ext.materialize(specs, led)
    clauses = [[0], [1, 2]]
    th = [0.02, 0.35]
    got = set(cnf_ops.evaluate_corpus(feats, clauses, th, tl=32, tr=64))
    il, jr = np.arange(ds.n_l), np.arange(ds.n_r)
    ok = None
    for ci, cl in enumerate(clauses):
        cd = None
        for f in cl:
            d = feats[f].distance_block(il, jr)
            cd = d if cd is None else np.minimum(cd, d)
        pas = cd <= th[ci]
        ok = pas if ok is None else ok & pas
    want = set(zip(*[x.tolist() for x in np.nonzero(ok)]))
    assert got == want


@pytest.mark.parametrize("k,c,g", [(300, 1, 50), (700, 3, 200), (1024, 5, 64)])
def test_threshold_sweep(k, c, g):
    rng = np.random.default_rng(k)
    cd = rng.uniform(0, 1, size=(k, c)).astype(np.float32)
    labels = rng.random(k) < 0.3
    th = rng.uniform(0, 1, size=(g, c)).astype(np.float32)
    pos, sel = sweep(cd, labels, th, tg=64, tk=256)
    expect = np.asarray(threshold_sweep_ref_jit(
        jnp.asarray(cd), jnp.asarray(labels.astype(np.float32)), jnp.asarray(th)))
    np.testing.assert_array_equal(pos, expect[:, 0])     # bit for bit
    np.testing.assert_array_equal(sel, expect[:, 1])


def test_threshold_sweep_grid_helper():
    rng = np.random.default_rng(0)
    pos = rng.uniform(0, 1, size=(40, 2)).astype(np.float32)
    grid = candidate_grid(pos, max_per_dim=5)
    assert grid.shape[1] == 2 and grid.shape[0] <= 25


def test_missing_value_encoding_forces_max_distance():
    """Augmented [e,m,1]/[e,1,m] rows make missing pairs distance 1."""
    from repro.core.featurize import FeaturizationSpec, vectorize
    spec = FeaturizationSpec("f", "", "word_overlap", "llm", "f")
    fd = vectorize(spec, ["alpha beta", None, "gamma"], ["alpha beta", "delta", None])
    d = fd.distance_block(np.arange(3), np.arange(3))
    assert d[0, 0] < 0.01            # identical token sets
    assert np.all(d[1, :] >= 0.999)  # missing left row
    assert np.all(d[:, 2] >= 0.999)  # missing right row


def _count_oracle(cd, labels, th):
    """Plain-numpy (pos, sel) counts — the ground truth both the kernel
    and the jitted ref must reproduce, pad rows or not."""
    selm = np.all(cd[None, :, :] <= th[:, None, :], axis=-1)
    return ((selm & labels[None, :]).sum(axis=1).astype(np.float32),
            selm.sum(axis=1).astype(np.float32))


def test_threshold_sweep_pad_rows_not_counted():
    """Regression: cd used to be padded with +inf, relying on ``inf <= th``
    being false — but ``inf <= inf`` is TRUE, so any +inf threshold column
    (emitted for positive-free samples, hit by all-missing features) counted
    every pad row into ``sel``.  With k=100 under a 256-row tile, the old
    kernel reported sel=256 for an all-+inf theta; the explicit validity
    mask must report exactly k."""
    k, c = 100, 2
    rng = np.random.default_rng(5)
    cd = rng.uniform(0, 1, size=(k, c)).astype(np.float32)
    labels = rng.random(k) < 0.4
    th = np.array([[np.inf, np.inf],       # admits every real row — and,
                                           # before the fix, every pad row
                   [np.inf, 0.5],
                   [-np.inf, 0.5]],        # admits nothing (d >= 0 > -inf)
                  np.float32)
    pos, sel = sweep(cd, labels, th, tg=64, tk=256)
    want_pos, want_sel = _count_oracle(cd, labels, th)
    np.testing.assert_array_equal(sel, want_sel)
    np.testing.assert_array_equal(pos, want_pos)
    assert sel[0] == k and pos[0] == labels.sum()
    assert sel[2] == 0 and pos[2] == 0


def test_threshold_sweep_inf_distances_ragged_tiles():
    """±inf thresholds and +inf distances through non-tile-multiple k and
    G — kernel, jitted ref, and plain numpy all agree exactly."""
    k, c, g = 333, 3, 37                   # 333 % 128 != 0, 37 % 16 != 0
    rng = np.random.default_rng(9)
    cd = rng.uniform(0, 1, size=(k, c)).astype(np.float32)
    cd[rng.random(size=(k, c)) < 0.08] = np.inf   # failed extractions
    labels = rng.random(k) < 0.3
    th = rng.uniform(0, 1, size=(g, c)).astype(np.float32)
    th[0] = np.inf
    th[-1] = -np.inf
    th[5, 1] = np.inf                      # mixed row
    pos, sel = sweep(cd, labels, th, tg=16, tk=128)
    want_pos, want_sel = _count_oracle(cd, labels, th)
    np.testing.assert_array_equal(pos, want_pos)
    np.testing.assert_array_equal(sel, want_sel)
    ref = np.asarray(threshold_sweep_ref(
        jnp.asarray(cd), jnp.asarray(labels.astype(np.float32)),
        jnp.asarray(th)))
    np.testing.assert_array_equal(ref[:, 0], want_pos)
    np.testing.assert_array_equal(ref[:, 1], want_sel)


def test_sweep_counts_dispatcher_parity():
    """The guarantee path's ``sweep_counts`` (jitted jnp ref on CPU, the
    pallas kernel on accelerators) is bit-for-bit the padded kernel."""
    rng = np.random.default_rng(11)
    k, c, g = 500, 2, 90
    cd = rng.uniform(0, 1, size=(k, c)).astype(np.float32)
    labels = rng.random(k) < 0.25
    th = rng.uniform(0, 1, size=(g, c)).astype(np.float32)
    th[3] = np.inf
    pos_d, sel_d = sweep_counts(cd, labels, th)
    pos_k, sel_k = sweep(cd, labels, th, tg=64, tk=256)
    np.testing.assert_array_equal(pos_d, pos_k)
    np.testing.assert_array_equal(sel_d, sel_k)
    # empty grid: well-defined empty counts, no kernel launch
    pos_e, sel_e = sweep_counts(cd, labels, np.zeros((0, c), np.float32))
    assert pos_e.shape == (0,) and sel_e.shape == (0,)


def test_candidate_grid_cap_and_recall_corner():
    """The cartesian grid is capped (no 24^C blowup) and always contains
    the per-dim positive-max corner, so recall-1 stays reachable."""
    rng = np.random.default_rng(3)
    pos = rng.uniform(0, 1, size=(600, 5)).astype(np.float32)
    grid = candidate_grid(pos, max_per_dim=24, max_grid=512)
    assert grid.shape[1] == 5
    # the shrink loop bounds prod(counts) by max_grid; the appended
    # recall-1 corner can at most double each axis
    assert grid.shape[0] <= 512 * 2 ** 5
    assert grid.shape[0] < 24 ** 5 / 100
    corner = pos.max(axis=0)
    assert any(np.allclose(row, corner) for row in grid), \
        "per-dim positive max (recall-1 corner) missing from the grid"
    # degenerate: no clauses
    empty = candidate_grid(np.zeros((4, 0), np.float32))
    assert empty.shape == (1, 0)
