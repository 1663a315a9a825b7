"""FeaturePlaneStore units: content-hash keying, byte-budget LRU eviction,
hit/miss/H2D counters, and the device-side kernel-layout assembly that
makes the warm path's zero-H2D claim true (pack_features_device must write
byte-identical arrays to the host pack path)."""

import numpy as np
import pytest

from repro.core.costs import CostLedger
from repro.core.featurize import FeaturizationSpec, vectorize
from repro.data import synth
from repro.data.cnf_fixtures import representative_cnf
from repro.data.simulated_llm import SimulatedExtractor
from repro.kernels.fused_cnf_join import ops as cnf_ops
from repro.serving.planes import (FeaturePlaneStore,
                                  corpus_fingerprint)


def _police(n=12, seed=3):
    return synth.police_records(n_incidents=n, reports_per_incident=2,
                                seed=seed)


def _provided(ds, store=None, ledger=None):
    store = store or FeaturePlaneStore()
    ext = SimulatedExtractor(ds)
    specs, clauses, thetas = representative_cnf(ds)
    fp_l = corpus_fingerprint(ds.name, "l", ds.texts_l, ds.fields_l)
    fp_r = corpus_fingerprint(ds.name, "r", ds.texts_r, ds.fields_r)
    planes = store.provide(specs, ext, ledger or CostLedger(),
                           fp_l=fp_l, fp_r=fp_r)
    return store, ext, planes, specs, clauses, thetas, (fp_l, fp_r)


# --- fingerprints -----------------------------------------------------------

def test_fingerprint_is_content_hash():
    ds = _police()
    fp1 = corpus_fingerprint(ds.name, "r", ds.texts_r, ds.fields_r)
    fp2 = corpus_fingerprint(ds.name, "r", list(ds.texts_r),
                             dict(ds.fields_r))
    assert fp1 == fp2                                  # same content, same fp
    # appended row, different side, different name: all change the fp
    assert corpus_fingerprint(ds.name, "l", ds.texts_r, ds.fields_r) != fp1
    assert corpus_fingerprint("other", "r", ds.texts_r, ds.fields_r) != fp1
    grown = corpus_fingerprint(ds.name, "r", ds.texts_r + ["new row"],
                               {k: v + [v[0]] for k, v in ds.fields_r.items()})
    assert grown != fp1


# --- provide: hits, misses, charges ----------------------------------------

def test_provide_charges_cold_then_serves_free():
    ds = _police()
    led1 = CostLedger()
    store, ext, planes, specs, *_ , fps = _provided(ds, ledger=led1)
    assert led1.inference > 0                          # cold: extraction paid
    assert store.misses == 2 * len(specs) and store.hits == 0
    assert store.bytes_to_device == sum(
        f.data_l.nbytes + f.data_r.nbytes for f in planes.feats)

    led2 = CostLedger()
    warm = store.provide(specs, SimulatedExtractor(ds), led2,
                         fp_l=fps[0], fp_r=fps[1])
    assert led2.inference == 0.0                       # warm: free
    assert store.hits == 2 * len(specs)
    # identical planes to a cold materialize
    ref = SimulatedExtractor(ds).materialize(specs, CostLedger())
    for got, want in zip(warm.feats, ref):
        np.testing.assert_array_equal(got.data_l, want.data_l)
        np.testing.assert_array_equal(got.data_r, want.data_r)
        assert got.scale == want.scale


def test_provide_is_sequence_of_feature_data():
    ds = _police()
    _, _, planes, specs, clauses, thetas, _ = _provided(ds)
    assert len(planes) == len(specs)
    # numpy engine consumes the plane set through the Sequence protocol
    from repro.engine import get_engine
    ref = get_engine("numpy").evaluate(
        SimulatedExtractor(ds).materialize(specs, CostLedger()),
        clauses, thetas)
    got = get_engine("numpy").evaluate(planes, clauses, thetas)
    assert got.candidates == ref.candidates


# --- device-side pack parity ------------------------------------------------

@pytest.mark.parametrize("mk", [
    lambda: _police(n=12),                 # scalar + semantic + word_overlap
    lambda: synth.citations(n_docs=37, seed=9),        # ragged, embed-only
], ids=["police_mixed_kinds", "citations_ragged"])
def test_device_pack_matches_host_pack(mk):
    ds = mk()
    _, ext, planes, specs, clauses, _, _ = _provided(ds)
    feats = SimulatedExtractor(ds).materialize(specs, CostLedger())
    for tl, tr in ((32, 64), (64, 128)):
        host = cnf_ops.pack_features(feats, clauses, tl=tl, tr=tr)
        dev = cnf_ops.pack_features_device(planes, clauses, tl=tl, tr=tr)
        for h, d in zip(host[:4], dev[:4]):            # the four plane stacks
            np.testing.assert_array_equal(np.asarray(h), np.asarray(d))
        assert host[4] == dev[4]                       # kclauses
        assert host[5:7] == dev[5:7]                   # (n_l, n_r)
    # assemblies are memoized per geometry on the resident arrays
    assert [cnf_ops.stage_planes(planes, clauses, tl=tl, tr=tr).pack_hit
            for tl, tr in ((32, 64), (64, 128))] == [True, True]


def test_stage_planes_reports_zero_h2d_for_resident_planes():
    ds = _police()
    _, _, planes, specs, clauses, _, _ = _provided(ds)
    feats = SimulatedExtractor(ds).materialize(specs, CostLedger())
    cold = cnf_ops.stage_planes(feats, clauses, tl=32, tr=64)
    warm = cnf_ops.stage_planes(planes, clauses, tl=32, tr=64)
    assert cold.bytes_h2d > 0 and warm.bytes_h2d == 0
    assert cold.bytes_reshard == 0 and warm.bytes_reshard == 0


def test_slice_r_views_delta_columns():
    ds = _police()
    _, _, planes, *_ = _provided(ds)
    off = 5
    sub = planes.slice_r(off)
    for full, view in zip(planes.feats, sub.feats):
        np.testing.assert_array_equal(view.data_r, full.data_r[off:])
        np.testing.assert_array_equal(view.data_l, full.data_l)
    for i in range(len(planes)):
        np.testing.assert_array_equal(np.asarray(sub.device_r(i)),
                                      np.asarray(planes.device_r(i))[off:])


# --- LRU eviction -----------------------------------------------------------

def test_byte_budget_evicts_lru():
    spec_a = FeaturizationSpec("a", "", "word_overlap", "llm", "a")
    spec_b = FeaturizationSpec("b", "", "word_overlap", "llm", "b")
    spec_c = FeaturizationSpec("c", "", "word_overlap", "llm", "c")
    vals = [f"tok {i}" for i in range(16)]
    fd = vectorize(spec_a, vals, vals)
    nbytes = fd.data_l.nbytes
    store = FeaturePlaneStore(byte_budget=3 * nbytes)

    for spec in (spec_a, spec_b, spec_c):
        store.put(spec, "l", "fp", vals, fd.data_l, "embed", 1.0)
    assert store.resident_bytes == 3 * nbytes and store.evictions == 0

    store.get(spec_a, "l", "fp")           # refresh a's recency: b is now LRU
    store.put(spec_c, "r", "fp", vals, fd.data_r, "embed", 1.0)
    assert store.evictions == 1 and store.evicted_bytes == nbytes
    assert store.resident_bytes <= 3 * nbytes
    assert store.peek(spec_b, "l", "fp") is None       # b evicted
    assert store.peek(spec_a, "l", "fp") is not None   # a survived (recent)


def test_unbudgeted_store_never_evicts():
    store = FeaturePlaneStore()
    spec = FeaturizationSpec("a", "", "word_overlap", "llm", "a")
    fd = vectorize(spec, ["x"] * 8, ["x"] * 8)
    for i in range(20):
        store.put(spec, "l", f"fp{i}", ["x"] * 8, fd.data_l, "embed", 1.0)
    assert store.evictions == 0 and store.snapshot()["entries"] == 20


def test_counter_delta_between_snapshots():
    store = FeaturePlaneStore()
    spec = FeaturizationSpec("a", "", "word_overlap", "llm", "a")
    fd = vectorize(spec, ["x"] * 8, ["x"] * 8)
    store.put(spec, "l", "fp", ["x"] * 8, fd.data_l, "embed", 1.0)
    s0 = store.snapshot()
    store.get(spec, "l", "fp")
    store.get(spec, "l", "other")                      # miss
    d = FeaturePlaneStore.delta(s0, store.snapshot())
    assert d["hits"] == 1 and d["misses"] == 1 and d["bytes_to_device"] == 0
    assert d["resident_bytes"] == store.resident_bytes  # level, not flow


# --- each side's kernel layout lives with its resident arrays ---------------

def _sides(ds):
    """Materialized planes, their CNF, and the resident arrays of each side
    as a store pins them."""
    import jax.numpy as jnp
    specs, clauses, thetas = representative_cnf(ds)
    feats = SimulatedExtractor(ds).materialize(specs, CostLedger())
    dev_l = [jnp.asarray(f.data_l) for f in feats]
    dev_r = [jnp.asarray(f.data_r) for f in feats]
    return feats, clauses, thetas, dev_l, dev_r


def _query(feats, clauses, thetas, dev_l, dev_r):
    """One sharded query over a new plane set: its candidates and its
    ``stage_planes`` span."""
    from repro.engine import get_engine
    from repro.obs.trace import Tracer, use_tracer
    from repro.serving.planes import DevicePlaneSet
    tracer = Tracer()
    with use_tracer(tracer):
        got = get_engine("sharded", tl=32, tr=32, r_chunk=64).evaluate(
            DevicePlaneSet(feats, dev_l, dev_r), clauses, thetas)
    span = next(s for s in tracer.spans() if s.name == "stage_planes")
    return got.candidates, span.attrs


def test_new_batch_over_resident_side_stages_only_the_batch():
    """A second plane set over the same resident L arrays and a new R
    batch reuses L's layout, stages R alone and finds the same pairs as a
    staging of both sides afresh."""
    import jax.numpy as jnp
    from repro.engine import get_engine
    ds = _police(n=20, seed=7)
    feats, clauses, thetas, dev_l, dev_r = _sides(ds)
    _, first = _query(feats, clauses, thetas, dev_l, dev_r)
    assert not first["l_hit"] and not first["r_hit"]
    batch = [jnp.asarray(f.data_r) for f in feats]
    pairs, second = _query(feats, clauses, thetas, dev_l, batch)
    assert second["l_hit"] and not second["r_hit"]
    assert not second["pack_hit"] and second["shards"] == 0
    # R's band stack, padded to whole bands of 64 columns, and no byte of L
    packed = cnf_ops.pack_features(feats, clauses, tl=32, tr=64)
    assert second["bytes_staged"] == second["bytes_staged_device"] == \
        packed[1].nbytes + packed[3].nbytes > 0
    assert second["bytes_reshard"] == second["bytes_h2d"] == 0
    fresh, _ = _query(feats, clauses, thetas,
                      [jnp.array(x, copy=True) for x in dev_l], batch)
    assert pairs == fresh == get_engine("numpy").evaluate(
        feats, clauses, thetas).candidates
    assert pairs


@pytest.mark.parametrize("side", ["l", "r"])
def test_new_arrays_are_staged_again(side):
    """A side whose arrays are new is staged, even where their contents
    equal arrays staged before: the memo keys on the arrays, never on a
    hash of what they hold."""
    import jax.numpy as jnp
    ds = _police(n=20, seed=7)
    feats, clauses, thetas, dev_l, dev_r = _sides(ds)
    _query(feats, clauses, thetas, dev_l, dev_r)
    again = {"l": dev_l, "r": dev_r}
    again[side] = [jnp.asarray(np.asarray(x)) for x in again[side]]
    _, span = _query(feats, clauses, thetas, again["l"], again["r"])
    assert span[f"{side}_hit"] is False
    assert span[f"{'r' if side == 'l' else 'l'}_hit"] is True
    assert span["bytes_staged"] > 0


def test_side_layout_goes_with_its_arrays():
    """Freeing the last reference to a side's resident arrays drops its
    staged layout: the memo keeps neither alive."""
    import gc
    import weakref
    from repro.serving.planes import DevicePlaneSet
    ds = _police(n=20, seed=7)
    feats, clauses, _, dev_l, dev_r = _sides(ds)
    before = len(cnf_ops._STAGED)
    staged = cnf_ops.stage_planes(DevicePlaneSet(feats, dev_l, dev_r),
                                  clauses, tl=32, tr=64)
    assert len(cnf_ops._STAGED) == before + 2
    layout_l, layout_r = weakref.ref(staged.emb_l), weakref.ref(staged.emb_r)
    del staged, dev_r
    gc.collect()
    assert layout_r() is None and layout_l() is not None
    assert len(cnf_ops._STAGED) == before + 1
    del dev_l
    gc.collect()
    assert layout_l() is None and len(cnf_ops._STAGED) == before
