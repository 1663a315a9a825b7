"""Observability spine (DESIGN.md §7): tracer, metrics, ledger binding.

Load-bearing invariants under test:
  * tracing is *inert*: traced and untraced runs return bit-identical
    candidate sets on all three backends, and the disabled-path guard
    (`if tracer:` against falsy NULL_TRACER) allocates nothing;
  * span trees survive the RefinementPump thread boundary (worker-side
    batch spans parent to the span captured on the spawning thread);
  * the prefetch ring's dispatch∩pull overlap is positive in the
    exported trace at depth 2 and exactly zero at depth 1;
  * `ledger_from_metrics(registry)` reconstructs any ledger bound to a
    fresh registry (the ledger↔metrics derivability invariant), and
    JoinService keeps it live across a whole query/append stream;
  * `CostLedger.absorb` never lets a ledger that skipped the plane
    store clobber the absorbed-into resident-bytes level.
"""

import tracemalloc

import pytest

from repro.core.costs import CostLedger, ledger_from_metrics
from repro.core.featurize import vectorize  # noqa: F401  (parity helper dep)
from repro.core.refine import RefinementPump
from repro.data import synth
from repro.data.cnf_fixtures import representative_cnf
from repro.data.simulated_llm import SimulatedExtractor
from repro.engine import ENGINES, get_engine
from repro.engine.base import CandidateChunk, EngineStats
from repro.launch import trace_report
from repro.obs import (NULL_SPAN, NULL_TRACER, MetricsRegistry, Tracer,
                       current_tracer, to_trace_events, use_tracer,
                       validate_trace)

_OPTS = {
    "numpy": dict(block=64),
    "pallas": dict(tl=32, tr=64),
    "sharded": dict(tl=32, tr=32, r_chunk=64),
}


# --- tracer core ------------------------------------------------------------

def test_span_nesting_retro_parents_and_events():
    tr = Tracer()
    with tr.span("root", kind="test") as root:
        with tr.span("child") as child:
            tr.event("mark", attrs_go_here=1)
        # retroactive spans default-parent to the innermost open span
        retro = tr.record_span("late", root.t0, root.t0 + 0.5,
                               attrs={"n": 3},
                               events=[("tick", root.t0 + 0.1, {"i": 0})])
    spans = {s.name: s for s in tr.spans()}
    assert spans["child"].parent_id == root.span_id
    assert retro.parent_id == root.span_id
    assert spans["root"].parent_id is None
    assert spans["root"].t1 is not None and root.attrs["kind"] == "test"
    assert child.events[0].name == "mark"
    assert retro.events[0].name == "tick" and retro.events[0].attrs == {"i": 0}
    # explicit parent beats the stack
    other = tr.record_span("explicit", 0.0, 1.0, parent=child)
    assert other.parent_id == child.span_id


def test_ambient_tracer_contextvar_and_null_default():
    assert current_tracer() is NULL_TRACER
    assert not NULL_TRACER and not current_tracer()
    t = Tracer()
    with use_tracer(t):
        assert current_tracer() is t and current_tracer()
        with use_tracer(None):                 # None ⇒ tracing stays off
            assert current_tracer() is NULL_TRACER
        assert current_tracer() is t
    assert current_tracer() is NULL_TRACER


def test_null_tracer_is_inert_and_guard_allocates_nothing():
    # unguarded accidental use returns shared singletons
    with NULL_TRACER.span("x", a=1) as sp:
        assert sp is NULL_SPAN
    assert NULL_TRACER.record_span("x", 0.0, 1.0) is NULL_SPAN
    assert NULL_TRACER.spans() == []

    tracer = current_tracer()
    assert tracer is NULL_TRACER

    def band_loop(n):
        # the instrumented hot-loop shape: one truthiness branch; the
        # attr dict is never built when tracing is off; the profiler
        # annotation is the shared NULL_SPAN
        acc = 0
        for i in range(n):
            with tracer.annotate("pull"):
                acc += i
            if tracer:
                tracer.record_span("band_step", 0.0, 1.0,
                                   attrs={"candidates": i})
        return acc

    assert tracer.annotate("pull") is NULL_SPAN

    band_loop(100)                             # warm bytecode/caches
    tracemalloc.start()
    band_loop(10_000)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 1024, f"disabled-path band loop allocated {peak} bytes"


# --- metrics ----------------------------------------------------------------

def test_histogram_quantiles_within_log_bucket_error():
    reg = MetricsRegistry()
    vals = [0.001 * (i + 1) for i in range(1000)]    # 1ms .. 1s uniform
    for v in vals:
        reg.observe("lat", v)
    h = reg.histogram("lat")
    s = h.summary()
    assert s["count"] == 1000 and abs(s["sum"] - sum(vals)) < 1e-9
    assert s["min"] == vals[0] and s["max"] == vals[-1]
    for q, true in [(0.50, 0.5005), (0.90, 0.9005), (0.99, 0.9905)]:
        est = h.quantile(q)
        assert abs(est - true) / true < 0.15, (q, est, true)
    assert s["p50"] <= s["p90"] <= s["p99"] <= s["max"]


def test_histogram_underflow_and_empty():
    reg = MetricsRegistry()
    assert reg.histogram("h").summary()["p50"] == 0.0
    reg.observe("h", 0.0)
    reg.observe("h", -5.0)
    assert reg.histogram("h").quantile(0.5) == 0.0


def test_registry_as_dict_flattens_histograms():
    reg = MetricsRegistry()
    reg.inc("c", 2)
    reg.set_gauge("g", 7)
    reg.observe("h", 1.0)
    d = reg.as_dict()
    assert d["c"] == 2 and d["g"] == 7.0
    assert d["h.count"] == 1 and d["h.p50"] == 1.0


# --- ledger <-> metrics derivability ----------------------------------------

def _busy_ledger():
    led = CostLedger()
    led.charge_label(1000, 10)
    led.charge_generation(500, 200)
    led.charge_extraction(800, 80)
    led.charge_embedding(400)
    led.charge_refine(300, 3)
    led.record_walls(1.5, 0.5, 0.25)
    led.record_engine_walls(0.8, 0.4, 0.1)
    led.record_plane_traffic(hits=3, misses=1, evicted_bytes=128,
                             resident_bytes=4096, bytes_h2d=2048,
                             bytes_reshard=64)
    led.record_recalibration(swapped=True, drift=0.02, dollars=0.003)
    return led


def test_ledger_from_metrics_round_trip():
    led = _busy_ledger()
    reg = MetricsRegistry()
    led.bind_metrics(reg)                      # mid-life bind: state published
    assert ledger_from_metrics(reg) == led
    led.charge_refine(100, 1)                  # post-bind flow streams in
    led.record_plane_traffic(hits=1, resident_bytes=5000)
    assert ledger_from_metrics(reg) == led
    # int fields come back as ints, not floats
    derived = ledger_from_metrics(reg)
    assert isinstance(derived.plane_hits, int)
    assert isinstance(derived.step2_conjunct_evals, int)
    assert derived.plane_level_set


def test_shared_registry_derives_absorbed_sum():
    reg = MetricsRegistry()
    lifetime = CostLedger()                    # stays UNBOUND (absorb would
    for _ in range(3):                         # double-feed the registry)
        q = CostLedger()
        q.bind_metrics(reg)
        q.charge_refine(200, 2)
        q.record_walls(0.1, 0.05, 0.0)
        q.record_plane_traffic(hits=2, resident_bytes=1000)
        lifetime.absorb(q)
    assert ledger_from_metrics(reg) == lifetime


def test_absorb_preserves_resident_level():
    """Regression: a ledger that never touched the plane store must not
    clobber the absorbed-into resident-bytes level with its default 0."""
    svc = CostLedger()
    svc.record_plane_traffic(hits=1, resident_bytes=4096)
    storeless = CostLedger()
    storeless.charge_refine(100, 1)            # a query without plane traffic
    svc.absorb(storeless)
    assert svc.plane_resident_bytes == 4096 and svc.plane_level_set
    toucher = CostLedger()
    toucher.record_plane_traffic(hits=1, resident_bytes=8192)
    svc.absorb(toucher)                        # a real level does transfer
    assert svc.plane_resident_bytes == 8192


# --- tracing is inert: candidate-set parity ---------------------------------

def _materialized_cnf(ds):
    specs, clauses, thetas = representative_cnf(ds)
    feats = SimulatedExtractor(ds).materialize(specs, CostLedger())
    return feats, clauses, thetas


@pytest.mark.parametrize("engine", list(ENGINES))
def test_traced_and_untraced_candidates_identical(engine):
    ds = synth.police_records(n_incidents=20, reports_per_incident=2, seed=7)
    feats, clauses, thetas = _materialized_cnf(ds)
    eng = get_engine(engine, **_OPTS[engine])
    plain = eng.evaluate(feats, clauses, thetas)
    tr = Tracer()
    with use_tracer(tr):
        traced = get_engine(engine, **_OPTS[engine]).evaluate(
            feats, clauses, thetas)
    assert traced.candidates == plain.candidates
    names = {s.name for s in tr.spans()}
    assert any(n.startswith("band_step[") for n in names)
    assert validate_trace(to_trace_events(tr)) == []


# --- pump spans cross the worker-thread boundary ----------------------------

def _chunks(groups):
    out = []
    for i, g in enumerate(groups):
        stats = EngineStats("scripted", n_l=10, n_r=10, n_candidates=len(g),
                            wall_s=0.001, bytes_to_host=8 * len(g))
        out.append(CandidateChunk(sorted(g), stats, i))
    return out


def test_pump_batch_spans_parent_to_query_root_across_thread():
    tr = Tracer()
    reg = MetricsRegistry()
    led = CostLedger()
    led.bind_metrics(reg)
    groups = [[(i, j) for j in range(3)] for i in range(4)]
    with use_tracer(tr):
        with tr.span("query_root") as root:
            pump = RefinementPump(lambda b: set(b), batch_pairs=4,
                                  max_queue_chunks=2)
            res = pump.run(iter(_chunks(groups)), ledger=led)
    assert res.pairs == {p for g in groups for p in g}
    batches = [s for s in tr.spans() if s.name == "refine_batch"]
    assert batches, "pump recorded no refine_batch spans"
    assert all(s.parent_id == root.span_id for s in batches)
    assert any(s.thread != root.thread for s in batches), \
        "worker-side spans should be recorded on the pump thread"
    assert all(s.track == "refine-pump" for s in batches)
    # pump metrics flowed through the bound registry
    assert reg.value("refine.batches") == len(batches)
    assert reg.value("refine.pairs") == sum(len(g) for g in groups)
    assert reg.has("refine.queue_depth")


# --- prefetch-ring overlap geometry -----------------------------------------

def _ring_trace(depth):
    ds = synth.citations(n_docs=101, seed=9)   # 4 R bands at r_chunk=32
    feats, clauses, thetas = _materialized_cnf(ds)
    eng = get_engine("sharded", tl=32, tr=32, r_chunk=32,
                     prefetch_depth=depth)
    tr = Tracer()
    with use_tracer(tr):
        res = eng.evaluate(feats, clauses, thetas)
    obj = to_trace_events(tr)
    assert validate_trace(obj) == []
    return res, obj


def test_ring_overlap_positive_at_depth2_zero_at_depth1():
    res1, obj1 = _ring_trace(1)
    res2, obj2 = _ring_trace(2)
    assert res1.candidates == res2.candidates  # ring depth never changes output
    s1, s2 = trace_report._slices(obj1), trace_report._slices(obj2)
    assert len([s for s in s2 if s["name"] == "pull"]) >= 3
    assert trace_report.ring_overlap_s(s1) == 0.0
    assert trace_report.ring_overlap_s(s2) > 0.0
    # depth 2 uses two ring-slot tracks; depth 1 serializes on one
    assert len({s["tid"] for s in s2 if s["name"] == "pull"}) == 2
    assert len({s["tid"] for s in s1 if s["name"] == "pull"}) == 1


def test_trace_reconciles_with_ledger_walls():
    res, obj = _ring_trace(2)
    led = CostLedger()
    led.record_engine_stats(res.stats)
    led.record_walls(res.stats.wall_s, 0.0, 0.0)
    obj["fdj"] = {"wall_summary": led.wall_summary()}
    assert trace_report.check(obj) == [], trace_report.check(obj)
    checks = trace_report.reconcile(obj, trace_report._slices(obj))
    assert {c[0] for c in checks} >= {
        "Σ pull slices vs step2_pull_wall",
        "Σ dispatch enqueue_s vs step2_dispatch_wall",
    }


# --- serving keeps the derivability invariant live --------------------------

def _ledgers_close(a, b):
    """Field-wise equality up to float association order: the registry
    accumulates per-charge deltas, the lifetime ledger per-query sums."""
    import dataclasses
    import math
    for f in dataclasses.fields(CostLedger):
        if not f.compare:
            continue
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if not math.isclose(va, vb, rel_tol=1e-9, abs_tol=1e-12):
            return False, (f.name, va, vb)
    return True, None


def test_join_service_metrics_always_derive_lifetime_ledger():
    from repro.core.join import FDJConfig
    from repro.serving.join_service import JoinService, hold_out_right
    ds = synth.movies_pages(n_movies=20, cast_size=4, filler_sentences=1,
                            seed=3)
    base, delta = hold_out_right(ds, n_delta=4)
    cfg = FDJConfig(engine="numpy", engine_opts=_OPTS["numpy"], seed=0,
                    mc_trials=4000)
    svc = JoinService(base, cfg)
    svc.query()
    ok, why = _ledgers_close(ledger_from_metrics(svc.metrics), svc.ledger)
    assert ok, why
    svc.query()
    svc.append_right(delta)
    svc.query()
    ok, why = _ledgers_close(ledger_from_metrics(svc.metrics), svc.ledger)
    assert ok, why
    assert svc.metrics.value("serve.plan_hits") >= 1.0
    assert svc.metrics.histogram("serve.query_wall_s").count == 3


# --- step-② ring spans, staging counters, profiler annotations --------------

def _by_parent(spans):
    kids = {}
    for sp in spans:
        kids.setdefault(sp.parent_id, []).append(sp)
    return kids


def test_sharded_pull_splits_into_children():
    ds = synth.citations(n_docs=101, seed=9)   # 4 R bands at r_chunk=32
    feats, clauses, thetas = _materialized_cnf(ds)
    tr = Tracer()
    with use_tracer(tr):
        res = get_engine("sharded", tl=32, tr=32, r_chunk=32,
                         prefetch_depth=2).evaluate(feats, clauses, thetas)
    spans = tr.spans()
    kids = _by_parent(spans)
    pulls = [s for s in spans if s.name == "pull"]
    assert len(pulls) == 4
    for pull in pulls:
        children = kids[pull.span_id]
        assert [c.name for c in children] == ["wait_counts", "fetch",
                                              "to_pairs"]
        assert sum(c.duration_s for c in children) <= pull.duration_s
        for c in children:
            assert pull.t0 <= c.t0 <= c.t1 <= pull.t1
            assert c.track == pull.track
        fetch = children[1]
        # the kept rows are 8 B a candidate plus the bases; the copy moves
        # each non-empty shard's whole buffer
        assert fetch.attrs["bytes_moved"] >= fetch.attrs["bytes"]
        assert children[2].attrs["candidates"] == pull.attrs["candidates"]
    steps = [s for s in spans if s.name.startswith("band_step[")]
    assert sum(s.attrs["candidates"] for s in steps) == len(res.candidates)
    assert all("programs_built" in s.attrs for s in steps)
    for s in steps:
        names = [c.name for c in kids[s.span_id]]
        assert names == ["dispatch", "pull", "sort_pairs"]
    assert validate_trace(to_trace_events(tr)) == []


def test_stage_planes_counts_staged_bytes_and_cache_hits():
    import jax.numpy as jnp

    from repro.kernels.fused_cnf_join import ops as cnf_ops
    from repro.serving.planes import DevicePlaneSet

    ds = synth.police_records(n_incidents=20, reports_per_incident=2, seed=7)
    feats, clauses, thetas = _materialized_cnf(ds)

    def plane_set():
        return DevicePlaneSet(feats, [jnp.asarray(f.data_l) for f in feats],
                              [jnp.asarray(f.data_r) for f in feats])

    planes = plane_set()
    eng = get_engine("sharded", **_OPTS["sharded"])
    tr = Tracer()
    with use_tracer(tr):
        eng.evaluate(planes, clauses, thetas)
        eng.evaluate(planes, clauses, thetas)      # same plane set: a hit
    miss, hit = [s.attrs for s in tr.spans() if s.name == "stage_planes"]
    staged = cnf_ops.stage_planes(plane_set(), clauses, tl=32, tr=64)
    assert miss["bytes_staged"] == sum(a.nbytes for a in staged.arrays) > 0
    assert miss["pack_hit"] is False and staged.pack_hit is False
    assert hit["bytes_staged"] == 0 and hit["pack_hit"] is True
    assert miss["bytes_h2d"] == hit["bytes_h2d"] == 0
    # the host path uploads what it stages
    host = cnf_ops.stage_planes(feats, clauses, tl=32, tr=64)
    assert host.bytes_staged == host.bytes_h2d > 0 and not host.pack_hit
    # on one device, one shard: the device holds all it staged
    assert miss["shards"] == 1 and hit["shards"] == 0
    assert miss["bytes_staged_device"] == miss["bytes_staged"]
    assert hit["bytes_staged_device"] == 0
    # trace_report totals them over the trace
    obj = to_trace_events(tr)
    assert trace_report.staging(trace_report._slices(obj)) == (
        {"bytes_staged": miss["bytes_staged"],
         "bytes_staged_device": miss["bytes_staged"], "shards": 1,
         "l_hit": 1, "r_hit": 1}, 2)
    assert f"stage_planes: 1 shards, {miss['bytes_staged']} B staged" in \
        trace_report.report(obj)


def test_trace_report_totals_side_hits():
    """Three queries: both sides new, a new R batch over the resident L,
    then the same plane set again; the report counts each side's reuse."""
    import jax.numpy as jnp

    from repro.serving.planes import DevicePlaneSet

    ds = synth.police_records(n_incidents=20, reports_per_incident=2, seed=7)
    feats, clauses, thetas = _materialized_cnf(ds)
    dev_l = [jnp.asarray(f.data_l) for f in feats]
    first = DevicePlaneSet(feats, dev_l, [jnp.asarray(f.data_r)
                                          for f in feats])
    probe = DevicePlaneSet(feats, dev_l, [jnp.asarray(f.data_r)
                                          for f in feats])
    eng = get_engine("sharded", **_OPTS["sharded"])
    tr = Tracer()
    with use_tracer(tr):
        for planes in (first, probe, probe):
            eng.evaluate(planes, clauses, thetas)
    spans = [s.attrs for s in tr.spans() if s.name == "stage_planes"]
    assert [(a["l_hit"], a["r_hit"], a["pack_hit"]) for a in spans] == [
        (False, False, False), (True, False, False), (True, True, True)]
    obj = to_trace_events(tr)
    totals, calls = trace_report.staging(trace_report._slices(obj))
    assert (totals["l_hit"], totals["r_hit"], calls) == (2, 1, 3)
    assert totals["bytes_staged"] == spans[0]["bytes_staged"] + \
        spans[1]["bytes_staged"]
    assert "L reused 2, R reused 1, over 3 calls" in trace_report.report(obj)


def test_annotations_reach_the_profiler_host_plane(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    ds = synth.citations(n_docs=101, seed=9)
    feats, clauses, thetas = _materialized_cnf(ds)
    eng = get_engine("sharded", tl=32, tr=32, r_chunk=32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with use_tracer(Tracer()):
            eng.evaluate(feats, clauses, thetas)
    finally:
        jax.profiler.stop_trace()
    pb, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(pb).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("fdj.")}
    assert names >= {"fdj.stage_planes", "fdj.pull", "fdj.wait_counts",
                     "fdj.fetch", "fdj.to_pairs", "fdj.sort_pairs"}
    assert names & {"fdj.enqueue", "fdj.compile"}


def test_overflow_retry_nests_in_pull_and_reconciles():
    """An overflow retry nests in its pull, the trace reconciles, and each
    pull's ``extract_blocks`` counts the extraction's loop trips, the
    retried attempt's included; trace_report totals them."""
    from repro.core.featurize import FeaturizationSpec

    n_l, n_r = 33, 128                         # matches only in R band 2
    spec = FeaturizationSpec("name", "", "word_overlap", "llm", "name")
    feats = [vectorize(spec, ["same text"] * n_l,
                       ["zzz yyy"] * 64 + ["same text"] * 32
                       + ["zzz yyy"] * 32)]
    eng = get_engine("sharded", tl=32, tr=32, r_chunk=32, capacity=1,
                     prefetch_depth=2)
    tr = Tracer()
    with use_tracer(tr):
        res = eng.evaluate(feats, [[0]], [0.25])
    spans = tr.spans()
    retries = [s for s in spans if s.name == "retry"]
    assert retries
    by_id = {s.span_id: s for s in spans}
    for r in retries:
        pull = by_id[r.parent_id]
        assert pull.name == "pull" and pull.t0 <= r.t0 <= r.t1 <= pull.t1
    # one device: an empty band makes no trip; the overflowed attempt at
    # capacity 1 makes one, its retry one more
    pulls = [s for s in spans if s.name == "pull"]
    assert [p.attrs["extract_blocks"] for p in pulls] == [0, 0, 2, 0]
    obj = to_trace_events(tr)
    assert trace_report.extract_blocks(trace_report._slices(obj)) == (2, 4)
    assert "extract_blocks: 2 over 4 pulls" in trace_report.report(obj)
    led = CostLedger()
    led.record_engine_stats(res.stats)
    led.record_walls(res.stats.wall_s, 0.0, 0.0)
    obj["fdj"] = {"wall_summary": led.wall_summary()}
    assert trace_report.check(obj) == [], trace_report.check(obj)

