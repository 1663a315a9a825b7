"""The readers of the program's own marks (marks.py): device scopes found
through the compiled text, host annotations on the trace clock, counters
on spans; and that each returns nothing on a program without them."""

import json
import os
import types

import pytest

import harness
import marks
import reduce
from harness import Query
from repro.obs.trace import Span

from conftest import CHIP

with open(os.path.join(CHIP, "tests", "data", "police_sweep_trace.json")) as f:
    SWEEP = json.load(f)
# one whole police_probe query (244 ms: staging, two band steps) on a TPU
# v5e, recorded with marks_report.py --out: the device events (long op
# names shortened, the kernel's kept), the host's fdj.* annotations and
# the band step's instruction scopes from its compiled text
with open(os.path.join(CHIP, "tests", "data", "police_probe_marks.json")) as f:
    PROBE = json.load(f)

HLO = """\
ENTRY %main.28 (emb_l.1: f32[2,128,256], k.1: s32[]) -> s32[4096,2] {
  %fusion.20 = s32[4096]{0} fusion(%a), kind=kLoop, calls=%c, \
metadata={op_name="jit(body)/fdj_extract/jit(searchsorted)/while/body/add"}
  %while.4 = (s32[]) while(%t), condition=%c1, body=%b1, \
metadata={op_name="jit(body)/fdj_extract/jit(searchsorted)/while" stack_frame_id=13}
  %custom-call.1 = s32[4,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", \
metadata={op_name="jit(body)/fdj_kernel/pallas_call"}
  ROOT %copy.3 = s32[1]{0} copy(%y), metadata={op_name="jit(body)/fdj_offsets/sub"}
  %add.9 = s32[] add(%p, %q), metadata={op_name="reduce_window_sum"}
  %copy.4 = s32[1]{0} copy(%y)
}
"""


def _trace(ops, modules, window=(0.0, 100.0)):
    plane = {"XLA Ops": [list(op) for op in ops],
             "XLA Modules": [list(m) for m in modules]}
    return {"window_ns": list(window), "planes": {"/device:TPU:0": plane}}


def test_instruction_scopes_from_compiled_text():
    scopes = marks.instruction_scopes([HLO])
    assert scopes == {"fusion.20": "fdj_extract", "while.4": "fdj_extract",
                      "custom-call.1": "fdj_kernel", "copy.3": "fdj_offsets"}
    # one name, two scopes in two programs: neither is trusted
    other = HLO.replace("jit(body)/fdj_offsets/sub", "jit(body)/fdj_kernel/x")
    assert "copy.3" not in marks.instruction_scopes([HLO, other])


def test_scope_time_is_the_union_inside_the_band_step():
    scopes = marks.instruction_scopes([HLO])
    ops = [("%custom-call.1 = s32[4,8] custom-call(...)", 10.0, 20.0),
           ("%while.4 = (s32[]) while(...)", 30.0, 40.0),
           ("%fusion.20 = s32[4096] fusion(...)", 35.0, 20.0),  # in the while
           ("%copy.3 = s32[1] copy(...)", 72.0, 2.0),
           ("%while.4 = (s32[]) while(...)", 85.0, 10.0)]     # other program
    modules = [("jit_body(123)", 5.0, 75.0), ("jit_pad(9)", 80.0, 20.0)]
    trace = _trace(ops, modules)
    ms = marks.scope_ms_per_step(trace, "fdj_extract", scopes)
    assert ms == pytest.approx(40.0 * 1e-6)        # 30..70, once
    assert marks.scope_ms_per_step(trace, "fdj_kernel", scopes) == \
        pytest.approx(20.0 * 1e-6)
    assert marks.scope_ms_per_step(trace, "fdj_offsets", scopes) == \
        pytest.approx(2.0 * 1e-6)


def test_scope_readers_read_nothing_without_the_programs_text(monkeypatch):
    monkeypatch.setattr(marks, "program_scopes", lambda: {})
    ctx = types.SimpleNamespace(trace=SWEEP, spans=[], queries=[])
    for name in ("extract_scope_ms.sweep", "extract_scope_ms.probe"):
        assert harness.load_reader(name)(ctx) is None
    # a trace whose band step carries no scoped instruction reads nothing
    assert marks.scope_ms_per_step(SWEEP, "fdj_extract",
                                   {"no_such_op": "fdj_extract"}) is None


def test_program_scopes_of_the_engine_in_this_process():
    # the engine's band_step_hlo on the CPU: scopes of the body's parts
    from repro.data import synth
    from repro.data.cnf_fixtures import representative_cnf
    from repro.data.simulated_llm import SimulatedExtractor
    from repro.core.costs import CostLedger
    from repro.engine import get_engine
    ds = synth.citations(n_docs=101, seed=9)
    specs, clauses, thetas = representative_cnf(ds)
    feats = SimulatedExtractor(ds).materialize(specs, CostLedger())
    get_engine("sharded", tl=32, tr=32, r_chunk=32, use_kernel=False) \
        .evaluate(feats, clauses, thetas)
    assert set(marks.program_scopes().values()) >= {"fdj_kernel",
                                                    "fdj_extract"}


def _spans_at(marks_ns, t0):
    """perf_counter spans for the given (name, start_ns, end_ns) marks,
    relative to a window opened at perf_counter ``t0``."""
    return [Span(name, i + 1, None, t0 + s * 1e-9, t0 + e * 1e-9)
            for i, (name, s, e) in enumerate(marks_ns)]


def test_idle_gaps_without_annotations_are_todays_mapping():
    a, _ = SWEEP["window_ns"]
    spans = _spans_at([("pull", 0.0, 8e7), ("dispatch", 7e7, 1.5e8)], 50.0)
    assert "host" not in SWEEP
    assert marks.idle_gaps(SWEEP, spans, 50.0) == \
        reduce.idle_gaps(SWEEP, spans, 50.0)
    assert marks.idle_gaps(dict(SWEEP, host=[]), [], a) == \
        reduce.idle_gaps(SWEEP, [], a)


def test_idle_gaps_go_to_the_innermost_annotation_on_the_trace_clock():
    # busy 0-10, 20-30, 50-90; idle 10-20, 30-50, 90-100
    trace = _trace([("%a = x", 0.0, 10.0), ("%b = x", 20.0, 10.0),
                    ("%c = x", 50.0, 40.0)], [])
    trace["host"] = [["fdj.pull", 5.0, 40.0],          # 5-45
                     ["fdj.wait_counts", 8.0, 14.0],   # 8-22
                     ["fdj.fetch", 31.0, 8.0]]         # 31-39
    # spans far off on their own clock must not matter once annotated
    spans = _spans_at([("stage_planes", 0.0, 100.0)], 7.0)
    gaps = dict(marks.idle_gaps(trace, spans, 7.0))
    # 10-20 wait_counts; 30-31 and 39-45 pull, 31-39 fetch, 45-50 and
    # 90-100 nothing
    assert gaps == pytest.approx({"wait_counts": 10e-9, "fetch": 8e-9,
                                  "pull": 7e-9, "no annotation": 15e-9})


def _probe_ctx(spans, windows):
    queries = [Query(k, t0, t1, 2, True, None)
               for k, (t0, t1) in enumerate(windows)]
    queries.append(Query(len(windows), 90.0, 99.0, 1, False, None))
    return types.SimpleNamespace(spans=spans, queries=queries, trace=None)


def test_pull_host_and_staged_bytes_per_query():
    spans = [Span("fetch", 1, None, 1.0, 1.002),
             Span("to_pairs", 2, None, 1.002, 1.003),
             Span("fetch", 3, None, 11.0, 11.004),
             Span("to_pairs", 4, None, 11.004, 11.005),
             Span("fetch", 5, None, 91.0, 91.5),          # unfinished query
             Span("stage_planes", 6, None, 0.5, 0.9,
                  attrs={"bytes_staged": 3_000_000, "pack_hit": False}),
             Span("stage_planes", 7, None, 10.5, 10.9,
                  attrs={"bytes_staged": 0, "pack_hit": True})]
    ctx = _probe_ctx(spans, [(0.0, 2.0), (10.0, 12.0)])
    pull_host = harness.load_reader("pull_host_ms.probe")(ctx)
    assert pull_host == pytest.approx((3e-3 + 5e-3) / 2 * 1e3)
    assert harness.load_reader("stage_mb.probe")(ctx) == pytest.approx(1.5)


def test_span_readers_read_nothing_on_a_program_without_the_counters():
    # the spans a program without the ring's split and counters records
    spans = [Span("pull", 1, None, 1.0, 1.2, attrs={"bytes": 8}),
             Span("stage_planes", 2, None, 0.5, 0.9,
                  attrs={"bytes_h2d": 0, "bytes_reshard": 0})]
    ctx = _probe_ctx(spans, [(0.0, 2.0)])
    for name in ("pull_host_ms.probe", "stage_mb.probe"):
        assert harness.load_reader(name)(ctx) is None
    assert harness.load_reader("pull_ms.probe")(ctx) is not None


def _union_ns(intervals):
    """Length of the union of ``[(start, end), ...]``, by a sweep over
    their end points."""
    edges = sorted([(a, 1) for a, _ in intervals] +
                   [(b, -1) for _, b in intervals])
    total, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_recorded_probe_extraction_scope():
    scopes = PROBE["scopes"]
    assert set(scopes.values()) == {"fdj_kernel", "fdj_extract"}
    a, b = PROBE["window_ns"]
    steps = [(t, t + d) for name, t, d in
             PROBE["planes"]["/device:TPU:0"]["XLA Modules"]
             if name.startswith("jit_body(")]
    assert len(steps) == 2 and all(a <= s and e <= b for s, e in steps)
    extract = []
    for name, t, d in PROBE["planes"]["/device:TPU:0"]["XLA Ops"]:
        m = marks.EVENT.match(name)
        if m and scopes.get(m.group(1)) == "fdj_extract" and \
                any(s <= t and t + d <= e for s, e in steps):
            extract.append((t, t + d))
    union = _union_ns(extract)
    # the while event holds its body's events: a sum would count them twice
    assert sum(e - s for s, e in extract) > 1.5 * union
    ms = marks.scope_ms_per_step(PROBE, "fdj_extract", scopes)
    assert ms == pytest.approx(union / 2 * 1e-6)
    # inside what the band step spends outside the kernel, and most of it
    program, n = reduce.op_time(PROBE, reduce.BAND_STEP, reduce.MODULES_LINE)
    kernel, calls = reduce.op_time(PROBE, reduce.KERNEL)
    outside = (program - kernel) / n * 1e3
    assert n == calls == 2 and 0.9 * outside < ms < outside
    # the kernel scope holds the kernel, named by its pallas_call
    kernel_ms = marks.scope_ms_per_step(PROBE, "fdj_kernel", scopes)
    assert kernel_ms >= kernel / 2 * 1e3
    assert all(e[0].startswith("%fused_cnf_join")
               for e in PROBE["planes"]["/device:TPU:0"]["XLA Ops"]
               if reduce.KERNEL.search(e[0]))


def test_recorded_probe_idle_by_annotation():
    names = {n[len(marks.HOST_PREFIX):] for n, _, _ in PROBE["host"]}
    assert names == {"stage_planes", "enqueue", "pull", "wait_counts",
                     "fetch", "to_pairs", "sort_pairs"}
    gaps = marks.idle_gaps(PROBE, [], PROBE["window_ns"][0], n=20)
    assert {k for k, _ in gaps} <= names | {"no annotation"}
    idle = reduce.window_s(PROBE) - reduce.busy_s(PROBE)
    assert sum(v for _, v in gaps) == pytest.approx(idle)
    # the host's staging dispatch is where the device waits most
    assert gaps[0][0] == "stage_planes"
    # without the annotations: the perf_counter spans' mapping, exactly
    bare = {k: v for k, v in PROBE.items() if k != "host"}
    spans = _spans_at([("stage_planes", 0.0, 5e7), ("pull", 5.3e7, 2.4e8)],
                      3.0)
    assert marks.idle_gaps(bare, spans, 3.0) == \
        reduce.idle_gaps(bare, spans, 3.0)
