"""The trace reduction on a small recorded trace: 155 ms of ``police_sweep``
on a TPU v5e (two whole band steps and the start of a third), recorded
with ``tests/record_trace.py`` and cut with long op names shortened."""

import json
import os
import types

import pytest

import harness
import kernel_work
import reduce

from conftest import CHIP

with open(os.path.join(CHIP, "tests", "data", "police_sweep_trace.json")) as f:
    TRACE = json.load(f)
TPU = TRACE["planes"]["/device:TPU:0"]
A, B = TRACE["window_ns"]
POLICE = [{"kind": "embed", "width": 128}, {"kind": "embed", "width": 128},
          {"kind": "scalar", "range": 40.0}]


def covered_ns(events):
    """Union length of the events' intervals inside the window, by a sweep
    over their start and end points."""
    edges = []
    for _, t0, dur in events:
        s, e = max(t0, A), min(t0 + dur, B)
        if e > s:
            edges += [(s, 1), (e, -1)]
    total, depth, last = 0.0, 0, None
    for t, d in sorted(edges):
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_window_and_busy():
    assert reduce.window_s(TRACE) == pytest.approx(0.155)
    busy = reduce.busy_s(TRACE)
    assert busy == pytest.approx(covered_ns(TPU["XLA Ops"]) * 1e-9)
    # the band step keeps the chip busy: only dispatch gaps between steps
    assert 0.95 * 0.155 < busy < 0.155
    assert reduce.idle_share(TRACE) == pytest.approx(
        (1 - busy / 0.155) * 100)


def test_planes_without_ops_are_not_devices():
    assert list(reduce.device_ops(TRACE)) == ["/device:TPU:0"]


def test_kernel_and_band_step():
    kernel = [e for e in TPU["XLA Ops"] if reduce.KERNEL.search(e[0])]
    steps = [e for e in TPU["XLA Modules"] if reduce.BAND_STEP.search(e[0])]
    # two whole steps and the start of a third; one kernel call each
    assert len(kernel) == 3 and len(steps) == 3
    assert all(e[0].startswith("jit_body(") for e in steps)
    assert [round(e[2] / 1e6, 1) for e in steps[:2]] == [75.9, 75.9]
    seconds, calls = reduce.op_time(TRACE, reduce.KERNEL)
    assert calls == 3
    assert seconds == pytest.approx(
        sum(min(t + d, B) - max(t, A) for _, t, d in kernel) * 1e-9)


def test_readers_on_the_trace():
    ctx = types.SimpleNamespace(
        trace=TRACE, spans=[], queries=[], device_kind="TPU v5 lite",
        work=kernel_work.band_step_work(100_000, 512, POLICE, [[0], [1, 2]]))
    kernel_s, calls = reduce.op_time(TRACE, reduce.KERNEL)
    program_s, steps = reduce.op_time(TRACE, reduce.BAND_STEP,
                                      reduce.MODULES_LINE)
    extract = harness.load_reader("extract_ms_per_step.sweep")(ctx)
    assert extract == pytest.approx((program_s - kernel_s) / steps * 1e3)
    roofline = harness.load_reader("cnf_kernel_roofline")(ctx)
    least, bound = kernel_work.least_time(ctx.work, "TPU v5 lite")
    assert bound == "memory"
    assert roofline == pytest.approx(least / (kernel_s / calls) * 100)
    assert 0 < roofline < 100
    idle = harness.load_reader("device_idle.sweep")(ctx)
    assert idle == pytest.approx(reduce.idle_share(TRACE))


def test_breakdown():
    top = reduce.top_ops(TRACE)
    assert len(top) == 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    gaps = reduce.idle_gaps(TRACE, [], A)
    assert gaps[0][0] == "no span"
    assert gaps[0][1] == pytest.approx(0.155 - reduce.busy_s(TRACE))


def test_no_device_no_reading():
    empty = {"window_ns": [0.0, 1e9], "planes": {}}
    assert reduce.busy_s(empty) is None and reduce.idle_share(empty) is None
    ctx = types.SimpleNamespace(trace=empty, spans=[], queries=[])
    for name in ("cnf_kernel_roofline", "extract_ms_per_step.probe",
                 "device_idle.probe", "pull_ms.probe", "stage_ms.probe"):
        assert harness.load_reader(name)(ctx) is None
