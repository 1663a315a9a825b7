"""The four-chip probe cell, ``police_probe_4chip``: its deployment's L side
split over four devices runs correct at a small size and a lost shard
does not; its five per-layer readers read a trace's numbers, and nothing
where the run has no trace.  The four-device runs are made in a process of
their own (``probe_4chip_cases.py``)."""

import json
import os
import subprocess
import sys

import pytest

import harness
import kernel_work
import marks
import stage_work
from harness import Context, Query
from repro.obs.trace import Span

from conftest import CHIP, ROOT

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL, CHIPS = "police_probe_4chip", 4
NEW = ["stage_mb_per_device.probe_4chip", "stage_roofline.probe_4chip",
       "cnf_kernel_roofline.probe_4chip", "offsets_ms_per_step.probe_4chip"]
# the one-chip probe's metrics, which read this cell too
PROBE = [m["name"] for m in harness.cell_metrics(BENCH, "police_probe", True)]
SPANS = ["pull_ms.probe", "stage_ms.probe", "pull_host_ms.probe",
         "stage_mb.probe"]


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, os.path.join(CHIP, "tests", "probe_4chip_cases.py")],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_cell_is_the_deployment_at_its_own_size():
    cell, config, mix = harness.load_cell(BENCH, CELL)
    assert cell["chips"] == CHIPS and mix["kind"] == "probe"
    assert config["rows_l"] == 10**6 and "published" not in config
    assert next(c for c in BENCH["configs"]
                if c["name"] == cell["config"])["reduced"] == []
    _, small, _ = harness.load_cell(BENCH, "police_probe")
    for key in ("features", "clauses", "theta_quantile", "limits",
                "boundary_margin", "boundary_share", "precision"):
        assert config[key] == small[key]
    assert [m["name"] for m in harness.cell_metrics(BENCH, CELL, True)] \
        == PROBE + NEW


def test_runs_correct_on_four_devices(cases):
    run = cases["program"]
    assert run["correct"], run["checks"]
    assert run["attempted"] >= 2
    assert run["device"]["count"] == CHIPS
    assert len(run["device"]["memory_peak_bytes_per_device"]) == CHIPS


def test_a_lost_shard_is_not_correct(cases):
    run = cases["lost_shard"]
    assert not run["correct"]
    assert run["failed"] == run["attempted"]
    assert run["checks"]["gap"]["value"] > run["checks"]["gap"]["limit"]


def test_traced_run_without_device_planes(cases):
    """On the CPU the trace holds no device plane: the span readers read,
    the per-device one one L shard (512 rows of two 384-lane embeds and a
    scalar) and the whole R band stack (512 rows); the device-trace
    readers read nothing."""
    run = cases["traced"]
    assert run["correct"]
    shard = 2 * 512 * 384 * 4 + 512 * 4
    assert sorted(run["metrics"]) == sorted(
        SPANS + ["stage_mb_per_device.probe_4chip"])
    assert run["metrics"]["stage_mb_per_device.probe_4chip"] == {
        "value": 2 * shard / 1e6, "unit": "MB"}
    assert run["metrics"]["stage_mb.probe"]["value"] == pytest.approx(
        (4 * shard + shard) / 1e6)


# -- the device-trace readers on a trace of four device planes ---------------

MS = 1e6                       # ns
STAGE_MS, KERNEL_MS, GATHER_MS = 20.0, 52.0, 0.05


def _plane(t0: float) -> dict:
    """One query on one device: staging, then two band steps, each a
    kernel call and a count gather."""
    modules = [[f"jit_fdj_stage_shards({t0:.0f})", t0, STAGE_MS * MS]]
    ops = [["%fusion.1 = f32[2,250112,3200] fusion(...)", t0, STAGE_MS * MS]]
    t = t0 + STAGE_MS * MS
    for _ in range(2):
        modules.append(["jit_body(9)", t, (KERNEL_MS + 1.0) * MS])
        ops.append(['%custom-call.1 = s32[4,8,250112] custom-call(...), '
                    'custom_call_target="tpu_custom_call"', t,
                    KERNEL_MS * MS])
        ops.append(["%all-gather.2 = s32[4] all-gather(...)",
                    t + KERNEL_MS * MS, GATHER_MS * MS])
        t += (KERNEL_MS + 1.0) * MS
    return {"XLA Modules": modules, "XLA Ops": ops}


def _ctx(trace, spans=(), n_l=10**6) -> Context:
    _, config, _ = harness.load_cell(BENCH, CELL)
    work = kernel_work.band_step_work(n_l, 512, config["features"],
                                      config["clauses"])
    return Context([Query(0, 0.0, 1.0, 2, True, None)], (0.0, 1.0),
                   [n_l * 512, n_l * 188], 60.0, work, "TPU v5 lite",
                   trace, list(spans), CHIPS)


def _read(name, ctx):
    return harness.load_reader(name)(ctx)


@pytest.fixture
def trace(monkeypatch):
    monkeypatch.setattr(marks, "program_scopes",
                        lambda: {"all-gather.2": "fdj_offsets"})
    return {"window_ns": [0.0, 200.0 * MS],
            "planes": {f"/device:TPU:{d}": _plane(0.0)
                       for d in range(CHIPS)}}


def test_stage_roofline_reads_one_read_and_write_of_a_shard(trace):
    least = 250_000 * (3074 + 3074 + 1) * 4 * 2 / 819e9
    assert stage_work.stage_bytes(harness.load_cell(BENCH, CELL)[1],
                                  CHIPS) == \
        250_000 * (3074 + 3074 + 1) * 8
    got = _read("stage_roofline.probe_4chip", _ctx(trace))
    assert got == pytest.approx(least / (STAGE_MS * 1e-3) * 100)
    assert 70 < got < 80


def test_kernel_roofline_counts_one_devices_real_columns(trace):
    rows, cols = 250_000, 350.0            # (512 + 188) / 2 real columns
    nbytes = rows * cols / 8 + (rows + cols) * (3074 * 2 + 1) * 4
    least = max(2 * rows * cols * 3074 * 2 / 197e12, nbytes / 819e9)
    got = _read("cnf_kernel_roofline.probe_4chip", _ctx(trace))
    assert got == pytest.approx(least / (KERNEL_MS * 1e-3) * 100)


def test_offsets_and_idle_read_every_device(trace):
    assert _read("offsets_ms_per_step.probe_4chip", _ctx(trace)) == \
        pytest.approx(GATHER_MS)
    busy = STAGE_MS + 2 * KERNEL_MS + 2 * GATHER_MS
    assert _read("device_idle.probe", _ctx(trace)) == \
        pytest.approx(100 * (1 - busy / 200.0))


def test_roofline_readers_read_nothing_at_another_size(trace):
    """A run of another size than the deployment file's (a test's
    overrides) gives the readers that count its work nothing to read."""
    for name in ("stage_roofline.probe_4chip",
                 "cnf_kernel_roofline.probe_4chip"):
        assert _read(name, _ctx(trace)) is not None
        assert _read(name, _ctx(trace, n_l=2044)) is None


def test_readers_read_nothing_without_a_trace():
    for name in NEW:
        assert _read(name, _ctx(None)) is None
    # a program whose staging span has no per-device counter (before it
    # was added) gives nothing to read either
    spans = [Span("stage_planes", 1, None, 0.1, 0.2,
                  attrs={"bytes_staged": 10})]
    assert _read(NEW[0], _ctx(None, spans)) is None
    spans[0].attrs["bytes_staged_device"] = 6_430_086_144
    assert _read(NEW[0], _ctx(None, spans)) == pytest.approx(6430.086144)
