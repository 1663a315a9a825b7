"""The harness finds every configuration, traffic mix and metric of
BENCHMARK.json by name, in a file of its own, and refuses names it lacks."""

import os

import pytest

import harness

from conftest import ROOT

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("workload", CELLS)
def test_cell_files_load(workload):
    cell, config, mix = harness.load_cell(BENCH, workload)
    assert mix["kind"] in ("sweep", "probe")
    for key in next(c for c in BENCH["configs"]
                    if c["name"] == cell["config"])["reduced"]:
        assert key in config
    assert set(config["limits"]) == {"gap", "duplicates"}


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["end_to_end"]
                                  + BENCH["per_layer"]])
def test_metric_reader_found(name):
    assert callable(harness.load_reader(name))


@pytest.mark.parametrize("workload", CELLS)
def test_cell_reports_setup_and_one_more(workload):
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, workload, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(BENCH, workload, True)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_cells_report_what_it_moves(metric):
    moves = E2E[metric["moves"]]
    for cell in metric["workloads"]:
        assert cell in moves.get("workloads", CELLS)


def test_unknown_names_refused():
    with pytest.raises(KeyError):
        harness.load_cell(BENCH, "no_such_cell")
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_metric")


@pytest.mark.parametrize("extra", [{"loop": "open"}, {"clients": 4}])
def test_mix_keys_the_generator_does_not_read_are_refused(extra):
    # one closed-loop client is the only mode; a mix asking for another
    # is refused rather than run as that one
    from traffic import Traffic
    _, config, mix = harness.load_cell(BENCH, "police_probe")
    with pytest.raises(ValueError):
        Traffic(config, dict(mix, **extra), 1)
