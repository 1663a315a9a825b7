"""The window closes at a query's end, so every query in it is whole and
the pair rate counts all the work sent; the engine runs at the mix's ring
depth."""

import os

import pytest

import harness
from repro.engine.sharded import ShardedEngine

from conftest import ROOT
from test_control import SMALL

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Seen(ShardedEngine):
    """The default engine, remembering each query's ring depth."""
    depths = []

    def evaluate_stream(self, feats, clauses, thetas):
        Seen.depths.append(self.effective_prefetch_depth)
        return super().evaluate_stream(feats, clauses, thetas)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_window_holds_whole_queries(workload, monkeypatch):
    import repro.engine.sharded as sharded
    monkeypatch.setattr(sharded, "ShardedEngine", Seen)
    Seen.depths = []
    ctx = {}
    reader = harness.load_reader

    def keep_ctx(name):
        read = reader(name)
        return lambda c: (ctx.setdefault("c", c), read(c))[1]

    monkeypatch.setattr(harness, "load_reader", keep_ctx)
    out = harness.run_cell(BENCH, workload, 3, 0.2,
                           overrides=SMALL[workload], log=lambda m: None)
    assert out["correct"]
    c = ctx["c"]
    assert c.queries and all(q.complete for q in c.queries)
    assert c.window == (c.window[0], c.queries[-1].t1)
    _, config, mix = harness.load_cell(BENCH, workload)
    n_l = SMALL[workload].get("rows_l", config["rows_l"])
    n_r = SMALL[workload].get("batch_rows") or \
        SMALL[workload].get("rows_r", config["rows_r"])
    assert sum(c.step_pairs) == len(c.queries) * n_l * n_r
    assert set(Seen.depths) == {int(mix["prefetch_depth"])}
