#!/usr/bin/env python3
"""On-chip smoke run of FDJ's main path, in one process.

  python chip_smoke.py             # one chip: phases a-d below
  python chip_smoke.py --chips 4   # four chips: phase b on a (1, 4, 1) join
                                   # mesh against the same planes on one chip

Phases (one chip), in order; any failure raises and the exit code is not 0:

  a. device   — the first JAX device must be a TPU; there is no CPU fallback.
  b. step ②   — 100,000 x 100,000 rows, planes built from ``--seed`` in the
                ``FeatureData`` format (2 embed features of 128 + 2 dims, one
                scalar), CNF ``(embed) AND (embed OR scalar)`` with planted
                matches.  ``ShardedEngine().evaluate`` at its default tiles;
                the candidates with i < 2048 must equal ``NumpyEngine`` on
                that L slice x all of R, and the band-step program's HLO
                must hold the compiled Pallas kernel (``tpu_custom_call``).
  c. join     — ``run_join`` on police_records at size 1.0, sharded engine
                against the numpy engine: recall, candidates and clauses
                must match.  The threshold-sweep kernel's counts must equal
                a numpy count on a random grid.
  d. serving  — a ``JoinService`` cold then warm query on the sharded
                engine: the warm query charges $0 extraction, moves 0 plane
                bytes host-to-device and returns the cold query's pairs.

Lines starting with ``smoke`` are smoke output (walls included), not
benchmark metrics.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_ROWS = 100_000          # rows per side in phase b
EMBED_DIM = 128           # embedder width; FeatureData adds 2 marker dims
CHECK_ROWS = 2048         # L rows phase b checks against the numpy engine
CLAUSES = [[0], [1, 2]]   # police_records shape: (embed) AND (embed OR scalar)


def smoke(phase: str, **fields) -> None:
    print("smoke " + json.dumps({"phase": phase, **fields}, default=str),
          flush=True)


def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (first device is {d0.platform})")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but {len(devs)} "
                         f"devices")
    smoke("a.device", platform=d0.platform, kind=d0.device_kind,
          count=len(devs))
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# --- phase b: planes -------------------------------------------------------

def _quantized_unit(x: np.ndarray) -> np.ndarray:
    """Unit rows rounded to multiples of 2^-7: every dot product of two
    such rows is exact in f32, so the device and the numpy oracle agree
    on every distance whatever order they sum in."""
    x = x / np.linalg.norm(x, axis=1, keepdims=True)
    return (np.round(x * 128.0) / 128.0).astype(np.float32)


def make_planes(n: int, seed: int):
    """(feats, thetas): two embed features and one scalar over n x n rows,
    half of the R rows planted near an L row, ~2% of values missing."""
    from repro.core.featurize import FeatureData, FeaturizationSpec, _augment

    rng = np.random.default_rng(seed)
    n_plant = n // 2
    li = rng.permutation(n)[:n_plant]
    rj = rng.permutation(n)[:n_plant]
    feats = []
    for f in range(2):
        el = _quantized_unit(rng.standard_normal((n, EMBED_DIM)))
        er = _quantized_unit(rng.standard_normal((n, EMBED_DIM)))
        noise = rng.standard_normal((n_plant, EMBED_DIM)) / np.sqrt(EMBED_DIM)
        er[rj] = _quantized_unit(el[li] + 0.5 * noise)
        ml, mr = rng.random(n) < 0.02, rng.random(n) < 0.02
        el[ml] = 0.0
        er[mr] = 0.0
        spec = FeaturizationSpec(f"embed{f}", "", "semantic", "llm", f"e{f}")
        feats.append(FeatureData(spec, "embed", _augment(el, ml, "l"),
                                 _augment(er, mr, "r")))
    xl = rng.uniform(0.0, 40.0, n).astype(np.float32)
    xr = rng.uniform(0.0, 40.0, n).astype(np.float32)
    xr[rj] = xl[li] + rng.normal(0.0, 0.05, n_plant).astype(np.float32)
    xl[rng.random(n) < 0.02] = 1e9
    xr[rng.random(n) < 0.02] = -1e9
    spec = FeaturizationSpec("scalar", "", "arithmetic", "code", "x")
    feats.append(FeatureData(spec, "scalar", xl, xr))
    # each embed clause admits ~0.2% of random pairs: the 0.002 quantile
    # of a random-pair sample (planted pairs sit far below it)
    si, sj = rng.integers(0, n, 200_000), rng.integers(0, n, 200_000)
    pairs = list(zip(si.tolist(), sj.tolist()))
    thetas = tuple(float(np.quantile(feats[f].pair_distances(pairs), 0.002))
                   for f in range(2))
    return feats, thetas


def _l_slice(feats, rows: int):
    import dataclasses
    return [dataclasses.replace(f, data_l=f.data_l[:rows]) for f in feats]


def band_step_compiled():
    """Compile the band-step program the sharded engine last ran, at the
    shapes and shardings of its first call."""
    from repro.engine.sharded import ShardedEngine

    fn = next(reversed(ShardedEngine._programs.values()))
    return fn.lower(*ShardedEngine._first_args[fn]).compile()


def phase_step2(seed: int, n: int = N_ROWS):
    from repro.engine.numpy_engine import NumpyEngine
    from repro.engine.sharded import ShardedEngine

    t0 = time.perf_counter()
    feats, thetas = make_planes(n, seed)
    t1 = time.perf_counter()
    res = ShardedEngine().evaluate(feats, CLAUSES, thetas)
    t2 = time.perf_counter()
    compiled = band_step_compiled()
    mem = compiled.memory_analysis()
    smoke("b.band_step", memory_analysis=str(mem),
          tpu_custom_call="tpu_custom_call" in compiled.as_text())
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("band-step HLO holds no compiled Pallas kernel")
    got = [p for p in res.candidates if p[0] < CHECK_ROWS]
    want = NumpyEngine().evaluate(_l_slice(feats, CHECK_ROWS), CLAUSES,
                                  thetas).candidates
    t3 = time.perf_counter()
    smoke("b.step2", rows=n, thetas=thetas, candidates=len(res.candidates),
          checked_rows=CHECK_ROWS, checked_candidates=len(want),
          bytes_h2d=res.stats.bytes_h2d,
          bytes_to_host=res.stats.bytes_to_host,
          planes_wall_s=t1 - t0, evaluate_wall_s=t2 - t1,
          check_wall_s=t3 - t2)
    if got != want:
        raise AssertionError(
            f"step-② candidates differ from the numpy engine on rows "
            f"< {CHECK_ROWS}: {len(got)} vs {len(want)}")
    if not 10**5 <= len(res.candidates) <= 10**6:
        raise AssertionError(f"{len(res.candidates)} candidates: planted "
                             f"data out of the intended 1e5-1e6 range")
    return feats, thetas, res.candidates


def phase_step2_chips(seed: int, chips: int, n: int = N_ROWS):
    """Phase b on a (1, chips, 1) join mesh vs the same planes on one chip;
    each chip must hold its own L shard."""
    import jax
    from repro.distributed.mesh import make_join_mesh
    from repro.engine.sharded import ShardedEngine
    from repro.kernels.fused_cnf_join.ops import stage_planes

    feats, thetas = make_planes(n, seed)
    mesh = make_join_mesh(1, chips, 1)
    one = jax.make_mesh((1, 1), ("data", "model"),
                        devices=jax.devices()[:1])
    eng = ShardedEngine(mesh=mesh)
    t0 = time.perf_counter()
    multi = eng.evaluate(feats, CLAUSES, thetas)
    t1 = time.perf_counter()
    mem = band_step_compiled().memory_analysis()
    single = ShardedEngine(mesh=one).evaluate(feats, CLAUSES, thetas)
    t2 = time.perf_counter()
    staged = stage_planes(feats, CLAUSES, tl=eng.tl,
                          tr=eng._resolve_r_chunk(1), mesh=mesh,
                          l_axes=("pod", "data"))
    pl_n = staged.emb_l.shape[1]
    owners = {}
    for sh in staged.emb_l.addressable_shards:
        rows = sh.index[1]
        owners[sh.device.id] = (rows.start or 0, rows.stop or pl_n)
        if sh.data.shape[1] != pl_n // chips or \
                sh.data.devices() != {sh.device}:
            raise AssertionError(f"L shard on {sh.device} has "
                                 f"{sh.data.shape[1]} rows on "
                                 f"{sh.data.devices()}")
    spans = sorted(owners.values())
    if len(owners) != chips or spans[0][0] != 0 or spans[-1][1] != pl_n or \
            any(a[1] != b[0] for a, b in zip(spans, spans[1:])):
        raise AssertionError(f"L shards do not partition the rows over "
                             f"{chips} chips: {owners}")
    smoke("b.step2_chips", chips=chips, rows=n, l_shards=owners,
          memory_analysis=str(mem), candidates=len(multi.candidates),
          one_chip_candidates=len(single.candidates),
          multi_wall_s=t1 - t0, one_chip_wall_s=t2 - t1)
    if multi.candidates != single.candidates:
        raise AssertionError(f"{chips}-chip candidates differ from one chip")


# --- phase c: full join ----------------------------------------------------

def phase_join(seed: int, size: float = 1.0):
    from repro.launch.join import run_join
    from repro.kernels.threshold_sweep.ops import sweep_counts

    keys = ("recall", "candidates", "clauses")
    out = {}
    for engine in ("sharded", "numpy"):
        t0 = time.perf_counter()
        r = run_join("police_records", engine=engine, size=size, seed=seed)
        out[engine] = {k: r[k] for k in keys}
        smoke("c.join", engine=engine, n_l=r["n_l"], n_r=r["n_r"],
              wall_s=time.perf_counter() - t0, **out[engine])
    if out["sharded"] != out["numpy"]:
        raise AssertionError(f"sharded join {out['sharded']} != numpy "
                             f"{out['numpy']}")
    # the guarantee path's threshold sweep (kernels/threshold_sweep) on a
    # ragged random grid against a plain count
    rng = np.random.default_rng(seed)
    cd = rng.random((5000, 3)).astype(np.float32)
    labels = rng.random(5000) < 0.3
    th = rng.random((700, 3)).astype(np.float32)
    pos, sel = sweep_counts(cd, labels, th)
    ok = (cd[None, :, :] <= th[:, None, :]).all(-1)
    if not (np.array_equal(pos, ok[:, labels].sum(1))
            and np.array_equal(sel, ok.sum(1))):
        raise AssertionError("threshold-sweep counts differ from numpy")
    smoke("c.sweep", k=cd.shape[0], grid=th.shape[0], clauses=cd.shape[1])


# --- phase d: serving ------------------------------------------------------

def phase_serving(seed: int, size: float = 1.0):
    from repro.core.join import FDJConfig
    from repro.launch._args import make_dataset
    from repro.serving.join_service import JoinService

    ds = make_dataset("police_records", size=size, seed=seed)
    svc = JoinService(ds, FDJConfig(engine="sharded", seed=seed))
    cold = svc.query()
    warm = svc.query()
    es = warm.join.engine_stats
    smoke("d.serving", cold_wall_s=cold.wall_s, warm_wall_s=warm.wall_s,
          pairs=len(warm.pairs), plan_hit=warm.plan_hit,
          warm_extraction_usd=warm.cost.inference,
          warm_bytes_h2d=warm.cost.bytes_h2d,
          warm_engine=es.engine if es else None)
    if es is None or es.engine != "sharded":
        raise AssertionError("warm query did not run the sharded engine")
    if not warm.plan_hit or warm.cost.inference != 0.0 or \
            warm.cost.bytes_h2d != 0 or es.bytes_h2d != 0:
        raise AssertionError("warm query re-paid extraction or plane H2D")
    if warm.pairs != cold.pairs:
        raise AssertionError("warm pairs differ from the cold query's")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch._args import use_compile_cache
    use_compile_cache()
    device = phase_device(args.chips)
    if args.chips > 1:
        phase_step2_chips(args.seed, args.chips)
    else:
        phase_step2(args.seed)
        phase_join(args.seed)
        phase_serving(args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
