"""Multi-pod join dry-run: the sharded CNF engine on an emulated pod mesh.

Emulates a ``(pod, data, model)`` mesh with XLA host devices (the same
override contract as ``launch.dryrun``: the flag is set at module import,
before any jax initialization, and ONLY inside this entry point — library
code and tests see the real device count) and validates the multi-pod
engine end to end:

  * **parity**   — sharded-on-pod-mesh candidates ≡ the numpy oracle on a
    ragged corpus, with the double-buffered band loop required to report
    nonzero overlap on the multi-step sweep, plus the capacity-1 overflow
    fixture (every chunk overflows; the ≥4× per-shard retry must recover
    the full cross product without mutating the engine's configured
    capacity);
  * **stream**   — per-step chunks are disjoint and their union ≡ batch;
  * **serving**  — a ``JoinService`` over a mesh-attached
    ``FeaturePlaneStore``: the warm repeated sharded query must charge $0
    extraction, move 0 plane bytes H2D, **and report 0 plane reshard
    bytes** (the pre-sharded-residency invariant); the delta-append query
    must evaluate only L × ΔR;
  * **hlo**      — the compiled chunk-step program's collectives, split by
    pod locality (``distributed.hlo_analysis.pod_crossing_stats``): every
    pod-spanning collective must be candidate-count sized — cross-pod
    interconnect carries counts, never feature planes or masks.

Usage (defaults to the assignment's (2, 16, 16) dry-run mesh):

  PYTHONPATH=src python -m repro.launch.multipod_dryrun --mesh 2,16,16
  PYTHONPATH=src python -m repro.launch.multipod_dryrun --mesh 1,8,1 \
      --skip-serving

Prints one JSON report on stdout (marker line ``MULTIPOD_DRYRUN_JSON``);
exits nonzero on any failed check.  ``benchmarks/engines.py`` runs this as
a subprocess for the CI gate; ``tests/test_multipod.py`` drives the small
meshes in tier-1 and the full 512-device mesh under ``-m slow``.
"""

import os as _os
import sys as _sys


def _mesh_arg(argv) -> tuple:
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            return tuple(int(x) for x in argv[i + 1].split(","))
        if a.startswith("--mesh="):
            return tuple(int(x) for x in a.split("=", 1)[1].split(","))
    return (2, 16, 16)


_SHAPE = _mesh_arg(_sys.argv)
if len(_SHAPE) != 3 or min(_SHAPE) < 1:
    raise SystemExit(f"--mesh must be P,D,M with P,D,M >= 1, got {_SHAPE}")
_os.environ["XLA_FLAGS"] = _os.environ.get("XLA_FLAGS", "") + \
    f" --xla_force_host_platform_device_count={_SHAPE[0] * _SHAPE[1] * _SHAPE[2]}"
# ^ MUST precede any other import (jax locks device count on first init).

import argparse
import json
import math
import time
import traceback


def _engine_opts(mesh, *, tl: int, tr: int, r_chunk: int, use_kernel: bool,
                 capacity=None) -> dict:
    opts = dict(mesh=mesh, tl=tl, tr=tr, r_chunk=r_chunk,
                use_kernel=use_kernel)
    if capacity is not None:
        opts["capacity"] = capacity
    return opts


def _check_parity(mesh, rep: dict, *, tl, tr, r_chunk, use_kernel,
                  contract=None) -> None:
    from repro.core.costs import CostLedger
    from repro.core.featurize import FeaturizationSpec, vectorize
    from repro.data.cnf_fixtures import representative_cnf
    from repro.data.simulated_llm import SimulatedExtractor
    from repro.data import synth
    from repro.engine import get_engine

    # corpus sized so the R sweep takes >= 2 stream steps on this mesh
    # (n_r = 2 * n_incidents with 2 reports/incident) — the per-pod band
    # rotation is only exercised when there is more than one band
    n_inc = max(37, r_chunk // 2 + 1)
    ds = synth.police_records(n_incidents=n_inc, reports_per_incident=2,
                              seed=5)
    specs, clauses, thetas = representative_cnf(ds)
    feats = SimulatedExtractor(ds).materialize(specs, CostLedger())
    oracle = get_engine("numpy", block=256).evaluate(feats, clauses, thetas)
    # prefetch_depth=4: the deep ring must change neither the candidate
    # set nor the counts-only pod-crossing profile (the hlo check compiles
    # the same per-step program the ring dispatches)
    eng = get_engine("sharded", prefetch_depth=4, **_engine_opts(
        mesh, tl=tl, tr=tr, r_chunk=r_chunk, use_kernel=use_kernel))
    res = eng.evaluate(feats, clauses, thetas)
    assert res.candidates == oracle.candidates, (
        f"pod-mesh candidates diverge from numpy: "
        f"{len(res.candidates)} vs {len(oracle.candidates)}")
    assert res.stats.n_candidates > 0, "degenerate parity corpus"
    s = res.stats
    rep["parity"] = {
        "n_l": s.n_l, "n_r": s.n_r, "candidates": s.n_candidates,
        "bytes_to_host": s.bytes_to_host, "bytes_h2d": s.bytes_h2d,
        "bytes_reshard": s.bytes_reshard, "plane_bytes": s.plane_bytes,
        "wall_s": round(s.wall_s, 3),
        "dispatch_wall_s": round(s.dispatch_wall_s, 4),
        "pull_wall_s": round(s.pull_wall_s, 4),
        "overlap_s": round(s.overlap_s, 4),
        "prefetch_depth": eng.effective_prefetch_depth,
        "conjunct_evals": s.conjunct_evals,
        "flops_per_candidate": round(s.flops_per_candidate, 2),
    }
    # the R sweep takes >= 2 steps here (corpus sized for it), so the
    # prefetch ring must have kept a successor step in flight during host
    # pulls: overlap_s == 0 means it degraded to serial
    assert s.overlap_s > 0, (
        "depth-4 prefetch ring reported zero overlap on a multi-step "
        "sweep — the pipeline degraded to the serial loop")
    assert s.conjunct_evals > 0, "conjunct-eval accounting missing"
    # host traffic must scale with candidates (8 B per pulled pair, plus
    # one count + one base + one conjunct-eval int32 per device per
    # step), never with the O(n_l*n_r) plane; the per-device-step ceiling
    # is contract policy from benchmarks/baseline/hlo_manifest.json
    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    n_steps = math.ceil(s.n_r / r_chunk)
    if contract is not None:
        allow = contract.host_pull_budget(s.n_candidates, n_dev, n_steps)
    else:
        allow = 8 * s.n_candidates + 12 * n_dev * n_steps + 1024
    assert s.bytes_to_host <= allow, (
        f"host traffic {s.bytes_to_host} not O(candidates) (allow {allow})")

    # stream: disjoint cover ≡ batch
    chunks = list(get_engine("sharded", **_engine_opts(
        mesh, tl=tl, tr=tr, r_chunk=r_chunk, use_kernel=use_kernel)
    ).evaluate_stream(feats, clauses, thetas))
    union = [p for ch in chunks for p in ch.candidates]
    assert len(union) == len(set(union)), "stream chunks overlap"
    assert sorted(union) == oracle.candidates, "stream union != batch"
    for ch in chunks:
        assert ch.candidates == sorted(ch.candidates), "chunk not sorted"
    rep["stream"] = {"chunks": len(chunks)}

    # capacity-1 fixture: every step overflows; retry must recover all
    n = 33
    spec = FeaturizationSpec("name", "", "word_overlap", "llm", "name")
    dense = [vectorize(spec, ["same text"] * n, ["same text"] * n)]
    eng1 = get_engine("sharded", **_engine_opts(
        mesh, tl=tl, tr=tr, r_chunk=r_chunk, use_kernel=use_kernel,
        capacity=1))
    res1 = eng1.evaluate(dense, [[0]], [0.5])
    want = [(i, j) for i in range(n) for j in range(n)]
    assert res1.candidates == want, "overflow retry truncated candidates"
    assert eng1.last_sweep_capacity >= 4, "capacity did not grow >=4x"
    assert eng1.capacity == 1, "overflow mutated the configured capacity"
    rep["overflow"] = {"candidates": len(res1.candidates),
                      "final_capacity": int(eng1.last_sweep_capacity)}


def _check_serving(mesh, rep: dict, *, tl, tr, r_chunk, use_kernel,
                   contract=None) -> None:
    from repro.core.join import FDJConfig
    from repro.data import synth
    from repro.serving.join_service import JoinService, hold_out_right
    from repro.serving.planes import FeaturePlaneStore

    # movies: embed-only planes keep the append on the incremental path
    ds = synth.movies_pages(n_movies=24, cast_size=4, filler_sentences=1,
                            seed=0)
    base, delta_rows = hold_out_right(ds, n_delta=ds.n_r // 5)
    cfg = FDJConfig(engine="sharded", seed=0, mc_trials=4000,
                    engine_opts=_engine_opts(mesh, tl=tl, tr=tr,
                                             r_chunk=r_chunk,
                                             use_kernel=use_kernel))
    svc = JoinService(base, cfg, store=FeaturePlaneStore(mesh=mesh))

    cold = svc.query()
    warm = svc.query()
    assert warm.pairs == cold.pairs, "warm pairs diverge from cold"
    assert warm.cost.inference == 0.0, (
        f"warm query charged ${warm.cost.inference} extraction")
    assert warm.cost.bytes_h2d == 0, (
        f"warm query moved {warm.cost.bytes_h2d} plane bytes H2D")
    assert warm.cost.bytes_reshard == 0, (
        f"warm query paid {warm.cost.bytes_reshard} reshard bytes — "
        f"resident planes were not pre-sharded onto the mesh")
    svc.append_right(delta_rows)
    dq = svc.query()
    assert dq.delta_rows == len(delta_rows.texts), (
        "delta query re-evaluated the full corpus")
    rep["serving"] = {
        "cold_reshard_bytes": cold.cost.bytes_reshard,
        "warm_reshard_bytes": warm.cost.bytes_reshard,
        "warm_h2d_bytes": warm.cost.bytes_h2d,
        "warm_extraction_cost": warm.cost.inference,
        "delta_rows": dq.delta_rows,
        "cold_wall_s": round(cold.wall_s, 3),
        "warm_wall_s": round(warm.wall_s, 3),
    }


def _lower_chunk_step(mesh, *, tl, tr, r_chunk, use_kernel) -> tuple:
    """Lower + compile the real chunk-step program; returns
    ``(hlo text, n_pods, pod_size, staged plane bytes)``."""
    import jax.numpy as jnp
    from repro.core.costs import CostLedger
    from repro.data.cnf_fixtures import representative_cnf
    from repro.data.simulated_llm import SimulatedExtractor
    from repro.data import synth
    from repro.engine.sharded import ShardedEngine, _mesh_geometry
    from repro.kernels.fused_cnf_join import ops as cnf_ops

    ds = synth.police_records(n_incidents=37, reports_per_incident=2, seed=5)
    specs, clauses, thetas = representative_cnf(ds)
    feats = SimulatedExtractor(ds).materialize(specs, CostLedger())
    eng = ShardedEngine(mesh, tl=tl, tr=tr, r_chunk=r_chunk,
                        use_kernel=use_kernel)
    l_axes, n_pods, n_data, n_model = _mesh_geometry(mesh)
    l_shards = n_pods * n_data
    staged = cnf_ops.stage_planes(feats, clauses, tl=eng.tl,
                                  tr=r_chunk, mesh=mesh, l_axes=l_axes)
    rows_shard = staged.emb_l.shape[1] // l_shards
    n_chunks = staged.emb_r.shape[1] // r_chunk
    cap = 4096
    fn = eng._build(mesh, staged.kclauses,
                    tuple(float(t) for t in thetas), rows_shard, cap,
                    r_chunk, n_chunks)
    hlo = fn.lower(*staged.arrays, staged.row0,
                   jnp.int32(0)).compile().as_text()
    plane_bytes = sum(int(a.nbytes) for a in staged.arrays)
    return hlo, n_pods, n_data * n_model, plane_bytes


def _check_hlo(mesh, rep: dict, *, tl, tr, r_chunk, use_kernel,
               contract=None) -> None:
    """Lower + compile one chunk-step program and gate its collectives
    against the committed manifest (benchmarks/baseline/hlo_manifest.json):
    cross-pod collectives exist (the count gather) but every one of them
    is counts-sized and of a reviewed kind — no plane or mask crosses a
    pod boundary, and no unreviewed collective lands green."""
    from repro.analysis.hlo_contracts import (DEFAULT_CONTRACTS,
                                              check_program)

    if contract is None:
        contract = DEFAULT_CONTRACTS["sharded_chunk_step"]
    hlo, n_pods, pod_size, plane_bytes = _lower_chunk_step(
        mesh, tl=tl, tr=tr, r_chunk=r_chunk, use_kernel=use_kernel)
    findings, rep["hlo"] = check_program(
        hlo, contract, n_pods=n_pods, pod_size=pod_size,
        plane_bytes=plane_bytes)
    assert not findings, "; ".join(str(f) for f in findings)


def main() -> None:
    # allow_abbrev=False: the XLA device-count override was derived from a
    # literal "--mesh" scan of sys.argv at import time, before jax — an
    # argparse prefix abbreviation ("--mes") would be honored here but
    # invisible to that scan, silently running the default mesh instead
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--mesh", default="2,16,16",
                    help="P,D,M pod-mesh shape (emulated host devices)")
    ap.add_argument("--skip-serving", action="store_true",
                    help="skip the serving regime (parity + hlo only)")
    ap.add_argument("--kernel", action="store_true",
                    help="run the Pallas kernel (interpret mode) instead "
                         "of the jnp reference math — slow at high device "
                         "counts, exercised on small meshes in tier-1")
    ap.add_argument("--manifest", default=None,
                    help="HLO contract manifest path (default: "
                         "benchmarks/baseline/hlo_manifest.json)")
    ap.add_argument("--write-manifest", action="store_true",
                    help="regenerate the manifest's op-sets from the "
                         "freshly lowered program (budgets keep committed "
                         "policy) instead of checking — review the diff")
    args = ap.parse_args()
    if tuple(int(x) for x in args.mesh.split(",")) != _SHAPE:
        raise SystemExit(f"--mesh {args.mesh} disagrees with the "
                         f"import-time device override {_SHAPE}")
    n_pods, n_data, n_model = _SHAPE

    import jax
    from repro.analysis.hlo_contracts import (DEFAULT_CONTRACTS,
                                              default_manifest_path,
                                              dump_manifest, load_manifest,
                                              observed_contract)
    from repro.distributed.mesh import make_join_mesh
    t0 = time.time()
    rep = {"mesh": list(_SHAPE), "devices": len(jax.devices()),
           "use_kernel": bool(args.kernel), "status": "ok"}
    mesh = make_join_mesh(n_pods, n_data, n_model)
    # tiles sized so the smallest L shard and the per-model sub-band are
    # whole tiles on any requested mesh; tr is pinned at the 32-bit packed
    # word, r_chunk covers one tile per model-axis device
    tl, tr = 8, 32
    r_chunk = tr * n_model

    manifest_path = args.manifest or default_manifest_path()
    if args.write_manifest:
        hlo, _, pod_size, _ = _lower_chunk_step(
            mesh, tl=tl, tr=tr, r_chunk=r_chunk, use_kernel=args.kernel)
        base = (load_manifest(manifest_path)
                if _os.path.exists(manifest_path)
                else dict(DEFAULT_CONTRACTS))
        base["sharded_chunk_step"] = observed_contract(
            hlo, "sharded_chunk_step", pod_size=pod_size,
            base=base.get("sharded_chunk_step"))
        out = dump_manifest(base, manifest_path)
        print(json.dumps({"wrote_manifest": out}))
        raise SystemExit(0)
    try:
        contract = load_manifest(manifest_path)["sharded_chunk_step"]
        rep["manifest"] = manifest_path
    except (OSError, KeyError) as e:
        rep["manifest"] = (f"unavailable ({type(e).__name__}) — "
                           f"falling back to DEFAULT_CONTRACTS policy")
        contract = DEFAULT_CONTRACTS["sharded_chunk_step"]

    failed = []
    for name, check in (("parity", _check_parity),
                        ("serving", _check_serving),
                        ("hlo", _check_hlo)):
        if name == "serving" and args.skip_serving:
            continue
        try:
            check(mesh, rep, tl=tl, tr=tr, r_chunk=r_chunk,
                  use_kernel=args.kernel, contract=contract)
        except Exception as e:
            failed.append(name)
            rep[name] = {"error": f"{type(e).__name__}: {e}",
                         "traceback": traceback.format_exc()}
    rep["wall_s"] = round(time.time() - t0, 1)
    if failed:
        rep["status"] = "failed"
        rep["failed"] = failed
    print("MULTIPOD_DRYRUN_JSON " + json.dumps(rep, default=str))
    raise SystemExit(1 if failed else 0)


if __name__ == "__main__":
    main()
