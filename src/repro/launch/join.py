"""FDJ join launcher — the paper's end-to-end pipeline as a CLI.

  PYTHONPATH=src python -m repro.launch.join --dataset police_records \
      --target 0.9 --delta 0.1 [--engine sharded|numpy|pallas]

``--engine`` defaults to ``sharded``: step ② runs on the local devices
(the fused Pallas kernel, compiled on a TPU, interpreted on the CPU).

Also exposes the *distributed join step* (``build_join_cell``): the fused
CNF evaluation over an L x R block plane lowered on the production mesh —
L rows sharded over (pod, data), R rows over model — which is the
paper-technique dry-run/roofline cell referenced in EXPERIMENTS.md.
"""

from __future__ import annotations

import argparse
import json


from typing import Optional

from repro.core.costs import naive_join_cost
from repro.core.join import FDJConfig, fdj_join
from repro.data.simulated_llm import SimulatedExtractor, SimulatedProposer
from repro.launch._args import (add_common_flags, engine_opts_from,
                                make_dataset, use_compile_cache)
from repro.obs import Tracer, use_tracer, write_trace


def run_join(dataset: str = "police_records", target: float = 0.9,
             delta: float = 0.1, precision_target: float = 1.0,
             engine: str = "numpy", size: float = 1.0, seed: int = 0,
             stream: bool = False, pods: int = 1,
             prefetch_depth: Optional[int] = None,
             r_chunk: Optional[int] = None,
             trace_out: Optional[str] = None) -> dict:
    ds = make_dataset(dataset, size=size, seed=seed)
    oracle = ds.make_oracle()
    cfg = FDJConfig(recall_target=target, delta=delta, engine=engine,
                    precision_target=precision_target, seed=seed,
                    stream_refinement=stream, pods=pods,
                    prefetch_depth=prefetch_depth,
                    engine_opts=engine_opts_from(r_chunk))
    tracer = Tracer() if trace_out else None
    with use_tracer(tracer):
        res = fdj_join(ds, oracle, SimulatedProposer(ds),
                       SimulatedExtractor(ds, seed=seed), cfg)
    if tracer is not None:
        write_trace(tracer, trace_out, metadata={
            "dataset": ds.name, "engine": engine, "stream": stream,
            "prefetch_depth": prefetch_depth,
            "wall_summary": res.cost.wall_summary(),
            "breakdown": res.cost.breakdown(),
        })
    naive = naive_join_cost(ds.texts_l, ds.texts_r)
    return {
        "dataset": ds.name, "n_l": ds.n_l, "n_r": ds.n_r,
        "recall": round(res.recall, 4), "precision": round(res.precision, 4),
        "recall_target": target, "t_prime": round(res.t_prime, 4),
        "met_target": res.met_target,
        "clauses": res.scaffold.clauses,
        "featurizations": [s.key for s in res.specs],
        "candidates": res.candidate_count,
        "cost_ratio": round(res.cost.total / naive, 4),
        "breakdown": {k: round(v / naive, 4) for k, v in res.cost.breakdown().items()},
        "engine": (res.engine_stats.as_dict() if res.engine_stats else None),
        "stream_refinement": stream,
        "walls": {k: round(v, 4) for k, v in res.cost.wall_summary().items()},
    }


# ---------------------------------------------------------------------------
# distributed join step (dry-run cell for the paper's technique)
# ---------------------------------------------------------------------------

def build_join_cell(mesh, *, n_l: int = 262144, n_r: int = 262144,
                    f_vec: int = 4, d: int = 128, n_clauses: int = 3):
    """jitted CNF-join step + abstract inputs, sharded over the mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.mesh import AxisEnv
    from repro.kernels.fused_cnf_join import ref as cref
    from repro.kernels.fused_cnf_join.kernel import VEC

    env = AxisEnv.from_mesh(mesh)
    rows_l = env.resolve(("batch",))[0]          # (pod, data)
    rows_r = "model"
    clauses = tuple(((VEC, i),) for i in range(n_clauses))
    thetas = tuple(0.4 for _ in range(n_clauses))

    def join_step(emb_l, emb_r):
        ok = cref.cnf_join_ref(emb_l, emb_r, None, None, clauses, thetas)
        return cref.pack_mask(ok)

    sds = jax.ShapeDtypeStruct
    a_l = sds((f_vec, n_l, d), jnp.float32,
              sharding=NamedSharding(mesh, P(None, rows_l, None)))
    a_r = sds((f_vec, n_r, d), jnp.float32,
              sharding=NamedSharding(mesh, P(None, rows_r, None)))
    out_sh = NamedSharding(mesh, P(rows_l, rows_r))
    fn = jax.jit(join_step, out_shardings=out_sh)
    return fn, (a_l, a_r)


def main():
    ap = add_common_flags(argparse.ArgumentParser())
    ap.add_argument("--precision-target", type=float, default=1.0)
    ap.add_argument("--pods", type=int, default=1,
                    help="pod-axis width for the sharded engine's 3-D "
                         "(pod, data, model) join mesh (FDJConfig.pods; "
                         "needs enough devices — see launch/multipod_dryrun "
                         "for the emulated (2, 16, 16) dry-run)")
    args = ap.parse_args()
    use_compile_cache()
    out = run_join(args.dataset, args.target, args.delta,
                   args.precision_target, args.engine, args.size, args.seed,
                   stream=args.stream, pods=args.pods,
                   prefetch_depth=args.prefetch_depth, r_chunk=args.r_chunk,
                   trace_out=args.trace_out)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
