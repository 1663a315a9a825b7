"""Per-shard staging on four CPU devices, in a process of its own (the
device count is fixed when JAX starts):

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
      PYTHONPATH=src python tests/shard_staging_cases.py

A resident plane set of 4 x 300 L rows split by rows over a (4, 1)
("data", "model") mesh, 200 R rows whole on every device, seeded random
planes in the police-records CNF shape (two embeds, one scalar; clause 0
one embed, clause 1 the other embed OR the scalar).  Prints one JSON
object, which ``test_shard_staging.py`` reads.
"""

from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.featurize import FeatureData, FeaturizationSpec
from repro.engine import get_engine
from repro.engine.sharded import ShardedEngine
from repro.kernels.fused_cnf_join import ops
from repro.obs.trace import Tracer, use_tracer
from repro.serving.planes import DevicePlaneSet

CHIPS, ROWS_SHARD, N_R, WIDTH, TL = 4, 300, 200, 62, 128
CLAUSES = [[0], [1, 2]]
THETAS = [0.3, 0.2]


def _unit(rng, n):
    e = rng.standard_normal((n, WIDTH)).astype(np.float32)
    return e / np.linalg.norm(e, axis=1, keepdims=True)


def _embed(e, side, missing):
    m = np.where(missing, -2.0, 0.0).astype(np.float32)[:, None]
    one = np.ones_like(m)
    e = np.where(missing[:, None], 0.0, e).astype(np.float32)
    return np.concatenate([e, m, one] if side == "l" else [e, one, m], 1)


def police_shape(seed: int, n_l: int) -> list:
    """Two embed features and a scalar; half of R planted near an L row."""
    rng = np.random.default_rng(seed)
    near = rng.integers(0, n_l, N_R)
    planted = rng.random(N_R) < 0.5
    feats = []
    for k in range(2):
        el = _unit(rng, n_l)
        er = _unit(rng, N_R)
        close = el[near] + 0.35 * _unit(rng, N_R)
        close /= np.linalg.norm(close, axis=1, keepdims=True)
        er = np.where(planted[:, None], close, er)
        feats.append(FeatureData(
            FeaturizationSpec(f"f{k}", "", "semantic", "code", f"f{k}"),
            "embed", _embed(el, "l", rng.random(n_l) < 0.02),
            _embed(er, "r", rng.random(N_R) < 0.02)))
    sl = rng.random(n_l).astype(np.float32)
    sr = np.where(planted, sl[near] + 0.01 * rng.standard_normal(N_R),
                  rng.random(N_R)).astype(np.float32)
    feats.append(FeatureData(
        FeaturizationSpec("f2", "", "arithmetic", "code", "f2"), "scalar",
        sl, sr))
    return feats


def resident(feats, mesh, sharded: bool) -> DevicePlaneSet:
    """The planes as a store holds them: L split by rows over the mesh
    (or whole on one device), R whole on every device."""
    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))
    if sharded:
        dev_l = [put(f.data_l, P("data", *[None] * (f.data_l.ndim - 1)))
                 for f in feats]
    else:
        dev_l = [jnp.asarray(f.data_l) for f in feats]
    dev_r = [put(f.data_r, P()) for f in feats]
    return DevicePlaneSet(feats, dev_l, dev_r, mesh=mesh)


def whole_l_on_one(n_l: int) -> list:
    """Shapes of live single-device arrays holding a whole L side."""
    return [list(a.shape) for a in jax.live_arrays()
            if len(a.devices()) == 1 and a.ndim >= 2
            and max(a.shape[:2]) >= n_l]


def case(name: str, n_l: int, sharded: bool, use_kernel: bool) -> dict:
    mesh = jax.make_mesh((CHIPS, 1), ("data", "model"))
    feats = police_shape(17, n_l)
    want = get_engine("numpy").evaluate(feats, CLAUSES, THETAS).candidates
    planes = resident(feats, mesh, sharded)
    tracer = Tracer()
    eng = ShardedEngine(tl=TL, tr=128, use_kernel=use_kernel)
    with use_tracer(tracer):
        got = eng.evaluate(planes, CLAUSES, THETAS)
    span = next(s.attrs for s in tracer.spans() if s.name == "stage_planes")
    # the next query: the same resident L, R uploaded again (a new batch
    # that happens to hold the same rows)
    tracer = Tracer()
    with use_tracer(tracer):
        again = eng.evaluate(DevicePlaneSet(
            feats, [planes.device_l(f) for f in range(len(feats))],
            [jax.device_put(f.data_r, NamedSharding(mesh, P()))
             for f in feats], mesh=mesh), CLAUSES, THETAS)
    span_again = next(s.attrs for s in tracer.spans()
                      if s.name == "stage_planes")
    staged = ops.stage_planes(planes, CLAUSES, tl=TL, tr=eng.r_chunk or 512,
                              mesh=mesh)
    shards = sorted([sh.device.id, sh.index[1].start or 0,
                     sh.index[1].stop, list(sh.data.shape),
                     [d.id for d in sh.data.devices()]]
                    for sh in staged.emb_l.addressable_shards)
    # the staging programs as they ran, compiled again from their arguments
    vec_l, scal_l, vec_r, scal_r = _stage_args(planes)
    hlo = (ops._stage_l_program(mesh, ("data",), n_l, *ops.shard_rows(
        n_l, CHIPS, TL), 128).lower(vec_l, scal_l).compile().as_text()
        + ops._stage_r_program(mesh, N_R, 512, 128).lower(
            vec_r, scal_r).compile().as_text()) if sharded else ""
    return {
        "name": name, "n_l": n_l, "candidates": len(want),
        "per_shard": np.bincount([i // ROWS_SHARD for i, _ in want],
                                 minlength=CHIPS).tolist(),
        "equal": got.candidates == want,
        "span": span, "pack_hit": staged.pack_hit,
        "span_again": span_again,
        "again_equal": again.candidates == got.candidates,
        "row_offsets": staged.row_offsets.tolist(),
        "row_counts": staged.row_counts.tolist(),
        "emb_l_shape": list(staged.emb_l.shape),
        "emb_r_bytes": int(staged.emb_r.nbytes + staged.scal_r.nbytes),
        "l_shard_bytes": int((staged.emb_l.nbytes + staged.scal_l.nbytes)
                             // CHIPS),
        "shards": shards,
        "collectives": [k for k in ("all-gather", "all-to-all",
                                    "collective-permute", "all-reduce")
                        if k in hlo],
        "whole_l_on_one": whole_l_on_one(n_l),
        "reshard": got.stats.bytes_reshard,
    }


def _stage_args(planes):
    """The staging programs' arguments for a plane set already on the
    mesh: L's features split by rows, R's whole."""
    vec = [i for i, f in enumerate(planes.feats) if f.kind == "embed"]
    scal = [i for i, f in enumerate(planes.feats) if f.kind == "scalar"]
    return (tuple(planes.device_l(f) for f in vec),
            tuple(planes.device_l(f) for f in scal),
            tuple(planes.device_r(f) for f in vec),
            tuple(planes.device_r(f) for f in scal))


def main() -> None:
    if len(jax.devices()) < CHIPS:
        sys.exit(f"needs {CHIPS} devices, JAX sees {len(jax.devices())}")
    n = CHIPS * ROWS_SHARD
    out = {
        "interpret": case("interpret", n, True, True),
        "jnp": case("jnp", n, True, False),
        # 1,198 rows a store holds on one device: shards of 300, 300, 300
        # and 298 real rows, put onto the mesh once
        "uneven": case("uneven", n - 2, False, False),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
