"""TPU compiles of the main path's kernels at real widths, without a chip.

Each test lowers and compiles for one chip of a *described* TPU v5e
topology (the TPU compiler is installed; nothing runs), so a kernel that
only passes in interpret mode — a block shape Mosaic refuses, a primitive
it cannot lower, a band step that does not fit 16 GB of HBM — fails here.

Real widths: embedding planes as ``pack_features`` lays them out for a
128-dim embedder plus the 2 missing-value marker columns (padded to 256
lanes), 2 embed features and 1 scalar feature in the police_records CNF
shape, and the engines' default tiles.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.fused_cnf_join.kernel import SCAL, VEC, cnf_join_block
from repro.kernels.threshold_sweep.kernel import threshold_sweep

D_PAD = 256                # 128-dim embedder + 2 marker dims, lane-padded
ROWS = 100_352             # 100,000 rows padded to the sharded L tile
HBM_BYTES = 16 * 10**9     # one v5e chip
# the band step's scratch at 100,352 rows when extraction searched every
# buffer slot at once (the compile below, before the blocked search)
BAND_STEP_TEMP_BYTES = 214_773_248
CLAUSES = (((VEC, 0),), ((VEC, 1), (SCAL, 0)))
THETAS = (0.3, 0.35)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("tl,tr,n_r,early_reject,with_evals", [
    (128, 128, 512, True, True),       # sharded engine: one R band
    (256, 512, 2048, True, True),      # pallas engine defaults
    (256, 512, 2048, False, False),    # full-width CNF, mask only
], ids=["sharded-tiles", "pallas-tiles", "pallas-tiles-plain"])
def test_fused_cnf_join_compiles(one_chip, tl, tr, n_r, early_reject,
                                 with_evals):
    def step(el, er, sl, sr):
        return cnf_join_block(el, er, sl, sr, CLAUSES, THETAS, tl=tl, tr=tr,
                              early_reject=early_reject,
                              with_evals=with_evals)

    args = (_spec((2, ROWS, D_PAD), one_chip), _spec((2, n_r, D_PAD), one_chip),
            _spec((1, ROWS), one_chip), _spec((1, n_r), one_chip))
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_clauses", [1, 3])
def test_threshold_sweep_compiles(one_chip, n_clauses):
    k, g = 8192, 1024

    def step(cd, labels, valid, thetas):
        return threshold_sweep(cd, labels, valid, thetas, tg=256, tk=512)

    args = (_spec((k, n_clauses), one_chip), _spec((k,), one_chip),
            _spec((k,), one_chip), _spec((g, n_clauses), one_chip))
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_band_step_compiles_and_fits(topo):
    """The whole band-step program (shard_map + fused kernel +
    extract_pairs) at 100,352 L rows on one chip."""
    from repro.engine.sharded import ShardedEngine
    from repro.kernels.fused_cnf_join.ops import _mesh_shardings

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    eng = ShardedEngine(interpret=False)
    r_chunk = eng._resolve_r_chunk(1)
    cap = max(4096, 4 * ROWS)          # the engine's default capacity
    fn = eng._build_uncached(mesh, CLAUSES, THETAS, ROWS, cap, r_chunk,
                             ROWS // r_chunk, False)
    shapes = [(2, ROWS, D_PAD), (2, ROWS, D_PAD), (1, ROWS), (1, ROWS)]
    args = [_spec(s, sh)
            for s, sh in zip(shapes, _mesh_shardings(mesh, ("data",)))]
    args.append(_spec((), NamedSharding(mesh, P()), jnp.int32))
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    # one kernel, the fused CNF join: the benchmark finds it by this call
    # target, and extraction adds none
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert text.startswith("HloModule jit_body")
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, mem
    assert mem.temp_size_in_bytes <= BAND_STEP_TEMP_BYTES, mem
