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
    args.append(_spec((1,), NamedSharding(mesh, P("data")), jnp.int32))
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


# one police_records_1m shard: 10^6 resident L rows over a (4, 1) mesh of
# v5e chips, two 3,074-wide embeds and a scalar, a 700-row probe
SHARD_ROWS, SHARD_PAD, WIDTH, WIDTH_PAD = 250_000, 250_112, 3074, 3200
STAGE_TEMP_BYTES = 64 * 2**20
# a device's resident shard plus its staged layout
SHARD_STAGED_BYTES = 12.7e9


def _as_made(shape, sharding, dtype=jnp.float32):
    """A resident array's spec in the layout a jitted program gives its
    output, as the planes' generator and a serving store make them: on the
    TPU a (250,000, 3,074) plane keeps its rows minor (3,074 rows of
    250,112 lanes pad less than 250,000 of 3,200), the opposite of the
    kernel layout's."""
    made = jax.jit(lambda: jnp.zeros(shape, dtype),
                   out_shardings=sharding).lower().compile()
    return jax.ShapeDtypeStruct(shape, dtype, sharding=made.output_formats)


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))


# (chips, resident L rows, R rows, R rows padded to whole bands) of each
# cell's staging: police_records_1m's probe over four chips, and the one-chip
# probe and sweep of police_records_100k, whose R side is resident too
STAGINGS = {
    "police_probe_4chip": (4, 4 * SHARD_ROWS, 700, 1024),
    "police_probe": (1, 100_000, 700, 1024),
    "police_sweep": (1, 100_000, 100_000, 100_352),
}


def _stage_module():
    """The benchmark's pattern for the module of L's staging program
    (``benchmarks/chip/stage_work.py`` ``STAGE``), which its roofline
    share reads."""
    import importlib.util
    import sys
    chip = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip")
    sys.path.insert(0, chip)
    try:
        spec = importlib.util.spec_from_file_location(
            "_bench_stage_work", os.path.join(chip, "stage_work.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(chip)
    return mod.STAGE


def _module(hlo: str) -> str:
    """A compiled program's module as a device trace names one run of it:
    ``HloModule jit_f, ...`` runs as ``jit_f(<id>)``."""
    return hlo.split()[1].rstrip(",") + "(1)"


@pytest.mark.parametrize("cell", list(STAGINGS))
def test_shard_staging_fits_one_chip(topo, cell):
    """L's staging program of each cell writes the kernel layout with no
    shard-sized temporary and no collective, from resident planes whose
    rows are minor, under the module name the benchmark's staging
    roofline reads."""
    from repro.kernels.fused_cnf_join import ops

    chips, n_l, n_r, pr_n = STAGINGS[cell]
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1),
                ("data", "model"))
    stride, rows_pad = ops.shard_rows(n_l, chips, 128)
    assert rows_pad == (SHARD_PAD if chips == 4 else 100_096)
    program = ops._stage_l_program(mesh, ("data",), n_l, stride, rows_pad,
                                   WIDTH_PAD)

    def spec(shape, *axes):
        return _as_made(shape, NamedSharding(mesh, P(*axes)))

    resident = spec((n_l, WIDTH), "data", None)
    assert resident.format.layout.major_to_minor == (1, 0)
    compiled = program.lower((resident,) * 2,
                             (spec((n_l,), "data"),)).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_fdj_stage_shards")
    assert _stage_module().match(_module(text))
    assert not any(op in text for op in ("all-gather", "all-to-all",
                                         "collective-permute"))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < STAGE_TEMP_BYTES, mem
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes <= \
        SHARD_STAGED_BYTES, mem


@pytest.mark.parametrize("cell", list(STAGINGS))
def test_batch_staging_is_named_apart(topo, cell):
    """R's staging program, which a probe runs on every query, writes R's
    band stack whole on each chip in a small temporary, under a module name
    the benchmark's L staging roofline does not read."""
    from repro.kernels.fused_cnf_join import ops

    chips, _, n_r, pr_n = STAGINGS[cell]
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1),
                ("data", "model"))

    def spec(shape):
        return _as_made(shape, NamedSharding(mesh, P()))

    compiled = ops._stage_r_program(mesh, n_r, pr_n, WIDTH_PAD).lower(
        (spec((n_r, WIDTH)),) * 2, (spec((n_r,)),)).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_fdj_stage_batch")
    assert not _stage_module().match(_module(text))
    assert not any(op in text for op in ("all-gather", "all-to-all",
                                         "collective-permute"))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < STAGE_TEMP_BYTES, mem
    # the band stack itself, and tile padding of the one-row scalar stack
    stack = (2 * WIDTH_PAD + 1) * pr_n * 4
    assert stack <= mem.output_size_in_bytes < stack + 2**16, mem


def test_shard_band_step_fits_beside_the_planes(four_chips):
    """The band step over one staged police_records_1m shard fits 16 GB
    beside the shard's resident planes."""
    from repro.engine.sharded import ShardedEngine

    mesh = four_chips
    eng = ShardedEngine(interpret=False)
    r_chunk = eng._resolve_r_chunk(1)
    cap = max(4096, 4 * SHARD_PAD)
    fn = eng._build_uncached(mesh, CLAUSES, THETAS, SHARD_PAD, cap, r_chunk,
                             2, False)
    n_pad = 4 * SHARD_PAD

    def spec(shape, *axes, dtype=jnp.float32):
        return _as_made(shape, NamedSharding(mesh, P(*axes)), dtype)

    compiled = fn.lower(
        spec((2, n_pad, WIDTH_PAD), None, "data", None),
        spec((2, 2 * r_chunk, WIDTH_PAD)), spec((1, n_pad), None, "data"),
        spec((1, 2 * r_chunk)), spec((4,), "data", dtype=jnp.int32),
        spec((), dtype=jnp.int32)).compile()
    mem = compiled.memory_analysis()
    planes = jax.jit(lambda: jnp.zeros((4 * SHARD_ROWS, WIDTH)),
                     out_shardings=NamedSharding(mesh, P("data", None))
                     ).lower().compile().memory_analysis()
    used = (2 * planes.output_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, mem
