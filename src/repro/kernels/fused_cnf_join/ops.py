"""jit'd wrapper + corpus driver for the fused CNF-join kernel.

``pack_features`` converts a list of core ``FeatureData`` (+ scaffold clause
structure) into the kernel's array layout, padding record counts to tile
multiples and embedding dims to a lane multiple (128).  ``evaluate_corpus``
is the engine behind ``FDJConfig(engine="pallas")``: it runs the kernel
block-wise (interpret mode on CPU, compiled on TPU) and returns candidate
pair indices.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels.fused_cnf_join import ref
from repro.kernels.fused_cnf_join.kernel import SCAL, VEC, cnf_join_block


# padded rows' values: an embed row carries the missing markers in its last
# two real columns ([.., m=-2, 1] on L, [.., 1, m=-2] on R), a scalar the
# missing value, so a padded row is at distance 1 from everything
_MARKERS = {"l": (-2.0, 1.0), "r": (1.0, -2.0)}
_SCALAR_FILL = {"l": 1e9, "r": -1e9}


def shard_rows(n_l: int, l_shards: int, tl: int) -> tuple:
    """``(stride, rows_pad)`` of the L layout split over ``l_shards``:
    shard ``s`` holds global rows ``[s * stride, min((s + 1) * stride,
    n_l))``, the last shard possibly fewer, padded to ``rows_pad`` (a
    multiple of ``tl``) with missing rows.  One shard is today's end
    padding: ``rows_pad = ceil(n_l / tl) * tl``."""
    stride = -(-n_l // l_shards)
    return stride, -(-stride // tl) * tl


def _row_bounds(n_l: int, l_shards: int, stride: int) -> tuple:
    """Each shard's first global row and its count of real rows."""
    offsets = np.arange(l_shards, dtype=np.int64) * stride
    return offsets, np.clip(n_l - offsets, 0, stride)


def _host_embed(x: np.ndarray, rows: int, d_pad: int, side: str):
    out = np.zeros((rows, d_pad), np.float32)
    n, d = x.shape
    out[:n, :d] = x
    out[n:, d - 2], out[n:, d - 1] = _MARKERS[side]
    return out


def _host_scalar(x: np.ndarray, rows: int, side: str):
    out = np.full((rows,), _SCALAR_FILL[side], np.float32)
    out[:x.shape[0]] = x
    return out


def pack_features(feats: Sequence, clauses: Sequence, *, tl: int, tr: int,
                  lane: int = 128, l_shards: int = 1):
    """Returns (emb_l, emb_r, scal_l, scal_r, kclauses, n_l, n_r).

    L is laid out as ``l_shards`` shards of ``shard_rows`` rows each,
    padded per shard to a multiple of ``tl``; R is padded at its end to a
    multiple of ``tr``.  Padded rows are marked missing (distance 1 to
    everything) so they can never produce spurious matches.
    """
    kclauses, vec_ids, scal_ids = _clause_layout(feats, clauses)
    used = sorted({f for c in clauses for f in c})
    n_l = feats[used[0]].data_l.shape[0]
    n_r = feats[used[0]].data_r.shape[0]
    stride, rows_pad = shard_rows(n_l, l_shards, tl)
    offsets, _ = _row_bounds(n_l, l_shards, stride)
    pl_n = l_shards * rows_pad
    pr_n = -(-n_r // tr) * tr
    d_pad = _embed_width(feats, vec_ids, lane)

    def side_l(x, one):
        return np.concatenate([one(x[o:o + stride], rows_pad, side="l")
                               for o in offsets])

    if vec_ids:
        emb_l = np.stack([side_l(feats[f].data_l, functools.partial(
            _host_embed, d_pad=d_pad)) for f in vec_ids])
        emb_r = np.stack([_host_embed(feats[f].data_r, pr_n, d_pad, side="r")
                          for f in vec_ids])
    else:
        emb_l = np.zeros((1, pl_n, d_pad), np.float32)
        emb_r = np.zeros((1, pr_n, d_pad), np.float32)
    if scal_ids:
        scal_l = np.stack([side_l(feats[f].data_l.astype(np.float32),
                                  _host_scalar) for f in scal_ids])
        scal_r = np.stack([_host_scalar(feats[f].data_r.astype(np.float32),
                                        pr_n, "r") for f in scal_ids])
    else:
        scal_l = np.full((1, pl_n), _SCALAR_FILL["l"], np.float32)
        scal_r = np.full((1, pr_n), _SCALAR_FILL["r"], np.float32)
    return emb_l, emb_r, scal_l, scal_r, kclauses, n_l, n_r


def _embed_width(feats: Sequence, vec_ids: list, lane: int) -> int:
    d_max = max([feats[f].data_l.shape[1] for f in vec_ids], default=lane)
    return -(-d_max // lane) * lane


def _clause_layout(feats: Sequence, clauses: Sequence):
    """Kernel-facing clause structure shared by host and device packing:
    (kclauses, vec_ids, scal_ids) with featurization indices remapped into
    the packed embed/scalar stacks."""
    used = sorted({f for c in clauses for f in c})
    vec_ids = [f for f in used if feats[f].kind == "embed"]
    scal_ids = [f for f in used if feats[f].kind == "scalar"]
    vmap = {f: i for i, f in enumerate(vec_ids)}
    smap = {f: i for i, f in enumerate(scal_ids)}
    kclauses = tuple(
        tuple((VEC, vmap[f]) if feats[f].kind == "embed" else (SCAL, smap[f])
              for f in c)
        for c in clauses)
    return kclauses, vec_ids, scal_ids


# rows of every embed plane one step of the staging loop copies.  A
# resident plane comes in the layout XLA gives a (rows, width) output,
# which on the TPU may keep its rows minor (fewer pad lanes), while the
# kernel reads rows major; copying in blocks of this many rows keeps that
# transposition in scoped memory, where a whole-plane copy would take an
# HBM temporary the size of the shard beside its output.
_STAGE_BLOCK = 2048


def _embed_stack(xs, rows: int, real, d_pad: int, side: str):
    """The embed planes ``xs`` stacked, each's first ``real`` rows kept and
    padded to ``rows`` x ``d_pad``: a padded row carries ``side``'s missing
    markers in the last two columns of its plane's own width, every other
    padded value is 0.  The result starts as marker rows and the planes'
    rows are copied in, ``_STAGE_BLOCK`` at a time, in place; the last
    block starts early enough to end at the planes' last row, so some rows
    are written twice, with the same values."""
    n = xs[0].shape[0]
    marker = np.zeros((len(xs), 1, d_pad), np.float32)
    for f, x in enumerate(xs):
        marker[f, 0, x.shape[1] - 2:x.shape[1]] = _MARKERS[side]
    marker = jnp.asarray(marker)
    b = min(_STAGE_BLOCK, n)

    def step(i, out):
        r0 = jnp.minimum(i * b, n - b)
        blk = jnp.stack([jnp.pad(lax.dynamic_slice_in_dim(x, r0, b),
                                 ((0, 0), (0, d_pad - x.shape[1])))
                         for x in xs])
        r = r0 + lax.broadcasted_iota(jnp.int32, blk.shape, 1)
        blk = jnp.where(r < real, blk, jnp.broadcast_to(marker, blk.shape))
        return lax.dynamic_update_slice(out, blk, (0, r0, 0))

    out = jnp.broadcast_to(marker, (len(xs), rows, d_pad))
    return lax.fori_loop(0, -(-n // b), step, out)


def _scalar_stack(xs, rows: int, real, side: str):
    x = jnp.stack(xs)
    x = jnp.pad(x, ((0, 0), (0, rows - x.shape[1])))
    r = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(r < real, x, jnp.asarray(_SCALAR_FILL[side], x.dtype))


@functools.lru_cache(maxsize=32)
def _stage_program(mesh, l_axes: tuple, n_l: int, stride: int,
                   rows_pad: int, n_r: int, pr_n: int, d_pad: int):
    """The jitted program that writes the kernel layout: each device builds
    its own L shard from the resident rows it holds (padded per shard to
    ``rows_pad`` rows and to ``d_pad`` lanes, the features stacked) and the
    whole replicated R (padded at its end to ``pr_n``), into its outputs
    with no temporary the size of a shard (``_embed_stack``); L moves no
    byte between devices.  Without a mesh it is the one-shard program on
    the arrays' own device."""

    def fdj_stage_shards(vec_l, scal_l, vec_r, scal_r):
        with jax.named_scope("fdj_stage"):
            shard = jnp.int32(0)
            for ax in l_axes if mesh is not None else ():
                shard = shard * mesh.shape[ax] + lax.axis_index(ax)
            real = jnp.clip(n_l - shard * stride, 0, stride)
            return (
                _embed_stack(vec_l, rows_pad, real, d_pad, "l") if vec_l
                else jnp.zeros((1, rows_pad, d_pad), jnp.float32),
                _embed_stack(vec_r, pr_n, n_r, d_pad, "r") if vec_r
                else jnp.zeros((1, pr_n, d_pad), jnp.float32),
                _scalar_stack(scal_l, rows_pad, real, "l") if scal_l
                else jnp.full((1, rows_pad), _SCALAR_FILL["l"], jnp.float32),
                _scalar_stack(scal_r, pr_n, n_r, "r") if scal_r
                else jnp.full((1, pr_n), _SCALAR_FILL["r"], jnp.float32))

    if mesh is None:
        return jax.jit(fdj_stage_shards)
    from jax.sharding import PartitionSpec as P
    row = _row_spec(l_axes)
    fn = jax.shard_map(
        fdj_stage_shards, mesh=mesh,
        in_specs=(P(row, None), P(row), P(), P()),
        out_specs=(P(None, row, None), P(), P(None, row), P()),
        check_vma=False)   # the copy loop's carry starts unsharded
    return jax.jit(fn)


def _row_spec(l_axes: tuple):
    return l_axes[0] if len(l_axes) == 1 else tuple(l_axes)


def _on_mesh(x, sharding, rows: int):
    """``x`` laid out as ``sharding``, and the bytes that moved: none when
    it already is (any placement on a one-device mesh is); otherwise ``x``
    padded to ``rows`` rows on its own device and put there."""
    if sharding is None or x.sharding.is_equivalent_to(sharding, x.ndim):
        return x, 0
    if x.shape[0] < rows:
        x = jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))
    return jax.device_put(x, sharding), int(x.nbytes)


def pack_features_device(planes, clauses: Sequence, *, tl: int, tr: int,
                         lane: int = 128):
    """``pack_features`` assembled on device from resident per-feature
    arrays (serving.planes.DevicePlaneSet) — zero host->device plane bytes.

    Writes the identical values as the host path (padding is constant
    writes, no arithmetic), so kernel outputs are bit-identical whichever
    path staged the planes.  Assemblies are memoized on the plane set
    (``_pack_device``).
    """
    s = _pack_device(planes, clauses, tl=tl, tr=tr, lane=lane)
    return s.arrays + (s.kclauses, s.n_l, s.n_r)


def _pack_device(planes, clauses: Sequence, *, tl: int, tr: int, lane: int,
                 mesh=None, l_axes: tuple = ("data",)) -> "StagedPlanes":
    """The kernel layout of a resident plane set, built on the devices that
    hold it by one program (``_stage_program``, jitted as
    ``fdj_stage_shards``): on a mesh, each device writes its own L shard,
    ``shard_rows`` rows padded to a multiple of ``tl``, from the rows it
    holds, and R whole; no whole-side concatenate, pad or stack, and no
    temporary the size of a shard.  An L plane not already split by rows
    over ``l_axes`` is first put so (``bytes_reshard``), an R plane not
    already whole on every device likewise.

    The result is memoized on the plane set's ``pack_cache`` per (features,
    geometry, mesh, axes): a query that hands over the same plane set again
    stages nothing (``pack_hit``); a new plane set, even over the same
    resident arrays, stages again.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    kclauses, vec_ids, scal_ids = _clause_layout(planes, clauses)
    used = sorted({f for c in clauses for f in c})
    n_l = planes[used[0]].data_l.shape[0]
    n_r = planes[used[0]].data_r.shape[0]
    l_shards = _l_shards(mesh, l_axes)
    stride, rows_pad = shard_rows(n_l, l_shards, tl)
    pr_n = -(-n_r // tr) * tr
    d_pad = _embed_width(planes, vec_ids, lane)
    offsets, counts = _row_bounds(n_l, l_shards, stride)
    layout = dict(kclauses=kclauses, n_l=n_l, n_r=n_r, row_offsets=offsets,
                  row_counts=counts)

    cache = getattr(planes, "pack_cache", None)
    key = (tuple(used), rows_pad, pr_n, d_pad, mesh, tuple(l_axes))
    if cache is not None and key in cache:
        *arrays, row0 = cache[key]
        return StagedPlanes(*arrays, row0=row0, pack_hit=True, **layout)

    def put(xs, spec, rows):
        sh = None if mesh is None else NamedSharding(mesh, spec)
        out = [_on_mesh(x, sh, rows) for x in xs]
        return tuple(x for x, _ in out), sum(m for _, m in out)

    row = _row_spec(tuple(l_axes))
    rows_l = l_shards * stride
    vec_l, m0 = put([planes.device_l(f) for f in vec_ids], P(row, None),
                    rows_l)
    scal_l, m1 = put([planes.device_l(f) for f in scal_ids], P(row), rows_l)
    vec_r, m2 = put([planes.device_r(f) for f in vec_ids], P(), 0)
    scal_r, m3 = put([planes.device_r(f) for f in scal_ids], P(), 0)
    program = _stage_program(mesh, tuple(l_axes), n_l, stride, rows_pad,
                             n_r, pr_n, d_pad)
    arrays = tuple(program(vec_l, scal_l, vec_r, scal_r))
    row0 = _row_starts(offsets, mesh, row)
    if cache is not None:
        cache[key] = arrays + (row0,)
    return StagedPlanes(*arrays, row0=row0, bytes_reshard=m0 + m1 + m2 + m3,
                        **_staged_bytes(arrays), shards=l_shards, **layout)


def _l_shards(mesh, l_axes: tuple) -> int:
    if mesh is None:
        return 1
    return int(np.prod([mesh.shape[a] for a in l_axes]))


def _row_starts(offsets: np.ndarray, mesh, row):
    """Each shard's first global row as an int32 array split by rows over
    the mesh (one value a shard): the band step's row offset."""
    if mesh is None:
        return None
    return _row_starts_on(tuple(int(o) for o in offsets), mesh, row)


@functools.lru_cache(maxsize=32)
def _row_starts_on(offsets: tuple, mesh, row):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.device_put(np.asarray(offsets, np.int32),
                          NamedSharding(mesh, P(row)))


def _staged_bytes(arrays) -> dict:
    """``bytes_staged`` (the arrays' global bytes) and
    ``bytes_staged_device`` (the bytes one device holds of them)."""
    return {
        "bytes_staged": sum(int(a.nbytes) for a in arrays),
        "bytes_staged_device": sum(
            int(np.prod(a.sharding.shard_shape(a.shape))) * a.dtype.itemsize
            for a in arrays)}


@dataclasses.dataclass(eq=False)
class StagedPlanes:
    """Device-staged kernel inputs, their row layout, and the transfer
    accounting for how they got there.

    Layout: ``emb_l`` / ``scal_l`` hold L as one shard per L-shard of the
    mesh, each padded to a tile multiple (``shard_rows``); shard ``s``
    holds global rows ``row_offsets[s]`` onwards, ``row_counts[s]`` of
    them real.  ``row0`` is ``row_offsets`` on the device, one value a
    shard (None without a mesh).  R is whole, padded at its end.

    Accounting: ``bytes_h2d``: host link; ``bytes_reshard``: device-to-
    device moves to lay planes out on a mesh — the quantity warm sharded
    serving queries must report as zero, DESIGN.md §4; ``bytes_staged``:
    the staged arrays this call built, on the device from resident planes
    or packed on the host and uploaded; ``bytes_staged_device``: the most
    of them one device holds; ``shards``: the L shards built.  All three
    are 0 when ``pack_hit``, the plane set's ``pack_cache`` already holding
    the arrays."""
    emb_l: object
    emb_r: object
    scal_l: object
    scal_r: object
    kclauses: tuple
    n_l: int
    n_r: int
    row_offsets: np.ndarray = None
    row_counts: np.ndarray = None
    row0: object = None
    bytes_h2d: int = 0
    bytes_reshard: int = 0
    bytes_staged: int = 0
    bytes_staged_device: int = 0
    shards: int = 0
    pack_hit: bool = False

    @property
    def arrays(self) -> tuple:
        return (self.emb_l, self.emb_r, self.scal_l, self.scal_r)


def _mesh_shardings(mesh, l_axes: tuple):
    """NamedShardings for the four plane stacks under the engine's layout:
    L rows sharded over ``l_axes`` (("pod", "data") on a pod mesh), R and
    scalars-R replicated (the within-pod broadcast)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    row = _row_spec(tuple(l_axes))
    return (NamedSharding(mesh, P(None, row, None)),   # emb_l
            NamedSharding(mesh, P()),                  # emb_r (replicated)
            NamedSharding(mesh, P(None, row)),         # scal_l
            NamedSharding(mesh, P()))                  # scal_r (replicated)


def stage_planes(feats: Sequence, clauses: Sequence, *, tl: int, tr: int,
                 lane: int = 128, mesh=None,
                 l_axes: tuple = ("data",)) -> StagedPlanes:
    """Stage feature planes for the kernel, preferring device residency.

    Returns a ``StagedPlanes`` with the four arrays on device, L split into
    one tile-padded shard per L-shard of ``mesh`` (rows over ``l_axes``),
    R whole on every device; without a mesh, one shard.  ``tl`` is the
    kernel's L tile: each shard is padded to a multiple of it.

    A plane set exposing ``device_l``/``device_r``
    (serving.planes.DevicePlaneSet) is assembled on device from the
    resident arrays, each shard on its own device (``_pack_device``;
    bytes_h2d = 0), and memoized on the plane set's ``pack_cache``: a
    repeated query over the same plane set reports ``bytes_reshard``,
    ``bytes_staged`` and ``bytes_staged_device`` of 0.  A plain
    ``FeatureData`` list is packed on the host in the same layout and put
    straight onto it (bytes_h2d = packed bytes).
    """
    if hasattr(feats, "device_l") and hasattr(feats, "device_r"):
        return _pack_device(feats, clauses, tl=tl, tr=tr, lane=lane,
                            mesh=mesh, l_axes=tuple(l_axes))
    l_shards = _l_shards(mesh, tuple(l_axes))
    emb_l, emb_r, scal_l, scal_r, kclauses, n_l, n_r = pack_features(
        feats, clauses, tl=tl, tr=tr, lane=lane, l_shards=l_shards)
    h2d = emb_l.nbytes + emb_r.nbytes + scal_l.nbytes + scal_r.nbytes
    if mesh is not None:
        shardings = _mesh_shardings(mesh, tuple(l_axes))
        arrays = tuple(jax.device_put(a, sh) for a, sh in
                       zip((emb_l, emb_r, scal_l, scal_r), shardings))
    else:
        arrays = tuple(jnp.asarray(a)
                       for a in (emb_l, emb_r, scal_l, scal_r))
    offsets, counts = _row_bounds(n_l, l_shards, shard_rows(
        n_l, l_shards, tl)[0])
    return StagedPlanes(
        *arrays, kclauses, n_l, n_r, row_offsets=offsets, row_counts=counts,
        row0=_row_starts(offsets, mesh, _row_spec(tuple(l_axes))),
        bytes_h2d=h2d, shards=l_shards, **_staged_bytes(arrays))


def evaluate_corpus(feats: Sequence, clauses: Sequence, thetas,
                    *, tl: int = 256, tr: int = 512, interpret=None,
                    return_mask_bytes: bool = False):
    """Full-corpus CNF evaluation through the kernel; returns [(i, j), ...].

    With ``return_mask_bytes=True`` also returns the device->host transfer
    size of the packed mask (the quantity the sharded engine eliminates).
    """
    pairs: list = []
    mask_bytes = 0
    for delta in evaluate_corpus_stream(
            feats, clauses, thetas, tl=tl, tr=tr, l_block=None,
            interpret=interpret):
        pairs.extend(delta.pairs)
        mask_bytes += delta.bytes_to_host
    if return_mask_bytes:
        return pairs, mask_bytes
    return pairs


def evaluate_corpus_stream(feats: Sequence, clauses: Sequence, thetas,
                           *, tl: int = 256, tr: int = 512,
                           l_block=None, interpret=None,
                           early_reject: bool = True):
    """Streaming corpus driver: yields an ``engine.base.ChunkDelta`` per
    L-row block.

    Features are staged once (host pack + upload, or assembled from
    device-resident planes with zero H2D — see ``stage_planes``); the
    kernel then grids one ``l_block``-row strip at a time (``l_block`` a
    multiple of ``tl``, default one whole pass — i.e. batch semantics).
    Each strip's packed mask is pulled and unpacked immediately, so
    candidates for early rows reach the consumer while later strips are
    still on the device.  The one-time plane upload is attributed to the
    first emitted block.  ``early_reject`` enables the kernel's tile-level
    conjunct short-circuit; either way the per-tile eval counts are
    pulled with the mask and charged to the chunk (``conjunct_evals``,
    in pair-clause units over padded tiles — honest device work).
    """
    from repro.engine.base import ChunkDelta
    from repro.obs.trace import current_tracer
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    tracer = current_tracer()
    staged = stage_planes(feats, clauses, tl=tl, tr=tr)
    demb_l, demb_r, dscal_l, dscal_r = staged.arrays
    kclauses, n_l, n_r, h2d = (staged.kclauses, staged.n_l, staged.n_r,
                               staged.bytes_h2d)
    pl_n, pr_n = demb_l.shape[1], demb_r.shape[1]
    if l_block is None:
        l_block = pl_n
    if l_block % tl != 0:
        raise ValueError(f"l_block={l_block} must be a multiple of tl={tl}")
    thetas = tuple(float(t) for t in thetas)
    for i0 in range(0, pl_n, l_block):
        rows = min(l_block, pl_n - i0)
        t0 = time.perf_counter()
        packed, evals = cnf_join_block(
            lax.slice_in_dim(demb_l, i0, i0 + rows, axis=1), demb_r,
            lax.slice_in_dim(dscal_l, i0, i0 + rows, axis=1), dscal_r,
            kclauses, thetas, tl=tl, tr=tr, interpret=interpret,
            early_reject=early_reject, with_evals=True)
        t1 = time.perf_counter()
        host_mask = np.asarray(packed)              # O(rows * n_r / 8) pull
        evals_host = np.asarray(evals)              # clauses over all tiles
        t2 = time.perf_counter()
        ok = ref.unpack_mask(host_mask, pr_n)[: max(n_l - i0, 0), :n_r]
        ii, jj = np.nonzero(ok)
        # trace sub-slices only (DESIGN.md §7): the kernel call vs the
        # blocking mask pull.  Deliberately NOT named dispatch/pull — this
        # backend's EngineStats carries no dispatch/pull walls, and the
        # reconciliation in launch/trace_report sums by those names.
        trace = None
        if tracer:
            trace = [
                {"name": "kernel", "t0": t0, "t1": t1,
                 "attrs": {"rows": rows}},
                {"name": "mask_pull", "t0": t1, "t1": t2,
                 "attrs": {"bytes": host_mask.nbytes + evals_host.nbytes}},
            ]
        yield ChunkDelta(
            list(zip((ii + i0).tolist(), jj.tolist())),
            bytes_to_host=host_mask.nbytes + evals_host.nbytes,
            bytes_h2d=h2d if i0 == 0 else 0,
            conjunct_evals=int(evals_host) * tl * tr,
            trace=trace)
