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
import weakref
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


def _side_stack(vec, scal, rows: int, real, d_pad: int, side: str):
    """One side's embed and scalar stacks (``_embed_stack``,
    ``_scalar_stack``), or a stack of one missing plane where the CNF names
    no feature of that kind."""
    return (_embed_stack(vec, rows, real, d_pad, side) if vec
            else jnp.zeros((1, rows, d_pad), jnp.float32),
            _scalar_stack(scal, rows, real, side) if scal
            else jnp.full((1, rows), _SCALAR_FILL[side], jnp.float32))


def _over_mesh(fn, mesh, in_spec, out_specs):
    """``fn`` jitted, run per device of ``mesh`` on its own block of the
    arguments (each ``in_spec``), or on the arguments' own device."""
    if mesh is None:
        return jax.jit(fn)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(in_spec, in_spec), out_specs=out_specs,
        check_vma=False))   # the copy loop's carry starts unsharded


@functools.lru_cache(maxsize=32)
def _stage_l_program(mesh, l_axes: tuple, n_l: int, stride: int,
                     rows_pad: int, d_pad: int):
    """The jitted program that writes L's kernel layout: each device builds
    its own shard from the resident rows it holds, padded per shard to
    ``rows_pad`` rows and to ``d_pad`` lanes, the features stacked, into
    its outputs with no temporary the size of a shard (``_embed_stack``);
    L moves no byte between devices.  Without a mesh it is the one-shard
    program on the arrays' own device."""

    def fdj_stage_shards(vec_l, scal_l):
        with jax.named_scope("fdj_stage"):
            shard = jnp.int32(0)
            for ax in l_axes if mesh is not None else ():
                shard = shard * mesh.shape[ax] + lax.axis_index(ax)
            real = jnp.clip(n_l - shard * stride, 0, stride)
            return _side_stack(vec_l, scal_l, rows_pad, real, d_pad, "l")

    from jax.sharding import PartitionSpec as P
    row = _row_spec(l_axes)
    return _over_mesh(fdj_stage_shards, mesh, P(row),
                      (P(None, row, None), P(None, row)))


@functools.lru_cache(maxsize=32)
def _stage_r_program(mesh, n_r: int, pr_n: int, d_pad: int):
    """The jitted program that writes R's kernel layout, whole on every
    device of ``mesh``: the features stacked, padded at the end to ``pr_n``
    rows and to ``d_pad`` lanes.  Its own name, apart from L's program, so
    that a probe's per-query R staging reads as what it is in a trace."""

    def fdj_stage_batch(vec_r, scal_r):
        with jax.named_scope("fdj_stage"):
            return _side_stack(vec_r, scal_r, pr_n, n_r, d_pad, "r")

    from jax.sharding import PartitionSpec as P
    return _over_mesh(fdj_stage_batch, mesh, P(), (P(), P()))


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
    path staged the planes.  Each side's assembly is memoized on its
    resident arrays (``_pack_device``).
    """
    s = _pack_device(planes, clauses, tl=tl, tr=tr, lane=lane)
    return s.arrays + (s.kclauses, s.n_l, s.n_r)


class _SideMemo:
    """Staged kernel layouts of one side, keyed on the identity of the
    resident arrays they were copied from and on the layout's geometry.

    The arrays are held only by weak reference: when one of them is freed
    its entry goes too, so a staged layout neither outlives nor keeps alive
    the planes it copies.  Contents are never the key: new rows equal to
    rows staged before are new arrays, and are staged again.  It is one
    memo for the process because the resident arrays are shared by every
    plane set built over them, whoever builds it.  Each dict operation is
    atomic under the interpreter lock; two threads that miss together both
    stage, and the last one's layout is kept."""

    def __init__(self):
        self._entries: dict = {}

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, srcs: list, geometry: tuple):
        entry = self._entries.get((tuple(map(id, srcs)), geometry))
        if entry is None or any(r() is not x for r, x in zip(entry[0], srcs)):
            return None
        return entry[1]

    def put(self, srcs: list, geometry: tuple, staged) -> None:
        key = (tuple(map(id, srcs)), geometry)
        entries = self._entries

        def drop(_):
            entries.pop(key, None)

        entries[key] = (tuple(weakref.ref(x, drop) for x in srcs), staged)


_STAGED = _SideMemo()


def _pack_device(planes, clauses: Sequence, *, tl: int, tr: int, lane: int,
                 mesh=None, l_axes: tuple = ("data",)) -> "StagedPlanes":
    """The kernel layout of a resident plane set, built on the devices that
    hold it, one program a side: L's (``_stage_l_program``, jitted as
    ``fdj_stage_shards``) has each device of a mesh write its own L shard,
    ``shard_rows`` rows padded to a multiple of ``tl``, from the rows it
    holds; R's (``_stage_r_program``, jitted as ``fdj_stage_batch``) writes
    R whole on every device.  No whole-side concatenate, pad or stack, and
    no temporary the size of a shard.  An L plane not already split by
    rows over ``l_axes`` is first put so (``bytes_reshard``), an R plane
    not already whole on every device likewise.

    Each side's stacks, with any such reshard, are memoized on that side's
    resident arrays per geometry (``_SideMemo``): a plane set over arrays
    staged before, whichever plane set handed them over, reuses their
    layout (``l_hit``, ``r_hit``), and only the other side is staged.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    kclauses, vec_ids, scal_ids = _clause_layout(planes, clauses)
    used = sorted({f for c in clauses for f in c})
    n_l = planes[used[0]].data_l.shape[0]
    n_r = planes[used[0]].data_r.shape[0]
    l_axes = tuple(l_axes)
    l_shards = _l_shards(mesh, l_axes)
    stride, rows_pad = shard_rows(n_l, l_shards, tl)
    pr_n = -(-n_r // tr) * tr
    d_pad = _embed_width(planes, vec_ids, lane)
    offsets, counts = _row_bounds(n_l, l_shards, stride)
    n_vec, row = len(vec_ids), _row_spec(l_axes)

    def side(srcs, geometry, spec, rows, program):
        """``(stacks, hit, bytes resharded)`` of one side."""
        staged = _STAGED.get(srcs, geometry)
        if staged is not None:
            return staged, True, 0
        sh = None if mesh is None else NamedSharding(mesh, spec)
        moved = [_on_mesh(x, sh, rows) for x in srcs]
        xs = [x for x, _ in moved]
        staged = tuple(program(tuple(xs[:n_vec]), tuple(xs[n_vec:])))
        _STAGED.put(srcs, geometry, staged)
        return staged, False, sum(m for _, m in moved)

    ids = vec_ids + scal_ids
    (emb_l, scal_l), l_hit, m_l = side(
        [planes.device_l(f) for f in ids],
        ("l", n_vec, rows_pad, d_pad, mesh, l_axes), P(row), l_shards * stride,
        _stage_l_program(mesh, l_axes, n_l, stride, rows_pad, d_pad))
    (emb_r, scal_r), r_hit, m_r = side(
        [planes.device_r(f) for f in ids], ("r", n_vec, pr_n, d_pad, mesh),
        P(), 0, _stage_r_program(mesh, n_r, pr_n, d_pad))
    built = (() if l_hit else (emb_l, scal_l)) + \
        (() if r_hit else (emb_r, scal_r))
    return StagedPlanes(
        emb_l, emb_r, scal_l, scal_r, kclauses, n_l, n_r, row_offsets=offsets,
        row_counts=counts, row0=_row_starts(offsets, mesh, row),
        bytes_reshard=m_l + m_r, **_staged_bytes(built),
        shards=0 if l_hit else l_shards, l_hit=l_hit, r_hit=r_hit)


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
    of them one device holds; ``shards``: the L shards built.  A side whose
    resident arrays were staged before is reused (``l_hit``, ``r_hit``)
    and counts in none of these; ``pack_hit`` is both sides reused."""
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
    l_hit: bool = False
    r_hit: bool = False

    @property
    def pack_hit(self) -> bool:
        return self.l_hit and self.r_hit

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
    bytes_h2d = 0), each side memoized on its resident arrays: a query
    over arrays staged before, in this plane set or another, stages only
    the side that is new (a probe's batch) and reports ``bytes_reshard``,
    ``bytes_staged`` and ``bytes_staged_device`` of that side.  A plain
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
