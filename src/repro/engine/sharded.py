"""Sharded streaming backend: shard_map over the mesh L-sharding axes.

Layout (see DESIGN.md §3):

  * L rows are sharded over the mesh's L axes — ``("pod", "data")`` on a
    multi-pod mesh, ``("data",)`` otherwise: each of the
    ``l_shards = n_pods * n_data`` shards owns a contiguous block of
    ``ceil(n_l / l_shards)`` global rows (the last may own fewer), padded
    on its own device to ``rows_shard``, a multiple of ``tl``
    (``ops.shard_rows``; embedding and scalar planes sliced with
    ``P(None, ("pod", "data"), ...)``).  A shard maps its local row to
    the global id through its first row, a small int32 array split the
    same way (``StagedPlanes.row0``);
  * R is replicated (the within-pod broadcast) and *streamed*: a host
    loop walks R in ``r_chunk``-column bands.  On a pod mesh the bands
    are **round-robined across pods** — at host step ``k`` pod ``p``
    works band ``(k + p * stride) % n_chunks`` — so the P pods occupy P
    distinct column bands at any instant while every pod still covers
    every band over the full sweep (its L shard exists nowhere else, so
    it must).  Within a pod the band is split across the "model" axis:
    each (data, model) device evaluates its L rows × an
    ``r_chunk / n_model``-column sub-band.  Device-resident working
    state stays O(rows_shard · r_chunk / n_model), never O(rows_shard ·
    n_r);
  * per step the fused CNF Pallas kernel produces the packed uint32 mask
    (grid = rows_shard/tl × r_sub/tr tiles), which is immediately
    compacted on-device into a per-device (i, j) candidate buffer via
    popcount + prefix-sum (engine.extract) — the mask never leaves HBM;
  * candidate counts are prefix-summed **hierarchically**: within each
    pod first (all_gather over ("data", "model")), then across pods
    (all_gather of the per-pod totals over "pod") —
    ``extract.hierarchical_offsets``.  That cross-pod gather of int32
    totals is the *only* collective that crosses a pod boundary: pod
    interconnect carries candidate counts, never feature planes or
    masks (asserted on the (2, 16, 16) dry-run via
    ``distributed.hlo_analysis.pod_crossing_stats``);
  * the band loop runs a **depth-k prefetch ring** (``prefetch_depth``,
    default 2 ≡ the PR-5 double buffer): up to ``k`` band steps are
    dispatched (JAX async dispatch — no host sync) before the host
    blocks pulling the oldest step's counts, bases and candidate shards,
    so successor bands' kernels run while the host filters padding,
    sorts, and the consumer holds the previous chunk — deeper rings
    ride out slower/burstier host pulls.  Per chunk the host pulls one
    int32 count, one int32 global base and one int32 conjunct-eval
    counter per device, then copies each non-empty device's whole
    ``(cap, 2)`` candidate buffer and keeps its first ``count`` rows:
    O(capacity) bytes per non-empty shard on the link (the ``fetch``
    span's ``bytes_moved``), O(candidates) kept (``bytes_to_host``), and
    the first candidates surface after one scan step.  Batch ``evaluate`` is a
    drain of this same stream.  ``prefetch_depth=1`` (≡ the legacy
    ``double_buffer=False``) is the serial A/B control — the ring holds
    nothing while the host pulls or the consumer holds, so its
    ``overlap_s`` is exactly 0 *and* every dispatch wall lands in its
    own chunk's ``dispatch_wall_s`` (no post-yield tail dispatch
    leaking into the consumer's hold window).  Overlap is accounted,
    not assumed: per-chunk ``dispatch_wall_s`` / ``pull_wall_s`` and an
    ``overlap_s`` that is exactly 0 when the loop degrades to serial
    (``benchmarks/run.py`` gates it against the committed baselines);
  * CNF evaluation **short-circuits** (``early_reject``, default on):
    the kernel evaluates the first conjunct unconditionally and runs
    the rest only where the first passed somewhere in the tile — a band
    whose first-conjunct popcount is zero costs 1 clause, not C (the
    jnp reference path makes the same skip per sub-band via
    ``lax.cond``).  The candidate set is identical either way; the work
    actually done is pulled per step as an int32 eval counter and
    surfaced as ``EngineStats.conjunct_evals``, so the win is measured,
    never assumed.  Conjunct *ordering* (most selective first, measured
    on the plan's threshold sample) happens upstream in core.join —
    the engine evaluates whatever clause order it is handed.

Each step is L-complete (all shards' row blocks × one band per pod), so
steps partition the candidate set — disjoint by construction, sorted
within the chunk by ``base.evaluate_stream``.

Capacity is bounded-and-retried, never silently truncated: the on-device
count keeps growing past the buffer; overflow is detected per (pod,
data, model) shard and the host reruns *that step* — invalidating and
re-dispatching **all** in-flight successor steps at the grown capacity,
so a retry can never emit a chunk computed at a stale buffer size no
matter how deep the ring was.  Capacities are
carried **per shard** across the steps of one sweep (``extract.
grow_caps``: only the overflowing shard grows ≥4×; the uniform SPMD
dispatch buffer is the per-shard max), and they are *sweep-local*: a
dense join grows buffers for its own remaining steps, never for later
evaluations through a shared (serving) engine — ``self.capacity`` is
construction-time config and is never mutated (the last sweep's final
sizes are exposed as ``last_sweep_caps`` / ``last_sweep_capacity`` for
tests and diagnostics).  Padded rows and columns carry the missing
markers, so they yield no candidate below a threshold of 1; the host
still drops any pair outside its shard's real rows or past ``n_r`` —
O(candidates) work.

The engine itself is reusable across stores and meshes: the evaluation
mesh is resolved per call (a mesh passed at construction wins; otherwise
the plane set's attached mesh, else the shared host mesh) and never
pinned on the instance.

On CPU the kernel runs in interpret mode on a 1-device "data" mesh, so
the same code path is exercised by tests; on a pod the identical program
lowers onto the (16, 16) / (2, 16, 16) production meshes from
``distributed.mesh`` (``make_join_mesh``).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
import weakref
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.engine import extract
from repro.engine.base import ChunkDelta, CnfEngine
from repro.obs.trace import current_tracer


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unpulled band step of the prefetch ring."""
    k: int                             # host step index
    cap: int                           # per-device buffer rows it was built at
    buf: object                        # device arrays (futures until pulled)
    cnt: object
    base: object
    evals: object                      # per-device int32 conjunct-eval units
    t_enq: float = 0.0                 # perf_counter at enqueue (trace)
    events: list = dataclasses.field(default_factory=list)  # trace instants


_HOST_MESH = None                      # shared default mesh: stable cache key
_HOST_MESH_LOCK = threading.Lock()     # fleet: engines resolve it concurrently


def _default_mesh():
    global _HOST_MESH
    with _HOST_MESH_LOCK:
        if _HOST_MESH is None:
            from repro.distributed.mesh import make_host_mesh
            _HOST_MESH = make_host_mesh()
        return _HOST_MESH


def _mesh_geometry(mesh):
    """(l_axes, n_pods, n_data, n_model) for any engine-usable mesh."""
    from repro.distributed.mesh import l_shard_axes
    names = mesh.axis_names
    if "data" not in names:
        raise ValueError(f"mesh {names} has no 'data' axis")
    n_pods = mesh.shape.get("pod", 1) if "pod" in names else 1
    n_model = mesh.shape.get("model", 1) if "model" in names else 1
    return l_shard_axes(mesh), n_pods, mesh.shape["data"], n_model


class ShardedEngine(CnfEngine):
    name = "sharded"

    def __init__(self, mesh=None, *, tl: int = 128, tr: int = 128,
                 r_chunk: Optional[int] = None, capacity: Optional[int] = None,
                 interpret: Optional[bool] = None, use_kernel: bool = True,
                 double_buffer: bool = True,
                 prefetch_depth: Optional[int] = None,
                 early_reject: bool = True,
                 scheduler=None):
        """mesh: any mesh with a "data" axis and optional "pod" / "model"
        axes.  When None, the mesh is resolved *per evaluation* — the
        plane set's attached mesh, else make_host_mesh() — so one engine
        can serve stores on different meshes; only a mesh passed here is
        honored across evaluations.  tl/tr: kernel tile edges
        (tr % 32 == 0).  r_chunk: R stream band (multiple of n_model*tr;
        default 4*tr*n_model).  capacity: initial per-device per-step
        candidate buffer (default heuristic); overflow grows a per-shard
        working copy >=4x within the sweep, never this config value.
        use_kernel=False swaps the Pallas kernel for the jnp reference —
        identical math, faster under CPU emulation (and the default-
        sensible choice for many-device dry-run meshes).
        prefetch_depth: how many band steps may be in flight at once
        (the ring; default 2 ≡ the classic double buffer, 1 = serial).
        double_buffer=False is the legacy spelling of prefetch_depth=1
        (an explicit prefetch_depth wins).  early_reject=False disables
        the conjunct short-circuit — full-width CNF on every band, the
        A/B control the conjunct_evals gate compares against.
        scheduler: the cross-query band-step gate (serving/fleet.py
        ``BandScheduler``).  When set, every band-step *enqueue* runs
        under ``scheduler.step()`` — a fleet running several queries on
        one mesh interleaves their band steps in admission order instead
        of letting one query's whole sweep monopolize the device queue.
        Only dispatch is gated; pulls/filtering proceed ungated, so one
        query's host work overlaps another's device compute."""
        if tr % 32 != 0:
            raise ValueError(f"tr={tr} must be a multiple of 32 (packed mask)")
        self.mesh = mesh
        self.tl = int(tl)
        self.tr = int(tr)
        self.r_chunk = int(r_chunk) if r_chunk else None
        if self.r_chunk and self.r_chunk % self.tr != 0:
            # necessary on any mesh; the full tr*n_model divisibility is
            # checked once the mesh (and its model-axis width) is known
            raise ValueError(
                f"r_chunk={self.r_chunk} must be a multiple of tr={tr}")
        self.capacity = capacity
        self.interpret = interpret
        self.use_kernel = use_kernel
        self.double_buffer = bool(double_buffer)
        if prefetch_depth is not None and int(prefetch_depth) < 1:
            raise ValueError(
                f"prefetch_depth={prefetch_depth} must be >= 1 (1 = serial)")
        self.prefetch_depth = int(prefetch_depth) if prefetch_depth else None
        self.early_reject = bool(early_reject)
        self.scheduler = scheduler
        # diagnostics only (tests, the dry-run report): the per-shard
        # capacities the most recent sweep ended at.  Not config — the
        # next evaluation starts from ``self.capacity`` again.
        self.last_sweep_caps: Optional[np.ndarray] = None

    @property
    def effective_prefetch_depth(self) -> int:
        """The ring depth evaluations run at: an explicit ``prefetch_depth``
        wins; otherwise 2 (double buffer) or 1 (``double_buffer=False``)."""
        if self.prefetch_depth is not None:
            return self.prefetch_depth
        return 2 if self.double_buffer else 1

    @property
    def last_sweep_capacity(self) -> int:
        """Max per-shard capacity the most recent sweep ended at (0 if the
        engine has not evaluated yet)."""
        if self.last_sweep_caps is None:
            return 0
        return int(self.last_sweep_caps.max())

    # class-level: engines are often constructed per join (get_engine in
    # core/join.py), so an instance cache would always be cold.  Bounded:
    # thetas are continuous per-join values, so keys rarely repeat across
    # joins and an unbounded dict would leak compiled programs for the
    # process lifetime.
    _programs: dict = {}               # build key -> jitted shard_map program
    _PROGRAM_CACHE_MAX = 32
    # fleet: concurrent queries dispatch through per-query engines that all
    # share this class-level cache; the lock covers lookup + LRU reorder +
    # insert (held through a cold compile too, so two threads racing the
    # same key compile once, not twice)
    _programs_lock = threading.Lock()
    # each cached program's first-call argument shapes and shardings: a
    # program not in it has not run yet (its next call compiles it), and
    # its compiled text can be read again (band_step_hlo).  Entries go
    # with their program.
    _first_args = weakref.WeakKeyDictionary()

    @classmethod
    def band_step_hlo(cls) -> list:
        """The optimized HLO text of every cached band-step program that
        has run, compiled again at its first call's shapes and shardings
        (a persistent-cache hit where JAX's compile cache is on).  Its
        instruction names are those of a device trace's op events, and
        their ``op_name`` metadata carries the body's named scopes
        (``fdj_kernel`` / ``fdj_extract`` / ``fdj_offsets``)."""
        with cls._programs_lock:
            runs = [(fn, cls._first_args.get(fn))
                    for fn in cls._programs.values()]
        return [fn.lower(*specs).compile().as_text()
                for fn, specs in runs if specs is not None]

    def _resolve_r_chunk(self, n_model: int) -> int:
        r_chunk = self.r_chunk if self.r_chunk else 4 * self.tr * n_model
        if r_chunk % (self.tr * n_model) != 0:
            raise ValueError(
                f"r_chunk={r_chunk} must be a multiple of tr*n_model="
                f"{self.tr * n_model} (each of the {n_model} model-axis "
                f"devices kernels a whole-tile sub-band)")
        return r_chunk

    # -- device program -----------------------------------------------------

    def _build(self, mesh, kclauses, thetas, rows_shard, cap, r_chunk,
               n_chunks):
        # jax.jit caches on function identity; without memoizing here every
        # chunk step would re-trace and re-compile an identical program.
        # The key carries every value the closure bakes in (the step index
        # is a traced argument, so one program serves the whole R sweep;
        # n_chunks is baked into the per-pod band rotation).
        interpret = self.interpret
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        key = (mesh, kclauses, thetas, rows_shard, cap, r_chunk, n_chunks,
               self.tl, self.tr, self.use_kernel, interpret,
               self.early_reject)
        with ShardedEngine._programs_lock:
            cached = ShardedEngine._programs.get(key)
            if cached is not None:
                # LRU, not FIFO: re-insert on hit so eviction tracks recency —
                # a hot serving program must survive any number of one-off
                # joins churning the other slots (dict preserves insert order)
                ShardedEngine._programs.pop(key)
                ShardedEngine._programs[key] = cached
                return cached
            fn = self._build_uncached(mesh, kclauses, thetas, rows_shard, cap,
                                      r_chunk, n_chunks, interpret)
            while len(ShardedEngine._programs) >= self._PROGRAM_CACHE_MAX:
                ShardedEngine._programs.pop(
                    next(iter(ShardedEngine._programs)))
            ShardedEngine._programs[key] = fn
            return fn

    def _build_uncached(self, mesh, kclauses, thetas, rows_shard, cap,
                        r_chunk, n_chunks, interpret):
        from repro.kernels.fused_cnf_join import ref as cref
        from repro.kernels.fused_cnf_join.kernel import cnf_join_block
        tl, tr = self.tl, self.tr
        use_kernel = self.use_kernel
        early_reject = self.early_reject
        l_axes, n_pods, n_data, n_model = _mesh_geometry(mesh)
        has_pod = len(l_axes) == 2
        has_model = "model" in mesh.axis_names
        r_sub = r_chunk // n_model
        # pods enter the band rotation evenly spread across the R extent
        stride = max(1, n_chunks // n_pods)
        inner_axes = ("data", "model") if has_model else ("data",)

        def body(emb_l, emb_r, scal_l, scal_r, row0, k):
            pod = lax.axis_index("pod") if has_pod else jnp.int32(0)
            data = lax.axis_index("data")
            model = lax.axis_index("model") if has_model else jnp.int32(0)
            row0 = row0[0]             # this shard's first global row
            band = (k + pod * stride) % n_chunks
            col0 = band * r_chunk + model * r_sub
            erk = lax.dynamic_slice_in_dim(emb_r, col0, r_sub, axis=1)
            srk = lax.dynamic_slice_in_dim(scal_r, col0, r_sub, axis=1)
            # evals: conjunct-eval units this device really computed —
            # kernel path: clauses per tile, summed over the tile grid
            # (unit = tl*tr pairs); ref path: clauses for the whole
            # sub-band (unit = rows_shard*r_sub pairs).  Device-local
            # (no collective): the host pulls one int32 per device,
            # alongside the counts, and converts units to pair-clause
            # evals.
            # the named scopes label each part's operations in a device
            # trace (their HLO op_name metadata)
            with jax.named_scope("fdj_kernel"):
                if use_kernel:
                    packed, evals = cnf_join_block(
                        emb_l, erk, scal_l, srk, kclauses, thetas, tl=tl,
                        tr=tr, interpret=interpret,
                        early_reject=early_reject, with_evals=True)
                else:
                    ok, evals = cref.cnf_join_ref_counted(
                        emb_l, erk, scal_l, srk, kclauses, thetas,
                        early_reject=early_reject)
                    packed = cref.pack_mask(ok)
            with jax.named_scope("fdj_extract"):
                buf, cnt = extract.extract_pairs(packed, capacity=cap,
                                                 row_offset=row0,
                                                 col_offset=col0)
            with jax.named_scope("fdj_offsets"):
                base, _ = extract.hierarchical_offsets(
                    cnt, inner_axes=inner_axes,
                    inner_index=data * n_model + model,
                    pod_axis="pod" if has_pod else None)
            return buf, cnt[None], base[None], evals[None]

        row_spec = l_axes[0] if len(l_axes) == 1 else l_axes
        dev_axes = l_axes + (("model",) if has_model else ())
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, row_spec, None), P(None, None, None),
                      P(None, row_spec), P(None, None), P(row_spec), P()),
            out_specs=(P(dev_axes, None), P(dev_axes), P(dev_axes),
                       P(dev_axes)),
            check_vma=False)   # pallas_call has no replication rule
        return jax.jit(fn)

    # -- evaluation ---------------------------------------------------------

    def _resolve_mesh(self, feats):
        """The evaluation mesh for this call — resolved fresh every time.

        A mesh passed at construction always wins; otherwise a serving
        plane set carries its store's mesh (pre-sharded residency,
        DESIGN.md §4), else the shared host mesh.  Never cached on the
        instance: an engine reused across stores/joins with different
        meshes must not keep the first plane set's mesh."""
        return self.mesh or getattr(feats, "mesh", None) or _default_mesh()

    def _evaluate_stream(self, feats, clauses, thetas, n_l, n_r):
        from repro.kernels.fused_cnf_join import ops as cnf_ops

        mesh = self._resolve_mesh(feats)
        l_axes, n_pods, n_data, n_model = _mesh_geometry(mesh)
        l_shards = n_pods * n_data
        n_dev = l_shards * n_model
        r_chunk = self._resolve_r_chunk(n_model)

        # pad each L shard to a multiple of tl (tile-aligned rows, on its
        # own device) and R to a multiple of r_chunk (whole stream steps).
        # stage_planes uploads a host pack once directly onto the mesh
        # layout — or assembles on device from a resident plane set
        # (serving store) with zero H2D.  The assembly and any D2D reshard
        # are memoized on that plane set: a query that hands over the same
        # plane set again stages nothing, one with a new plane set (even
        # over the same resident arrays) assembles again (bytes_staged).
        tracer = current_tracer()
        t_stage0 = time.perf_counter()
        with tracer.annotate("stage_planes"):
            staged = cnf_ops.stage_planes(feats, clauses, tl=self.tl,
                                          tr=r_chunk, mesh=mesh,
                                          l_axes=l_axes)
        if tracer:
            tracer.record_span(
                "stage_planes", t_stage0, time.perf_counter(),
                attrs={"bytes_h2d": staged.bytes_h2d,
                       "bytes_reshard": staged.bytes_reshard,
                       "bytes_staged": staged.bytes_staged,
                       "bytes_staged_device": staged.bytes_staged_device,
                       "shards": staged.shards,
                       "pack_hit": staged.pack_hit})
        kclauses = staged.kclauses
        pl_n, pr_n = staged.emb_l.shape[1], staged.emb_r.shape[1]
        rows_shard = pl_n // l_shards
        n_chunks = pr_n // r_chunk
        args = staged.arrays + (staged.row0,)
        # the end of each shard's real rows, by device of the candidate
        # buffers (shard-major, model-minor)
        row_end = np.repeat(staged.row_offsets + staged.row_counts, n_model)
        thetas = tuple(float(t) for t in thetas)

        # per-(pod, data, model)-shard capacities, local to THIS sweep:
        # growth persists across the sweep's remaining steps but never
        # mutates the engine — a shared serving engine that once hit a
        # dense join must not over-allocate every later query.
        caps = np.full(n_dev, self.capacity or max(4096, 4 * rows_shard),
                       np.int64)
        timing = {"dispatch": 0.0, "built": 0}
        # host conversion factor from device eval *units* to (pair,
        # clause) evaluations: the kernel counts per tile, the jnp
        # reference per whole sub-band (see body)
        unit_pairs = (self.tl * self.tr if self.use_kernel
                      else rows_shard * (r_chunk // n_model))

        sched = self.scheduler

        def dispatch(k) -> _InFlight:
            """Enqueue band step k at the current uniform capacity (JAX
            async dispatch: returns futures, no host sync).  Under a fleet
            scheduler the enqueue itself is the scheduling point: steps
            from concurrent queries take turns in ticket order."""
            cap = int(caps.max())
            t0 = time.perf_counter()
            with sched.step() if sched is not None \
                    else contextlib.nullcontext():
                fn = self._build(mesh, kclauses, thetas, rows_shard, cap,
                                 r_chunk, n_chunks)
                first = fn not in ShardedEngine._first_args
                # a program's first call traces and compiles it
                with tracer.annotate("compile" if first else "enqueue"):
                    buf, cnt, base, evals = fn(*args, jnp.int32(k))
                if first:
                    with ShardedEngine._programs_lock:
                        ShardedEngine._first_args[fn] = tuple(
                            jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                 sharding=a.sharding)
                            for a in args) + (
                                jax.ShapeDtypeStruct((), jnp.int32),)
            timing["dispatch"] += time.perf_counter() - t0
            timing["built"] += first
            return _InFlight(k, cap, buf, cnt, base, evals, t_enq=t0)

        def pull_counts(step, children):
            """Block on step's counts + eval units; returns (counts,
            pair-clause evals, bytes pulled)."""
            t0 = time.perf_counter()
            with tracer.annotate("wait_counts"):
                counts = np.asarray(jax.device_get(step.cnt))
                ev = np.asarray(jax.device_get(step.evals))
            if tracer:
                children.append({"name": "wait_counts", "t0": t0,
                                 "t1": time.perf_counter()})
            return counts, int(ev.sum()) * unit_pairs, counts.nbytes + ev.nbytes

        def fetch(step, counts):
            """The step's global bases (checked against the counts) and
            each device's candidate rows, host-side; returns (the non-empty
            shards' rows in device order, bytes kept, bytes copied)."""
            bases = np.asarray(jax.device_get(step.base))
            expect = np.cumsum(counts) - counts
            if not np.array_equal(bases, expect):
                raise RuntimeError(
                    "hierarchical candidate-count prefix-sum disagrees with "
                    f"host bookkeeping: device bases {bases.tolist()} vs "
                    f"expected {expect.tolist()}")
            # each device's first `count` buffer rows, straight off its
            # shard (no jit dispatch: a jnp slice of the global array would
            # compile one distributed program per (device, count) pair —
            # minutes of churn on a 512-device dry-run mesh).  The copy
            # moves the shard's whole (cap, 2) buffer and keeps the first
            # `count` rows: O(capacity) bytes a non-empty shard.
            out, kept, moved = [], bases.nbytes, bases.nbytes
            for sh in step.buf.addressable_shards:
                d = (sh.index[0].start or 0) // step.cap
                take = int(counts[d])
                if not take:
                    continue
                whole = np.asarray(sh.data)
                kept += whole[:take].nbytes
                moved += whole.nbytes
                out.append((d, whole[:take]))
            return sorted(out, key=lambda t: t[0]), kept, moved

        def to_pairs(segs) -> list:
            """Concatenated candidate rows as tuples, dropping any row past
            its shard's real rows and any column past ``n_r``."""
            if not segs:
                return []
            arr = np.concatenate([seg[(seg[:, 0] < row_end[d])
                                      & (seg[:, 1] < n_r)]
                                  for d, seg in segs], axis=0)
            return list(zip(arr[:, 0].tolist(), arr[:, 1].tolist()))

        depth = self.effective_prefetch_depth
        ring: collections.deque = collections.deque()   # oldest first
        next_k = 0
        hold_overlap = 0.0             # consumer hold with a step in flight
        while ring or next_k < n_chunks:
            # keep up to `depth` steps in flight: refill BEFORE blocking on
            # the oldest step's pull, so successor bands compute while the
            # host pulls/filters and the consumer holds the chunk.  At
            # depth 1 this is the serial loop — the ring is empty during
            # the pull and the hold, and each step's dispatch wall lands
            # in its own chunk (no post-yield tail dispatch).
            while len(ring) < depth and next_k < n_chunks:
                ring.append(dispatch(next_k))
                next_k += 1
            step = ring.popleft()
            k = step.k
            t_enq = step.t_enq         # first enqueue: the in-flight window
            step_events = step.events  # opens here even across retries
            # sub-slices of this pull (wait_counts / retry / fetch /
            # to_pairs) for the trace; None when tracing is off
            children = [] if tracer else None
            t_pull0 = time.perf_counter()
            retry_s = 0.0              # re-dispatch: pull_s leaves it out
            bytes_to_host = 0
            conjunct_evals = 0         # includes retry attempts: real work
            with tracer.annotate("pull"):
                counts, ev, nb = pull_counts(step, children)
                conjunct_evals += ev
                bytes_to_host += nb
                # extraction's loop trips, from the counts (no device work)
                blocks = extract.extract_blocks(counts, step.cap)
                while (counts > step.cap).any():
                    # overflow: grow only the overflowing shards (>=4x
                    # each, extract.grow_caps); counts are exact true
                    # totals, so the retried step — dispatched at the new
                    # per-shard max — cannot overflow again.  Every
                    # in-flight successor in the ring was built at the
                    # stale capacity: invalidate them all (drop the
                    # futures) and re-dispatch them right after the retry,
                    # in order, so the pipeline stays full and no chunk is
                    # ever emitted at a stale size.
                    caps[:] = extract.grow_caps(caps, counts)
                    t_retry0 = time.perf_counter()
                    successors = [s.k for s in ring]
                    if tracer:
                        step_events.append(
                            ("overflow", t_retry0,
                             {"counts_max": int(counts.max()),
                              "cap": step.cap}))
                        if successors:
                            step_events.append(
                                ("invalidate", t_retry0,
                                 {"steps": successors}))
                    ring.clear()
                    with tracer.annotate("retry"):
                        step = dispatch(k)
                        for kk in successors:
                            redis = dispatch(kk)
                            if tracer:
                                redis.events.append(
                                    ("redispatch", redis.t_enq,
                                     {"cap": redis.cap}))
                            ring.append(redis)
                    t_retry1 = time.perf_counter()
                    retry_s += t_retry1 - t_retry0   # it's dispatch, not pull
                    if tracer:
                        children.append({"name": "retry", "t0": t_retry0,
                                         "t1": t_retry1,
                                         "attrs": {"cap": step.cap}})
                    counts, ev, nb = pull_counts(step, children)
                    conjunct_evals += ev
                    bytes_to_host += nb
                    blocks += extract.extract_blocks(counts, step.cap)
                cap = step.cap
                t_fetch0 = time.perf_counter()
                with tracer.annotate("fetch"):
                    segs, fetched, moved = fetch(step, counts)
                bytes_to_host += fetched
                t_fetch1 = time.perf_counter()
                with tracer.annotate("to_pairs"):
                    pairs = to_pairs(segs)
            chunk_h2d = staged.bytes_h2d if k == 0 else 0
            chunk_reshard = staged.bytes_reshard if k == 0 else 0
            t_pull1 = time.perf_counter()
            pull_s = t_pull1 - t_pull0 - retry_s
            dispatch_s, timing["dispatch"] = timing["dispatch"], 0.0
            built, timing["built"] = timing["built"], 0
            # overlap accounting: host work done while a successor step was
            # in flight on the device — this pull/filter window, plus the
            # time the consumer held the previous chunk.  Exactly 0 for the
            # depth-1 (serial) ring, so a pipeline that silently degrades
            # to serial is visible in EngineStats (and gated in
            # benchmarks/run.py).
            overlap_s = (pull_s if ring else 0.0) + hold_overlap
            trace = track = None
            if tracer:
                children += [
                    {"name": "fetch", "t0": t_fetch0, "t1": t_fetch1,
                     "attrs": {"bytes": fetched, "bytes_moved": moved}},
                    {"name": "to_pairs", "t0": t_fetch1, "t1": t_pull1,
                     "attrs": {"candidates": len(pairs)}}]
                # the "dispatch" slice is the *in-flight window* (enqueue →
                # pull-begin): at depth ≥ 2 it contains predecessors' pull
                # windows — the ring overlap, visible as cross-track slice
                # overlap in Perfetto; at depth 1 it never does.  The host
                # enqueue wall itself rides along as ``enqueue_s`` (that is
                # what reconciles against wall.step2_dispatch_s).  The
                # "pull" slice holds any overflow retry as its child, which
                # the pull wall (pull_s) leaves out.
                trace = [
                    {"name": "dispatch", "t0": t_enq, "t1": t_pull0,
                     "attrs": {"enqueue_s": dispatch_s, "cap": cap,
                               "band": k}},
                    {"name": "pull", "t0": t_pull0, "t1": t_pull1,
                     "attrs": {"bytes": bytes_to_host,
                               "candidates": len(pairs),
                               "extract_blocks": blocks},
                     "children": children},
                ]
                track = f"ring{k % depth}"
            t_yield = time.perf_counter()
            yield ChunkDelta(pairs, bytes_to_host, chunk_h2d, chunk_reshard,
                             dispatch_s=dispatch_s, pull_s=pull_s,
                             overlap_s=overlap_s,
                             conjunct_evals=conjunct_evals,
                             programs_built=built,
                             trace=trace, trace_events=step_events or None,
                             track=track)
            hold = time.perf_counter() - t_yield
            hold_overlap = hold if ring else 0.0
        self.last_sweep_caps = caps.copy()
