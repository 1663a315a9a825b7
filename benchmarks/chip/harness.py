"""One run of one cell: set-up, the measured window, the check, the result.

The window drives the system under test, ``ShardedEngine.evaluate_stream``
at its default tiles and the mix's ring depth.  One query is one call,
drained chunk by chunk until every candidate is on the host as the engine
yields it: plane staging, the band-step program (the fused CNF kernel and
on-device extraction), the ring's pulls and the host's conversion to pairs.

* Set-up builds the cell's mesh over its ``chips`` devices, makes the
  resident planes from the seed on the device, sharded over that mesh, and
  a probe's batches on the host (``traffic.py``), and runs the cell's own
  shapes once (a band step of a sweep, or whole probe queries), so that
  every program the window runs is compiled.  Compilations inside the
  window are counted.
* Resident planes reach the engine as a ``serving.planes.DevicePlaneSet``
  on the cell's mesh, the serving store's residency path: staging
  assembles them on the device and moves no resident byte host-to-device.
  A probe's new rows are put on the device inside the query, as new
  records' planes would be.
* The window closes with the first query that ends past ``seconds``: every
  query in it runs to its last candidate, so all the work sent counts, over
  all the time it took.  The mix sets the ring depth (``prefetch_depth``):
  a sweep keeps band steps queued on the device ahead of the host, so a
  host that stands still for a moment leaves the chip busy.
* Checked queries keep their candidates as arrays, not as the engine's
  tuples, so the window's garbage collections stay as short as the
  program's own.
* After the window each of the cell's devices' peak memory is read, the
  program's device state is freed and the resident rows the check reads
  are fetched to the host, shard by shard; then the checked queries are
  compared with the plain reference (``reference.py``) on the host.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time

import numpy as np

from traffic import Traffic, cell_mesh

HERE = os.path.dirname(os.path.abspath(__file__))
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str) -> tuple:
    """``(cell, config, mix)`` of a workload named in ``BENCHMARK.json``,
    each read from its own file."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    root = os.path.dirname(os.path.dirname(HERE))
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return cell, config, mix


def load_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries a run of ``workload`` reports: the end-to-end ones
    untraced, the per-layer ones traced."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if workload in m.get("workloads", [workload])]


@dataclasses.dataclass
class Query:
    k: int
    t0: float
    t1: float
    steps: int
    complete: bool
    chunks: list | None            # (band index, (n, 2) pairs) when checked


@dataclasses.dataclass
class Context:
    """What a metric reader gets (``metrics/<name>.py``)."""
    queries: list                  # Query records of the window
    window: tuple                  # perf_counter (start, last query's end)
    step_pairs: list               # cross-product pairs of each band step
    setup_s: float
    work: dict                     # kernel_work.band_step_work of a step
    device_kind: str
    trace: dict | None             # devtrace.extract of the window
    spans: list                    # the program's Tracer spans
    chips: int                     # devices of the cell's mesh


class _Compiles:
    """Counts lowerings to MLIR: one per program JAX had to compile."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **kw):
        if event == LOWERING_EVENT:
            self.n += 1


def device_memory(devices) -> list:
    """Each device's peak bytes in use so far (0 where the backend does not
    report it)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def _plane_set(kinds, dev_l, dev_r, mesh):
    """The planes as the serving store hands them to the engine, on the
    cell's mesh; the engine reads only the shapes of the features' own
    arrays."""
    from repro.core.featurize import FeatureData, FeaturizationSpec
    from repro.serving.planes import DevicePlaneSet
    feats = [FeatureData(FeaturizationSpec(
        f"f{i}", "", "semantic" if k == "embed" else "arithmetic", "code",
        f"f{i}"), k, dl, dr) for i, (k, dl, dr) in
        enumerate(zip(kinds, dev_l, dev_r))]
    return DevicePlaneSet(feats, dev_l, dev_r, mesh=mesh)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool = False, *, engine=None, overrides: dict | None = None,
             t_start: float | None = None, log=None,
             on_trace=None) -> dict:
    """One run; returns the result object ``run.py`` prints.  ``engine``
    replaces the system under test (the control, a planted fault);
    ``overrides`` replaces cell, config and mix keys (small sizes and
    device counts in tests);
    ``on_trace`` is handed the captured trace of a traced run."""
    import jax
    import jax.monitoring
    from repro.obs.trace import Tracer, use_tracer

    import devtrace
    import kernel_work
    import reference

    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell, config, mix = load_cell(bench, workload)
    cell = dict(cell)
    for key, value in (overrides or {}).items():
        (cell if key in cell else mix if key in mix else config)[key] = value
    if engine is None:
        from repro.engine.sharded import ShardedEngine
        engine = ShardedEngine(prefetch_depth=int(mix["prefetch_depth"]))
    r_chunk = getattr(engine, "r_chunk", None) or 4 * engine.tr

    chips = int(cell["chips"])
    mesh = cell_mesh(chips)
    t_planes = time.perf_counter()
    traffic = Traffic(config, mix, seed, mesh)
    jax.block_until_ready(traffic.resident)
    t_planes = time.perf_counter() - t_planes
    clauses, thetas, kinds = traffic.clauses, traffic.thetas, traffic.kinds
    resident = traffic.resident
    sweep_set = None
    if traffic.kind == "sweep":
        sweep_set = _plane_set(kinds, resident["l"], resident["r"], mesh)

    def plane_set(k: int, tracer=None):
        if sweep_set is not None:
            return sweep_set
        t0 = time.perf_counter()
        dev = [jax.device_put(a) for a in traffic.batch(k)]
        if tracer:
            tracer.record_span("upload_batch", t0, time.perf_counter())
        dev_l = dev if traffic.new_side == "l" else resident["l"]
        dev_r = dev if traffic.new_side == "r" else resident["r"]
        return _plane_set(kinds, dev_l, dev_r, mesh)

    n_l, n_r = traffic.n_l, traffic.n_r
    steps_per_query = -(-n_r // r_chunk)

    def step_pairs(index: int) -> int:
        return n_l * min(r_chunk, n_r - index * r_chunk)

    # -- set-up: the cell's own shapes, once ---------------------------------
    t_warm = time.perf_counter()
    if traffic.kind == "sweep":
        stream = engine.evaluate_stream(plane_set(0), clauses, thetas)
        for i, _ in enumerate(stream):
            if i + 1 >= int(mix["warmup_steps"]):
                break
        stream.close()
    else:
        for k in range(int(mix["warmup_queries"])):
            for _ in engine.evaluate_stream(plane_set(k), clauses, thetas):
                pass
    # a one-element program queued behind whatever the set-up left running
    (jax.device_put(np.int32(0)) + 1).block_until_ready()
    log(f"set-up: planes made in {t_planes:.2f} s, shapes run in "
        f"{time.perf_counter() - t_warm:.2f} s")
    gc.collect()
    gc.freeze()

    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    capture = devtrace.Capture() if trace else None
    tracer = Tracer() if trace else None
    if capture:
        capture.start()

    # -- the measured window ----------------------------------------------------
    queries, pairs_done, resident_h2d = [], [], 0
    t_window = capture.open_window() if capture else time.perf_counter()
    setup_s = t_window - t_start
    deadline = t_window + seconds
    t_last = t_window
    k = 0
    with use_tracer(tracer):
        while True:
            keep = [] if traffic.checked(k) else None
            tq0 = time.perf_counter()
            steps = 0
            for ch in engine.evaluate_stream(plane_set(k, tracer), clauses,
                                             thetas):
                steps += 1
                pairs_done.append(step_pairs(ch.index))
                resident_h2d += getattr(getattr(ch, "stats", None),
                                        "bytes_h2d", 0)
                if keep is not None:
                    keep.append((ch.index, np.asarray(
                        ch.candidates, np.int64).reshape(-1, 2)))
            tq1 = t_last = time.perf_counter()
            if tracer:
                tracer.record_span("query", tq0, tq1, attrs={"k": k})
            queries.append(Query(k, tq0, tq1, steps,
                                 steps == steps_per_query, keep))
            k += 1
            if tq1 >= deadline:
                break
    if capture:
        capture.close_window()
    n_compiles = compiles.n
    jax.monitoring.unregister_event_duration_listener(compiles)
    captured = capture.stop() if capture else None
    if on_trace is not None and captured is not None:
        on_trace(captured)
    gc.unfreeze()

    dev0 = jax.devices()[0]
    peaks = device_memory(mesh.devices.flat)
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(peaks),
              "memory_peak_bytes_per_device": peaks}
    del sweep_set, resident
    checked = [(q, min(n_r, max(i for i, _ in q.chunks) * r_chunk
                       + r_chunk))
               for q in queries if q.chunks is not None and q.steps]
    traffic.fetch(max((n for _, n in checked), default=0))
    gc.collect()
    log(f"window: {len(queries)} queries, {len(pairs_done)} band steps, "
        f"{n_compiles} compilations, {resident_h2d} resident bytes "
        f"host-to-device")

    # -- metrics ------------------------------------------------------------------
    ctx = Context(queries, (t_window, t_last), pairs_done, setup_s,
                  kernel_work.band_step_work(n_l, min(r_chunk, n_r),
                                             config["features"], clauses),
                  device["kind"], captured, tracer.spans() if tracer else [],
                  chips)
    metrics = {}
    for entry in cell_metrics(bench, workload, trace):
        value = load_reader(entry["name"])(ctx)
        if value is None:
            if not trace:
                raise RuntimeError(f"{entry['name']}: nothing to read")
            continue
        metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    breakdown = None
    if captured is not None:
        import reduce
        device["busy_s"] = reduce.busy_s(captured) or 0.0
        device["window_s"] = reduce.window_s(captured)
        breakdown = {
            "device_ops": reduce.top_ops(captured),
            "idle_gaps": reduce.idle_gaps(captured, ctx.spans, t_window)}

    # -- the check ------------------------------------------------------------------
    limits = config["limits"]
    gap, dups, failed = 0.0, 0, 0
    for q, n_cols in checked:
        host_l, host_r = traffic.planes(q.k)
        pairs = np.concatenate([c for _, c in q.chunks])
        if sorted(i for i, _ in q.chunks) != list(range(q.steps)):
            raise RuntimeError(f"query {q.k}: band steps out of order")
        got = reference.compare(pairs, host_l, host_r, clauses, thetas,
                                traffic.check_rows, n_cols, n_l)
        gap, dups = max(gap, got["gap"]), dups + got["duplicates"]
        if got["gap"] > limits["gap"] or got["duplicates"] > \
                limits["duplicates"]:
            failed += 1
        if got["mismatches"] or got["duplicates"]:
            log(f"check q{q.k}: {got}")
    if not checked:
        raise RuntimeError("no query was checked")
    log(f"checked {len(checked)} queries, {failed} over a limit")
    out = {"correct": failed == 0, "attempted": len(queries),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {
        "gap": {"value": gap, "limit": limits["gap"]},
        "duplicates": {"value": dups, "limit": limits["duplicates"]}}
    return out
