"""Reduction of a captured trace (``devtrace.py``) and of the program's spans
to the numbers the per-layer readers report.

Device time is read from each device plane's ``XLA Ops`` line: one event per
operation that ran, with its start and duration on the trace clock.  Busy
time is the union of those intervals within the window, averaged over the
device planes; idle is the rest of the window.  The band-step program is
found on the ``XLA Modules`` line by its jitted name (``jit_body``), and
the fused CNF kernel on the ops line as the Pallas custom call: the band
step holds no other.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# an op's name in the trace is its HLO text; the pallas_call of
# kernels/fused_cnf_join has no name= yet, so it is found by its target
KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
# the band step is jax.jit(jax.shard_map(body)) in engine/sharded.py
BAND_STEP = re.compile(r"^jit_body\(")
NEARBY_SPANS = 256             # spans searched for the one covering a gap


def window_s(trace: dict) -> float:
    a, b = trace["window_ns"]
    return (b - a) * 1e-9


def _clipped(trace: dict, events) -> list:
    a, b = trace["window_ns"]
    out = []
    for name, t0, dur in events:
        s, e = max(t0, a), min(t0 + dur, b)
        if e > s:
            out.append((s, e, name))
    return out


def device_ops(trace: dict) -> dict:
    """``{plane: [(start_ns, end_ns, name), ...]}`` of device operations,
    clipped to the window and sorted by start."""
    return {plane: sorted(_clipped(trace, lines.get(OPS_LINE, ())))
            for plane, lines in trace["planes"].items()
            if lines.get(OPS_LINE)}


def busy_intervals(ops: list) -> list:
    """The union of ``[(start, end, name), ...]`` as sorted disjoint
    ``[(start, end), ...]``."""
    out = []
    for s, e, _ in ops:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def busy_s(trace: dict):
    """Seconds in which an operation ran, averaged over the device planes;
    None when the trace holds no device operation."""
    per_plane = device_ops(trace)
    if not per_plane:
        return None
    total = sum(sum(e - s for s, e in busy_intervals(ops))
                for ops in per_plane.values())
    return total / len(per_plane) * 1e-9


def idle_share(trace: dict):
    """Per cent of the window in which no operation ran on the device;
    None when the trace holds no device operation."""
    busy = busy_s(trace)
    if busy is None:
        return None
    return (1.0 - busy / window_s(trace)) * 100.0


def op_time(trace: dict, pattern, line: str = OPS_LINE) -> tuple:
    """``(seconds, count)`` of the events on ``line`` of every device plane
    whose name matches ``pattern``, clipped to the window."""
    total, count = 0.0, 0
    for lines in trace["planes"].values():
        for s, e, name in _clipped(trace, lines.get(line, ())):
            if pattern.search(name):
                total += e - s
                count += 1
    return total * 1e-9, count


def top_ops(trace: dict, n: int = 10) -> list:
    """The ``n`` device operations that took most time in the window,
    ``[[name, seconds], ...]``, averaged over the device planes."""
    per_plane = device_ops(trace)
    acc = defaultdict(float)
    for ops in per_plane.values():
        for s, e, name in ops:
            acc[name] += (e - s) * 1e-9 / len(per_plane)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, spans: list, t0: float, n: int = 10) -> list:
    """Device idle time in the window by what the host was doing then:
    each gap between busy intervals (on the first device plane) goes to the
    innermost host span, of ``spans`` recorded with ``perf_counter``, that
    covers the gap's middle; ``t0`` is the window's start on that clock.
    Returns the ``n`` largest ``[[span name, seconds], ...]``."""
    per_plane = device_ops(trace)
    if not per_plane:
        return []
    a, b = trace["window_ns"]
    busy = busy_intervals(next(iter(per_plane.values())))
    gaps, cur = [], a
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if b > cur:
        gaps.append((cur, b))
    # spans on the trace clock; innermost = shortest covering span, looked
    # for among the spans that began shortly before the gap's middle
    marks = sorted(((sp.t0 - t0) * 1e9 + a, (sp.t1 - t0) * 1e9 + a, sp.name)
                   for sp in spans if sp.t1 is not None)
    starts = [m[0] for m in marks]
    acc = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid)
        best = None
        for m0, m1, name in marks[max(0, i - NEARBY_SPANS):i]:
            if m1 >= mid and (best is None or m1 - m0 < best[0]):
                best = (m1 - m0, name)
        acc[best[1] if best else "no span"] += (e - s) * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def span_ms_per_query(spans: list, name: str, queries: list):
    """Sum of the ``name`` spans inside completed queries, per completed
    query, in milliseconds; None with no completed query."""
    done = sorted((q.t0, q.t1) for q in queries if q.complete)
    if not done:
        return None
    starts = [t0 for t0, _ in done]
    total = 0.0
    for sp in spans:
        if sp.name != name or sp.t1 is None:
            continue
        i = bisect.bisect_right(starts, sp.t0) - 1
        if i >= 0 and sp.t1 <= done[i][1]:
            total += sp.t1 - sp.t0
    return total / len(done) * 1e3
