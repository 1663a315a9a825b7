"""The program's own marks, read where the work happens.

* Device scopes.  The band step's body runs its parts under named scopes
  (``fdj_kernel``, ``fdj_extract``, ``fdj_offsets``; engine/sharded.py).
  A TPU trace's op events are named by their HLO instruction's text,
  ``%fusion.20 = ...``, without its metadata, so the scope is found by the
  instruction's name in the program's compiled text, whose ``op_name``
  metadata holds it (``ShardedEngine.band_step_hlo``).  Only events inside
  the band-step program's module events count: other programs reuse the
  same instruction names.  A scope's device time is the *union* of its
  events' intervals, since a ``while`` event contains its body's events.
* Host annotations.  ``Tracer.annotate`` marks the step-② ring's host
  intervals as ``fdj.<name>`` events on the profiler's own clock
  (stage_planes, enqueue or compile, pull and its parts wait_counts /
  retry / fetch / to_pairs, sort_pairs).  ``Capture`` keeps them beside
  the device events (``"host"``), and ``idle_gaps`` gives the device's
  idle time to the innermost annotation covering it; a trace without them
  falls back to ``reduce.idle_gaps``, which maps the ``perf_counter``
  spans onto the trace clock from the window's start.
* Span counters.  ``span_values_per_query`` sums a span attribute (or
  duration) over the spans inside finished queries.

On a program without these marks every reader here returns None.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from collections import defaultdict

import devtrace
import reduce

HOST_PREFIX = "fdj."
SCOPE = re.compile(r"(?:^|/)(fdj_[a-z]+)(?:/|$)")
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*?"
                         r'metadata=\{[^}]*op_name="([^"]*)"')
EVENT = re.compile(r"^%([^\s=]+)\s*=")


# -- device scopes -----------------------------------------------------------

def instruction_scopes(texts) -> dict:
    """``{instruction name: scope}`` over compiled HLO texts, for every
    instruction whose ``op_name`` names an ``fdj_*`` scope (the innermost
    one).  A name that two texts give different scopes is left out."""
    out, clash = {}, set()
    for text in texts:
        for line in text.splitlines():
            m = INSTRUCTION.match(line)
            if not m:
                continue
            scopes = SCOPE.findall(m.group(2))
            if not scopes:
                continue
            name, scope = m.group(1), scopes[-1]
            if out.setdefault(name, scope) != scope:
                clash.add(name)
    for name in clash:
        del out[name]
    return out


def program_scopes() -> dict:
    """``instruction_scopes`` of the band-step programs this process ran;
    empty when the program cannot give its compiled text."""
    from repro.engine.sharded import ShardedEngine
    hlo = getattr(ShardedEngine, "band_step_hlo", None)
    return instruction_scopes(hlo()) if hlo is not None else {}


def _band_steps(trace: dict) -> dict:
    """``{plane: [(start, end), ...]}`` of the band-step program's module
    events, clipped to the window."""
    return {plane: sorted((s, e) for s, e, name in reduce._clipped(
                trace, lines.get(reduce.MODULES_LINE, ()))
                if reduce.BAND_STEP.search(name))
            for plane, lines in trace["planes"].items()}


def scope_intervals(trace: dict, scopes: dict) -> dict:
    """``{plane: [(start, end, scope), ...]}`` of the op events inside the
    band-step program whose instruction carries an ``fdj_*`` scope, clipped
    to the window and sorted by start."""
    steps = _band_steps(trace)
    out = {}
    for plane, ops in reduce.device_ops(trace).items():
        modules = steps.get(plane, [])
        starts = [s for s, _ in modules]
        evs = []
        for s, e, name in ops:
            m = EVENT.match(name)
            scope = scopes.get(m.group(1)) if m else None
            i = bisect.bisect_right(starts, s) - 1
            if scope and i >= 0 and e <= modules[i][1]:
                evs.append((s, e, scope))
        out[plane] = evs
    return out


def scope_ms_per_step(trace: dict, scope: str, scopes: dict | None = None):
    """Device time of ``scope`` per band step, in ms: the union of its op
    events' intervals, over the band-step module events in the window;
    None when the trace or the program gives nothing to read."""
    if scopes is None:
        scopes = program_scopes()
    if not scopes:
        return None
    _, steps = reduce.op_time(trace, reduce.BAND_STEP, reduce.MODULES_LINE)
    per_plane = scope_intervals(trace, scopes)
    evs = {p: [ev for ev in v if ev[2] == scope] for p, v in per_plane.items()}
    if not steps or not any(evs.values()):
        return None
    total = sum(e - s for v in evs.values()
                for s, e in reduce.busy_intervals(v))
    return total / steps * 1e-6


# -- host annotations ----------------------------------------------------------

def host_events(profile, window: list) -> list:
    """``[[name, start_ns, duration_ns], ...]`` of the ``fdj.*`` events of
    the host planes that overlap ``window``, on the trace clock."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX) and \
                        ev.start_ns + ev.duration_ns > window[0] and \
                        ev.start_ns < window[1]:
                    out.append([ev.name, float(ev.start_ns),
                                float(ev.duration_ns)])
    return sorted(out, key=lambda ev: ev[1])


class Capture(devtrace.Capture):
    """``devtrace.Capture`` whose trace also holds the host annotations,
    under ``"host"``."""

    def stop(self) -> dict:
        import jax
        from jax.profiler import ProfileData
        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            profile = ProfileData.from_file(max(files, key=os.path.getmtime))
            trace = devtrace.extract(profile)
            trace["host"] = host_events(profile, trace["window_ns"])
            return trace
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _gaps(trace: dict) -> list:
    """Idle intervals of the first device plane within the window."""
    per_plane = reduce.device_ops(trace)
    if not per_plane:
        return []
    a, b = trace["window_ns"]
    gaps, cur = [], a
    for s, e in reduce.busy_intervals(next(iter(per_plane.values()))):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if b > cur:
        gaps.append((cur, b))
    return gaps


def idle_gaps(trace: dict, spans: list, t0: float, n: int = 10) -> list:
    """Device idle time in the window by what the host was doing then, on
    the trace clock: each stretch of an idle gap between two annotation
    boundaries goes to the innermost ``fdj.*`` annotation (the shortest
    one) covering it, or to "no annotation".  Returns the ``n`` largest
    ``[[name, seconds], ...]``.  A trace without annotations gets
    ``reduce.idle_gaps(trace, spans, t0, n)``, which gives each whole gap
    to the span covering its middle."""
    host = trace.get("host")
    if not host:
        return reduce.idle_gaps(trace, spans, t0, n)
    marks = sorted((s, s + d, name[len(HOST_PREFIX):]) for name, s, d in host)
    starts = [m[0] for m in marks]
    edges = sorted({t for m in marks for t in m[:2]})
    longest = max(m[1] - m[0] for m in marks)
    acc = defaultdict(float)
    for s, e in _gaps(trace):
        cuts = [s] + edges[bisect.bisect_right(edges, s):
                           bisect.bisect_left(edges, e)] + [e]
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            best = None
            for m0, m1, name in marks[bisect.bisect_left(
                    starts, mid - longest):bisect.bisect_right(starts, mid)]:
                if m1 >= mid and (best is None or m1 - m0 < best[0]):
                    best = (m1 - m0, name)
            acc[best[1] if best else "no annotation"] += (b - a) * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


# -- span counters ---------------------------------------------------------------

def span_values_per_query(spans: list, name: str, queries: list, value):
    """Sum of ``value(span)`` over the ``name`` spans inside finished
    queries, per finished query; None with no finished query or no such
    span."""
    done = sorted((q.t0, q.t1) for q in queries if q.complete)
    named = [sp for sp in spans if sp.name == name and sp.t1 is not None]
    if not done or not named:
        return None
    starts = [t0 for t0, _ in done]
    total = 0.0
    for sp in named:
        i = bisect.bisect_right(starts, sp.t0) - 1
        if i >= 0 and sp.t1 <= done[i][1]:
            total += value(sp)
    return total / len(done)
