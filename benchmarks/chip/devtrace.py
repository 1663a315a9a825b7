"""Profiler capture of a run's window, read into a compact event list.

``Capture`` traces with JAX's profiler (host Python tracing off, so the
window's host code runs at its untraced speed), marks the measured window
with a ``TraceAnnotation`` and, when stopped, reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` into a plain dict:

    {"window_ns": [start, end],       # the annotation, on the trace clock
     "planes": {plane: {line: [[name, start_ns, duration_ns], ...]}}}

holding every event of the device planes (``/device:...``) that overlaps
the window.  ``reduce.py`` works on that dict alone, so a small recorded
one (``tests/data/``) checks the reduction without a chip.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time

WINDOW = "fdj_bench_window"


class Capture:
    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="fdj_bench_trace_")
        self._annotation = None
        self.t0 = None                 # perf_counter at the window's start

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def open_window(self) -> float:
        import jax
        self._annotation = jax.profiler.TraceAnnotation(WINDOW)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self.t0

    def close_window(self) -> None:
        self._annotation.__exit__(None, None, None)

    def stop(self) -> dict:
        import jax
        from jax.profiler import ProfileData
        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            return extract(ProfileData.from_file(max(files,
                                                     key=os.path.getmtime)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def extract(profile) -> dict:
    """The window annotation and the device planes' events within it."""
    window = None
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = [float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns)]
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} annotation in the trace")
    planes = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {}
        for line in plane.lines:
            evs = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                   for ev in line.events
                   if ev.start_ns + ev.duration_ns > window[0]
                   and ev.start_ns < window[1]]
            if evs:
                lines[line.name] = evs
        planes[plane.name] = lines
    return {"window_ns": window, "planes": planes}
