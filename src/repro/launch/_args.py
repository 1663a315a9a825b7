"""Shared launcher wiring: dataset generators + the common flag set.

Every join launcher (``launch/join.py``, ``launch/serve_join.py``,
``launch/serve_fleet.py``) takes the same core knobs — dataset/size/seed,
engine selection, streaming, the sharded engine's prefetch-depth and
R-band width, and trace output.  They were once duplicated per launcher
by hand; this module is the single place a new flag (or dataset) is
added so every launcher inherits it.
"""

from __future__ import annotations

import argparse
import os
from typing import Optional

from repro.data import synth
from repro.engine import ENGINES


def make_dataset(name: str, *, size: float = 1.0, seed: int = 0,
                 scale: float = 1.0):
    """The benchmark corpora at launcher scale.  ``size`` is the user's
    CLI multiplier; ``scale`` is the launcher's own base factor (the
    one-shot join launcher runs 2x the serving launchers' corpora)."""
    def n(base: int) -> int:
        return int(base * size * scale)

    gens = {
        "police_records": lambda: synth.police_records(
            n_incidents=n(300), reports_per_incident=3, seed=seed),
        "citations": lambda: synth.citations(n_docs=n(900), seed=seed),
        "movies": lambda: synth.movies_pages(n_movies=n(400), seed=seed),
        "products": lambda: synth.products(n_products=n(700), seed=seed),
        "categorize": lambda: synth.categorize(n_items=n(2000), seed=seed),
        "biodex": lambda: synth.biodex(n_notes=n(1500), seed=seed),
    }
    return gens[name]()


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache and nothing is
    set here.  Otherwise the cache lives at ``<checkout>/.cache/jax``,
    derived from this file's location the way ``core.adj_target.cache_dir``
    derives its curve cache: the directory is part of the cache key, so it
    must not move between runs of the same checkout.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".cache", "jax"))


def add_common_flags(ap: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """The flag set every join launcher shares.  ``--engine`` defaults to
    the sharded engine, so the launchers run step ② on the device."""
    ap.add_argument("--dataset", default="police_records")
    ap.add_argument("--engine", default="sharded", choices=list(ENGINES))
    ap.add_argument("--stream", action="store_true",
                    help="pipeline refinement over the step-② candidate "
                         "stream (FDJConfig.stream_refinement)")
    ap.add_argument("--size", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--target", type=float, default=0.9)
    ap.add_argument("--delta", type=float, default=0.1)
    ap.add_argument("--prefetch-depth", type=int, default=None,
                    help="sharded engine: band steps in flight at once "
                         "(FDJConfig.prefetch_depth; 1 = serial)")
    ap.add_argument("--r-chunk", type=int, default=None,
                    help="R-band width in columns (engine_opts; smaller = "
                         "more band steps, e.g. to exercise the prefetch "
                         "ring on a small corpus)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write a Perfetto/Chrome trace-event JSON of the "
                         "run (load in ui.perfetto.dev, or summarize with "
                         "python -m repro.launch.trace_report FILE)")
    return ap


def engine_opts_from(r_chunk: Optional[int]) -> dict:
    """engine_opts for the common flags (--r-chunk is the only one that
    rides in engine_opts; prefetch_depth is a first-class cfg field)."""
    return {"r_chunk": r_chunk} if r_chunk else {}
