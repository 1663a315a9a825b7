"""FDJ's on-chip benchmark: step ② on resident feature planes.

``run.py`` runs one cell of ``BENCHMARK.json`` once.  Everything that
belongs to one deployment, traffic mix or per-layer metric sits in a file
of its own (``configs/``, ``traffic/``, ``metrics/``), found by name.
"""
