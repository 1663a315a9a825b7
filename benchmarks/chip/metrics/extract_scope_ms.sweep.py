"""Device time of the band step's candidate extraction, per band step, in
ms: the union of the intervals of the band-step program's op events whose
instruction carries the ``fdj_extract`` named scope (engine/sharded.py),
found by name in the program's compiled text (marks.py)."""

import marks


def read(ctx):
    return marks.scope_ms_per_step(ctx.trace, "fdj_extract") \
        if ctx.trace else None
