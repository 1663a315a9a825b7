"""Device time of the band step's count gather, per band step and device,
in ms: the union of the intervals of the band-step program's op events
whose instruction carries the ``fdj_offsets`` named scope
(``extract.hierarchical_offsets``: an all-gather of the devices' candidate
counts), found by name in the program's compiled text (marks.py)."""

import marks


def read(ctx):
    return marks.scope_ms_per_step(ctx.trace, "fdj_offsets") \
        if ctx.trace else None
