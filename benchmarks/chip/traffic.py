"""The query stream of one cell: a deployment under a traffic mix.

A traffic mix (``traffic/<mix>.json``) is data this one generator reads.
Every mix is one client in a closed loop: it sends the next query when the
last one's candidates are all on the host, with the engine's ring of band
steps ``prefetch_depth`` deep (``ShardedEngine(prefetch_depth=...)``).

* ``"kind": "sweep"``: both sides are resident; every query joins all of L
  with all of R.  ``check_rows`` L rows, drawn from the seed, are checked
  in every ``check_every``-th query.
* ``"kind": "probe"``: the deployment's planted side (``new_side``) is not
  resident; each query brings ``batch_rows`` new rows of it, planted near
  resident rows, and joins them to the resident side.  Batches are drawn
  from the seed up front, ``distinct_batches`` of them, kept on the host
  and sent in turn, so every seed sends the same sizes.  Every
  ``check_every``-th query, from an offset drawn from the seed, is checked
  in full.

Seeds are any non-negative whole number; each stream of draws hangs off its
own ``(seed, tag)`` sequence, so the same seed gives the same inputs.
Resident planes are made on the device and stay there; the check fetches
them to the host once the window has closed (``fetch``).
"""

from __future__ import annotations

import jax
import numpy as np

from planes import Deployment, key

RESIDENT, BATCH, CHECK_OFFSET, CHECK_ROWS = 0, 1, 2, 3   # draw-stream tags
MIX_KEYS = {
    "sweep": {"kind", "about", "prefetch_depth", "warmup_steps",
              "check_every", "check_rows"},
    "probe": {"kind", "about", "prefetch_depth", "new_side", "batch_rows",
              "distinct_batches", "warmup_queries", "check_every",
              "check_rows"},
}


class Traffic:
    def __init__(self, config: dict, mix: dict, seed: int):
        self.config = config
        self.mix = mix
        self.kind = mix["kind"]
        if self.kind not in MIX_KEYS:
            raise ValueError(f"unknown traffic kind {self.kind!r}")
        if set(mix) != MIX_KEYS[self.kind]:
            raise ValueError(f"a {self.kind} mix has the keys "
                             f"{sorted(MIX_KEYS[self.kind])}, not "
                             f"{sorted(mix)}")
        self.dep = Deployment(config)
        self.clauses = self.dep.clauses
        self.thetas = self.dep.thetas
        self.kinds = [f["kind"] for f in config["features"]]
        planted = config["planted_side"]
        other = "l" if planted == "r" else "r"
        base = self.dep.rows(key(seed, RESIDENT, 0),
                             int(config[f"rows_{other}"]))
        self.resident = {}
        share = float(config["planted_share"])
        self.batches = []
        if self.kind == "sweep":
            rows = self.dep.rows(key(seed, RESIDENT, 1),
                                 int(config[f"rows_{planted}"]),
                                 partner=base, planted_share=share)
            self.resident[planted] = rows.encode(planted)
            del rows
            self.new_side = None
        else:
            if mix["new_side"] != planted:
                raise ValueError(f"probe sends {mix['new_side']} rows; the "
                                 f"deployment plants {planted}")
            self.new_side = planted
            for b in range(int(mix["distinct_batches"])):
                rows = self.dep.rows(key(seed, BATCH, b),
                                     int(mix["batch_rows"]), partner=base,
                                     planted_share=share)
                self.batches.append(jax.device_get(rows.encode(planted)))
        # encoded last, so that the raw and encoded planes of the resident
        # side are never on the device together with the other side's
        self.resident[other] = base.encode(other)
        del base
        self.n_l = self.planes_shape("l")
        self.n_r = self.planes_shape("r")
        self._host = None
        every = int(mix["check_every"])
        self.check_every = every
        self.check_offset = int(
            np.random.default_rng([seed, CHECK_OFFSET]).integers(every))
        rows = mix["check_rows"]
        if rows is None or rows >= self.n_l:
            self.check_rows = np.arange(self.n_l)
        else:
            self.check_rows = np.sort(np.random.default_rng(
                [seed, CHECK_ROWS]).choice(self.n_l, int(rows),
                                           replace=False))

    def planes_shape(self, side: str) -> int:
        if side in self.resident:
            return int(self.resident[side][0].shape[0])
        return int(self.batches[0][0].shape[0])

    def batch(self, k: int) -> list:
        """Host planes of query ``k``'s new rows."""
        return self.batches[k % len(self.batches)]

    def fetch(self) -> None:
        """Move the resident planes to the host for the check, and let the
        device ones go."""
        self._host = {side: jax.device_get(planes)
                      for side, planes in self.resident.items()}
        self.resident = {}

    def planes(self, k: int) -> tuple:
        """Host planes ``(l, r)`` of query ``k``, after ``fetch``: lists,
        one per feature."""
        host = dict(self._host)
        if self.new_side is not None:
            host[self.new_side] = self.batch(k)
        return host["l"], host["r"]

    def checked(self, k: int) -> bool:
        return k % self.check_every == self.check_offset
