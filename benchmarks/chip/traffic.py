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
Resident planes are made on the device, sharded over the cell's mesh, and
stay there (``planes.Deployment.side``); a probe's batches are made on the
mesh's first device.  Once the window has closed, the check copies to the
host only the rows it reads, shard by shard (``fetch``).
"""

from __future__ import annotations

import jax
import numpy as np

from planes import Deployment, draw

RESIDENT, BATCH, CHECK_OFFSET, CHECK_ROWS = 0, 1, 2, 3   # draw-stream tags
MIX_KEYS = {
    "sweep": {"kind", "about", "prefetch_depth", "warmup_steps",
              "check_every", "check_rows"},
    "probe": {"kind", "about", "prefetch_depth", "new_side", "batch_rows",
              "distinct_batches", "warmup_queries", "check_every",
              "check_rows"},
}


def cell_mesh(chips: int):
    """The ``(chips, 1)`` ``("data", "model")`` mesh over the first
    ``chips`` devices: on a host of that many, the engine's own default
    (``distributed.mesh.make_host_mesh``)."""
    return jax.make_mesh((chips, 1), ("data", "model"),
                         devices=jax.devices()[:chips])


@jax.jit
def _take_rows(x, i):
    return x[i]


def fetch_rows(array, rows: np.ndarray) -> np.ndarray:
    """Rows ``rows`` (sorted, distinct) of a device array split by rows,
    copied to the host shard by shard: a shard that holds some of them
    gathers those on its device, padded to the next power of two of their
    count (at most ``rows.size``), and sends that; one that holds only
    rows asked for sends itself whole; one that holds none sends nothing.
    So at most twice the rows asked for cross to the host."""
    out = np.empty((rows.size,) + array.shape[1:], array.dtype)
    for sh in array.addressable_shards:
        lo, hi, _ = sh.index[0].indices(array.shape[0])
        a, b = np.searchsorted(rows, [lo, hi])
        if a == b:
            continue
        if b - a == hi - lo:
            out[a:b] = np.asarray(sh.data)
            continue
        # padded, so that few programs serve every shard and every seed
        i = np.zeros(min(1 << int(b - a - 1).bit_length(), rows.size),
                     np.int32)
        i[:b - a] = rows[a:b] - lo
        out[a:b] = np.asarray(_take_rows(sh.data, jax.device_put(
            i, sh.device)))[:b - a]
    return out


class Traffic:
    def __init__(self, config: dict, mix: dict, seed: int, mesh=None):
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
        self.mesh = mesh if mesh is not None else cell_mesh(1)
        devices = list(self.mesh.devices.flat)
        planted = config["planted_side"]
        other = "l" if planted == "r" else "r"
        n_other = int(config[f"rows_{other}"])
        share = float(config["planted_share"])
        if self.kind == "sweep":
            self.new_side = None
            shards = self.dep.draws(seed, (RESIDENT, 1),
                                    int(config[f"rows_{planted}"]), devices)
            feed = [d for blocks in shards for d in blocks]
        else:
            if mix["new_side"] != planted:
                raise ValueError(f"probe sends {mix['new_side']} rows; the "
                                 f"deployment plants {planted}")
            self.new_side = planted
            feed = [draw(seed, (BATCH, b), int(mix["batch_rows"]), devices[0])
                    for b in range(int(mix["distinct_batches"]))]
        # the other side, with the raw rows the planted rows lie near
        self.resident = {}
        self.resident[other], near = self.dep.side(
            self.dep.draws(seed, (RESIDENT, 0), n_other, devices), other,
            self.mesh, [self.dep.pick(d, n_other) for d in feed])
        if self.kind == "sweep":
            self.resident[planted], _ = self.dep.side(
                shards, planted, self.mesh, near=near, planted_share=share)
            self.batches = []
        else:
            self.batches = [jax.device_get(self.dep.rows(d, share, rows)
                                           .encode(planted))
                            for d, rows in zip(feed, near)]
        del feed, near
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

    def fetch(self, n_cols: int | None = None) -> None:
        """Copy to the host the rows the check reads, shard by shard: L's
        ``check_rows`` and R's first ``n_cols`` (all of them by default)
        where that side is resident; and let the device planes go."""
        n_cols = self.n_r if n_cols is None else n_cols
        want = {"l": self.check_rows, "r": np.arange(n_cols)}
        self._host = {side: [fetch_rows(p, want[side]) for p in planes]
                      for side, planes in self.resident.items()}
        self.resident = {}

    def planes(self, k: int) -> tuple:
        """Host planes ``(l, r)`` of query ``k``, after ``fetch``: lists,
        one per feature, of L's ``check_rows`` and R's first rows."""
        host = dict(self._host)
        if self.new_side == "l":
            host["l"] = [p[self.check_rows] for p in self.batch(k)]
        elif self.new_side == "r":
            host["r"] = self.batch(k)
        return host["l"], host["r"]

    def checked(self, k: int) -> bool:
        return k % self.check_every == self.check_offset
