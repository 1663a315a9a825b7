"""The control: the reference put in the program's place, one precision down.

The deployments state float32 planes with embed dot products at
``Precision.HIGHEST``.  The nearest precision below is ``Precision.HIGH``:
three bfloat16 passes, ``hi*hi + hi*lo + lo*hi``, which drops the product
of the two remainders.  ``ControlEngine`` computes the CNF that way, band
by band on the device, written out in bfloat16 halves so that it does the
same arithmetic on any backend, and yields its candidates as the engine
yields chunks.  Run in the harness in the engine's place, its output must
fail the check: the deployments' boundary rows (``planes.py``) lie closer
to the threshold than the remainders' product moves them.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class Chunk:
    index: int                 # R band
    candidates: list           # (i, j) pairs


@functools.partial(jax.jit, static_argnames=("clauses", "thetas"))
def _band(ls, rs, *, clauses, thetas):
    def halves(x):
        # reduce_precision, not a round trip through bfloat16, which XLA
        # may fold away and leave the remainder 0
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

    def dot(a, b):
        return jnp.dot(a, b.T, preferred_element_type=jnp.float32)

    dist = []
    for a, b in zip(ls, rs):
        if a.ndim == 2:
            (ah, al), (bh, bl) = halves(a), halves(b)
            d = 0.5 - 0.5 * (dot(ah, bh) + (dot(ah, bl) + dot(al, bh)))
        else:
            d = jnp.abs(a[:, None] - b[None, :])
        dist.append(jnp.clip(d, 0.0, 1.0))
    ok = None
    for clause, theta in zip(clauses, thetas):
        cd = functools.reduce(jnp.minimum, [dist[f] for f in clause])
        ok = cd <= theta if ok is None else ok & (cd <= theta)
    return jnp.packbits(ok, axis=1)


def _whole(x):
    """``x`` whole on every device of its mesh where it is split by rows,
    so that each band of it meets every shard of L."""
    if isinstance(x.sharding, NamedSharding):
        return jax.device_put(x, NamedSharding(x.sharding.mesh, P()))
    return x


class ControlEngine:
    """Yields one chunk of candidates per ``r_chunk`` R columns."""

    tr = 128
    r_chunk = 512

    def evaluate_stream(self, planes, clauses, thetas):
        n = len(planes)
        ls = tuple(planes.device_l(f) for f in range(n))
        rs = tuple(_whole(planes.device_r(f)) for f in range(n))
        key = dict(clauses=tuple(tuple(c) for c in clauses),
                   thetas=tuple(float(t) for t in thetas))
        for k, c0 in enumerate(range(0, rs[0].shape[0], self.r_chunk)):
            band = tuple(r[c0:c0 + self.r_chunk] for r in rs)
            packed = np.asarray(_band(ls, band, **key))
            # candidates are sparse: unpack only the bytes that hold one
            i, byte = np.nonzero(packed)
            row, bit = np.nonzero(np.unpackbits(packed[i, byte][:, None],
                                                axis=1))
            j = byte[row] * 8 + bit + c0
            yield Chunk(k, list(zip(i[row].tolist(), j.tolist())))
