"""Seeded feature planes of one deployment: the benchmark's one generator.

A deployment file (``configs/<name>.json``) states the rows resident on each
side, the features with kind and width, the CNF and each clause's threshold
quantile, the missing-value rate, which side's rows are planted near a row of
the other side and how many, and the share of planted rows that sit just
inside clause 0's threshold ("boundary" rows, below).

Planes are made on the device from keys drawn from the seed, and come out in
the layout the engines take (``core.featurize``):
an embed row of width ``D`` is a unit vector with two marker columns
appended, ``[e, m, 1]`` on L and ``[e, 1, m]`` on R with ``m = -2`` where the
value is missing, so a missing value is at distance 1 from everything; a
missing scalar is ``+1e9`` on L and ``-1e9`` on R.  The generator uses no
matrix product, whose precision differs by backend: only elementwise
arithmetic and row sums.

A resident side is made one shard at a time, each on its own device of the
cell's mesh, in blocks of at most ``BLOCK_ROWS`` rows (``Deployment.side``):
a block is drawn raw, encoded into its shard's planes and let go, so a
device holds its shard's planes and one block's draw, never a whole side
raw and encoded.  The shards are then one ``jax.Array`` whose rows are split
over the mesh's ``"data"`` axis, as the engine splits L.  Block ``j`` of
shard ``s`` draws from ``(seed, *tags, s, j)``, and block 0 of shard 0 from
``(seed, *tags)``: a side of one block on one chip is the single draw it
always was, and a row's values do not depend on the device that made it.
Rows planted near rows of the other side name their partners there
(``Deployment.pick``); ``Deployment.side`` returns those partner rows,
gathered from each raw block as it is made, not from a whole raw side.

Thresholds are calibrated once per deployment, on rows drawn from a fixed
calibration key and not from the run's seed: the same seed-independent
thresholds are compiled into the band-step program of every run, so every
run after a cell's first finds that program in the compile cache.  Random
unit vectors have the same pair-distance law whatever the seed, so each
clause still admits its quantile of a run's random pairs.

Precision probe.  Float32 planes whose components sit at a whole number of
bfloat16 steps (``chip_smoke.py`` rounds to multiples of 2^-7) give the same
dot products in every matmul precision, so a lower-precision path would pass
any comparison on them.  Here clause 0's embed feature instead carries, in
every component, a remainder of 0.49 bfloat16 steps past the bfloat16 value
it rounds to.  A three-pass bfloat16 product (``Precision.HIGH``) drops the
product of the two remainders: about 2^-16 of each component product, which
adds up to some 2e-6 of distance at 3072 dims where the two rows' remainders
agree in sign.  A boundary row is built so that they do, at a distance
``boundary_margin`` inside clause 0's threshold from its partner; float32
products place it inside, three-pass products outside.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BLOCK_ROWS = 131_072           # rows a resident side is drawn in at a time
                               # (read when drawn: tests set it smaller)
CALIBRATION_ROWS = 8192        # rows a side the thresholds are calibrated on
CALIBRATION_PAIRS = 200_000    # random pairs the quantiles are read from
CALIBRATION_TAG = 7
CALIBRATION_BLOCK = 20_000   # pairs a block (a block's gathered rows)
SATURATION = 0.49              # remainder, in bfloat16 steps, of clause 0's
                               # embed components (< 0.5: rounds back)


def key(seed: int, *tags: int):
    """A JAX key for the draw stream ``(seed, *tags)``: any whole numbers,
    all of their bits kept."""
    state = np.random.SeedSequence([int(seed), *map(int, tags)]) \
        .generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state), impl="threefry2x32")


# --- bfloat16 arithmetic on float32 arrays ----------------------------------

def bf16_round(x):
    """Round float32 values to the nearest bfloat16 value (ties to even),
    returned as float32."""
    u = lax.bitcast_convert_type(jnp.asarray(x, jnp.float32), jnp.uint32)
    r = (u + jnp.uint32(0x7FFF) + ((u >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(r, jnp.float32)


def bf16_step(h, toward):
    """Distance from bfloat16 values ``h`` (normal float32 numbers) to their
    neighbour on the side of sign ``toward`` (the step below a power of two
    is half the one above)."""
    u = lax.bitcast_convert_type(h, jnp.int32)
    expo = (u >> 23) & 0xFF
    up = lax.bitcast_convert_type(jnp.maximum(expo - 7, 1) << 23,
                                  jnp.float32)                 # 2^(e-7)
    shrinking = (jnp.sign(toward) * jnp.sign(h) < 0) & ((u & 0x7F0000) == 0)
    return jnp.where(shrinking, up * 0.5, up)


def saturate(x, signs):
    """Move every component to its bfloat16 value plus ``SATURATION`` steps
    in the direction of ``signs`` (each +1 or -1)."""
    h = bf16_round(x)
    return h + signs * SATURATION * bf16_step(h, signs)


def remainder_signs(x):
    """Sign of each component's remainder past its bfloat16 value."""
    return jnp.sign(x - bf16_round(x))


# --- rows ---------------------------------------------------------------------

def _rowdot(a, b):
    return jnp.sum(a * b, axis=1, keepdims=True)


def _unit(x):
    return x / jnp.sqrt(_rowdot(x, x))


@dataclasses.dataclass
class Rows:
    """Raw values of one side's rows, on the device: per feature an embed
    (n, D) float32 matrix or a scalar (n,) float32 vector, and its missing
    mask."""
    values: list
    missing: list

    @property
    def n(self) -> int:
        return int(self.missing[0].shape[0])

    def encode(self, side: str) -> list:
        """The planes in the engines' layout (module docstring)."""
        return list(_encode(tuple(self.values), tuple(self.missing), side))


@dataclasses.dataclass(frozen=True)
class Draw:
    """One call of the generator: ``n`` rows from key ``key``, made on
    ``device``."""
    key: object
    n: int
    device: object


def draw(seed: int, tags: tuple, n: int, device) -> Draw:
    """``n`` rows from the stream ``(seed, *tags)``, made on ``device``."""
    return Draw(jax.device_put(key(seed, *tags), device), int(n), device)


@functools.partial(jax.jit, static_argnums=2)
def _encode(values, missing, side):
    out = []
    for v, miss in zip(values, missing):
        if v.ndim == 2:
            m = jnp.where(miss, -2.0, 0.0)[:, None]
            one = jnp.ones_like(m)
            e = jnp.where(miss[:, None], 0.0, v)
            out.append(jnp.concatenate(
                [e, m, one] if side == "l" else [e, one, m], axis=1))
        else:
            out.append(jnp.where(miss, 1e9 if side == "l" else -1e9, v))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=4, donate_argnums=0)
def _encode_into(planes, values, missing, row0, side):
    """``planes`` with the rows from ``row0`` on replaced by a block's,
    encoded, in place."""
    return tuple(lax.dynamic_update_slice_in_dim(p, e, row0, axis=0)
                 for p, e in zip(planes, _encode(values, missing, side)))


@jax.jit
def _take(block, pi, row0):
    """Raw rows of indices ``pi`` from ``block`` (values, missing), the
    side's rows from ``row0`` on, and which of ``pi`` lie in it."""
    n = block[1][0].shape[0]
    inside = (pi >= row0) & (pi < row0 + n)
    i = jnp.clip(pi - row0, 0, n - 1)
    return jax.tree.map(lambda b: b[i], block), inside


@functools.partial(jax.jit, donate_argnums=0)
def _merge(rows, taken):
    """Raw rows ``rows`` with ``taken``'s (``_take``) where they lie in
    its block, in place."""
    new, inside = taken
    return jax.tree.map(
        lambda old, b: jnp.where(
            inside.reshape((-1,) + (1,) * (old.ndim - 1)), b, old),
        rows, new)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _pick(k, n, n_partner, n_feats):
    """Each of ``n`` rows' partner among ``n_partner``, drawn as ``_rows``
    splits its key."""
    return jax.random.randint(jax.random.split(k, 5 + n_feats)[1], (n,), 0,
                              n_partner)


class Deployment:
    """One deployment file with its calibrated thresholds."""

    def __init__(self, config: dict):
        self.config = config
        self.features = config["features"]
        self.clauses = [list(c) for c in config["clauses"]]
        self.boundary_feature = self.clauses[0][0]
        if self.features[self.boundary_feature]["kind"] != "embed":
            raise ValueError("clause 0's first feature must be an embed")
        self._static = (
            tuple((f["kind"], int(f.get("width", 0)), float(f.get("range", 0)))
                  for f in self.features),
            self.boundary_feature, float(config["planted_noise"]["embed"]),
            float(config["planted_noise"]["scalar"]),
            float(config["missing_rate"]), float(config["boundary_share"]),
            float(config["boundary_margin"]))
        self.thetas = _thresholds(self._static, json.dumps(self.clauses),
                                  json.dumps(config["theta_quantile"]),
                                  int(config["calibration_seed"]))

    # -- generation -----------------------------------------------------------

    @staticmethod
    def draws(seed: int, tags: tuple, n: int, devices: list) -> list:
        """A side of ``n`` rows split evenly over ``devices``: per shard,
        the draws of its blocks of at most ``BLOCK_ROWS`` rows, keyed as
        the module docstring says."""
        if n % len(devices):
            raise ValueError(f"{n} rows do not split evenly over "
                             f"{len(devices)} chips")
        per = n // len(devices)
        return [[draw(seed, (*tags, s, j) if s or j else tags,
                      min(BLOCK_ROWS, per - r0), dev)
                 for j, r0 in enumerate(range(0, per, BLOCK_ROWS))]
                for s, dev in enumerate(devices)]

    def pick(self, d: Draw, n_partner: int):
        """Each of ``d``'s rows' partner among the other side's
        ``n_partner`` rows: row indices, on ``d``'s device."""
        return _pick(d.key, d.n, int(n_partner), len(self.features))

    def rows(self, d: Draw, planted_share: float = 0.0,
             near: Rows | None = None) -> Rows:
        """``d``'s rows; with ``near`` (the raw partner rows it picked) a
        ``planted_share`` of them lie near their partner, and a
        ``boundary_share`` of all rows are boundary rows of such a pair."""
        near = (None, None) if near is None else \
            (tuple(near.values), tuple(near.missing))
        values, missing = _rows(d.key, *near, float(planted_share),
                                float(self.thetas[0]), d.n, self._static)
        return Rows(list(values), list(missing))

    def _zeros(self, n: int, device, extra: int) -> tuple:
        """A float32 array a feature of ``n`` rows, embeds ``extra``
        columns wider than their width, and (``extra`` 0) a bool missing
        mask a feature, made on ``device`` itself (``jnp.zeros(device=)``
        makes them on the default device and copies)."""
        feats = self._static[0]
        with jax.default_device(device):
            planes = tuple(jnp.zeros((n, d + extra) if kind == "embed"
                                     else (n,), jnp.float32)
                           for kind, d, _ in feats)
            return planes if extra else \
                (planes, tuple(jnp.zeros((n,), bool) for _ in feats))

    def side(self, shards: list, side: str, mesh, partners: list = (),
             near: list | None = None, planted_share: float = 0.0) -> tuple:
        """The side drawn as ``shards`` (``draws``), and rows of it.

        Returns its planes, one ``jax.Array`` a feature with its rows split
        over ``mesh``'s ``"data"`` axis (``P("data", None)`` embeds,
        ``P("data")`` scalars), and for each array of row indices in
        ``partners`` (``pick``) those rows raw, on that array's device.
        ``near`` holds each draw's partner rows on the other side (one
        ``Rows`` a draw, in order), where a ``planted_share`` of its rows
        are planted.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        near = iter(near or ())
        # each array's rows, on its device, gathered from every block
        homes = [next(iter(pi.devices())) for pi in partners]
        got = [self._zeros(pi.shape[0], home, 0)
               for pi, home in zip(partners, homes)]
        done, row0 = [], 0
        for blocks in shards:
            planes, at = self._zeros(sum(d.n for d in blocks),
                                     blocks[0].device, 2), 0
            for d in blocks:
                raw = self.rows(d, planted_share, next(near, None))
                block = (tuple(raw.values), tuple(raw.missing))
                for k, (pi, home) in enumerate(zip(partners, homes)):
                    got[k] = _merge(got[k], jax.device_put(_take(
                        block, jax.device_put(pi, d.device), row0), home))
                planes = _encode_into(planes, *block, at, side)
                del raw, block
                at, row0 = at + d.n, row0 + d.n
            done.append(planes)
        return [jax.make_array_from_single_device_arrays(
            (row0,) + parts[0].shape[1:],
            NamedSharding(mesh, P("data", None) if parts[0].ndim == 2
                          else P("data")), list(parts))
            for parts in zip(*done)], [Rows(list(v), list(m))
                                       for v, m in got]


@functools.cache
def _thresholds(static: tuple, clauses: str, quantiles: str,
                seed: int) -> tuple:
    """Clause c's threshold: the ``quantiles[c]`` quantile of its first
    feature's distance over random pairs of calibration rows, as the
    float32 value the kernel compares against.  Cached: a pure function of
    the deployment, made once per process."""
    def rows(tag):
        values, missing = _rows(key(seed, CALIBRATION_TAG, tag), None, None,
                                0.0, 0.0, CALIBRATION_ROWS, static)
        return Rows(list(values), list(missing))
    a, b = rows(0).encode("l"), rows(1).encode("r")
    ki, kj = jax.random.split(key(seed, CALIBRATION_TAG, 2))
    i = jax.random.randint(ki, (CALIBRATION_PAIRS,), 0, CALIBRATION_ROWS)
    j = jax.random.randint(kj, (CALIBRATION_PAIRS,), 0, CALIBRATION_ROWS)
    out = []
    for clause, q in zip(json.loads(clauses), json.loads(quantiles)):
        dist = jnp.concatenate([
            _pair_dist(a[clause[0]], b[clause[0]], i[s:s + CALIBRATION_BLOCK],
                       j[s:s + CALIBRATION_BLOCK])
            for s in range(0, CALIBRATION_PAIRS, CALIBRATION_BLOCK)])
        # the order statistic below the quantile, exactly: no interpolation,
        # so every backend reads the same float32 value
        rank = int(np.floor(q * (CALIBRATION_PAIRS - 1)))
        out.append(float(np.asarray(jnp.sort(dist)[rank])))
    return tuple(out)


@jax.jit
def _pair_dist(a, b, i, j):
    """Distances of the pairs ``(a[i[k]], b[j[k]])``."""
    x, y = a[i], b[j]
    if x.ndim == 2:
        return jnp.clip(0.5 - 0.5 * _rowdot(x, y)[:, 0], 0.0, 1.0)
    return jnp.clip(jnp.abs(x - y), 0.0, 1.0)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _rows(k, near_values, near_missing, planted_share, theta0, n, static):
    """The jitted body of ``Deployment.rows``: ``near_*`` are the raw
    partner rows, one a row, or ``None``."""
    feats, fb, noise_e, noise_s, missing_rate, boundary_share, margin = static
    # the second key picks the partners (``_pick``)
    k_plant, _, k_miss, k_want, k_bound, *k_feat = \
        jax.random.split(k, 5 + len(feats))
    has_partner = near_values is not None
    if has_partner:
        plant = jax.random.uniform(k_plant, (n,)) < planted_share
    values = []
    for fi, (kind, d, span) in enumerate(feats):
        k1, k2, k3 = jax.random.split(k_feat[fi], 3)
        if kind == "embed":
            v = _unit(jax.random.normal(k1, (n, d), jnp.float32))
            if has_partner:
                near = near_values[fi] + noise_e / np.sqrt(d) * \
                    jax.random.normal(k2, (n, d), jnp.float32)
                v = jnp.where(plant[:, None], _unit(near), v)
            if fi == fb:
                signs = jnp.where(jax.random.bernoulli(k3, 0.5, (n, d)),
                                  1.0, -1.0)
                v = saturate(v, signs)
        else:
            v = jax.random.uniform(k1, (n,), jnp.float32, 0.0, span)
            if has_partner:
                near = near_values[fi] + noise_s * \
                    jax.random.normal(k2, (n,), jnp.float32)
                v = jnp.where(plant, near, v)
        values.append(v)
    missing = [jax.random.uniform(km, (n,)) < missing_rate
               for km in jax.random.split(k_miss, len(feats))]
    if has_partner:
        partner_ok = ~functools.reduce(jnp.logical_or, near_missing)
        boundary = plant & partner_ok & \
            (jax.random.uniform(k_want, (n,)) < boundary_share)
        rows = _boundary_rows(near_values[fb], k_bound, theta0, margin)
        values[fb] = jnp.where(boundary[:, None], rows, values[fb])
        missing = [m & ~boundary for m in missing]
    return tuple(values), tuple(missing)


def _boundary_rows(p, k, theta0: float, margin: float):
    """Rows at distance ``theta0 - margin`` from partners ``p`` whose
    remainders agree in sign with the partners' (module doc)."""
    t = 1.0 - 2.0 * (theta0 - margin)
    pp = _rowdot(p, p)
    u = jax.random.normal(k, p.shape, jnp.float32)
    u = _unit(u - _rowdot(u, p) / pp * p)
    r = (t / pp) * p + jnp.sqrt(1.0 - t * t / pp) * u
    r = saturate(r, remainder_signs(p))
    # put the dot product on its target through the partner's largest
    # component (that one component's remainder is given up)
    big = jnp.argmax(jnp.abs(p), axis=1)
    at = jnp.arange(p.shape[1])[None, :] == big[:, None]
    p_big = jnp.sum(jnp.where(at, p, 0.0), axis=1, keepdims=True)
    return r + jnp.where(at, (t - _rowdot(p, r)) / p_big, 0.0)
