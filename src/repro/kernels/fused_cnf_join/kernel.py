"""Fused CNF-join Pallas TPU kernel.

Evaluates a featurized decomposition (CNF with per-clause tied thresholds,
Lemma D.1 form) over an (L_TILE x R_TILE) block of the cross product in one
pass:

  * vector features (semantic / word-overlap):  dist = 0.5 - 0.5 * (A @ B^T)
    — an MXU matmul over embeddings staged in VMEM (augmented [e, m, 1] /
    [e, 1, m] rows encode missing values, see repro.core.featurize);
  * scalar features (arithmetic / date):        dist = |x - y|  (VPU);
  * per clause: min over member features, compare against the clause
    threshold; AND across clauses;
  * output: uint32 bitmask packed along the R dimension (32 pairs/word) —
    n^2/8 bytes of HBM traffic instead of F * n^2 * 4 for the unfused
    XLA lowering that materializes every feature's distance plane.

The clause structure and thresholds are *compile-time constants* (closed
over), so the kernel body unrolls into a static sequence of matmuls +
vector ops — the only data-dependent control flow is the optional
``early_reject`` tile skip below.

Output layout.  A tile's packed words are ``tr // 32`` per L row — 4 at
tr=128 — which no TPU block may hold as its lane dim.  The kernel
therefore writes each tile *transposed*, as a lane-dense (P, TL) int32
block of a (n_r // tr, P, n_l) array (P = max(8, tr // 32) sublane-aligned
word rows, the surplus rows zero), and ``cnf_join_block`` turns that back
into the (n_l, n_r // 32) uint32 mask with one XLA transpose.  The bits
are packed on the MXU: the 0/1 pass plane times a constant (2P, TR) matrix
of powers of two gives each word's low and high 16 bits as exact integer
sums in f32 (bf16 holds 0/1 and 2^b exactly; every sum is < 2^16).

``early_reject=True`` short-circuits the conjunction: the first clause is
evaluated unconditionally, and the remaining clauses run under a
``pl.when`` predicated on the first clause passing *somewhere* in the
tile.  A tile (and hence a whole band, when every tile of the band is
dead) whose first-conjunct popcount is zero writes a zero mask without
touching the later clauses' planes.  The candidate set is identical
either way — skipped work can only be ANDed against an all-false mask.

``with_evals=True`` adds an int32 scalar output (in SMEM, accumulated over
the whole grid) counting the clauses actually evaluated, summed over
tiles (1 per tile rejected early, len(clauses) otherwise), so hosts can
charge conjunct FLOPs honestly instead of assuming the short-circuit
saved anything.

VMEM budget per grid step (TL=256, TR=512, D=256, F=2):
  emb_l  F*TL*D*4  = 512 KiB     emb_r  F*TR*D*4 = 1   MiB
  planes 2*TL*TR*4 = 1   MiB     out    TL*TR/8  = 16 KiB      < 4 MiB total.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# feature kind tags used in the static clause structure
VEC, SCAL = 0, 1


def _clause_min_dist(emb_l_ref, emb_r_ref, scal_l_ref, scal_r_ref, members):
    """min over a clause's member features of the (TL, TR) distance plane."""
    dmin = None
    for kind, fi in members:
        if kind == VEC:
            a = emb_l_ref[fi, :, :]                       # (TL, D)
            b = emb_r_ref[fi, :, :]                       # (TR, D)
            # full f32 contraction: the candidate set must match the f32
            # numpy oracle, which a single bf16 MXU pass would not
            dot = jax.lax.dot_general(
                a, b, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)       # (TL, TR) MXU
            d = jnp.clip(0.5 - 0.5 * dot, 0.0, 1.0)
        else:
            x = scal_l_ref[fi, :]                         # (TL,)
            y = scal_r_ref[fi, :]                         # (TR,)
            d = jnp.clip(jnp.abs(x[:, None] - y[None, :]), 0.0, 1.0)
        dmin = d if dmin is None else jnp.minimum(dmin, d)
    return dmin


def _word_rows(tr: int) -> int:
    """Sublane-aligned rows of packed words per tile (>= tr // 32)."""
    return max(8, tr // 32)


def _bit_weights(tr: int):
    """(2P, TR) bf16 packing matrix: row w holds 2^b at column 32w + b for
    the low bits b < 16, row P + w holds 2^(b-16) for the high bits."""
    p = _word_rows(tr)
    j = np.arange(tr)
    word, bit = j // 32, j % 32
    hi = bit >= 16
    w = np.zeros((2 * p, tr), np.float32)
    w[word + p * hi, j] = 2.0 ** (bit - 16 * hi)
    return jnp.asarray(w, jnp.bfloat16)


def _pack_tile(ok, bits_ref):
    """Pack a boolean (TL, TR) tile to transposed int32 words (P, TL)."""
    p = bits_ref.shape[0] // 2
    halves = jax.lax.dot_general(
        bits_ref[...], ok.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # (2P, TL), exact
    lo = halves[:p].astype(jnp.int32)
    hi = halves[p:].astype(jnp.int32)
    return lo | (hi << 16)


def _cnf_kernel(bits_ref, emb_l_ref, emb_r_ref, scal_l_ref, scal_r_ref,
                out_ref, evals_ref=None, *, clauses, thetas, early_reject):
    """clauses: tuple of clauses, each a tuple of (kind, idx); thetas: floats."""
    n_c = len(clauses)
    if evals_ref is not None:
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            evals_ref[0, 0] = jnp.int32(0)

    def pass_matrix(ci):
        dmin = _clause_min_dist(emb_l_ref, emb_r_ref, scal_l_ref, scal_r_ref,
                                clauses[ci])
        return dmin <= thetas[ci]

    def full(ok0=None):
        ok = ok0
        for ci in range(0 if ok0 is None else 1, n_c):
            pas = pass_matrix(ci)
            ok = pas if ok is None else jnp.logical_and(ok, pas)
        out_ref[...] = _pack_tile(ok, bits_ref)
        if evals_ref is not None:
            evals_ref[0, 0] += jnp.int32(n_c)

    if not early_reject or n_c < 2:
        full()
        return

    ok0 = pass_matrix(0)
    live = jnp.any(ok0)

    @pl.when(live)
    def _():
        full(ok0)

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)
        if evals_ref is not None:
            evals_ref[0, 0] += jnp.int32(1)


def cnf_join_block(emb_l, emb_r, scal_l, scal_r, clauses, thetas, *,
                   tl: int = 256, tr: int = 512, interpret: bool = False,
                   early_reject: bool = False, with_evals: bool = False):
    """Launch the fused kernel over the full (n_l x n_r) plane.

    emb_l: (F_v, n_l, D) f32   emb_r: (F_v, n_r, D) f32
    scal_l: (F_s, n_l) f32     scal_r: (F_s, n_r) f32
    clauses: static structure (tuple of tuples of (kind, idx))
    thetas: tuple of python floats (compile-time constants)
    early_reject: predicate later clauses on the first clause passing
        somewhere in the tile (candidate set unchanged; see module doc)
    with_evals: also return an int32 scalar — clauses evaluated, summed
        over the (n_l//tl, n_r//tr) tiles

    Returns packed uint32 mask (n_l, n_r // 32); with ``with_evals`` a
    ``(mask, evals)`` pair.
    """
    fv, n_l, d = emb_l.shape
    n_r = emb_r.shape[1]
    if tr % 32 != 0:
        raise ValueError(
            f"tr={tr} must be a multiple of 32: the output bitmask packs "
            f"32 R-neighbours per uint32 word and a ragged tile would be "
            f"silently truncated")
    if n_l % tl != 0 or n_r % tr != 0:
        raise ValueError(
            f"(n_l={n_l}, n_r={n_r}) must be multiples of tiles "
            f"(tl={tl}, tr={tr}); pad via ops.pack_features")
    grid = (n_l // tl, n_r // tr)
    bits = _bit_weights(tr)
    p = _word_rows(tr)
    in_specs = [
        pl.BlockSpec(bits.shape, lambda i, j: (0, 0)),
        pl.BlockSpec((fv, tl, d), lambda i, j: (0, i, 0)),
        pl.BlockSpec((fv, tr, d), lambda i, j: (0, j, 0)),
        pl.BlockSpec((max(scal_l.shape[0], 1), tl), lambda i, j: (0, i)),
        pl.BlockSpec((max(scal_r.shape[0], 1), tr), lambda i, j: (0, j)),
    ]
    out_specs = [pl.BlockSpec((None, p, tl), lambda i, j: (j, 0, i))]
    out_shape = [jax.ShapeDtypeStruct((grid[1], p, n_l), jnp.int32)]
    if with_evals:
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((1, 1), jnp.int32))
    kernel = functools.partial(_cnf_kernel, clauses=tuple(clauses),
                               thetas=tuple(float(t) for t in thetas),
                               early_reject=early_reject)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="fused_cnf_join",
    )(bits, emb_l, emb_r, scal_l, scal_r)
    # (n_r//tr, P, n_l) transposed words -> (n_l, n_r//32) row-major mask
    words = outs[0][:, : tr // 32, :].transpose(2, 0, 1)
    mask = jax.lax.bitcast_convert_type(words.reshape(n_l, n_r // 32),
                                        jnp.uint32)
    if with_evals:
        return mask, outs[1][0, 0]
    return mask
