"""Threshold-sweep Pallas kernel.

Evaluates G candidate threshold vectors against k labeled sample rows of
clause distances in one pass — the inner loop of Eq 1 / Eq 4 (scaffold cost
estimation and final threshold selection).  For each grid row g:

    pos[g] = sum_i valid_i * labels_i * AND_c (cd[i,c] <= theta[g,c])
    sel[g] = sum_i valid_i *            AND_c (cd[i,c] <= theta[g,c])

The (TG x TK) pass/fail plane is built on the VPU from C unrolled broadcast
compares of a sample row (1, TK) against a threshold column (TG, 1); both
counts are lane reductions of that plane.  Output accumulates across the k
grid dimension (out block revisited; initialized at program_id(1)==0).

``valid`` masks padded sample rows *explicitly*.  The historical scheme
padded cd rows with +inf and relied on ``inf <= theta`` being false — but
``inf <= inf`` is true, so any non-finite threshold column (which
``min_fpr_thresholds`` emits when a sample has no positives) or +inf
distance row inflated ``sel`` by the pad count.  Pad rows now carry
valid = 0 and count nothing under *any* threshold, finite or not.

Layout: the wrapper hands the kernel the samples clause-major, (C, k), and
the per-row weights as a (2, k) stack [labels * valid, valid], so every
block is lane-dense along the sample axis.  Output (G, 128) f32, col 0 =
positive count, col 1 = selected count (lane-padded for TPU tiling); both
are exact integer sums of 0/1 terms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sweep_kernel(cd_ref, w_ref, th_ref, out_ref, *, n_clauses):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ok = None
    for c in range(n_clauses):                       # static unroll
        d = cd_ref[c:c + 1, :]                       # (1, TK)
        t = th_ref[:, c:c + 1]                       # (TG, 1)
        pas = d <= t                                 # (TG, TK)
        ok = pas if ok is None else jnp.logical_and(ok, pas)
    okf = ok.astype(jnp.float32)
    # w row 0 = labels * valid, row 1 = valid: a padded sample row counts
    # in neither sum, regardless of the threshold values (inf <= inf!)
    pos = jnp.sum(okf * w_ref[0:1, :], axis=1, keepdims=True)   # (TG, 1)
    sel = jnp.sum(okf * w_ref[1:2, :], axis=1, keepdims=True)
    col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] += jnp.where(col == 0, pos, jnp.where(col == 1, sel, 0.0))


def threshold_sweep(cd, labels, valid, thetas, *, tg: int = 256, tk: int = 512,
                    interpret: bool = False):
    """cd: (k, C) f32; labels: (k,) f32 in {0,1}; valid: (k,) f32 in {0,1}
    (0 marks padded rows); thetas: (G, C) f32.

    k and G must be tile multiples (pad labels/valid with 0; cd pad values
    are arbitrary — the valid mask, not the compare, excludes them; pad
    thetas rows with -inf so padded grid rows select nothing real).
    Returns (G, 128) f32; [:, 0] = positives, [:, 1] = selected.
    """
    k, c = cd.shape
    g = thetas.shape[0]
    assert k % tk == 0 and g % tg == 0
    weights = jnp.stack([labels * valid, valid])     # (2, k)
    kernel = functools.partial(_sweep_kernel, n_clauses=c)
    return pl.pallas_call(
        kernel,
        grid=(g // tg, k // tk),
        in_specs=[
            pl.BlockSpec((c, tk), lambda i, j: (0, j)),
            pl.BlockSpec((2, tk), lambda i, j: (0, j)),
            pl.BlockSpec((tg, c), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tg, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, 128), jnp.float32),
        interpret=interpret,
    )(cd.T, weights, thetas)
