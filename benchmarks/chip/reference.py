"""Plain reference for step ②, and the comparison that decides ``correct``.

The reference evaluates the CNF straight from its definition on the same
planes the program was given (the benchmark made them and copies back only
the rows it checks; nothing the program made is read): per feature the
distance ``clip(0.5 - 0.5 a.b, 0, 1)`` for embeds and
``clip(|x - y|, 0, 1)`` for scalars, per clause the least distance over
its features, and a pair is a candidate when every clause's distance is
at most its threshold.  It computes in float32 and computes
again in float64 every pair that float32 places within ``NEAR`` of a
threshold, so each decision is the float64 one.  Its score for a pair is
``max over clauses (clause distance - threshold)``: a candidate scores
``<= 0``, and ``|score|`` is how far the pair lies from the decision.

The comparison takes the pairs the program returned for a query, restricted
to the checked rows and to the R columns of the band steps it completed, and
reads two numbers:

* ``gap``: the largest ``|score|`` of a pair on which the program and the
  reference disagree, 0 when they agree on every pair, infinite when the
  program returned a pair outside the checked region.  Float32 rounding can
  flip only pairs within about 1e-8 of a threshold; a lower-precision
  product flips the boundary rows, which sit ``boundary_margin`` inside it
  (``planes.py``); a lost or altered answer flips pairs far from it.
* ``duplicates``: pairs the program returned more than once.
"""

from __future__ import annotations

import numpy as np

BLOCK_ELEMS = 1 << 22          # score-matrix elements per block
# float32 products of these rows are off by far less than this; a pair
# scored closer than this to a threshold is scored again in float64
NEAR = 1e-4


def _dist(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Float32 distances of rows ``a`` to rows ``b``."""
    if a.ndim == 2:
        d = a @ b.T
        d *= -0.5
        d += 0.5
    else:
        d = np.abs(a[:, None] - b[None, :])
    np.clip(d, 0.0, 1.0, out=d)
    return d


def _dist_pairs(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Float64 distances of the pairs ``(a[k], b[k])``."""
    if a.ndim == 2:
        d = 0.5 - 0.5 * np.einsum("ij,ij->i", a.astype(np.float64),
                                  b.astype(np.float64))
    else:
        d = np.abs(a.astype(np.float64) - b)
    return np.clip(d, 0.0, 1.0)


def _score(dist, clauses, thetas):
    score = None
    for clause, theta in zip(clauses, thetas):
        cd = dist[clause[0]]
        for f in clause[1:]:
            cd = np.minimum(cd, dist[f])
        s = cd - theta
        score = s if score is None else np.maximum(score, s)
    return score


def scores(planes_l: list, planes_r: list, clauses, thetas, rows: np.ndarray,
           n_cols: int):
    """Yield ``(row_block, score_matrix)`` over ``rows`` x R columns
    ``[0, n_cols)``, in blocks of rows, where ``planes_l`` holds L's rows
    ``rows`` in that order: float64 scores, exact to float64 rounding
    within ``NEAR`` of a threshold and to float32 rounding elsewhere, where
    no rounding can change a decision."""
    used = sorted({f for c in clauses for f in c})
    right = {f: planes_r[f][:n_cols] for f in used}
    step = max(1, BLOCK_ELEMS // max(n_cols, 1))
    for r0 in range(0, rows.size, step):
        at = np.arange(r0, min(r0 + step, rows.size))
        score = _score({f: _dist(planes_l[f][at], right[f]) for f in used},
                       clauses, thetas).astype(np.float64)
        ii, jj = np.nonzero(np.abs(score) < NEAR)
        if ii.size:
            exact = {f: _dist_pairs(planes_l[f][at[ii]], right[f][jj])
                     for f in used}
            score[ii, jj] = _score(exact, clauses, thetas)
        yield rows[at], score


def compare(pairs: np.ndarray, planes_l: list, planes_r: list, clauses,
            thetas, rows: np.ndarray, n_cols: int, n_l: int) -> dict:
    """Compare one query's program pairs ``(k, 2)`` with the reference over
    L rows ``rows`` x R columns ``[0, n_cols)``, where ``planes_l`` holds
    L's rows ``rows`` in that order and L has ``n_l`` rows.  ``pairs`` may
    hold rows outside ``rows``; those are not judged."""
    rows = np.asarray(rows, np.int64)
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    pos = np.full(n_l, -1, np.int64)
    pos[rows] = np.arange(rows.size)
    inside = (pairs[:, 0] >= 0) & (pairs[:, 0] < pos.size) & \
        (pairs[:, 1] >= 0) & (pairs[:, 1] < n_cols)
    out = {"gap": 0.0 if inside.all() else float("inf"), "duplicates": 0,
           "mismatches": int((~inside).sum()), "reference": 0}
    pairs = pairs[inside]
    pairs = pairs[pos[pairs[:, 0]] >= 0]
    out["candidates"] = int(pairs.shape[0])
    flat = pos[pairs[:, 0]] * n_cols + pairs[:, 1]
    uniq = np.unique(flat)
    out["duplicates"] = int(flat.size - uniq.size)
    for blk, score in scores(planes_l, planes_r, clauses, thetas, rows,
                             n_cols):
        b0 = pos[blk[0]]
        lo, hi = b0 * n_cols, (b0 + blk.size) * n_cols
        got = np.zeros(score.size, bool)
        sel = uniq[(uniq >= lo) & (uniq < hi)] - lo
        got[sel] = True
        want = (score <= 0.0).ravel()
        out["reference"] += int(want.sum())
        diff = got != want
        if diff.any():
            out["mismatches"] += int(diff.sum())
            out["gap"] = max(out["gap"],
                             float(np.abs(score.ravel()[diff]).max()))
    return out
