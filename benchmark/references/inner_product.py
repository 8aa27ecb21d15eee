"""The plain reference under the inner-product score: float64
brute-force maximum-inner-product search (what FAISS ``IndexFlatIP``
computes), for a configuration that names it
(``"modules": {"reference": "inner_product"}``).

The benchmark's own copy of the contract, written from its documented
semantics and importing nothing of the program:

- the score of row x for query q is s(q, x) = sum_a q_a x_a, float64;
- neighbours are the k rows of LARGEST s, ordered by (s descending, id
  DESCENDING on ties);
- predicted label = majority over the k selected, tie -> LARGER label;
- fewer than k rows: pad ids with -1 (padding does not vote);
- checksum: FNV-1a over the label, then each neighbour id + 1, all as
  unsigned 64-bit (``benchmark.reference.fnv1a``: the same checksum
  whatever the score);
- ``dists`` carries s itself, in that order; padded slots are -inf
  (the worst score, as +inf is the worst distance).

``knn_plain`` is the contract the slow way: every query against every
row, the products summed by ``einsum`` in row blocks. ``knn_exact``
gives the same answers at the cells' sizes in seconds: a float64 BLAS
pass (``queries @ rows.T``) keeps the k + SLACK best of every query,
those are rescored by the plain pass's own expression, and the answer
stands only when the k-th rescored score clears the screen's horizon
(the worst score it kept) by more than the BLAS pass's rounding bound;
otherwise that query is redone by ``knn_plain``. The tests hold the two
to each other, ties included.

``dist_scale``: a score may be zero or negative, so |reference| is no
denominator for ``dist_rel_err_max``. The error of an inner product
scales with |q||x|, which the scores alone do not tell; of a query's k
best the largest in magnitude does, within a small factor (on this
configuration's rows the best score is ~23 where |q||x| is ~70), so the
scale is the answer's largest |s|. Under it two float64 summation
orders of 200 products differ by at most 200 * 2^-53 * |q||x| = 1.7e-12,
7e-14 of the scale, two decades and more under the 1e-11 limit, and a
score accumulated in float32 errs by some 2^-24 * sqrt(200) of |q||x|,
~1e-6 of the scale, five decades over it (the control's, taken from
bfloat16 rows, by 1e-3).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List

import numpy as np

from benchmark.reference import Answer, fnv1a, vote

SLACK = 32            # candidates kept beyond k by the BLAS pass
_BLOCK_ROWS = 1 << 16        # the BLAS screen's row blocks
_PLAIN_ROWS = 1 << 13        # the plain pass's
_THREADS = 8

__all__ = ["Answer", "knn_plain", "knn_exact", "dist_scale"]


def dist_scale(want: np.ndarray) -> np.ndarray:
    """The denominator of ``dist_rel_err_max``: the largest |s| of the
    answer's finite scores (the module docstring says why)."""
    return np.maximum(np.abs(want).max(), np.finfo(np.float64).tiny)


def _scores(q: np.ndarray, rows: np.ndarray) -> np.ndarray:
    return np.einsum("na,a->n", rows, q)


def _answer(scores: np.ndarray, ids: np.ndarray, labels: np.ndarray,
            k: int) -> Answer:
    """Candidates (any superset of the true top-k) -> the answer."""
    order = np.lexsort((-ids, -scores))[:min(k, len(ids))]
    sel_i, sel_s = ids[order].astype(np.int64), scores[order]
    label = vote(labels[sel_i])
    if len(sel_i) < k:
        pad = k - len(sel_i)
        sel_i = np.concatenate([sel_i, np.full(pad, -1, np.int64)])
        sel_s = np.concatenate([sel_s, np.full(pad, -np.inf)])
    return Answer(label, sel_i, sel_s, fnv1a(label, sel_i))


def knn_plain(rows: np.ndarray, labels: np.ndarray, queries: np.ndarray,
              ks) -> List[Answer]:
    """Every query against every row, in row blocks."""
    rows = np.asarray(rows, np.float64)
    queries = np.asarray(queries, np.float64)
    labels = np.asarray(labels, np.int64)
    n = rows.shape[0]
    ids = np.arange(n, dtype=np.int64)
    out = []
    with ThreadPoolExecutor(_THREADS) as pool:     # row blocks side by side
        for q, k in zip(queries, ks):
            s = np.empty(n, np.float64)

            def fill(a: int, q=q, s=s) -> None:
                s[a:a + _PLAIN_ROWS] = _scores(q, rows[a:a + _PLAIN_ROWS])
            list(pool.map(fill, range(0, n, _PLAIN_ROWS)))
            out.append(_answer(s, ids, labels, int(k)))
    return out


def knn_exact(rows: np.ndarray, labels: np.ndarray, queries: np.ndarray,
              ks) -> List[Answer]:
    """Same answers as :func:`knn_plain`, screened by a float64 BLAS pass."""
    rows = np.asarray(rows, np.float64)
    queries = np.ascontiguousarray(queries, np.float64)
    labels = np.asarray(labels, np.int64)
    ks = np.asarray(ks, np.int64)
    n, na = rows.shape
    nq = len(queries)
    if nq == 0:
        return []
    kk = int(min(n, ks.max() + SLACK))
    if kk >= n:
        return knn_plain(rows, labels, queries, ks)
    qn = np.einsum("qa,qa->q", queries, queries)
    starts = list(range(0, n, _BLOCK_ROWS))

    def screen(a: int):
        blk = rows[a:a + _BLOCK_ROWS]
        s = queries @ blk.T                                       # (q, b)
        keep = min(kk, s.shape[1])
        idx = np.argpartition(-s, keep - 1, axis=1)[:, :keep]
        return (np.take_along_axis(s, idx, axis=1), idx + a,
                float(np.einsum("na,na->n", blk, blk).max()))

    with ThreadPoolExecutor(_THREADS) as pool:
        parts = list(pool.map(screen, starts))
    cand_s = np.concatenate([p[0] for p in parts], axis=1)
    cand_i = np.concatenate([p[1] for p in parts], axis=1)
    dn_max = max(p[2] for p in parts)
    top = np.argpartition(-cand_s, kk - 1, axis=1)[:, :kk]
    cand_s = np.take_along_axis(cand_s, top, axis=1)
    cand_i = np.take_along_axis(cand_i, top, axis=1)
    # Rounding of the BLAS dot: na products of magnitude at most |q||x|
    # in whatever order the library sums them; 64x headroom on the unit
    # bound. A row the screen dropped scored no more than the horizon
    # by the screen's own arithmetic, so no more than horizon + err by
    # the plain pass's.
    err = 64.0 * np.finfo(np.float64).eps * (na + 2) \
        * np.sqrt(qn * dn_max)
    out = []
    for j in range(nq):
        ids = cand_i[j].astype(np.int64)
        ans = _answer(_scores(queries[j], rows[ids]), ids, labels,
                      int(ks[j]))
        kth = ans.dists[min(int(ks[j]), kk) - 1]
        if not kth > cand_s[j].min() + err[j]:
            ans = knn_plain(rows, labels, queries[j:j + 1], ks[j:j + 1])[0]
        out.append(ans)
    return out
