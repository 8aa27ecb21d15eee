"""The plain reference under the cosine score: float64 brute-force
nearest neighbours by angular distance (what ann-benchmarks computes for
its ``angular`` datasets: ``1 - dot(a, b) / (|a| |b|)``), for a
configuration that names it (``"modules": {"reference": "cosine"}``).

The benchmark's own copy of the contract, written from its documented
semantics and importing nothing of the program:

- the score of row x for query q is
  s(q, x) = (sum_a q_a x_a) / (sqrt(sum_a q_a^2) * sqrt(sum_a x_a^2)),
  every sum, root, product and quotient in float64, on the rows and the
  query as they were given (nothing is normalised beforehand);
- a zero row or a zero query scores s = 0 against everything (FAISS's
  ``normalize_L2`` leaves a zero vector zero);
- neighbours are the k rows of LARGEST s, ordered by (s descending, id
  DESCENDING on ties);
- predicted label = majority over the k selected, tie -> LARGER label;
- fewer than k rows: pad ids with -1 (padding does not vote);
- checksum: FNV-1a over the label, then each neighbour id + 1, all as
  unsigned 64-bit (``benchmark.reference.fnv1a``: the same checksum
  whatever the score);
- ``dists`` carries the angular distance d = 1 - s, ASCENDING in that
  order; padded slots are +inf;
- exact copies of a row tie exactly; scaled copies are not promised to.

``knn_plain`` is the contract the slow way: every query against every
row by the expression above, in row blocks. ``knn_exact`` gives the same
answers at the cells' sizes in seconds: a float64 BLAS pass
(``queries @ rows.T`` over the norms) keeps the k + SLACK best of every
query, those are rescored by the plain pass's own expression, and the
answer stands only when the k-th rescored score clears the screen's
horizon (the worst score it kept) by more than the BLAS pass's rounding
bound; otherwise that query is redone by ``knn_plain``. The tests hold
the two to each other, ties and zero rows included.

``dist_scale``: d lies in [0, 2] and is ~1e-16 where a query is a row,
so |reference| is no denominator for ``dist_rel_err_max``. The error of
a cosine scales with the unit operands it is made of, 1: two float64
summation orders of 1536 products differ by at most 1536 * 2^-53 =
1.7e-13, two decades under the 1e-11 limit, and a score accumulated in
float32 errs by some 2^-24 * sqrt(1536) = 2e-6, five decades over it.
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
    """The denominator of ``dist_rel_err_max``: 1, the magnitude of the
    unit operands a cosine is made of (the module docstring says why)."""
    return np.ones_like(want)


def _norms(rows: np.ndarray) -> np.ndarray:
    return np.sqrt(np.einsum("na,na->n", rows, rows))


def _scores(q: np.ndarray, qnorm: float, rows: np.ndarray) -> np.ndarray:
    """The contract's expression, one query against ``rows``."""
    dot = np.einsum("na,a->n", rows, q)
    den = qnorm * _norms(rows)
    out = np.zeros(len(rows))
    np.divide(dot, den, out=out, where=den > 0)
    return out


def _answer(scores: np.ndarray, ids: np.ndarray, labels: np.ndarray,
            k: int) -> Answer:
    """Candidates (any superset of the true top-k) -> the answer."""
    order = np.lexsort((-ids, -scores))[:min(k, len(ids))]
    sel_i, sel_d = ids[order].astype(np.int64), 1.0 - scores[order]
    label = vote(labels[sel_i])
    if len(sel_i) < k:
        pad = k - len(sel_i)
        sel_i = np.concatenate([sel_i, np.full(pad, -1, np.int64)])
        sel_d = np.concatenate([sel_d, np.full(pad, np.inf)])
    return Answer(label, sel_i, sel_d, fnv1a(label, sel_i))


def knn_plain(rows: np.ndarray, labels: np.ndarray, queries: np.ndarray,
              ks) -> List[Answer]:
    """Every query against every row, in row blocks."""
    rows = np.asarray(rows, np.float64)
    queries = np.ascontiguousarray(queries, np.float64)
    labels = np.asarray(labels, np.int64)
    n = rows.shape[0]
    ids = np.arange(n, dtype=np.int64)
    qnorms = _norms(queries)
    out = []
    with ThreadPoolExecutor(_THREADS) as pool:     # row blocks side by side
        for q, qnorm, k in zip(queries, qnorms, ks):
            s = np.empty(n, np.float64)

            def fill(a: int, q=q, qnorm=qnorm, s=s) -> None:
                s[a:a + _PLAIN_ROWS] = _scores(q, qnorm,
                                               rows[a:a + _PLAIN_ROWS])
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
    qnorms = _norms(queries)
    qsafe = np.where(qnorms > 0, qnorms, 1.0)
    starts = list(range(0, n, _BLOCK_ROWS))

    def screen(a: int):
        blk = rows[a:a + _BLOCK_ROWS]
        dn = _norms(blk)
        s = queries @ blk.T                                       # (q, b)
        s /= np.where(dn > 0, dn, 1.0)[None, :]      # a zero row's dots are 0
        s /= qsafe[:, None]
        keep = min(kk, s.shape[1])
        idx = np.argpartition(-s, keep - 1, axis=1)[:, :keep]
        return np.take_along_axis(s, idx, axis=1), idx + a

    with ThreadPoolExecutor(_THREADS) as pool:
        parts = list(pool.map(screen, starts))
    cand_s = np.concatenate([p[0] for p in parts], axis=1)
    cand_i = np.concatenate([p[1] for p in parts], axis=1)
    top = np.argpartition(-cand_s, kk - 1, axis=1)[:, :kk]
    cand_s = np.take_along_axis(cand_s, top, axis=1)
    cand_i = np.take_along_axis(cand_i, top, axis=1)
    # Rounding of the screen against the plain expression: the BLAS dot
    # (na products of unit operands in whatever order the library sums
    # them) and two quotients where the plain pass takes a product and
    # one; 64x headroom on the unit bound. A row the screen dropped
    # scored no more than the horizon by the screen's own arithmetic, so
    # no more than horizon + err by the plain pass's.
    err = 64.0 * np.finfo(np.float64).eps * (na + 4)
    out = []
    for j in range(nq):
        ids = cand_i[j].astype(np.int64)
        ans = _answer(_scores(queries[j], qnorms[j], rows[ids]), ids,
                      labels, int(ks[j]))
        kth = 1.0 - ans.dists[min(int(ks[j]), kk) - 1]
        if not kth > cand_s[j].min() + err:
            ans = knn_plain(rows, labels, queries[j:j + 1], ks[j:j + 1])[0]
        out.append(ans)
    return out
