"""The plain reference: float64 brute-force k-nearest-neighbour search.

The benchmark's own copy of the output contract, written from its
documented semantics and importing nothing of the program:

- squared Euclidean distance in float64, difference form sum((q-x)^2);
- neighbours ordered by (distance ascending, id DESCENDING on ties);
- predicted label = majority over the k selected, tie -> LARGER label;
- fewer than k rows: pad ids with -1 (padding does not vote);
- checksum: FNV-1a over the label, then each neighbour id + 1, all as
  unsigned 64-bit.

``knn_plain`` is the straightforward version. ``knn_exact`` gives the
same answers at the cells' sizes in seconds: a float64 BLAS pass
(|q|^2 + |x|^2 - 2 q.x) picks k + SLACK candidates per query, those are
rescored in difference form, and the answer stands only when the
candidate horizon clears the k-th exact distance by more than the BLAS
pass's rounding bound — otherwise that query is redone by ``knn_plain``.
The tests hold the two to each other, ties included.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, NamedTuple

import numpy as np

FNV_BASIS = 1469598103934665603
FNV_PRIME = 1099511628211
_MASK = (1 << 64) - 1

SLACK = 32            # candidates kept beyond k by the BLAS pass
_BLOCK_ROWS = 1 << 16        # the BLAS screen's row blocks
_PLAIN_ROWS = 1 << 13        # the plain pass's: (q - x) stays in cache
_THREADS = 8


class Answer(NamedTuple):
    label: int
    ids: np.ndarray      # (k,) int64, -1 padded
    dists: np.ndarray    # (k,) float64, +inf padded
    checksum: int


def fnv1a(label: int, ids) -> int:
    c = FNV_BASIS
    c ^= int(label) & _MASK
    c = (c * FNV_PRIME) & _MASK
    for i in ids:
        c ^= (int(i) + 1) & _MASK
        c = (c * FNV_PRIME) & _MASK
    return c


def vote(labels: np.ndarray) -> int:
    if labels.size == 0:
        return -1
    uniq, counts = np.unique(labels, return_counts=True)
    return int(uniq[counts == counts.max()].max())


def _answer(dists: np.ndarray, ids: np.ndarray, labels: np.ndarray,
            k: int) -> Answer:
    """Candidates (any superset of the true top-k) -> the answer."""
    order = np.lexsort((-ids, dists))[:min(k, len(ids))]
    sel_i, sel_d = ids[order].astype(np.int64), dists[order]
    label = vote(labels[sel_i])
    if len(sel_i) < k:
        pad = k - len(sel_i)
        sel_i = np.concatenate([sel_i, np.full(pad, -1, np.int64)])
        sel_d = np.concatenate([sel_d, np.full(pad, np.inf)])
    return Answer(label, sel_i, sel_d, fnv1a(label, sel_i))


def _diff_dists(q: np.ndarray, rows: np.ndarray) -> np.ndarray:
    diff = rows - q[None, :]
    return np.einsum("na,na->n", diff, diff)


def knn_plain(rows: np.ndarray, labels: np.ndarray, queries: np.ndarray,
              ks) -> List[Answer]:
    """Every query against every row, difference form, in row blocks."""
    rows = np.asarray(rows, np.float64)
    queries = np.asarray(queries, np.float64)
    labels = np.asarray(labels, np.int64)
    n = rows.shape[0]
    ids = np.arange(n, dtype=np.int64)
    out = []
    with ThreadPoolExecutor(_THREADS) as pool:     # row blocks side by side
        for q, k in zip(queries, ks):
            d = np.empty(n, np.float64)

            def fill(a: int, q=q, d=d) -> None:
                d[a:a + _PLAIN_ROWS] = _diff_dists(
                    q, rows[a:a + _PLAIN_ROWS])
            list(pool.map(fill, range(0, n, _PLAIN_ROWS)))
            out.append(_answer(d, ids, labels, int(k)))
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
        dn = np.einsum("na,na->n", blk, blk)
        d = dn[None, :] + qn[:, None] - 2.0 * (queries @ blk.T)   # (q, b)
        keep = min(kk, d.shape[1])
        idx = np.argpartition(d, keep - 1, axis=1)[:, :keep]
        return (np.take_along_axis(d, idx, axis=1), idx + a,
                float(dn.max()))

    with ThreadPoolExecutor(_THREADS) as pool:
        parts = list(pool.map(screen, starts))
    cand_d = np.concatenate([p[0] for p in parts], axis=1)
    cand_i = np.concatenate([p[1] for p in parts], axis=1)
    dn_max = max(p[2] for p in parts)
    top = np.argpartition(cand_d, kk - 1, axis=1)[:, :kk]
    cand_d = np.take_along_axis(cand_d, top, axis=1)
    cand_i = np.take_along_axis(cand_i, top, axis=1)
    # Rounding of the expansion form: three float64 terms of magnitude
    # (qn + dn) and an na-term dot product; 64x headroom on the unit bound.
    err = 64.0 * np.finfo(np.float64).eps * (na + 2) * (qn + dn_max)
    out = []
    for j in range(nq):
        ids = cand_i[j].astype(np.int64)
        exact = _diff_dists(queries[j], rows[ids])
        ans = _answer(exact, ids, labels, int(ks[j]))
        kth = ans.dists[min(int(ks[j]), kk) - 1]
        if not kth < cand_d[j].max() - err[j]:
            ans = knn_plain(rows, labels, queries[j:j + 1], ks[j:j + 1])[0]
        out.append(ans)
    return out
