"""A test-only reference (``tests/benchmark_tests/test_seam.py``): the
output contract of ``benchmark/reference.py`` written the slow way,
query by query with no screen, and a ``dist_scale`` of its own. It
exists to show that ``check.py`` takes the reference from the cell."""

import numpy as np

from benchmark.reference import Answer, fnv1a, vote


def squared_l2(rows: np.ndarray, q: np.ndarray) -> np.ndarray:
    diff = rows - q[None, :]
    return (diff * diff).sum(axis=1)


def knn_under(score):
    """The contract's search under ``score(rows, q) -> (n,)``."""
    def knn(rows, labels, queries, ks):
        rows = np.asarray(rows, np.float64)
        labels = np.asarray(labels, np.int64)
        ids = np.arange(len(rows), dtype=np.int64)
        out = []
        for q, k in zip(np.asarray(queries, np.float64), ks):
            d = score(rows, q)
            order = np.lexsort((-ids, d))[:int(k)]
            pad = int(k) - len(order)
            sel = np.concatenate([ids[order], np.full(pad, -1, np.int64)])
            dists = np.concatenate([d[order], np.full(pad, np.inf)])
            label = vote(labels[ids[order]])
            out.append(Answer(label, sel, dists, fnv1a(label, sel)))
        return out
    return knn


knn_plain = knn_exact = knn_under(squared_l2)


def dist_scale(want: np.ndarray) -> np.ndarray:
    """A score that may be zero has no relative error against itself:
    absolute under 1, relative above."""
    return np.maximum(np.abs(want), 1.0)
