"""The order-sensitive FNV-1a result checksum — the correctness contract.

Reference: common.cpp:57-71. The oracle folds, in order:

1. the predicted label (cast to unsigned 64-bit);
2. each neighbor id **+ 1** ("+1 to distinguish from -1 sentinel",
   common.cpp:66), in report order (distance asc, tie -> larger id,
   engine.cpp:334-338).

Any deviation in k-selection, tie-breaking, vote, or ordering changes the
value, which is what makes it a differential-testing oracle (survey §4).
"""

from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np

FNV_BASIS = 1469598103934665603  # common.cpp:59
FNV_PRIME = 1099511628211        # common.cpp:62
_MASK = (1 << 64) - 1


def fnv1a_checksum(label: int, neighbor_ids: Iterable[int]) -> int:
    """Checksum of one query result (exact reimplementation of common.cpp:57-71).

    ``label`` and ids are folded with C++ ``static_cast<unsigned long long>``
    semantics: negative values wrap mod 2**64 (so the -1 sentinel id folds in
    as (+1 =) 0, and a -1 label folds as 2**64-1).
    """
    c = FNV_BASIS
    c ^= int(label) & _MASK
    c = (c * FNV_PRIME) & _MASK
    for idx in neighbor_ids:
        c ^= (int(idx) + 1) & _MASK
        c = (c * FNV_PRIME) & _MASK
    return c


def fnv1a_checksum_batch(labels: Sequence[int], neighbor_ids: np.ndarray,
                         valid_counts: Sequence[int]) -> np.ndarray:
    """Checksums of a batch of query results, folded a neighbour
    POSITION at a time over all queries at once: Kmax array steps for
    the Q * Kmax scalar ones of :func:`fnv1a_checksum`, which stays the
    contract's reference (equal values: tests/test_checksum.py).

    The fold runs in uint64, whose sum and product wrap mod 2**64: the
    scalar's ``& _MASK``, and C++'s ``static_cast<unsigned long long>``
    of a negative label or the -1 sentinel id. A query whose list has
    ended (a request may mix k) is stepped with ``c ^= 0; c *= 1``,
    which leaves it as it is.

    Made of elementwise operations alone: on a small batch NumPy runs
    those without letting go of the interpreter lock, where a sort or
    a fancy index hands it to whichever thread waits, and a handler
    that answers a 2-query request then waits for the lock's return
    (serve/protocol.py:query_response calls this on handler threads).

    Args:
      labels: (Q,) predicted labels.
      neighbor_ids: (Q, Kmax) integer neighbor ids in report order;
        entries at or beyond each query's valid count are ignored.
      valid_counts: (Q,) number of reported neighbors per query (its
        k), each in [0, Kmax].

    Returns:
      (Q,) uint64 checksums.
    """
    ids = np.asarray(neighbor_ids)
    counts = np.asarray(valid_counts, np.int64).reshape(-1)
    q, kmax = ids.shape
    if not np.issubdtype(ids.dtype, np.integer):
        raise TypeError(f"neighbor ids must be integers, got {ids.dtype}")
    if len(labels) != q or len(counts) != q:
        raise ValueError("labels, neighbor_ids and valid_counts must "
                         "describe the same queries")
    if q and not (0 <= counts.min() and counts.max() <= kmax):
        raise ValueError(f"valid_counts must lie in [0, {kmax}]")
    # live[j]: the queries whose list reaches position j (the positions
    # from a range: np.arange lets go of the lock, as a sort does)
    live = np.array(range(kmax), np.int64).reshape(-1, 1) < counts
    # steps[j]: position j's (id + 1) of every query, contiguous; 0
    # where the list has ended, and there the multiplier is 1
    steps = np.empty((kmax, q), np.uint64)
    np.add(ids.astype(np.int64, copy=False).view(np.uint64).T,
           np.uint64(1), out=steps)
    steps *= live
    prime = np.uint64(FNV_PRIME)
    primes = np.where(live, prime, np.uint64(1))
    c = np.asarray(labels, np.int64).reshape(-1).view(np.uint64) \
        ^ np.uint64(FNV_BASIS)
    c *= prime
    for step, mult in zip(steps, primes):
        c ^= step
        c *= mult
    return c
