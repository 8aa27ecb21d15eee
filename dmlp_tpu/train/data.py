"""Synthetic labeled data for the training extension.

Two sources, both seeded/deterministic like the reference generator
(generate_input.py:37-50):

- :func:`teacher_batches` — a learnable task: labels are the argmax of a
  fixed random linear teacher over uniform attribute vectors (so loss
  actually falls and tests can assert learning).
- :func:`knn_input_batches` — batches drawn from a parsed KNN problem
  instance (io.grammar), training a classifier on the same records the
  parity engine consumes.
"""

from __future__ import annotations

from typing import Iterator, Tuple

import numpy as np


def teacher_batches(num_attrs: int, num_classes: int, batch_size: int,
                    seed: int = 42) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Infinite stream of (x (B, A) f32, y (B,) i32) from a linear teacher."""
    rng = np.random.default_rng(seed)
    teacher = rng.normal(size=(num_attrs, num_classes)).astype(np.float32)
    while True:
        x = rng.uniform(-1.0, 1.0, (batch_size, num_attrs)).astype(np.float32)
        y = np.argmax(x @ teacher, axis=1).astype(np.int32)
        yield x, y


def prefetch_to_device(it: Iterator[Tuple[np.ndarray, np.ndarray]],
                       shardings: Tuple, depth: int = 2,
                       ) -> Iterator[Tuple]:
    """Double-buffered device feed: keep ``depth`` batches in flight.

    ``jax.device_put`` is async, so enqueueing the next batch's transfer
    before the current step's results are consumed overlaps host->device
    DMA with device compute — without this the train loop eats a full
    transfer latency per step (the round-1 loop's synchronous per-step
    device_put, flagged in the round-4 review).
    """
    import collections

    import jax

    xsh, ysh = shardings
    buf: collections.deque = collections.deque()
    for xy in it:
        buf.append((jax.device_put(xy[0], xsh), jax.device_put(xy[1], ysh)))
        if len(buf) >= depth:
            yield buf.popleft()
    while buf:
        yield buf.popleft()


def knn_input_batches(inp, batch_size: int, seed: int = 42,
                      ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Infinite shuffled epochs over a KNNInput's labeled data points."""
    rng = np.random.default_rng(seed)
    x_all = np.asarray(inp.data_attrs, np.float32)
    y_all = np.asarray(inp.labels, np.int32)
    n = x_all.shape[0]
    if batch_size > n:
        raise ValueError(f"batch_size {batch_size} > {n} data points")
    while True:
        perm = rng.permutation(n)
        for i0 in range(0, n - batch_size + 1, batch_size):
            sel = perm[i0:i0 + batch_size]
            yield x_all[sel], y_all[sel]
