"""Seeded inputs of every cell: corpus rows, labels, query rows.

NumPy only (the load generator imports this and must never import jax).
The same (config, seed) gives the same arrays in the harness, in the
load generator's process and in the tests; a different seed gives
different data. Rows are drawn in a fixed number of slabs, each from its
own child of the seed, so the result does not depend on how many threads
fill them. Values come from the generator the configuration names
(``modules.generator``: ``generators/<name>.py``), or from ``draw``
below.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Tuple

import numpy as np

from benchmark import spec

_SLABS = 16          # fixed: part of what a seed means
_FILL_THREADS = 8
_BLOCK_ROWS = 1 << 16

# seed-sequence tags: one stream per kind of array
_TAG_ROWS, _TAG_LABELS, _TAG_REQUEST = 1, 2, 5


def draw(rng: np.random.Generator, shape, values: Dict[str, Any],
         seed: int = 0) -> np.ndarray:
    """The generator of a configuration that names none. Uniform values
    as the configuration's ``values`` block states them: ``float32``
    draws float32 and widens, so every value is exactly representable
    in float32; float64 draws otherwise. Nothing here hangs on
    ``seed``: ``rng`` is already a child of it."""
    low, high = float(values["low"]), float(values["high"])
    if values.get("float32"):
        out = rng.random(shape, dtype=np.float32)
        out *= np.float32(high - low)
        if low:
            out += np.float32(low)
        return out
    return rng.uniform(low, high, shape)


def _draw_of(cfg: Dict[str, Any]):
    name = cfg.get("modules", {}).get("generator")
    return draw if name is None else spec.generator(name).draw


def corpus(cfg: Dict[str, Any], seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(labels int32 (n,), rows float64 (n, a)) of a configuration."""
    n, na = int(cfg["num_data"]), int(cfg["num_attrs"])
    draw_values = _draw_of(cfg)
    rows = np.empty((n, na), np.float64)
    children = np.random.SeedSequence([int(seed), _TAG_ROWS]).spawn(_SLABS)
    step = -(-n // _SLABS)

    def fill(i: int) -> None:
        rng = np.random.default_rng(children[i])
        lo, hi = min(i * step, n), min((i + 1) * step, n)
        for a in range(lo, hi, _BLOCK_ROWS):
            b = min(a + _BLOCK_ROWS, hi)
            rows[a:b] = draw_values(rng, (b - a, na), cfg["values"],
                                    int(seed))

    with ThreadPoolExecutor(_FILL_THREADS) as pool:
        list(pool.map(fill, range(_SLABS)))
    rng = np.random.default_rng([int(seed), _TAG_LABELS])
    labels = rng.integers(0, int(cfg["num_labels"]), n).astype(np.int32)
    return labels, rows


def request_queries(cfg: Dict[str, Any], seed: int, index: int,
                    nq: int) -> np.ndarray:
    """Query rows of served request ``index`` — the generator encodes
    them, the check regenerates them from the same three numbers."""
    rng = np.random.default_rng([int(seed), _TAG_REQUEST, int(index)])
    return np.asarray(_draw_of(cfg)(
        rng, (int(nq), int(cfg["num_attrs"])), cfg["values"], int(seed)),
        np.float64)
