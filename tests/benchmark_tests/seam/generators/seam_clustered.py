"""A test-only generator (``tests/benchmark_tests/test_seam.py``): a
mixture of ``values.clusters`` Gaussian blobs of width ``values.spread``
whose centres are drawn from the run's seed alone, so that every slab of
the corpus and the load generator's process place them alike; which blob
a row falls in, and where, comes from ``rng``. Values are float32-exact,
as the uniform draw's are."""

import numpy as np

_TAG_CENTRES = 77


def centres(values, na: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), _TAG_CENTRES])
    return rng.uniform(float(values["low"]), float(values["high"]),
                       (int(values["clusters"]), na))


def draw(rng: np.random.Generator, shape, values, seed: int) -> np.ndarray:
    n, na = shape
    mid = centres(values, na, seed)
    out = mid[rng.integers(0, len(mid), n)] \
        + rng.normal(0.0, float(values["spread"]), (n, na))
    return out.astype(np.float32).astype(np.float64)
