"""The WRONG reference for a program that serves squared L2 (test-only:
``tests/benchmark_tests/test_seam.py``): the same contract under the
Manhattan distance. A cell held to it must come out ``correct: false``."""

import numpy as np

from benchmark.references.seam_l2 import (Answer, dist_scale,  # noqa: F401
                                          knn_under)


def manhattan(rows: np.ndarray, q: np.ndarray) -> np.ndarray:
    return np.abs(rows - q[None, :]).sum(axis=1)


knn_plain = knn_exact = knn_under(manhattan)
