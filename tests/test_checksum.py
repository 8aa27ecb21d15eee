"""Checksum contract tests (reference common.cpp:57-71).

The hardcoded expected values were produced by compiling the reference
checksum routine (the FNV-1a fold in common.cpp:59-68) with g++ and running
it on the same inputs — see tools/verify_checksum.cpp.
"""

import numpy as np
import pytest

from dmlp_tpu.io.checksum import FNV_BASIS, FNV_PRIME, fnv1a_checksum, fnv1a_checksum_batch


def cpp_reference_fold(values):
    """Literal transcription of the C++ fold for cross-checking."""
    c = FNV_BASIS
    for v in values:
        c ^= v % (1 << 64)
        c = (c * FNV_PRIME) % (1 << 64)
    return c


def test_empty_neighbors():
    assert fnv1a_checksum(3, []) == cpp_reference_fold([3])


def test_basic_fold_order_sensitive():
    a = fnv1a_checksum(1, [0, 1, 2])
    b = fnv1a_checksum(1, [2, 1, 0])
    assert a != b
    assert a == cpp_reference_fold([1, 1, 2, 3])  # ids folded as id+1


def test_sentinel_minus_one_folds_as_zero():
    # id=-1 + 1 == 0 (the sentinel distinction in common.cpp:66)
    assert fnv1a_checksum(0, [-1]) == cpp_reference_fold([0, 0])


def test_negative_label_wraps_like_cpp_cast():
    # static_cast<unsigned long long>(-1) == 2**64 - 1
    assert fnv1a_checksum(-1, []) == cpp_reference_fold([(1 << 64) - 1])


def test_matches_compiled_cpp_goldens():
    # Values printed by tools/verify_checksum.cpp built with g++ -O2.
    assert fnv1a_checksum(3, []) == 4953160058118402688
    assert fnv1a_checksum(1, [0, 1, 2]) == 11099651899989310290
    assert fnv1a_checksum(0, [-1]) == 11126445248426326267
    assert fnv1a_checksum(-1, []) == 13493579617544636084
    assert fnv1a_checksum(7, [41, 12, 3, -1, -1]) == 9584307944621426467


def test_batch_matches_scalar():
    ids = np.array([[4, 2, 9], [7, 7, 7]])
    out = fnv1a_checksum_batch([1, 2], ids, [3, 2])
    assert out[0] == fnv1a_checksum(1, [4, 2, 9])
    assert out[1] == fnv1a_checksum(2, [7, 7])


# -- the batch fold (PR 52): a neighbour position at a time, in uint64 --------

I64 = np.iinfo(np.int64)


def scalar_fold(labels, ids, counts):
    return [fnv1a_checksum(int(l), [int(i) for i in row[:n]])
            for l, row, n in zip(labels, ids, counts)]


def _random(q, k, dtype=np.int64, seed=52):
    rng = np.random.default_rng([seed, q, k])
    info = np.iinfo(dtype)
    return (rng.integers(0, 10, q),
            rng.integers(info.min, info.max, (q, k), dtype=dtype,
                         endpoint=True),
            np.full(q, k))


def _padded_tails():
    labels, ids, _ = _random(6, 8)
    counts = np.array([8, 3, 0, 5, 8, 1])
    ids[np.arange(8) >= counts[:, None]] = -1       # the padded slots
    return labels, ids, counts


def _sentinel_inside():
    """Fewer candidates than k: the list itself ends in -1 ids, which
    are reported and folded (as 0)."""
    labels, ids, counts = _random(4, 6)
    ids[:, 4:] = -1
    return labels, ids, counts


def _negative_labels():
    _, ids, counts = _random(5, 7)
    return np.array([-1, -2, I64.min, 0, I64.max]), ids, counts


def _extreme_ids():
    ids = np.array([[I64.max, I64.min, -1, 0],
                    [I64.min, I64.min, I64.max, I64.max],
                    [-2, I64.max - 1, I64.min + 1, 1]])
    return [0, -1, 7], ids, [4, 4, 4]


def _mixed_counts():
    labels, ids, _ = _random(40, 12)
    counts = np.random.default_rng(7).integers(0, 13, 40)
    counts[:4] = [0, 12, 0, 12]                     # 0 and Kmax, twice
    return labels, ids, counts


BATCHES = {
    "q1_k1": lambda: _random(1, 1),
    "q2_k10": lambda: _random(2, 10),
    "q64_k10": lambda: _random(64, 10),
    "q1024_k1000": lambda: _random(1024, 1000),
    "int32_ids": lambda: _random(64, 10, np.int32),
    "int32_everything": lambda: tuple(
        a.astype(np.int32) for a in _random(16, 5, np.int32)),
    "python_lists": lambda: tuple(a.tolist() for a in _random(3, 4)),
    "padded_tails": _padded_tails,
    "sentinel_inside": _sentinel_inside,
    "negative_labels": _negative_labels,
    "extreme_ids": _extreme_ids,
    "mixed_counts": _mixed_counts,
    "no_query": lambda: (np.zeros(0, np.int64), np.zeros((0, 5), np.int64),
                         np.zeros(0, np.int64)),
    "no_neighbour": lambda: ([3, -1], np.zeros((2, 0), np.int64), [0, 0]),
}


@pytest.mark.parametrize("case", sorted(BATCHES))
def test_batch_fold_equals_the_scalar(case):
    labels, ids, counts = BATCHES[case]()
    got = fnv1a_checksum_batch(labels, ids, counts)
    assert got.dtype == np.uint64 and got.shape == (len(labels),)
    assert got.tolist() == scalar_fold(labels, np.asarray(ids), counts)


def test_batch_fold_ignores_what_lies_past_a_count():
    labels, ids, counts = _mixed_counts()
    other = ids.copy()
    other[np.arange(ids.shape[1]) >= counts[:, None]] = 123456789
    assert fnv1a_checksum_batch(labels, other, counts).tolist() \
        == fnv1a_checksum_batch(labels, ids, counts).tolist()


def test_batch_fold_matches_the_compiled_cpp_goldens():
    ids = np.full((5, 5), -1, np.int64)
    ids[1, :3] = [0, 1, 2]
    ids[4] = [41, 12, 3, -1, -1]
    got = fnv1a_checksum_batch([3, 1, 0, -1, 7], ids, [0, 3, 1, 0, 5])
    assert got.tolist() == [4953160058118402688, 11099651899989310290,
                            11126445248426326267, 13493579617544636084,
                            9584307944621426467]


@pytest.mark.parametrize("labels,ids,counts,error", [
    ([1], np.zeros((1, 3)), [3], TypeError),            # float ids
    ([1], np.zeros((1, 3), np.int64), [4], ValueError),  # past Kmax
    ([1], np.zeros((1, 3), np.int64), [-1], ValueError),
    ([1, 2], np.zeros((1, 3), np.int64), [3], ValueError),
    ([1], np.zeros((1, 3), np.int64), [3, 3], ValueError),
], ids=["float_ids", "count_past_kmax", "negative_count", "labels_differ",
        "counts_differ"])
def test_batch_fold_refuses_what_it_cannot_fold(labels, ids, counts, error):
    with pytest.raises(error):
        fnv1a_checksum_batch(labels, ids, counts)
