"""The benchmark's plain reference against the program's golden oracle,
the engine and the checksum, on small seeded problems with ties."""

import numpy as np
import pytest

from benchmark import check, reference
from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.checksum import fnv1a_checksum
from dmlp_tpu.io.grammar import KNNInput, Params

LIMITS = {"checksum_mismatches": 0, "dist_rel_err_max": 1e-11,
          "reference_plain_mismatches": 0}

# (name, rows, queries, attrs, k range, value grid): a coarse integer grid
# makes distance ties (id-desc order), few labels make vote ties, k > rows
# makes -1 padding
CASES = [
    ("real", 400, 24, 8, (1, 16), None),
    ("ties", 300, 24, 3, (1, 24), 3),
    ("dups", 64, 16, 2, (1, 64), 2),
    ("pad", 10, 8, 4, (8, 16), None),
    ("one_row", 1, 4, 4, (1, 3), None),
]


def problem(case, seed):
    _name, n, nq, na, (k0, k1), grid = case
    rng = np.random.default_rng(seed)
    if grid:
        rows = rng.integers(0, grid, (n, na)).astype(np.float64)
        queries = rng.integers(0, grid, (nq, na)).astype(np.float64)
    else:
        rows = np.round(rng.uniform(0, 100, (n, na)), 6)
        queries = np.round(rng.uniform(0, 100, (nq, na)), 6)
    labels = rng.integers(0, 3, n).astype(np.int32)
    ks = rng.integers(k0, k1 + 1, nq).astype(np.int32)
    return KNNInput(Params(n, nq, na), labels, rows, ks, queries)


def same(ans, res):
    return (ans.label == res.predicted_label
            and ans.checksum == res.checksum()
            and np.array_equal(ans.ids, res.neighbor_ids)
            and np.array_equal(ans.dists, res.neighbor_dists))


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_plain_reference_is_the_golden_oracle(case, seed):
    inp = problem(case, seed)
    got = reference.knn_plain(inp.data_attrs, inp.labels,
                              inp.query_attrs, inp.ks)
    assert all(same(a, r) for a, r in zip(got, knn_golden(inp)))


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_screened_reference_is_the_plain_one(case, seed, monkeypatch):
    inp = problem(case, seed)
    monkeypatch.setattr(reference, "SLACK", 4)   # so the screen really cuts
    monkeypatch.setattr(reference, "_BLOCK_ROWS", 64)
    fast = reference.knn_exact(inp.data_attrs, inp.labels, inp.query_attrs,
                               inp.ks)
    plain = reference.knn_plain(inp.data_attrs, inp.labels,
                                inp.query_attrs, inp.ks)
    for a, b in zip(fast, plain):
        assert a.label == b.label and a.checksum == b.checksum
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.dists, b.dists)


@pytest.mark.parametrize("case", CASES[:3], ids=[c[0] for c in CASES[:3]])
def test_engine_agrees_with_the_reference(case):
    inp = problem(case, 11)
    results = SingleChipEngine(EngineConfig()).run(inp)
    refs = reference.knn_exact(inp.data_attrs, inp.labels, inp.query_attrs,
                               inp.ks)
    v = check.Verdict(LIMITS)
    v.add_plain(refs[0], reference.knn_plain(
        inp.data_attrs, inp.labels, inp.query_attrs[:1], inp.ks[:1])[0])
    for ref, r in zip(refs, results):
        v.add(ref, r.predicted_label, r.checksum(), r.neighbor_dists)
    assert v.correct and v.queries == len(refs), v.numbers


@pytest.mark.parametrize("label,ids", [
    (0, []), (3, [0]), (-1, [-1, -1]), (7, [5, 2, 9, -1]),
    (2, list(range(40, 0, -1))), (9, [2 ** 31 - 1, 0, 2 ** 22]),
])
def test_fnv1a_is_the_programs_checksum(label, ids):
    assert reference.fnv1a(label, ids) == fnv1a_checksum(label, ids)


def test_vote_ties_go_to_the_larger_label_and_padding_does_not_vote():
    assert reference.vote(np.array([1, 2, 2, 1])) == 2
    assert reference.vote(np.array([], np.int64)) == -1
    rows = np.array([[0.0], [1.0], [1.0]])
    ans = reference.knn_plain(rows, np.array([5, 1, 2]),
                              np.array([[1.0]]), [5])[0]
    # two rows tie at distance 0: the larger id first; then the far one
    assert list(ans.ids) == [2, 1, 0, -1, -1]
    assert ans.label == 5 and np.isinf(ans.dists[3:]).all()


def test_verdict_fails_on_a_wrong_label_a_wrong_order_and_float32_dists():
    rows = np.arange(12, dtype=np.float64).reshape(6, 2) ** 2
    ref = reference.knn_plain(rows, np.arange(6) % 2, rows[:1] + 0.3, [3])[0]

    def verdict(label, ids, dists):
        v = check.Verdict(LIMITS)
        v.add_plain(ref, ref)
        v.add(ref, label, reference.fnv1a(label, ids), dists)
        return v
    assert verdict(ref.label, ref.ids, ref.dists).correct
    assert not verdict(ref.label + 1, ref.ids, ref.dists).correct
    assert not verdict(ref.label, ref.ids[::-1], ref.dists).correct
    f32 = ref.dists.astype(np.float32).astype(np.float64) * (1 + 1e-7)
    v = verdict(ref.label, ref.ids, f32)
    assert v.mismatches == 0 and not v.correct and v.rel_err > 1e-8
    # nothing compared is not correct either
    assert not check.Verdict(LIMITS).correct


@pytest.mark.parametrize("field,value", [
    ("label", 1), ("checksum", 1), ("ids", np.array([4, 3, 5])),
    ("dists", np.array([1.0, 2.0, 3.0]))])
def test_verdict_fails_when_the_screened_reference_leaves_the_plain_one(
        field, value):
    rows = np.arange(12, dtype=np.float64).reshape(6, 2) ** 2
    ref = reference.knn_plain(rows, np.arange(6) % 2, rows[:1] + 0.3, [3])[0]
    v = check.Verdict(LIMITS)
    v.add(ref, ref.label, ref.checksum, ref.dists)
    v.add_plain(ref._replace(**{field: value}), ref)
    assert v.plain_mismatches == 1 and not v.correct
