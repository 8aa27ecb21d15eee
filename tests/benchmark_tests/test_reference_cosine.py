"""The cosine reference (``benchmark/references/cosine.py``, PR 49): its
plain search against the contract written the slow way, zero rows, zero
queries and exact copies included; its screened search against its plain
one; both against the program's golden model under ``score="cosine"``;
its imports (nothing of the program); its scale; and the seam's pattern
at the cosine cell's own toy size: the same served run held to the
inner-product reference or to the squared-L2 one comes out ``correct:
false``."""

import json
import math
import os

import numpy as np
import pytest

from benchmark import check, spec
from benchmark import run as bench_run
from benchmark.references import cosine as ref_cos

CELL = "dbpedia-openai-1m.bulk"

# (name, rows, queries, attrs, k range, value grid): a coarse integer grid
# makes exact copies (they tie; id-desc order) and zero vectors, k > rows
# makes -1 padding
CASES = [
    ("real", 400, 24, 8, (1, 16), None),
    ("copies", 300, 24, 3, (1, 24), 1),
    ("dups", 64, 16, 2, (1, 64), 1),
    ("pad", 10, 8, 4, (8, 16), None),
    ("one_row", 1, 4, 4, (1, 3), None),
    ("zeros", 200, 8, 6, (1, 8), None),
    ("norms", 300, 12, 12, (1, 12), None),
]


def problem(case, seed):
    name, n, nq, na, (k0, k1), grid = case
    rng = np.random.default_rng([seed, 49])
    if grid:
        rows = rng.integers(-grid, grid + 1, (n, na)).astype(np.float64)
        queries = rng.integers(-grid, grid + 1, (nq, na)).astype(np.float64)
    else:
        rows = np.round(rng.uniform(-1, 1, (n, na)), 6)
        queries = np.round(rng.uniform(-1, 1, (nq, na)), 6)
    if name == "zeros":            # zero rows and a zero query
        rows[::7] = 0.0
        queries[2] = 0.0
    if name == "norms":            # norms over eight decades
        rows *= 10.0 ** rng.integers(-4, 5, (n, 1))
    labels = rng.integers(0, 3, n).astype(np.int32)
    ks = rng.integers(k0, k1 + 1, nq).astype(np.int32)
    return rows, labels, queries, ks


def slow_cosine(q, x):
    """The contract, one pair at a time, in Python's own floats (the
    sums by ``math.fsum``: exactly rounded, so every float64 order of
    summation is within its rounding of this)."""
    qn = math.sqrt(math.fsum(v * v for v in q))
    xn = math.sqrt(math.fsum(v * v for v in x))
    if qn * xn == 0.0:
        return 0.0
    return math.fsum(a * b for a, b in zip(q, x)) / (qn * xn)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_plain_reference_is_the_contract_and_the_golden_model(case, seed):
    rows, labels, queries, ks = problem(case, seed)
    got = ref_cos.knn_plain(rows, labels, queries, ks)
    n = len(rows)
    for q, k, a in zip(queries, ks, got):
        real = a.ids[a.ids >= 0]
        assert len(real) == min(k, n)
        assert a.ids[len(real):].tolist() == [-1] * (k - len(real))
        assert np.all(np.isposinf(a.dists[len(real):]))
        s = np.array([slow_cosine(q, x) for x in rows])
        assert np.allclose(a.dists[:len(real)], 1.0 - s[real], rtol=0,
                           atol=1e-13)
        if not q.any():                 # a zero query: 0 against every row
            assert np.all(a.dists[:len(real)] == 1.0)
            assert real.tolist() == list(range(n - 1, n - 1 - len(real), -1))
        zero = ~rows[real].any(axis=1)
        assert np.all(a.dists[:len(real)][zero] == 1.0)   # a zero row: s = 0
        # its own order: d ascending, larger id first on an exact tie
        d = a.dists[:len(real)]
        assert np.all((d[:-1] < d[1:]) | ((d[:-1] == d[1:])
                                          & (real[:-1] > real[1:])))
        # no row left out scores better than the k-th by more than rounding
        out = np.setdiff1d(np.arange(n), real)
        if len(out) and len(real):
            assert s[out].max() <= (1.0 - d[-1]) + 1e-13
        votes = np.bincount(labels[real], minlength=3)
        assert a.label == max(np.flatnonzero(votes == votes.max()))
    from dmlp_tpu.golden.reference import knn_golden
    from dmlp_tpu.io.grammar import KNNInput, Params
    gold = knn_golden(KNNInput(Params(n, len(queries), rows.shape[1]),
                               labels, rows, ks, queries), score="cosine")
    for a, g in zip(got, gold):
        assert a.label == g.predicted_label and a.checksum == g.checksum()
        assert np.array_equal(a.ids, g.neighbor_ids)
        real = a.ids >= 0
        assert np.all(np.abs(a.dists[real] - g.neighbor_dists[real])
                      <= 1e-13)
        assert np.array_equal(np.isposinf(a.dists),
                              np.isposinf(g.neighbor_dists))


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_screened_reference_is_the_plain_one(case, seed, monkeypatch):
    rows, labels, queries, ks = problem(case, seed)
    monkeypatch.setattr(ref_cos, "SLACK", 4)   # so the screen really cuts
    monkeypatch.setattr(ref_cos, "_BLOCK_ROWS", 64)
    fast = ref_cos.knn_exact(rows, labels, queries, ks)
    plain = ref_cos.knn_plain(rows, labels, queries, ks)
    for a, b in zip(fast, plain):
        assert a.label == b.label and a.checksum == b.checksum
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.dists, b.dists)


def test_exact_copies_tie_and_the_larger_id_comes_first():
    rng = np.random.default_rng(4910)
    rows = rng.uniform(-1, 1, (50, 5))
    rows[[3, 17, 41]] = rows[9]
    q = rows[9][None, :] * 2.0
    (a,) = ref_cos.knn_plain(rows, np.zeros(50, np.int64), q, [4])
    assert a.ids.tolist() == [41, 17, 9, 3]
    assert len(set(a.dists.tolist())) == 1 and abs(a.dists[0]) < 1e-15


def test_the_reference_imports_nothing_of_the_program():
    from tests.benchmark_tests import test_reference_imports as walker
    path = os.path.join(spec.HERE, "references", "cosine.py")
    assert path in walker.REFERENCES
    seen = walker.walk(path, {path})
    assert os.path.join(spec.HERE, "reference.py") in seen
    assert spec.Cell(CELL, rehearse=True).reference is ref_cos


def test_the_scale_is_one():
    """d is ~1e-16 where a query is a row, and the error of a cosine is
    that of unit operands: the denominator is 1, so a float64 difference
    of 1e-13 passes at d = 0 and a float32-sized one fails at d = 1."""
    want = np.array([0.0, 0.9, 1.0])
    assert np.array_equal(ref_cos.dist_scale(want), np.ones(3))
    limits = {"checksum_mismatches": 0, "dist_rel_err_max": 1e-11,
              "reference_plain_mismatches": 0}
    ref = ref_cos.Answer(1, np.array([5, 3, 1]), want, 7)
    for at, off, ok in ((0, 1e-13, True), (2, 1e-13, True),
                        (2, 1e-7, False), (0, 1e-7, False)):
        v = check.Verdict(limits, ref_cos.dist_scale)
        v.add_plain(ref, ref)
        got = want.copy()
        got[at] += off
        v.add(ref, 1, 7, got)
        assert v.correct is ok, (at, off)
    # under the default scale a distance of zero has no relative error
    v = check.Verdict(limits)
    v.add_plain(ref, ref)
    v.add(ref, 1, 7, want + np.array([1e-13, 0.0, 0.0]))
    assert v.correct is False


def run_cell(capsys, seed=4900000321):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "1",
            "--trace", "0", "--rehearse"]
    assert bench_run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("other", ["inner_product", None],
                         ids=["ip", "l2"])
def test_the_cosine_cell_held_to_another_reference_is_not_correct(
        monkeypatch, capsys, other):
    """The seam's pattern on the real cell: the rehearsal is correct
    under the reference its configuration names; the same run held to
    the inner-product reference, or to ``benchmark/reference.py`` (the
    configuration's ``modules`` taken away), is not: the rows are not
    unit vectors, so the three scores pick other neighbours."""
    load = spec._load
    cfg_path = os.path.join(spec.HERE, "configs", "dbpedia-openai-1m.json")

    def loaded(path):
        doc = load(path)
        if path == cfg_path:
            if other is None:
                doc.pop("modules")
            else:
                doc["modules"] = {"reference": other}
        return doc
    monkeypatch.setattr(spec, "_load", loaded)
    line = run_cell(capsys)
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["checksum_mismatches"]["value"] > 0
    assert line["checks"]["reference_plain_mismatches"]["value"] == 0
