"""The inner-product reference (``benchmark/references/inner_product.py``,
PR 46): its screened search against its plain one, ties included; both
against the program's golden model under ``score="ip"``; its imports
(nothing of the program); its scale; and the seam's pattern at the ip
cell's own toy size: the same served run held to the squared-L2 reference
comes out ``correct: false``."""

import json
import os

import numpy as np
import pytest

from benchmark import check, spec
from benchmark import run as bench_run
from benchmark.references import inner_product as ref_ip

CELL = "text2image-10m.bulk"

# (name, rows, queries, attrs, k range, value grid): a coarse integer grid
# makes the products tie (id-desc order), k > rows makes -1 padding
CASES = [
    ("real", 400, 24, 8, (1, 16), None),
    ("ties", 300, 24, 3, (1, 24), 3),
    ("dups", 64, 16, 2, (1, 64), 2),
    ("pad", 10, 8, 4, (8, 16), None),
    ("one_row", 1, 4, 4, (1, 3), None),
    ("negative", 200, 8, 6, (1, 8), None),
]


def problem(case, seed):
    name, n, nq, na, (k0, k1), grid = case
    rng = np.random.default_rng([seed, 46])
    if grid:
        rows = rng.integers(-grid, grid + 1, (n, na)).astype(np.float64)
        queries = rng.integers(-grid, grid + 1, (nq, na)).astype(np.float64)
    else:
        rows = np.round(rng.uniform(-1, 1, (n, na)), 6)
        queries = np.round(rng.uniform(-1, 1, (nq, na)), 6)
    if name == "negative":          # every score below zero
        rows, queries = np.abs(rows) + 0.1, -np.abs(queries) - 0.1
    labels = rng.integers(0, 3, n).astype(np.int32)
    ks = rng.integers(k0, k1 + 1, nq).astype(np.int32)
    return rows, labels, queries, ks


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_plain_reference_is_the_contract_and_the_golden_model(case, seed):
    rows, labels, queries, ks = problem(case, seed)
    got = ref_ip.knn_plain(rows, labels, queries, ks)
    n = len(rows)
    for q, k, a in zip(queries, ks, got):
        s = rows @ q
        order = sorted(range(n), key=lambda i: (-s[i], -i))[:k]
        assert a.ids[:len(order)].tolist() == order
        assert a.ids[len(order):].tolist() == [-1] * (k - len(order))
        assert np.all(np.isneginf(a.dists[len(order):]))
        assert np.allclose(a.dists[:len(order)], s[order], rtol=0,
                           atol=1e-12)
        votes = np.bincount(labels[order], minlength=3)
        assert a.label == max(np.flatnonzero(votes == votes.max()))
    from dmlp_tpu.golden.reference import knn_golden
    from dmlp_tpu.io.grammar import KNNInput, Params
    gold = knn_golden(KNNInput(Params(n, len(queries), rows.shape[1]),
                               labels, rows, ks, queries), score="ip")
    for a, g in zip(got, gold):
        assert a.label == g.predicted_label and a.checksum == g.checksum()
        assert np.array_equal(a.ids, g.neighbor_ids)
        assert np.array_equal(a.dists, g.neighbor_dists)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_screened_reference_is_the_plain_one(case, seed, monkeypatch):
    rows, labels, queries, ks = problem(case, seed)
    monkeypatch.setattr(ref_ip, "SLACK", 4)   # so the screen really cuts
    monkeypatch.setattr(ref_ip, "_BLOCK_ROWS", 64)
    fast = ref_ip.knn_exact(rows, labels, queries, ks)
    plain = ref_ip.knn_plain(rows, labels, queries, ks)
    for a, b in zip(fast, plain):
        assert a.label == b.label and a.checksum == b.checksum
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.dists, b.dists)


def test_the_reference_imports_nothing_of_the_program():
    """``test_reference_imports.py`` walks ``references/*.py``; held
    here too, by its own walker, so that the module stays in."""
    from tests.benchmark_tests import test_reference_imports as walker
    path = os.path.join(spec.HERE, "references", "inner_product.py")
    assert path in walker.REFERENCES
    seen = walker.walk(path, {path})
    assert os.path.join(spec.HERE, "reference.py") in seen
    assert spec.Cell(CELL, rehearse=True).reference is ref_ip


def test_the_scale_is_the_answers_largest_score():
    """A score may be zero or negative: the denominator is the largest
    |s| of the answer, so a float64 difference of 1e-13 beside a score
    of zero is 1e-14 of the scale and a float32-sized one fails."""
    want = np.array([10.0, 0.0, -2.0])
    assert ref_ip.dist_scale(want) == 10.0
    assert ref_ip.dist_scale(np.zeros(3)) == np.finfo(np.float64).tiny
    limits = {"checksum_mismatches": 0, "dist_rel_err_max": 1e-11,
              "reference_plain_mismatches": 0}
    ref = ref_ip.Answer(1, np.array([5, 3, 1]), want, 7)
    for off, ok in ((1e-13, True), (1e-6, False)):
        v = check.Verdict(limits, ref_ip.dist_scale)
        v.add_plain(ref, ref)
        v.add(ref, 1, 7, want + np.array([0.0, off, 0.0]))
        assert v.correct is ok, off
    # under the default scale the zero score has no relative error
    v = check.Verdict(limits)
    v.add_plain(ref, ref)
    v.add(ref, 1, 7, want + np.array([0.0, 1e-13, 0.0]))
    assert v.correct is False


def run_cell(capsys, seed=4600000321):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "1",
            "--trace", "0", "--rehearse"]
    assert bench_run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_ip_cell_held_to_the_l2_reference_is_not_correct(monkeypatch,
                                                             capsys):
    """The seam's pattern on the real cell: the rehearsal is correct
    under the reference its configuration names, and the same run held
    to ``benchmark/reference.py`` (the configuration's ``modules`` taken
    away) is not: other neighbours, other checksums."""
    line = run_cell(capsys)
    assert line["correct"] is True and line["failed"] == 0
    load = spec._load
    cfg_path = os.path.join(spec.HERE, "configs", "text2image-10m.json")

    def loaded(path):
        doc = load(path)
        if path == cfg_path:
            doc.pop("modules")
        return doc
    monkeypatch.setattr(spec, "_load", loaded)
    line = run_cell(capsys)
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["checksum_mismatches"]["value"] > 0
    assert line["checks"]["reference_plain_mismatches"]["value"] == 0
