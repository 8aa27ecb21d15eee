"""Inner-product search on the one-chip served path (PR 46).

``EngineConfig(score="ip")``: the corpus is ranked by LARGEST inner
product s(q, x) = sum_a q_a x_a (float64; s descending, id DESCENDING on
ties; ``dists`` carries s itself, padded slots -inf). A ``ServeDaemon``
on the extract path (interpret mode here), rows staged in bfloat16, is
held over TCP to the benchmark's plain reference
(``benchmark/references/inner_product.py``, both its plain and its
screened search) and to the golden model under "ip" — labels, ids and
checksums identical, scores within 1e-11 of |q| max|x| — on corpora
built to break each piece: integer rows whose products tie by the
hundred (id order, boundary repair), all-negative scores (a clamp at 0
would pass none of them), a zero row among them, a zero query, k past the
row count, rows of very unequal norms, queries the hazard test flags
(cleared by the device retry; not cleared, so the host oracle's). The
control (fast mode) must differ, and ``score="l2"`` must answer and
compile as it did.
"""

from __future__ import annotations

import json
import socket

import numpy as np
import pytest

from benchmark.references import inner_product as ref_ip
from dmlp_tpu.config import EngineConfig
from dmlp_tpu.golden.fast import knn_golden_fast
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.serve.daemon import ServeDaemon

LIMIT = 1e-11


def corpus_of(rows: np.ndarray, seed: int = 0) -> KNNInput:
    rows = np.asarray(rows, np.float64)
    n, na = rows.shape
    labels = np.random.default_rng([seed, 9]).integers(0, 5, n)
    return KNNInput(Params(n, 0, na), labels.astype(np.int32), rows,
                    np.zeros(0, np.int32), np.zeros((0, na)))


def f32(x: np.ndarray) -> np.ndarray:
    """Values a wire and a float32 stage both hold exactly."""
    return np.asarray(x, np.float32).astype(np.float64)


def ask(port: int, obj: dict) -> dict:
    with socket.create_connection(("127.0.0.1", port), timeout=120) as s:
        f = s.makefile("rwb")
        f.write((json.dumps(obj) + "\n").encode())
        f.flush()
        return json.loads(f.readline())


def served(corpus: KNNInput, queries: np.ndarray, k: int, **engine):
    """(response with neighbours and scores, stats) of one request
    through a daemon on the extract path under bfloat16 staging."""
    cfg = EngineConfig(**{"use_pallas": True, "select": "extract",
                          "dtype": "bfloat16", "score": "ip", **engine})
    daemon = ServeDaemon(corpus, cfg, warm_buckets=[(len(queries), k)])
    try:
        daemon.start()
        resp = ask(daemon.port, {"op": "query", "k": k, "debug": True,
                                 "queries": queries.tolist()})
        stats = ask(daemon.port, {"op": "stats"})["stats"]
    finally:
        daemon.close()
    assert resp["ok"], resp
    return resp, stats


def assert_exact(resp: dict, corpus: KNNInput, queries: np.ndarray, k: int):
    """The response against the two references and the golden model."""
    rows, labels = corpus.data_attrs, corpus.labels
    ks = np.full(len(queries), k)
    plain = ref_ip.knn_plain(rows, labels, queries, ks)
    exact = ref_ip.knn_exact(rows, labels, queries, ks)
    inp = KNNInput(Params(len(rows), len(queries), rows.shape[1]), labels,
                   rows, ks.astype(np.int32), queries)
    gold = knn_golden(inp, score="ip")
    fast = knn_golden_fast(inp, score="ip")
    scale = np.linalg.norm(queries, axis=1) * np.linalg.norm(
        rows, axis=1).max()
    for j, (p, e, g, f) in enumerate(zip(plain, exact, gold, fast)):
        got_ids = np.asarray(resp["neighbors"][j], np.int64)
        got_s = np.asarray(resp["dists"][j], np.float64)
        for want_ids, want_s, label, checksum in (
                (p.ids, p.dists, p.label, p.checksum),
                (e.ids, e.dists, e.label, e.checksum),
                (g.neighbor_ids, g.neighbor_dists, g.predicted_label,
                 g.checksum()),
                (f.neighbor_ids, f.neighbor_dists, f.predicted_label,
                 f.checksum())):
            assert np.array_equal(got_ids, want_ids), j
            assert resp["labels"][j] == label, j
            assert resp["checksums"][j] == checksum, j
            real = got_ids >= 0
            assert np.all(np.isneginf(got_s[~real]))
            assert np.all(np.isneginf(np.asarray(want_s)[~real]))
            assert np.all(np.abs(got_s[real] - np.asarray(want_s)[real])
                          <= LIMIT * max(scale[j], 1e-300)), j
        # the contract's order: s descending, larger id first on ties
        s, i = got_s[real], got_ids[real]
        assert np.all((s[:-1] > s[1:]) | ((s[:-1] == s[1:])
                                          & (i[:-1] > i[1:]))), j


def _uniform(na, rng):
    return f32(rng.uniform(-1, 1, (2000, na))), \
        f32(rng.uniform(-1, 1, (24, na))), 10


def _integer_ties(na, rng):
    """Thirty integer points a hundred times each, shuffled: a query's
    products tie by the hundred, so the order within a tie is the ids'
    and a 120-slot window cannot hold the boundary's group."""
    pts = rng.integers(-3, 4, (30, na)).astype(np.float64)
    rows = np.repeat(pts, 100, axis=0)[rng.permutation(3000)]
    return rows, rng.integers(-3, 4, (8, na)).astype(np.float64), 10


def _all_negative(na, rng):
    return f32(rng.uniform(0.1, 1, (2000, na))), \
        f32(rng.uniform(-1, -0.1, (16, na))), 10


def _zero_row(na, rng):
    """All scores negative but one row's, which is zero: the best row
    of every query scores 0, where the padded sentinel rows score 0
    too and must stay out."""
    rows, queries, k = _all_negative(na, rng)
    rows[7] = 0.0
    return rows, queries, k


def _zero_query(na, rng):
    """Every row scores 0 for the zero query: the answer is the k
    largest ids, and no window holds the tie (the host oracle's)."""
    rows, queries, k = _uniform(na, rng)
    queries[3] = 0.0
    return rows, queries, k


def _k_past_rows(na, rng):
    return f32(rng.uniform(-1, 1, (100, na))), \
        f32(rng.uniform(-1, 1, (8, na))), 150


def _unequal_norms(na, rng):
    """Row norms over six decades: the bound is the largest norm's, the
    best rows are the large ones, and small rows' scores sit far inside
    the bound of each other."""
    rows = rng.uniform(-1, 1, (2000, na)) \
        * 10.0 ** rng.uniform(-3, 3, (2000, 1))
    return f32(rows), f32(rng.uniform(-1, 1, (16, na))), 10


CASES = {
    "uniform_200": (_uniform, 200), "uniform_128": (_uniform, 128),
    "integer_ties": (_integer_ties, 16), "all_negative": (_all_negative, 200),
    "zero_row": (_zero_row, 128), "zero_query": (_zero_query, 200),
    "k_past_rows": (_k_past_rows, 200), "unequal_norms": (_unequal_norms, 72),
}


@pytest.mark.parametrize("case", CASES)
def test_served_inner_product_is_the_reference(case):
    build, na = CASES[case]
    rows, queries, k = build(na, np.random.default_rng([46, len(case)]))
    corpus = corpus_of(rows)
    resp, stats = served(corpus, queries, k)
    assert_exact(resp, corpus, queries, k)
    eng, device = stats["engine"], stats["device"]
    assert device["score"] == "ip" and device["select"] == "extract"
    assert device["kernel_variant"]["score"] == "ip"
    assert set(eng["paths"].values()) == {"extract"}
    from dmlp_tpu.ops.pallas_extract import lane_padded
    assert eng["staged_attrs"] == lane_padded(na)
    # the scorer's bounds are squared L2's: it does not run under ip
    assert eng["summary_blocks"] == 0
    repairs = eng["repairs"]
    if case == "integer_ties":
        # products tie past the window: flagged, and the 512-slot retry
        # holds some groups whole (device) and not others (host)
        assert repairs["flagged_queries"] > 0 and repairs["device"] > 0
    if case == "zero_query":
        assert repairs["host"] >= 1       # 2000 rows tie: the oracle's
    if case == "zero_row":
        assert all(r[0] == 7 and s[0] == 0.0
                   for r, s in zip(resp["neighbors"], resp["dists"]))
    if case == "all_negative":
        assert max(max(s) for s in resp["dists"]) < 0.0
    if case == "k_past_rows":
        assert all(r[100:] == [-1] * 50 for r in resp["neighbors"])


def test_a_flagged_query_the_retry_cannot_clear_goes_to_the_oracle():
    """Six hundred copies of one point: the best group overflows the
    retry's 512 slots too, so the host oracle answers, under ip."""
    rng = np.random.default_rng(4601)
    pts = rng.integers(-3, 4, (4, 16)).astype(np.float64)
    rows = np.repeat(pts, 600, axis=0)[rng.permutation(2400)]
    queries = pts[:2] * 2.0
    corpus = corpus_of(rows)
    resp, stats = served(corpus, queries, 10)
    assert_exact(resp, corpus, queries, 10)
    assert stats["engine"]["repairs"]["host"] >= 1


def test_the_control_differs():
    """Fast mode (the configuration's control): the device's float32
    scores of the bfloat16 rows, no float64 rescore. Its ids are right
    on almost every query; its scores are off by far more than the
    limit."""
    rows, queries, k = _uniform(200, np.random.default_rng(4602))
    corpus = corpus_of(rows)
    resp, _ = served(corpus, queries, k, exact=False)
    want = ref_ip.knn_exact(rows, corpus.labels, queries,
                            np.full(len(queries), k))
    worst = max(float(np.max(np.abs(np.asarray(resp["dists"][j])
                                    - w.dists) / ref_ip.dist_scale(w.dists)))
                for j, w in enumerate(want))
    assert worst > 1e-6 > LIMIT


def test_a_float32_rescore_fails_the_limit():
    """What ``dist_rel_err_max`` is there to catch: the same products
    accumulated in float32 are 1e-7 of the scale off."""
    rows, queries, k = _uniform(200, np.random.default_rng(4603))
    want = ref_ip.knn_exact(rows, np.zeros(len(rows), np.int64), queries,
                            np.full(len(queries), k))
    worst = 0.0
    for q, w in zip(queries, want):
        s32 = (rows[w.ids].astype(np.float32)
               * q.astype(np.float32)).sum(axis=1, dtype=np.float32)
        worst = max(worst, float(np.max(
            np.abs(s32 - w.dists) / ref_ip.dist_scale(w.dists))))
    assert worst > 1e-9 > LIMIT


def test_l2_answers_and_compiles_as_before():
    """``score="l2"`` (stated or left out) is the parent's engine: the
    golden model's squared-L2 answers to the byte, one compile a bucket
    and one for the retry, and the fold's jit holds two programs a
    daemon shape (the bucket's, the retry's) whichever way the score is
    spelled; an ip daemon of the same shape adds its own two."""
    from dmlp_tpu.io.report import format_results
    from dmlp_tpu.serve.engine import _fold_stack
    rows, queries, k = _uniform(37, np.random.default_rng(4604))
    corpus = corpus_of(rows)
    inp = KNNInput(Params(len(rows), len(queries), 37), corpus.labels, rows,
                   np.full(len(queries), k, np.int32), queries)
    gold = knn_golden(inp)
    seen = []
    for kw in ({}, {"score": "l2"}, {"score": "ip"}):
        before = _fold_stack._cache_size()
        cfg = EngineConfig(use_pallas=True, select="extract",
                           dtype="bfloat16", **kw)
        daemon = ServeDaemon(corpus, cfg, warm_buckets=[(len(queries), k)])
        try:
            daemon.start()
            resp = ask(daemon.port, {"op": "query", "k": k, "debug": True,
                                     "queries": queries.tolist()})
            count = daemon.engine.compile_count
            res = daemon.engine.solve_batch(queries, inp.ks)
        finally:
            daemon.close()
        seen.append((_fold_stack._cache_size() - before, count))
        if kw.get("score") != "ip":
            assert format_results(res) == format_results(gold)
            assert resp["checksums"] == [g.checksum() for g in gold]
            assert resp["dists"] == [g.neighbor_dists.tolist()
                                     for g in gold]
    assert seen == [(2, 2), (0, 2), (2, 2)]
