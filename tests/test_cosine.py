"""Cosine search on the one-chip served path (PR 49).

``EngineConfig(score="cosine")``: the corpus is ranked by LARGEST
s(q, x) = q.x / (|q||x|) (float64 on the rows as given; 0 against a
zero vector; s descending, id DESCENDING on ties; ``dists`` carries the
angular distance 1 - s, ascending, padded slots +inf). The resident
engine on the extract path (interpret mode here) holds x / |x| on the
device and runs the kernel's "ip" form over it; it is held to the
golden model (strict and fast) and to the benchmark's plain reference
(``benchmark/references/cosine.py``) on corpora built to break each
piece: the cell's own width and one that is not whole lanes, both
staging dtypes, a zero row, a zero query, exact copies of a row
straddling the k-th (id order), rows of very unequal norms (where the
inner product and the cosine disagree most), a tie group the device
retry clears and one only the host oracle can, rows ingested after the
first batch. The host keeps the rows as they were given.
"""

from __future__ import annotations

import json
import socket

import numpy as np
import pytest

from benchmark.references import cosine as ref_cos
from dmlp_tpu.config import EngineConfig
from dmlp_tpu.golden.fast import knn_golden_fast
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import telemetry
from dmlp_tpu.serve.engine import ResidentEngine
from tests.test_inner_product import corpus_of, f32

LIMIT = 1e-12


def engine_of(corpus: KNNInput, **engine) -> ResidentEngine:
    return ResidentEngine(corpus, EngineConfig(**{
        "use_pallas": True, "select": "extract", "dtype": "float32",
        "score": "cosine", **engine}))


def repairs() -> dict:
    return ResidentEngine._repair_stats()


def assert_exact(results, rows, labels, queries, k):
    """Engine results against the golden models and both references."""
    ks = np.full(len(queries), k)
    inp = KNNInput(Params(len(rows), len(queries), rows.shape[1]), labels,
                   rows, ks.astype(np.int32), queries)
    gold = knn_golden(inp, score="cosine")
    fast = knn_golden_fast(inp, score="cosine")
    plain = ref_cos.knn_plain(rows, labels, queries, ks)
    exact = ref_cos.knn_exact(rows, labels, queries, ks)
    for j, (r, g, f, p, e) in enumerate(zip(results, gold, fast, plain,
                                            exact)):
        for want_ids, want_d, label, checksum in (
                (g.neighbor_ids, g.neighbor_dists, g.predicted_label,
                 g.checksum()),
                (f.neighbor_ids, f.neighbor_dists, f.predicted_label,
                 f.checksum()),
                (p.ids, p.dists, p.label, p.checksum),
                (e.ids, e.dists, e.label, e.checksum)):
            assert np.array_equal(r.neighbor_ids, want_ids), j
            assert r.predicted_label == label and r.checksum() == checksum
            real = r.neighbor_ids >= 0
            assert np.all(np.isposinf(r.neighbor_dists[~real]))
            assert np.all(np.isposinf(np.asarray(want_d)[~real]))
            assert np.all(np.abs(r.neighbor_dists[real]
                                 - np.asarray(want_d)[real]) <= LIMIT), j
        # the contract's order: d ascending, larger id first on ties
        d, i = r.neighbor_dists[real], r.neighbor_ids[real]
        assert np.all((d[:-1] < d[1:]) | ((d[:-1] == d[1:])
                                          & (i[:-1] > i[1:]))), j


def _uniform(na, rng):
    return f32(rng.uniform(-1, 1, (2000, na))), \
        f32(rng.uniform(-1, 1, (12, na))), 10


def _zero_row(na, rng):
    """A zero row scores 0 against every query: with every other score
    negative it is the best row of all, as the contract says, though the
    device holds it as zeros like a padded sentinel."""
    rows = f32(rng.uniform(0.1, 1, (2000, na)))
    rows[7] = 0.0
    return rows, f32(rng.uniform(-1, -0.1, (8, na))), 10


def _zero_query(na, rng):
    """Every row scores 0 for the zero query: the answer is the k
    largest ids at d = 1, and no window holds the tie."""
    rows, queries, k = _uniform(na, rng)
    queries[3] = 0.0
    return rows, queries, k


def _copies_at_the_kth(na, rng):
    """Fourteen exact copies of a row that is each query's near-best,
    scattered: they tie exactly, the k-th falls inside the group, and
    the ten reported are the LARGEST ids of it."""
    rows, queries, k = _uniform(na, rng)
    at = rng.choice(len(rows), 14, replace=False)
    rows[at] = rows[at[0]]
    queries[:] = f32(rows[at[0]] * rng.uniform(0.5, 3, (len(queries), 1))
                     + rng.uniform(-0.05, 0.05, queries.shape))
    return rows, queries, k


def _unequal_norms(na, rng):
    """Row norms over six decades: the inner product ranks the long rows
    first, the cosine does not see a row's length at all."""
    rows = rng.uniform(-1, 1, (2000, na)) \
        * 10.0 ** rng.uniform(-3, 3, (2000, 1))
    return f32(rows), f32(rng.uniform(-1, 1, (12, na))), 10


def _k_past_rows(na, rng):
    return f32(rng.uniform(-1, 1, (100, na))), \
        f32(rng.uniform(-1, 1, (8, na))), 150


CASES = {
    "uniform_1536": (_uniform, 1536, "float32"),
    "uniform_200_bf16": (_uniform, 200, "bfloat16"),
    "uniform_100": (_uniform, 100, "float32"),
    "zero_row": (_zero_row, 136, "float32"),
    "zero_query": (_zero_query, 1536, "float32"),
    "copies_at_the_kth": (_copies_at_the_kth, 200, "float32"),
    "unequal_norms": (_unequal_norms, 72, "bfloat16"),
    "k_past_rows": (_k_past_rows, 200, "float32"),
}


@pytest.mark.parametrize("case", CASES)
def test_the_resident_engine_under_cosine_is_the_golden_model(case):
    build, na, dtype = CASES[case]
    rows, queries, k = build(na, np.random.default_rng([49, len(case)]))
    corpus = corpus_of(rows)
    before = repairs()
    eng = engine_of(corpus, dtype=dtype)
    results = eng.solve_batch(queries, np.full(len(queries), k, np.int32))
    assert_exact(results, rows, corpus.labels, queries, k)
    assert eng._last_select == "extract"
    assert eng.last_variant["score"] == "cosine"
    # the scorer's bounds are squared L2's: it does not run
    assert eng.bucket_stats()["summary_blocks"] == 0
    host = repairs()["host"] - before["host"]
    if case == "zero_row":
        assert all(r.neighbor_ids[0] == 7 and r.neighbor_dists[0] == 1.0
                   for r in results)
    if case == "zero_query":
        assert host >= 1                   # 2000 rows tie: the oracle's
        assert np.array_equal(results[3].neighbor_ids,
                              np.arange(1999, 1989, -1))
        assert np.all(results[3].neighbor_dists == 1.0)
    if case == "copies_at_the_kth":
        group = np.sort(np.nonzero((rows == rows[results[0].neighbor_ids[0]]
                                    ).all(axis=1))[0])[::-1]
        assert len(group) == 14
        for r in results:
            assert np.array_equal(r.neighbor_ids, group[:10])
            assert len(set(r.neighbor_dists)) == 1      # an exact tie
    if case == "k_past_rows":
        assert all(np.all(r.neighbor_ids[100:] == -1) for r in results)


def test_the_host_keeps_the_rows_as_given_and_the_device_unit_rows():
    """``corpus_slice`` (what seeds a replica) returns the caller's rows
    to the bit, the corpus signature is the one an l2 engine of the same
    rows reports, the norms lie beside the rows, and the stack holds
    x / |x| in float32 (a zero row as zeros)."""
    rows, _q, _k = _unequal_norms(200, np.random.default_rng(4901))
    rows[11] = 0.0
    corpus = corpus_of(rows)
    eng = engine_of(corpus)
    eng.solve_batch(rows[:4] * 3.0, np.full(4, 5, np.int32))
    labels, got = eng.corpus_slice(0, len(rows))
    assert np.array_equal(got, rows) and np.array_equal(labels, corpus.labels)
    plain = ResidentEngine(corpus, EngineConfig(
        use_pallas=True, select="extract", dtype="float32"))
    assert eng.corpus_state() == plain.corpus_state()
    norms = np.sqrt(np.einsum("na,na->n", rows, rows))
    assert np.array_equal(eng._host_norms[:len(rows)], norms)
    assert eng._dn_max() == 1.0
    staged = np.asarray(eng._chunks).reshape(-1, eng._ex_attrs)[
        :len(rows), :200]
    want = (rows / np.where(norms > 0, norms, 1.0)[:, None]
            ).astype(np.float32)
    assert np.array_equal(staged, want)
    assert not staged[11].any()
    flat = np.asarray(eng._d_attrs)[:len(rows)]
    assert np.array_equal(flat, want)
    # a replica seeded from the slice answers identically
    twin = engine_of(corpus_of(got))
    q = f32(np.random.default_rng(4902).uniform(-1, 1, (6, 200)))
    ks = np.full(6, 10, np.int32)
    for a, b in zip(eng.solve_batch(q, ks), twin.solve_batch(q, ks)):
        assert np.array_equal(a.neighbor_ids, b.neighbor_ids)
        assert np.array_equal(a.neighbor_dists, b.neighbor_dists)


def test_rows_ingested_after_the_first_batch_are_normalised_like_staged():
    """An append and an overwrite (a zero row among them) after the
    stack has staged: the answers are the golden model's over the corpus
    as it then stands, ``corpus_slice`` returns what was given, and the
    zero rows met are counted."""
    rng = np.random.default_rng(4903)
    rows = f32(rng.uniform(-1, 1, (1500, 200)))
    corpus = corpus_of(rows)
    zero0 = telemetry.registry().counter("serve.zero_rows").total()
    eng = ResidentEngine(corpus, EngineConfig(
        use_pallas=True, select="extract", dtype="float32",
        score="cosine"), capacity=2048)
    q = f32(rng.uniform(-1, 1, (6, 200)))
    ks = np.full(6, 10, np.int32)
    eng.solve_batch(q, ks)                        # stages the stack
    more = f32(rng.uniform(-1, 1, (300, 200)) * 40.0)
    more[5] = 0.0
    more[:3] = q[:3] * 7.0       # scaled copies of queries: s = 1 to rounding
    eng.ingest(rng.integers(0, 5, 300), more)
    over = f32(rng.uniform(-1, 1, (10, 200)) * 1e-3)
    eng.ingest(np.arange(10) % 5, over, start=100)
    labels, got = eng.corpus_slice(0, 1800)
    want = np.concatenate([rows, more])
    want[100:110] = over
    assert np.array_equal(got, want)
    assert telemetry.registry().counter("serve.zero_rows").total() \
        - zero0 == 1
    results = eng.solve_batch(q, ks)
    assert_exact(results, want, labels, q, 10)
    assert [r.neighbor_ids[0] for r in results[:3]] == [1500, 1501, 1502]


@pytest.mark.parametrize("copies, where", [(100, "device"), (600, "host")])
def test_a_flagged_query_is_cleared_by_the_retry_or_by_the_oracle(
        copies, where):
    """A tie group at the top that overflows the bucket's window: the
    hazard test flags the query; the retry's 512 slots hold a group of
    100 whole (cleared on the device) and not one of 600 (the host
    oracle's, under cosine, from the norms the engine keeps)."""
    rng = np.random.default_rng([4904, copies])
    rows = f32(rng.uniform(-1, 1, (3000, 72)))
    at = rng.choice(3000, copies, replace=False)
    rows[at] = rows[at[0]]
    queries = f32(rows[at[:2]] * 2.5)
    corpus = corpus_of(rows)
    before = repairs()
    eng = engine_of(corpus)
    results = eng.solve_batch(queries, np.full(2, 10, np.int32))
    assert_exact(results, rows, corpus.labels, queries, 10)
    after = repairs()
    assert after["flagged_queries"] - before["flagged_queries"] == 2
    assert after[where] - before[where] == 2
    assert np.array_equal(results[0].neighbor_ids,
                          np.sort(at)[::-1][:10])


def test_on_the_rehearsal_corpus_the_three_scores_answer_differently():
    """The cell's own rows at its rehearsal size: rows that are NOT unit
    vectors, on purpose, so that the top 10 by cosine is neither the top
    10 by inner product nor by squared L2 for the checked queries, and a
    program that computed another score would fail the cell's check."""
    from benchmark import data, spec
    cell = spec.Cell("dbpedia-openai-1m.bulk", rehearse=True)
    labels, rows = data.corpus(cell.config, 49)
    queries = data.request_queries(cell.config, 49, 0, 16)
    inp = KNNInput(Params(len(rows), 16, rows.shape[1]), labels, rows,
                   np.full(16, 10, np.int32), queries)
    tops = {s: [tuple(r.neighbor_ids) for r in knn_golden_fast(inp, score=s)]
            for s in ("cosine", "ip", "l2")}
    assert cell.config["engine"]["score"] == "cosine"
    assert sum(c != i for c, i in zip(tops["cosine"], tops["ip"])) >= 8
    assert sum(c != l for c, l in zip(tops["cosine"], tops["l2"])) >= 8
    want = ref_cos.knn_exact(rows, labels, queries, np.full(16, 10))
    assert [tuple(w.ids) for w in want] == tops["cosine"]


def test_a_cosine_daemon_over_the_wire():
    """Through the daemon, the batcher and the protocol: ``dists`` are
    the angular distances, ascending; the stamp says cosine and
    extract; a zero query is answered."""
    from dmlp_tpu.serve.daemon import ServeDaemon
    rows, queries, k = _zero_query(136, np.random.default_rng(4905))
    corpus = corpus_of(rows)
    cfg = EngineConfig(use_pallas=True, select="extract", dtype="float32",
                       score="cosine")
    daemon = ServeDaemon(corpus, cfg, warm_buckets=[(len(queries), k)])

    def ask(obj):
        with socket.create_connection(("127.0.0.1", daemon.port),
                                      timeout=120) as s:
            f = s.makefile("rwb")
            f.write((json.dumps(obj) + "\n").encode())
            f.flush()
            return json.loads(f.readline())
    try:
        daemon.start()
        resp = ask({"op": "query", "k": k, "debug": True,
                    "queries": queries.tolist()})
        stats = ask({"op": "stats"})["stats"]
    finally:
        daemon.close()
    assert resp["ok"], resp
    want = ref_cos.knn_exact(rows, corpus.labels, queries,
                             np.full(len(queries), k))
    for j, w in enumerate(want):
        assert resp["neighbors"][j] == w.ids.tolist()
        assert resp["checksums"][j] == w.checksum
        assert resp["labels"][j] == w.label
        assert np.abs(np.asarray(resp["dists"][j]) - w.dists).max() <= LIMIT
        assert resp["dists"][j] == sorted(resp["dists"][j])
    device = stats["device"]
    assert device["score"] == "cosine" and device["select"] == "extract"
    assert device["kernel_variant"]["score"] == "cosine"


def test_the_control_differs():
    """Fast mode (the configuration's control): the device's float32
    cosines of the normalised float32 rows, no float64 rescore: off by
    far more than the cell's limit in the reference's own scale."""
    rows, queries, k = _uniform(1536, np.random.default_rng(4906))
    corpus = corpus_of(rows)
    eng = engine_of(corpus, exact=False)
    got = eng.solve_batch(queries, np.full(len(queries), k, np.int32))
    want = ref_cos.knn_exact(rows, corpus.labels, queries,
                             np.full(len(queries), k))
    worst = max(float(np.max(np.abs(g.neighbor_dists - w.dists)
                             / ref_cos.dist_scale(w.dists)))
                for g, w in zip(got, want))
    assert worst > 1e-9 > 1e-11


@pytest.mark.parametrize("score", ["cosine", "ip", "l2"])
def test_the_cli_golden_engine_takes_the_score(score):
    """``python -m dmlp_tpu --engine golden --score S``: the golden
    model's text under S, checksums and ``--debug`` alike (cosine's
    debug text prints the angular distances); the three differ."""
    import io

    from dmlp_tpu.cli import main
    from dmlp_tpu.golden.reference import solve_text
    from dmlp_tpu.io.datagen import generate_input_text
    text = generate_input_text(200, 12, 6, -2, 2, 3, 8, 4, seed=49)
    outs = {}
    for debug in (False, True):
        out, err = io.StringIO(), io.StringIO()
        args = ["--engine", "golden", "--score", score] \
            + (["--debug"] if debug else [])
        assert main(args, stdin=io.StringIO(text), stdout=out,
                    stderr=err) == 0
        assert out.getvalue() == solve_text(text, debug=debug, score=score)
        outs[debug] = out.getvalue()
    others = [solve_text(text, score=s) for s in ("cosine", "ip", "l2")
              if s != score]
    assert outs[False] not in others


def test_the_batch_cli_refuses_cosine_by_name():
    import io

    from dmlp_tpu.cli import main
    from dmlp_tpu.io.datagen import generate_input_text
    text = generate_input_text(50, 4, 4, -1, 1, 1, 3, 2, seed=5)
    with pytest.raises(ValueError, match=r"SingleChipEngine \(the batch "
                       r"solve\) has no score='cosine' form"):
        main(["--score", "cosine"], stdin=io.StringIO(text),
             stdout=io.StringIO(), stderr=io.StringIO())
