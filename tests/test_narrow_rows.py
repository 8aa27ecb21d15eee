"""Rows that are not whole 128-lane vectors, signed or not (PR 40).

MS Turing-ANNS's rows are 100 wide and signed. Left as they are, the
chip's compiler keeps the resident stack rows-minor and every fold
re-lays it out (``tests/test_tpu_aot.py`` holds the compiled program to
that); the serving engine therefore stages a row that fills more than
half a lane vector on whole lanes
(``ops.pallas_extract.lane_padded``), and nothing a client, the host
rows, the float64 rescore or a checksum sees knows of the zero columns.
These tests hold the served path (``ServeDaemon`` + ``start()``, the
extract path, the kernel in interpret mode) to the benchmark's own
plain float64 reference at the widths the field runs (20, 96, 100, 200,
300), under both staging dtypes and both signs; the device retry and an
ingest at 100 attributes; and the staging of 128-, 960- and 1024-wide
rows to what it was before the rule reached below 128.
"""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import reference
from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.single import np_staging_dtype
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.ops.pallas_extract import lane_padded
from dmlp_tpu.serve import client as sc
from dmlp_tpu.serve.daemon import ServeDaemon
from dmlp_tpu.serve.engine import ResidentEngine

N, NQ, K = 13000, 8, 10        # two chunks of 12 800: a carried fold


def config(dtype: str) -> EngineConfig:
    # ``python -m dmlp_tpu.serve --pallas --dtype <dtype>``, with chunks
    # of one extraction block so that a small corpus folds two
    return EngineConfig(use_pallas=True, dtype=dtype, data_block=12800)


def draw(rng, shape, signed: bool) -> np.ndarray:
    """float32-exact reals as ``benchmark/data.py`` draws them: uniform
    in [-1, 1) (``msturing-10m``'s ``values``) or in [0, 255)."""
    x = rng.random(shape, dtype=np.float32)
    x = x * np.float32(2) - np.float32(1) if signed else x * np.float32(255)
    return x.astype(np.float64)


def corpus_of(n: int, na: int, seed: int, signed: bool = True) -> KNNInput:
    rng = np.random.default_rng(seed)
    return KNNInput(Params(n, 0, na),
                    rng.integers(0, 10, n).astype(np.int32),
                    draw(rng, (n, na), signed),
                    np.zeros(0, np.int32), np.zeros((0, na)))


def assert_plain(resp, rows, labels, q, k=K):
    """A ``debug`` response against ``knn_plain``: labels, ids,
    checksums, and the float64 distances to the last bit."""
    assert resp["ok"], resp
    want = reference.knn_plain(rows, labels, q, [k] * len(q))
    for i, ref in enumerate(want):
        assert resp["labels"][i] == ref.label
        assert resp["neighbors"][i] == [int(v) for v in ref.ids]
        assert resp["checksums"][i] == ref.checksum
        assert np.array_equal(resp["dists"][i], ref.dists)


def spans(tracer, name):
    return [e.get("args", {}) for e in tracer.events()
            if e.get("ph") == "X" and e["name"] == name]


@pytest.fixture()
def tracer():
    t = obs_trace.install(obs_trace.Tracer())
    try:
        yield t
    finally:
        obs_trace.uninstall()


# -- the rule ------------------------------------------------------------------

def test_rows_past_half_a_lane_vector_are_staged_on_whole_lanes():
    assert [lane_padded(a) for a in (20, 64, 65, 96, 100, 127, 128, 129,
                                     200, 300)] \
        == [20, 64, 128, 128, 128, 128, 128, 256, 256, 384]


# -- the served path against the plain reference -------------------------------

@pytest.mark.parametrize("signed", [True, False],
                         ids=["signed", "nonnegative"])
@pytest.mark.parametrize("staging", ["bfloat16", "float32"])
@pytest.mark.parametrize("na", [20, 96, 100, 200, 300])
def test_the_daemon_answers_as_the_plain_reference(na, staging, signed,
                                                   tracer):
    corpus = corpus_of(N, na, seed=40 + na, signed=signed)
    d = ServeDaemon(corpus, config(staging), port=0,
                    warm_buckets=[(NQ, K)])
    d.start()
    try:
        cli = sc.ServeClient(d.port)
        q = draw(np.random.default_rng(7 + na), (NQ, na), signed)
        assert_plain(cli.query(q, k=K, debug=True), corpus.data_attrs,
                     corpus.labels, q)
        eng = cli.stats()["stats"]["engine"]
        cli.close()
    finally:
        d.close()
    a_pad = lane_padded(na)
    assert eng["paths"] == {"q128k16": "extract"}
    assert (eng["num_attrs"], eng["staged_attrs"]) == (na, a_pad)
    assert eng["repairs"]["host"] == 0
    assert d.engine._chunks.shape == (2, 12800, a_pad)
    assert d.engine._chunks.dtype == np_staging_dtype(staging)
    # the streaming paths' copy, the host rows and the wire keep the width
    assert d.engine._d_attrs.shape[1] == na
    assert d.engine._host_attrs.shape[1] == na
    item = 2 if staging == "bfloat16" else 4
    (chunks,) = spans(tracer, "serve.stage_chunks")
    assert (chunks["na"], chunks["a_pad"], chunks["pad_bytes"]) \
        == (na, a_pad, 2 * 12800 * (a_pad - na) * item)
    (resident,) = spans(tracer, "serve.stage_resident")
    assert (resident["na"], resident["a_pad"], resident["pad_bytes"]) \
        == (na, na, 0)
    for name in ("serve.solve_extract", "serve.warmup_bucket"):
        assert {s["a_pad"] for s in spans(tracer, name)} == {a_pad}
    assert d.engine.last_variant["a_pad"] == a_pad


# -- the device retry at 100 attributes ----------------------------------------

def test_a_flagged_query_is_cleared_by_the_device_retry_at_100_attributes(
        tracer):
    """300 near-duplicates of one row fill the 120-slot window that
    bfloat16 staging plans: the queries beside them are flagged, solved
    again over the padded stack at 512 slots, and cleared there; the
    host oracle never scans."""
    na = 100
    corpus = corpus_of(N, na, seed=4)
    rng = np.random.default_rng(5)
    rows = corpus.data_attrs.copy()
    rows[1000:1300] = rows[999] + rng.random((300, na)) * 1e-4
    corpus = KNNInput(corpus.params, corpus.labels, rows, corpus.ks,
                      corpus.query_attrs)
    eng = ResidentEngine(corpus, config("bfloat16"))
    eng.warmup([(NQ, K)])
    warm = len(spans(tracer, "single.retry"))   # warm-up drives its own
    before = dict(eng.bucket_stats()["repairs"])
    q = draw(rng, (NQ, na), True)
    q[0] = rows[999] + 2e-3
    q[1] = rows[999] - 1e-3
    got = eng.solve_batch(q, np.full(NQ, K, np.int32))
    want = reference.knn_plain(rows, corpus.labels, q, [K] * NQ)
    for res, ref in zip(got, want):
        assert res.predicted_label == ref.label
        assert np.array_equal(res.neighbor_ids, ref.ids)
        assert np.array_equal(res.neighbor_dists, ref.dists)
    (retry,) = spans(tracer, "single.retry")[warm:]
    assert retry["queries"] >= 2 and retry["kcap"] == 512
    assert retry["cleared"] == retry["queries"]
    assert retry["fell_through"] == 0
    assert not spans(tracer, "single.repair")
    after = eng.bucket_stats()["repairs"]
    assert after["device"] - before["device"] == retry["queries"]
    assert after["host"] == before["host"]
    assert eng._retry_kernel(eng.bucket_plan(NQ, K)[2]) is not None


# -- ingest after start at 100 attributes --------------------------------------

def test_rows_ingested_after_start_are_read_back_at_100_attributes():
    na = 100
    corpus = corpus_of(12800, na, seed=8)
    d = ServeDaemon(corpus, config("bfloat16"), port=0, capacity=25600,
                    warm_buckets=[(NQ, K)])
    d.start()
    try:
        cli = sc.ServeClient(d.port)
        rng = np.random.default_rng(9)
        newa = draw(rng, (300, na), True)        # all of them in chunk 2
        newl = rng.integers(0, 10, 300).astype(np.int32)
        before = cli.stats()["stats"]["engine"]["extract_chunks"]
        r = cli.ingest(newl, newa)
        assert r["ok"] and r["corpus_rows"] == 13100
        q = newa[:NQ] + 1e-3
        resp = cli.query(q, k=K, debug=True)
        rows = np.vstack([corpus.data_attrs, newa])
        assert_plain(resp, rows, np.concatenate([corpus.labels, newl]), q)
        assert [n[0] for n in resp["neighbors"]] \
            == list(range(12800, 12800 + NQ))
        after = cli.stats()["stats"]["engine"]["extract_chunks"]
        cli.close()
    finally:
        d.close()
    assert (before, after) == (1, 2)
    stack = np.asarray(d.engine._chunks).astype(np.float32)
    assert stack.shape[1:] == (12800, 128)      # capacity: a chunk to spare
    assert not stack[..., na:].any()                 # zeros stay zeros
    assert np.array_equal(
        stack[1, :300, :na],
        newa.astype(np_staging_dtype("bfloat16")).astype(np.float32))


# -- what the memory model prices ----------------------------------------------

@pytest.mark.parametrize("staging,item", [("bfloat16", 2), ("float32", 4)])
def test_the_memory_model_prices_the_staged_width(staging, item):
    na = 100
    eng = ResidentEngine(corpus_of(N, na, seed=2), config(staging))
    eng.warmup([(NQ, K)])
    qpad, _kb, kcap = eng.bucket_plan(NQ, K)
    terms = eng.mem_model(NQ, K)["terms"]
    assert terms["extract_chunks"] == 2 * 12800 * 128 * item
    assert terms["resident_corpus"] == eng.capacity_rows * na * item
    assert terms["query_blocks"] == qpad * 128 * item
    assert terms["resident_summaries"] == 2 * (8 * 128 + 12)
    # the window and the rescore's block reckon from the row's own width
    from dmlp_tpu.engine.finalize import rescore_block
    from dmlp_tpu.engine.single import resolve_kcap
    assert kcap == resolve_kcap(eng.config, 16, "extract",
                                eng.capacity_rows, staging=staging,
                                precision=eng._precision_plan, na=na)
    assert eng._kcap_attrs == na
    assert (kcap, rescore_block(kcap, na)) \
        == {"bfloat16": (120, 174), "float32": (32, 512)}[staging]


# -- whole-lane rows are staged as they were -----------------------------------

@pytest.mark.parametrize("na,want", [(128, 128), (960, 1024), (1024, 1024)])
def test_whole_lane_and_wide_rows_are_staged_as_before(na, want, tracer):
    """The rule below 128 moves nothing at 128, 960 or 1024: the width
    ``lane_padded`` gives, the stack's shape and every byte of it are
    what the parent staged (rows in the leading columns, zeros after)."""
    assert lane_padded(na) == want
    corpus = corpus_of(N, na, seed=na, signed=False)
    eng = ResidentEngine(corpus, config("float32"))
    eng.warmup([(NQ, K)])
    assert eng._chunks.shape == (2, 12800, want)
    staged = np.zeros((2 * 12800, want), np.float32)
    staged[:N, :na] = corpus.data_attrs
    assert np.array_equal(np.asarray(eng._chunks).reshape(-1, want), staged)
    assert eng._d_attrs.shape == (eng.capacity_rows, na)
    (chunks,) = spans(tracer, "serve.stage_chunks")
    assert (chunks["na"], chunks["a_pad"], chunks["pad_bytes"]) \
        == (na, want, 2 * 12800 * (want - na) * 4)
    stats = eng.bucket_stats()
    assert (stats["num_attrs"], stats["staged_attrs"]) == (na, want)
