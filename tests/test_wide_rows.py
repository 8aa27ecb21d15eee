"""Rows wider than one lane vector on the extract path (PR 31).

The kernel's data block follows the row width
(``ops.pallas_extract.resolve_variant``), the resident stack holds a
row on whole lanes (``lane_padded``), and everything a client or the
benchmark sees keeps the corpus' own width. On the CPU the kernel runs
in interpret mode, so these tests hold the path and the answers, not a
time.
"""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import reference
from dmlp_tpu.config import EngineConfig
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.io.report import format_results
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.ops import pallas_extract, pallas_fused
from dmlp_tpu.ops.pallas_extract import (_TN, lane_padded, variant_supports,
                                         vmem_bytes)
from dmlp_tpu.serve.engine import ResidentEngine

#: the variants the rule gave before the width entered it: what the
#: accepted cells' Mosaic programs were built from
#: (the fold pass's lane vectors ride with the tiles since PR 47: 10 a
#: bucket at a 12 800-row block; the same PR re-measured the wide
#: lists' query tile with the pass in: 128 rows, not 64)
NARROW = {"tile_q": 128, "ne": 2, "unroll": 1, "fold": 10}
WIDE_K = {"tile_q": 128, "ne": 4, "unroll": 1, "fold": 10}


def config():
    # ``python -m dmlp_tpu.serve --pallas --dtype float32``, with chunks
    # of one extraction block so that a small corpus folds several
    return EngineConfig(use_pallas=True, dtype="float32", data_block=12800)


def corpus_of(n: int, na: int, seed: int) -> KNNInput:
    rng = np.random.default_rng(seed)
    rows = rng.random((n, na), dtype=np.float32).astype(np.float64)
    return KNNInput(Params(n, 0, na),
                    rng.integers(0, 10, n).astype(np.int32), rows,
                    np.zeros(0, np.int32), np.zeros((0, na)))


# -- the rule ------------------------------------------------------------------

@pytest.mark.parametrize("kc,b,qb,a,want", [
    (32, 51200, 1024, 128, NARROW),    # bigann.bulk, bigann-mesh4.bulk
    (32, 51200, 128, 128, NARROW),     # bigann.steady's buckets
    (32, 51200, 256, 128, NARROW),
    (32, 51200, 512, 128, NARROW),
    (144, 51200, 10112, 64, WIDE_K),   # the batch benchmark's config 4
    (32, 12800, 128, 16, NARROW),      # the rehearsals' toy width
    (32, 51200, 1024, 512, NARROW),    # the widest row _TN still holds
], ids=["bulk", "steady128", "steady256", "steady512", "config4", "toy",
        "a512"])
def test_narrow_rows_resolve_to_the_variants_they_always_did(
        kc, b, qb, a, want):
    resolver = pallas_extract.resolve_variant
    assert resolver(kc, b, qb, a) == want      # no tile_n key at all
    assert resolver(kc, b) == want             # shape unknown: as before


@pytest.mark.parametrize("a,tile_n", [(513, 10240), (960, 6400),
                                      (1024, 6400), (2048, 2560),
                                      (4096, 1280)])
def test_the_data_block_follows_the_width(a, tile_n):
    v = pallas_extract.resolve_variant(32, 51200, 1024, a)
    assert v == {**NARROW, "tile_n": tile_n,
                 "fold": pallas_extract.fold_slabs(tile_n)}
    assert v["fold"] == {10240: 8, 6400: 5, 2560: 2, 1280: 2}[tile_n]
    assert pallas_extract.supports(1024, 51200, a, 32)
    kern, impl = pallas_fused.resolve_topk_kernel(1024, 51200, a, 32)
    assert impl == "fused" and kern is pallas_fused.fused_topk
    # the largest tile of 51 200 rows that fits: the next one up does not
    up = min(t for t in (1280, 2560, 5120, 6400, 10240, 12800)
             if t > tile_n)
    assert not variant_supports(1024, 51200, a, 32, {**NARROW, "tile_n": up})


#: (qb, b, a, kc, variant, VMEM bytes priced, supported)
SUPPORTS = [
    (1024, 51200, 128, 32, NARROW, 19857408, True),
    (1024, 51200, 64, 32, NARROW, 19857408, True),      # a lane is a lane
    (1024, 51200, 960, 32, NARROW, 112525312, False),   # the parent's answer
    (1024, 51200, 960, 32, {**NARROW, "tile_n": 6400}, 56819712, True),
    (1024, 51200, 1024, 32, {**NARROW, "tile_n": 6400}, 56819712, True),
    (1024, 51200, 2048, 32, {**NARROW, "tile_n": 6400}, 110297088, False),
    (1024, 51200, 2048, 32, {**NARROW, "tile_n": 2560}, 45416448, True),
    (1024, 51200, 960, 32, {**NARROW, "tile_n": 6400, "ne": 3}, 0, False),
    (1020, 51200, 960, 32, {**NARROW, "tile_n": 6400}, 0, False),
    (1024, 51200, 128, 600, NARROW, 0, False),          # kc past 512
]


@pytest.mark.parametrize("qb,b,a,kc,v,priced,want", SUPPORTS)
def test_variant_supports_table(qb, b, a, kc, v, priced, want):
    assert variant_supports(qb, b, a, kc, v) is want
    if priced:
        assert vmem_bytes(v["tile_q"], v.get("tile_n", _TN), a,
                          kc) == priced
        assert (priced <= 64 * 2**20) is want


def test_lane_padding_at_the_widths_pr31_pinned():
    # (since PR 40 the rule reaches down to 65: tests/test_narrow_rows.py)
    assert [lane_padded(a) for a in (1, 16, 64, 128, 129, 200, 960, 1024,
                                     2048)] == [1, 16, 64, 128, 256, 256,
                                                1024, 1024, 2048]


# -- the served path -----------------------------------------------------------

@pytest.mark.parametrize("na", [128, 960, 2048])
def test_served_extract_path_is_exact_at_every_width(na):
    """A resident engine as the daemon builds it, over seeded rows of
    ``na`` attributes: the bucket is on the extract path, the stack
    holds whole lanes, and labels, ids, checksums and float64 distances
    are the golden model's and the benchmark's own reference's."""
    n, nq, k = 13000, 9, 10
    corpus = corpus_of(n, na, seed=31 + na)
    eng = ResidentEngine(corpus, config())
    rng = np.random.default_rng(7 + na)
    q = rng.random((nq, na), dtype=np.float32).astype(np.float64)
    ks = np.full(nq, k, np.int32)
    got = eng.solve_batch(q, ks)

    (entry,) = eng._buckets.values()
    assert entry.path == "extract" and eng._last_select == "extract"
    assert eng.num_attrs == na
    assert eng._chunks.shape == (2, 12800, lane_padded(na))
    assert eng.bucket_stats()["extract_chunks"] == 2
    v = eng.last_variant
    assert v["a_pad"] == lane_padded(na) and v["norms"] == "staged"
    assert v.get("tile_n", _TN) == {128: _TN, 960: 6400, 2048: 2560}[na]

    inp = KNNInput(Params(n, nq, na), corpus.labels, corpus.data_attrs,
                   ks, q)
    assert format_results(got) == format_results(knn_golden(inp))
    want = reference.knn_exact(corpus.data_attrs, corpus.labels, q, ks)
    for res, ans in zip(got, want):
        assert res.predicted_label == ans.label
        assert list(res.neighbor_ids) == list(ans.ids)
        assert reference.fnv1a(res.predicted_label,
                               res.neighbor_ids) == ans.checksum
        assert np.array_equal(res.neighbor_dists, ans.dists)


def test_wide_rows_ingest_and_model_price_the_padded_stack():
    na = 200
    corpus = corpus_of(12800, na, seed=5)
    eng = ResidentEngine(corpus, config(), capacity=25600)
    rng = np.random.default_rng(6)
    q = rng.random((5, na))
    ks = np.full(5, 6, np.int32)
    eng.solve_batch(q, ks)
    assert eng._chunks.shape[-1] == 256
    # the capacity's further chunks are staged and hold no row yet
    assert eng._ex_nchunks > 1
    assert eng.bucket_stats()["extract_chunks"] == 1
    terms = eng.mem_model(5, 6)["terms"]
    assert terms["extract_chunks"] == eng._ex_nchunks * 12800 * 256 * 4
    assert terms["resident_corpus"] == eng.capacity_rows * na * 4
    assert terms["query_blocks"] == 128 * 256 * 4
    m = 300                                     # spills into chunk 2
    newl = rng.integers(0, 10, m).astype(np.int32)
    newa = rng.random((m, na))
    eng.ingest(newl, newa)
    assert eng.bucket_stats()["extract_chunks"] == 2
    grown = KNNInput(Params(12800 + m, 5, na),
                     np.concatenate([corpus.labels, newl]),
                     np.vstack([corpus.data_attrs, newa]), ks, q)
    assert format_results(eng.solve_batch(q, ks)) \
        == format_results(knn_golden(grown))
    # the padded columns of every staged row are zeros
    assert not np.asarray(eng._chunks[..., na:]).any()


# -- the candidate window at 960-d ---------------------------------------------

@pytest.mark.parametrize("na,staging,want", [
    (None, "float32", 32),      # every caller that gives no width
    (64, "float32", 32), (128, "float32", 32), (677, "float32", 32),
    (678, "float32", 40),       # (na + 2) // 40 passes the 16-slot margin
    (960, "float32", 40), (2048, "float32", 72),
    (960, "bfloat16", 120),     # bf16 staging has its own, deeper rule
])
def test_the_window_deepens_with_the_bound_it_must_clear(na, staging, want):
    from dmlp_tpu.engine.single import resolve_kcap
    assert resolve_kcap(EngineConfig(), 16, "extract", 1 << 20,
                        staging=staging, precision="f32", na=na) == want


def test_fast_mode_deepens_too_and_a_small_corpus_caps_the_window():
    """The hazard test and its repair run in fast mode as well: its 8
    slots of slack took a repair a batch at 960-d (the cell's control,
    my chip run, PR 31)."""
    from dmlp_tpu.engine.single import resolve_kcap
    fast = EngineConfig(exact=False)
    assert resolve_kcap(fast, 16, "extract", 1 << 20, na=128) == 24
    assert resolve_kcap(fast, 16, "extract", 1 << 20, na=960) == 40
    assert resolve_kcap(EngineConfig(), 16, "extract", 36, na=960) == 36


def _spans(tracer, name):
    return [e.get("args", {}) for e in tracer.events()
            if e.get("ph") == "X" and e["name"] == name]


def test_a_window_the_bound_does_not_clear_is_flagged_and_repaired():
    """960-d rows: the float32 bound the hazard test must clear grows
    with the width ((A + 2) * (qn + dn_max)). 56 rows packed closer to
    the query than that bound fill the 40-slot window, so its last slot
    does not clear the k-th by the bound: the query is flagged and the
    host repair restores the exact answer. A query far from the pack is
    not flagged, and its ``clear_min`` is the span's."""
    na, n, k = 960, 13000, 10
    corpus = corpus_of(n, na, seed=96)
    rng = np.random.default_rng(97)
    q = rng.random((2, na), dtype=np.float32).astype(np.float64)
    rows = corpus.data_attrs.copy()
    pack = rng.choice(n, 56, replace=False)
    rows[pack] = q[0] + rng.normal(0, 1e-3, (56, na))
    corpus = KNNInput(corpus.params, corpus.labels, rows,
                      corpus.ks, corpus.query_attrs)
    eng = ResidentEngine(corpus, config())
    ks = np.full(2, k, np.int32)
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        got = eng.solve_batch(q, ks)
    finally:
        obs_trace.uninstall()
    (hz,) = _spans(tracer, "single.hazard")
    (fin,) = _spans(tracer, "single.finalize")
    assert hz["flagged"] == 1 and 0 <= hz["clear_min"] < 1
    assert fin["repairs"] == 1 and eng.last_repairs == 1
    assert eng.bucket_plan(2, k) == (128, 16, 40)
    # the flagged query's band is its whole window (the pack sits inside
    # the bound), the other query's a few slots past its k-th: the
    # finalize gathers the bands' rows, not the 2 x 40 slots
    (rs,) = _spans(tracer, "single.rescore")
    assert rs["slots"] == 40 and 40 + k <= rs["rows"] < 2 * 40
    assert fin["gather_bytes"] == rs["rows"] * na * 8
    inp = KNNInput(Params(n, 2, na), corpus.labels, rows, ks, q)
    assert format_results(got) == format_results(knn_golden(inp))
    assert set(got[0].neighbor_ids) <= set(pack.tolist())

    # the same engine, a batch with no such pack: nothing flagged, and
    # the window clears its bound more than once over
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        eng.solve_batch(q[1:], ks[1:])
    finally:
        obs_trace.uninstall()
    (hz,) = _spans(tracer, "single.hazard")
    assert hz["flagged"] == 0 and hz["clear_min"] > 1
    (sx,) = _spans(tracer, "serve.solve_extract")
    assert (sx["tile_q"], sx["tile_n"], sx["ne"], sx["a_pad"]) \
        == (128, 6400, 2, 1024)


def test_warmup_span_names_the_variant_it_compiled():
    eng = ResidentEngine(corpus_of(13000, 960, seed=3), config())
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        eng.warmup([(16, 10)])
    finally:
        obs_trace.uninstall()
    (wb,) = _spans(tracer, "serve.warmup_bucket")
    assert (wb["qpad"], wb["tile_q"], wb["tile_n"], wb["ne"],
            wb["a_pad"]) == (128, 128, 6400, 2, 1024)


# -- the float64 rescore's block is sized by its bytes -----------------------

@pytest.mark.parametrize("k,na,want", [
    (32, 128, 512),     # bigann-4m, bigann-mesh4: the block they always ran
    (32, 64, 512),      # narrower rows: never more than 512 queries
    (40, 960, 54),      # gist-1m: 16.6 MB a temporary, not 157
    (72, 2048, 14),
    (4608, 64, 7),      # the wide-k shape the old 512 was swept at
    (512, 1 << 20, 1),  # a row over the budget still gets a block
])
def test_rescore_block_is_sized_by_its_bytes(k, na, want):
    from dmlp_tpu.engine.finalize import RESCORE_BLOCK_BYTES, rescore_block
    assert rescore_block(k, na) == want
    assert want == 1 or want * k * na * 8 <= RESCORE_BLOCK_BYTES


@pytest.mark.parametrize("q,k,na", [(130, 40, 960), (70, 16, 128),
                                    (33, 72, 2048)])
def test_rescore_is_the_same_bits_whatever_the_block(q, k, na):
    from dmlp_tpu.engine.finalize import rescore_f64
    rng = np.random.default_rng(q)
    data = rng.random((500, na))
    queries = rng.random((q, na))
    ids = rng.integers(-1, 500, (q, k))
    want = np.where(ids < 0, np.inf, ((data[np.clip(ids, 0, None)]
                                       - queries[:, None, :]) ** 2).sum(-1))
    got = rescore_f64(ids, queries, data)
    np.testing.assert_allclose(got, want, rtol=1e-13)
    for block in (1, 7, 512):
        assert np.array_equal(got, rescore_f64(ids, queries, data,
                                               block=block))
