"""Inner product and cosine on the mesh daemon's extract path (PR 53).

``MeshResidentEngine`` under ``EngineConfig(score="ip" | "cosine")`` on
the tests' virtual devices (2 x 1 and 4 x 1 meshes, the kernel in
interpret mode): every shard folds its rows with the kernel's "ip" form,
under "cosine" over x / |x| staged from the host's float64 rows, and the
all-gather merge re-selects what the kernel emits. The answers are held
to the golden model (``golden/reference.py``: label, ids, checksum and
the float64 scores to the bit), to the benchmark's plain references,
and byte for byte to the one-chip ``ResidentEngine`` over the same
corpus, at k = 10 and k = 100, with rows on every shard so that
neighbours come from all of them; a tie group that overflows the merged
window goes to the host oracle (``fleet.repair``) and still matches;
rows ingested under cosine are restaged as x / |x| with their norms;
under ``l2`` the engine builds the programs it built before; and what
still ranks by squared L2 alone (the monolithic ``stream`` layout, a k
past one kernel pass) refuses a product score by name.
"""

from __future__ import annotations

import functools
import json
import socket

import numpy as np
import pytest

from benchmark.references import cosine as ref_cos
from benchmark.references import inner_product as ref_ip
from dmlp_tpu.config import EngineConfig
from dmlp_tpu.fleet.mesh_engine import MeshResidentEngine
from dmlp_tpu.golden.fast import knn_golden_fast
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve.engine import (RequestShapeError, ResidentEngine,
                                   _kernel_statics)
from tests.test_inner_product import corpus_of, f32

NA = 24
#: the extract path shards in whole extraction blocks of 12 800 rows:
#: these row counts put rows on EVERY shard of their mesh (the last
#: shard part-full)
ROWS = {(2, 1): 20000, (4, 1): 45000}
MESHES = sorted(ROWS)
REFERENCE = {"ip": ref_ip, "cosine": ref_cos}


def mesh_config(score: str, **engine) -> EngineConfig:
    return EngineConfig(**{
        "mode": "sharded", "use_pallas": True, "select": "extract",
        "dtype": "float32", "score": score, **engine})


def one_chip_config(score: str) -> EngineConfig:
    return EngineConfig(use_pallas=True, select="extract", dtype="float32",
                        score=score)


@functools.lru_cache(maxsize=None)
def rows_of(mesh) -> np.ndarray:
    """Seeded rows of very unequal norms (three decades), so that the
    three scores order them differently, with neighbours of the test's
    queries on every shard."""
    rng = np.random.default_rng([53, *mesh])
    n = ROWS[mesh]
    return f32(rng.uniform(-1, 1, (n, NA))
               * 10.0 ** rng.uniform(-1.5, 1.5, (n, 1)))


def queries_of(nq=9, seed=5301) -> np.ndarray:
    return f32(np.random.default_rng(seed).uniform(-1, 1, (nq, NA)))


def repairs() -> dict:
    return MeshResidentEngine._repair_stats()


def same_bytes(got, want):
    assert len(got) == len(want)
    for j, (a, b) in enumerate(zip(got, want)):
        assert a.predicted_label == b.predicted_label, j
        assert np.array_equal(a.neighbor_ids, b.neighbor_ids), j
        assert a.neighbor_dists.tobytes() == b.neighbor_dists.tobytes(), j
        assert a.checksum() == b.checksum(), j


def assert_golden(results, corpus: KNNInput, queries, k, score):
    """Against the strict golden model (to the bit), the fast one and,
    under a product score, the benchmark's plain reference."""
    ks = np.full(len(queries), k, np.int32)
    inp = KNNInput(Params(corpus.params.num_data, len(queries), NA),
                   corpus.labels, corpus.data_attrs, ks, queries)
    same_bytes(results, knn_golden(inp, score=score))
    same_bytes(results, knn_golden_fast(inp, score=score))
    if score in REFERENCE:
        want = REFERENCE[score].knn_plain(corpus.data_attrs, corpus.labels,
                                          queries, ks)
        for r, w in zip(results, want):
            assert np.array_equal(r.neighbor_ids, w.ids)
            assert r.predicted_label == w.label
            assert r.checksum() == w.checksum


# -- (a) the mesh engine, the golden model and one chip ------------------------

@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("score", ["ip", "cosine"])
@pytest.mark.parametrize("mesh", MESHES, ids=["2x1", "4x1"])
def test_a_mesh_engine_under_a_product_score_is_the_golden_model_and_one_chips(
        mesh, score, k):
    rows = rows_of(mesh)
    corpus = corpus_of(rows)
    queries = queries_of()
    ks = np.full(len(queries), k, np.int32)
    eng = MeshResidentEngine(corpus, mesh_config(score), mesh_shape=mesh,
                             capacity=mesh[0] * 12800)
    assert eng._shard_rows == 12800
    results = eng.solve_batch(queries, ks)
    assert_golden(results, corpus, queries, k, score)
    one = ResidentEngine(corpus, one_chip_config(score))
    same_bytes(results, one.solve_batch(queries, ks))
    # the extract path, the kernel's form, no scorer, every shard asked
    assert eng._last_select == "extract"
    assert eng.last_variant["score"] == score
    stats = eng.bucket_stats()
    assert stats["score"] == score and stats["summary_blocks"] == 0
    assert set(stats["paths"].values()) == {"extract"}
    assert stats["last_prune"]["dense_bytes"] == len(rows) * NA * 4
    assert stats["extract_chunks"] == 1
    owners = {int(i) // 12800 for r in results for i in r.neighbor_ids}
    assert owners == set(range(mesh[0]))
    # the three scores answer differently on these rows
    l2 = knn_golden_fast(KNNInput(
        Params(len(rows), len(queries), NA), corpus.labels, rows, ks,
        queries))
    assert sum(tuple(a.neighbor_ids) != tuple(b.neighbor_ids)
               for a, b in zip(results, l2)) >= len(queries) - 1


@pytest.mark.parametrize("merge", ["ring", "auto"])
def test_the_other_merges_order_what_the_kernel_emits_too(merge):
    """No merge has a form of its own: each re-selects by (value
    ascending, id descending), and under a product score the value is
    -q.x."""
    mesh = (2, 1)
    corpus = corpus_of(rows_of(mesh))
    queries = queries_of(5, 5302)
    eng = MeshResidentEngine(corpus, mesh_config("cosine"), mesh_shape=mesh,
                             merge=merge)
    results = eng.solve_batch(queries, np.full(5, 10, np.int32))
    assert_golden(results, corpus, queries, 10, "cosine")


# -- (b) a flagged query is the host oracle's ----------------------------------

@pytest.mark.parametrize("score", ["ip", "cosine"])
@pytest.mark.parametrize("mesh", MESHES, ids=["2x1", "4x1"])
def test_a_tie_group_past_the_merged_window_goes_through_fleet_repair(
        mesh, score):
    """300 exact copies of one row, scattered over every shard, tie at
    the top of two queries: the merged 32-slot window cannot hold the
    group, the hazard test (the score's own bound) flags both, the host
    oracle answers under the score, and the ten reported are the group's
    LARGEST ids. A mesh daemon has no device retry."""
    rng = np.random.default_rng([5303, len(score), *mesh])
    rows = rows_of(mesh).copy()
    at = rng.choice(len(rows), 300, replace=False)
    rows[at] = rows[at[0]] = f32(rng.uniform(0.5, 1, NA) * 40.0)
    queries = np.concatenate([f32(rows[at[:2]] * 2.5), queries_of(3)])
    corpus = corpus_of(rows)
    eng = MeshResidentEngine(corpus, mesh_config(score), mesh_shape=mesh)
    before = repairs()
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        results = eng.solve_batch(queries, np.full(5, 10, np.int32))
    finally:
        obs_trace.uninstall()
    assert_golden(results, corpus, queries, 10, score)
    after = repairs()
    # (the group's long rows raise the inner product's bound for every
    # query: one of the other three may be flagged too)
    flagged = after["flagged_queries"] - before["flagged_queries"]
    assert 2 <= flagged <= 5
    assert after["host"] - before["host"] == flagged
    assert after["device"] == before["device"]
    for r in results[:2]:
        assert np.array_equal(r.neighbor_ids, np.sort(at)[::-1][:10])
    spans = {e["name"]: e.get("args", {}) for e in tracer.events()
             if e.get("ph") == "X"}
    assert spans["fleet.repair"]["queries"] == flagged
    assert spans["fleet.hazard"]["flagged"] == flagged
    assert spans["fleet.hazard"]["clear_min"] <= 1.0
    assert eng.last_repairs == flagged
    one = ResidentEngine(corpus, one_chip_config(score))
    same_bytes(results, one.solve_batch(queries, np.full(5, 10, np.int32)))


# -- (c) ingest under cosine ----------------------------------------------------

def staged_rows(eng: MeshResidentEngine) -> np.ndarray:
    """The stack's rows in global row order, (capacity, A)."""
    r = eng.mesh.devices.shape[0]
    t, cr = eng._nchunks, eng._chunk_rows
    stack = np.asarray(eng._chunks).reshape(t, r, cr, -1)
    return stack.transpose(1, 0, 2, 3).reshape(r, t * cr, -1)[
        :, :eng._shard_rows].reshape(r * eng._shard_rows, -1)


def test_rows_ingested_under_cosine_are_restaged_as_unit_rows_with_norms():
    """An append that crosses the shard boundary (a zero row and scaled
    copies of the queries among it) and an overwrite: the host keeps the
    rows as given with their norms beside them, the shards hold x / |x|
    in float32 (a zero row as zeros), ``_dn_max`` stays the unit rows',
    no program is built again, and the answers are the golden model's
    over the corpus as it then stands and the one-chip engine's."""
    mesh = (2, 1)
    rng = np.random.default_rng(5304)
    rows = rows_of(mesh)[:12000]
    corpus = corpus_of(rows)
    eng = MeshResidentEngine(corpus, mesh_config("cosine"), mesh_shape=mesh,
                             capacity=25600)
    queries = queries_of(6, 5305)
    ks = np.full(6, 10, np.int32)
    eng.solve_batch(queries, ks)
    built = eng.compile_count, set(eng._fns), eng.norm_restages
    more = f32(rng.uniform(-1, 1, (2000, NA)) * 40.0)
    more[5] = 0.0
    more[:3] = queries[:3] * 7.0     # s = 1 to rounding, on shard 0
    more[-3:] = queries[3:] * 0.01   # ... and on shard 1
    eng.ingest(rng.integers(0, 5, 2000), more)
    over = f32(rng.uniform(-1, 1, (10, NA)) * 1e-3)
    eng.ingest(np.arange(10) % 5, over, start=100)
    want = np.concatenate([rows, more])
    want[100:110] = over
    labels, got = eng.corpus_slice(0, 14000)
    assert np.array_equal(got, want) and eng.n_real == 14000
    norms = np.sqrt(np.einsum("na,na->n", want, want))
    assert np.array_equal(eng._host_norms[:14000], norms)
    assert not eng._host_norms[14000:].any()
    assert eng._dn_max() == 1.0
    unit = (want / np.where(norms > 0, norms, 1.0)[:, None]
            ).astype(np.float32)
    staged = staged_rows(eng)
    assert np.array_equal(staged[:14000], unit)
    assert not staged[12005].any() and not staged[14000:].any()
    # the staged norms beside the stack are those of the unit rows
    dev_norms = np.asarray(eng._norms)[0, 0].reshape(2, -1)[
        :, :eng._shard_rows].reshape(-1)
    assert np.allclose(dev_norms[:14000], (norms > 0), atol=1e-6)
    results = eng.solve_batch(queries, ks)
    assert (eng.compile_count, set(eng._fns)) == built[:2]
    assert eng.norm_restages > built[2]
    now = KNNInput(Params(14000, 0, NA), labels, want,
                   np.zeros(0, np.int32), np.zeros((0, NA)))
    assert_golden(results, now, queries, 10, "cosine")
    assert [int(r.neighbor_ids[0]) for r in results] == [
        12000, 12001, 12002, 13997, 13998, 13999]
    same_bytes(results, ResidentEngine(
        now, one_chip_config("cosine")).solve_batch(queries, ks))


# -- (d) under l2 nothing moved --------------------------------------------------

def test_under_l2_the_mesh_engine_builds_the_programs_it_built_before():
    """The fold's cache key under ``l2`` is the kernel statics the
    engine resolved before it took a score (``_kernel_statics`` without
    the argument), the merge's has no score at all, one bucket is one
    compile, the block summaries are still built and scored, no
    normalisation span runs, and the answers are the golden model's and
    the one-chip engine's."""
    mesh = (2, 1)
    corpus = corpus_of(rows_of(mesh))
    queries = queries_of()
    ks = np.full(len(queries), 10, np.int32)
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        eng = MeshResidentEngine(corpus, mesh_config("l2"), mesh_shape=mesh)
        results = eng.solve_batch(queries, ks)
        again = eng.solve_batch(queries, ks)
    finally:
        obs_trace.uninstall()
    assert eng.compile_count == 1 and eng._host_norms is None
    kern = _kernel_statics(eng.last_extract_impl, 32, eng._chunk_rows, 128,
                           NA, eng.last_precision["active"], True)
    assert kern["score"] == "l2"
    assert set(eng._fns) == {
        ("residentfold", *(kern[s] for s in (
            "kc", "interpret", "tile_q", "tile_n", "ne", "unroll", "fold",
            "mxu_gate", "precision", "score"))),
        ("chunkmerge", 32, "allgather")}
    assert eng.bucket_stats()["summary_blocks"] == 2
    names = {e["name"] for e in tracer.events() if e.get("ph") == "X"}
    assert "fleet.prune_score" in names and "fleet.rescore" in names
    assert not {"fleet.normalize_rows", "fleet.normalize_queries"} & names
    assert_golden(results, corpus, queries, 10, "l2")
    same_bytes(results, again)
    same_bytes(results, ResidentEngine(
        corpus, one_chip_config("l2")).solve_batch(queries, ks))


# -- (e) what still ranks by squared L2 alone refuses by name --------------------

@pytest.mark.parametrize("score", ["ip", "cosine"])
def test_a_corpus_off_the_extract_path_is_refused_by_name(score):
    """No ``use_pallas``: every bucket would run the engines' merged
    program over the monolithic layout, which ranks by squared L2."""
    with pytest.raises(ValueError, match=(
            r"fleet\.mesh_engine\.MeshResidentEngine's monolithic stream "
            rf"path \(a corpus that does not take the extract path.*\) has "
            rf"no score='{score}' form")):
        MeshResidentEngine(corpus_of(rows_of((2, 1))[:300]),
                           EngineConfig(mode="sharded", score=score),
                           mesh_shape=(2, 1))


@pytest.mark.parametrize("score", ["ip", "cosine"])
def test_a_stream_path_bucket_is_refused_by_name(score):
    """A k whose window passes the kernel's one pass: refused at
    admission (``max_k`` is the last one-pass bucket, where squared L2
    serves the capacity), and the bucket itself, built by hand, refuses
    the monolithic layout by name; under l2 the same bucket builds."""
    corpus = corpus_of(rows_of((2, 1)))
    eng = MeshResidentEngine(corpus, mesh_config(score), mesh_shape=(2, 1))
    assert eng.max_k == 256 and eng._mono is None
    with pytest.raises(RequestShapeError, match=(
            rf"k=300 beyond the serving cap 256 under score='{score}': "
            r"fleet\.mesh_engine\.MeshResidentEngine's monolithic stream "
            r"path \(a window past 512 slots\) rank by squared L2 alone")):
        eng.solve_batch(np.ones((2, NA)), np.full(2, 300, np.int32))
    with pytest.raises(ValueError, match=(
            r"monolithic stream path \(bucket q128k512: 5\d\d slots the "
            rf"kernel does not tile\) has no score='{score}' form")):
        eng._build_bucket(128, 512)
    assert eng._mono is None
    l2 = MeshResidentEngine(corpus, mesh_config("l2"), mesh_shape=(2, 1))
    assert l2.max_k == l2.capacity_rows
    assert l2._build_bucket(128, 512).path == "stream"


def test_the_batch_mesh_engines_still_refuse():
    """The refusal is a per-engine list: the mesh daemon's engine says
    three scores, the batch engines it is built on say one."""
    from dmlp_tpu.engine.auto import AutoShardedEngine
    from dmlp_tpu.engine.sharded import RingEngine, ShardedEngine
    assert MeshResidentEngine._scores == ("l2", "ip", "cosine")
    for cls in (ShardedEngine, RingEngine, AutoShardedEngine):
        assert cls._scores == ("l2",)


# -- (f) one implementation, two engines ------------------------------------------

SHARED = ["_batch_input", "_init_host_norms", "_note_norms", "_staged_rows",
          "_staged_queries", "_dn_max", "_note_ingested_norms", "_check_k",
          "_k_refusal"]


@pytest.mark.parametrize("name", SHARED + ["max_k", "_one_pass_max_k"])
def test_both_resident_engines_take_the_step_from_the_shared_core(name):
    """What a score asks of staging, of a batch's input and of admission
    is ``ResidentServingCore``'s, once: neither engine overrides it."""
    from dmlp_tpu.serve.engine import ResidentServingCore
    assert name in vars(ResidentServingCore)
    for cls in (MeshResidentEngine, ResidentEngine):
        assert name not in vars(cls), (cls.__name__, name)


@pytest.mark.parametrize("score", ["l2", "ip", "cosine"])
def test_staged_queries_are_the_same_rows_on_both_engines(score):
    """q as given, or q / |q| taken in float64 and cast under cosine (a
    zero query stays zero), padded to the bucket's rows: the mesh
    engine's panel is the one-chip engine's, to the bit, under its own
    span's name."""
    corpus = corpus_of(rows_of((2, 1)))
    queries = queries_of(5, 5307) * 30.0
    queries[1] = 0.0
    mesh = MeshResidentEngine(corpus, mesh_config(score), mesh_shape=(2, 1))
    one = ResidentEngine(corpus, one_chip_config(score))
    inp = mesh._batch_input(queries, np.full(5, 3, np.int32))
    assert (inp.data_norms is None) == (score != "cosine")
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        a = mesh._staged_queries(inp, 8, NA)
        b = one._staged_queries(inp, 8, one._ex_attrs)
    finally:
        obs_trace.uninstall()
    assert a.dtype == np.float32 and a.shape == (8, NA)
    assert np.array_equal(a, b[:, :NA]) and not b[:, NA:].any()
    assert not a[5:].any() and not a[1].any()
    want = queries if score != "cosine" else queries / np.where(
        np.sqrt((queries ** 2).sum(1)) > 0,
        np.sqrt((queries ** 2).sum(1)), 1.0)[:, None]
    assert np.array_equal(a[:5], want.astype(np.float32))
    names = [e["name"] for e in tracer.events() if e.get("ph") == "X"]
    assert names == (["fleet.normalize_queries", "serve.normalize_queries"]
                     if score == "cosine" else [])
    for eng in (mesh, one):
        assert eng.max_k == (256 if score != "l2" else eng.capacity_rows)


# -- (g) the cell's own arithmetic -------------------------------------------------

def test_the_cells_own_plan_is_what_its_configuration_reckons():
    """``openai-c4-mesh4``'s file against the program's own plan, without
    building it: the stated capacity gives four shards of 704 000 rows in
    14 chunks of 51 200 (the last shard 688 000), the window at k = 100
    is 168 slots on the extract path, the stack alone is over a quarter
    of a v5e chip, and one float32 copy passes a whole chip."""
    from benchmark import spec
    from dmlp_tpu.engine.single import plan_chunks, resolve_kcap
    from dmlp_tpu.serve.engine import k_bucket
    cell = spec.Cell("openai-c4-mesh4.bulk")
    cfg, serve = cell.config, cell.config["serve"]
    econf = EngineConfig(**cfg["engine"])
    r, c = serve["mesh_shape"]
    n, na = cfg["num_data"], cfg["num_attrs"]
    assert econf.score == "cosine" and (r, c) == (4, 1) and cell.chips == 4
    assert cfg["k_min"] == cfg["k_max"] == cell.params["k"] == 100
    assert cell.workload["warm_buckets"] == [[1024, 100]]
    sr, t, cr = plan_chunks(-(-serve["capacity"] // r),
                            econf.resolve_granule("extract"),
                            econf.data_block)
    assert (sr, t, cr) == (704000, 14, 51200)
    assert econf.resolve_select(sr) == "extract" == cfg["expect_select"]
    held = [max(min(n - rr * sr, sr), 0) for rr in range(r)]
    assert held == [704000, 704000, 704000, 688000]
    kc = resolve_kcap(econf, k_bucket(100), "extract", r * sr,
                      staging="float32", precision="bf16x3", na=na)
    assert kc == 168 <= 512
    chip = 16909336064                     # a v5e chip, as its runtime says
    assert t * cr * na * 4 == 4404019200 > 0.25 * chip
    assert n * na * 4 > chip               # no chip holds one copy
    assert cfg["reduced"] == ["num_data"] and cfg["modules"] == {
        "reference": "cosine"}
    assert cfg["control"]["set"] == {"engine": {"exact": False}}


def test_on_the_cells_rehearsal_corpus_the_three_scores_answer_differently():
    """The cell's own rows at its rehearsal size and its own k: rows that
    are NOT unit vectors, on purpose, so that the top 100 by cosine is
    neither the inner product's nor squared L2's for any checked query,
    and a mesh daemon that computed another score would fail the check."""
    from benchmark import data, spec
    cell = spec.Cell("openai-c4-mesh4.bulk", rehearse=True)
    assert cell.config["serve"]["mesh_shape"] == [4, 1]
    assert cell.config["num_attrs"] == 1536
    labels, rows = data.corpus(cell.config, 53)
    queries = data.request_queries(cell.config, 53, 0, 8)
    inp = KNNInput(Params(len(rows), 8, rows.shape[1]), labels, rows,
                   np.full(8, 100, np.int32), queries)
    tops = {s: [tuple(r.neighbor_ids) for r in knn_golden_fast(inp, score=s)]
            for s in ("cosine", "ip", "l2")}
    assert all(c != i for c, i in zip(tops["cosine"], tops["ip"]))
    assert all(c != l for c, l in zip(tops["cosine"], tops["l2"]))
    want = ref_cos.knn_exact(rows, labels, queries, np.full(8, 100))
    assert [tuple(w.ids) for w in want] == tops["cosine"]


# -- (h) through the daemon, with its spans ---------------------------------------

def ask(port, obj):
    with socket.create_connection(("127.0.0.1", port), timeout=120) as s:
        f = s.makefile("rwb")
        f.write((json.dumps(obj) + "\n").encode())
        f.flush()
        return json.loads(f.readline())


def test_a_cosine_mesh_daemon_over_the_wire_and_its_spans():
    """``ServeDaemon(..., mesh_shape=(4, 1))`` under cosine, k = 100: the
    response is the plain reference's (ids, checksum, label, angular
    distances ascending), ``stats`` names the score, and the spans the
    new per-layer metrics read are where they say: the norms pass and
    one span a staged piece at set-up (``fleet.normalize_rows``), a
    batch's q / |q| inside ``fleet.stage_queries``
    (``fleet.normalize_queries``), the float64 gather-and-score inside
    ``fleet.finalize`` (``fleet.rescore``) with its rows, slots, band
    and score; ``fleet.hazard`` says the score and how far the window
    cleared; no scorer ran."""
    from dmlp_tpu.serve.daemon import ServeDaemon
    mesh = (4, 1)
    corpus = corpus_of(rows_of(mesh))
    queries = queries_of(7, 5306)
    queries[2] = 0.0               # a zero query: every row at d = 1
    k = 100
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        daemon = ServeDaemon(corpus, mesh_config("cosine"),
                             capacity=4 * 12800,
                             warm_buckets=[(len(queries), k)],
                             mesh_shape=mesh)
        try:
            daemon.start()
            mark = len(tracer.events())
            resp = ask(daemon.port, {"op": "query", "k": k, "debug": True,
                                     "queries": queries.tolist()})
            stats = ask(daemon.port, {"op": "stats"})["stats"]
        finally:
            daemon.close()
    finally:
        obs_trace.uninstall()
    assert resp["ok"], resp
    want = ref_cos.knn_exact(corpus.data_attrs, corpus.labels, queries,
                             np.full(len(queries), k))
    for j, w in enumerate(want):
        assert resp["neighbors"][j] == w.ids.tolist(), j
        assert resp["checksums"][j] == w.checksum
        assert resp["labels"][j] == w.label
        assert np.abs(np.asarray(resp["dists"][j]) - w.dists).max() <= 1e-12
        assert resp["dists"][j] == sorted(resp["dists"][j])
    assert resp["neighbors"][2] == list(range(44999, 44899, -1))
    assert stats["engine"]["score"] == "cosine"
    assert stats["engine"]["mesh"] == [4, 1]
    assert stats["device"]["kernel_variant"]["score"] == "cosine"
    assert stats["engine"]["repairs"]["host"] >= 1     # the zero query
    events = [e for e in tracer.events() if e.get("ph") == "X"]
    setup = [e for e in events[:mark] if e["name"] == "fleet.normalize_rows"]
    assert [e["args"]["site"] for e in setup] == ["norms"] + ["stage"] * 4
    assert setup[0]["args"]["rows"] == 45000
    assert sum(e["args"]["rows"] for e in setup[1:]) == 45000
    stage = next(e for e in events if e["name"] == "fleet.stage_resident")
    assert stage["args"]["score"] == "cosine"
    assert all(inside(e, stage) for e in setup[1:])
    served = {e["name"]: e for e in events[mark:]}
    assert "fleet.prune_score" not in served
    assert "fleet.summary_build" not in {e["name"] for e in events}
    assert served["fleet.normalize_queries"]["args"]["queries"] == 7
    assert served["fleet.normalize_queries"]["args"]["zero_queries"] == 1
    assert inside(served["fleet.normalize_queries"],
                  served["fleet.stage_queries"])
    rescore, final = served["fleet.rescore"], served["fleet.finalize"]
    assert inside(rescore, final)
    for key in ("rows", "slots", "band_pct", "score"):
        assert rescore["args"][key] == final["args"][key]
    assert rescore["args"]["score"] == "cosine"
    assert rescore["args"]["slots"] == 144 and rescore["args"]["queries"] == 7
    assert 7 * 100 <= rescore["args"]["rows"] <= 7 * 144
    assert rescore["args"]["bytes"] == rescore["args"]["rows"] * NA * 8
    hazard = served["fleet.hazard"]["args"]
    assert hazard["score"] == "cosine" and hazard["flagged"] >= 1
    assert "clear_min" in hazard


def inside(inner, outer) -> bool:
    return outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
