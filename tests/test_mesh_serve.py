"""A 4x1 mesh daemon against the benchmark's plain reference and against
a one-chip resident engine, at small sizes on the CPU's virtual devices:
the answers a sharded corpus gives are those of the whole corpus (ties
across shard boundaries, winners on the last shard, every merge), a
placement that leaves a device without its share is refused, the mesh
micro-batch is tiled by spans that share its ``batch``, and ``stats``
answers "fold, merge or finalize?" without a tracer."""

from __future__ import annotations

import json
import socket

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import reference
from dmlp_tpu.config import EngineConfig
from dmlp_tpu.fleet.mesh_engine import MeshResidentEngine
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve.daemon import PHASE_HISTOGRAMS, ServeDaemon
from dmlp_tpu.serve.engine import ResidentEngine

NA = 16
K = 10
MESH = (4, 1)
#: the extract path shards in whole extraction blocks of 12 800 rows, so
#: four real shards of two chunks each need 8 x 12 800 rows of capacity
CAP = 102400
SR = CAP // MESH[0]          # rows a shard
MERGES = ["allgather", "ring", "auto"]

#: the spans that tile serve.micro_batch on a mesh daemon, in order
IN_BATCH = ["fleet.stage_queries", "fleet.prune_score",
            "fleet.fold_schedule", "fleet.solve_resident",
            "fleet.merge_drain", "fleet.merge", "fleet.fetch",
            "fleet.hazard", "fleet.finalize", "fleet.after_batch"]
SETUP = ["fleet.init.host_copy", "fleet.init.row_hashes",
         "fleet.stage_resident", "fleet.summary_build"]


def mesh_config() -> EngineConfig:
    # data_block=12800: two chunks a shard at this capacity
    return EngineConfig(mode="sharded", use_pallas=True, select="extract",
                        dtype="float32", data_block=12800)


def make_corpus(rows: np.ndarray, labels: np.ndarray) -> KNNInput:
    n, na = rows.shape
    return KNNInput(Params(n, 0, na), np.asarray(labels, np.int32),
                    np.asarray(rows, np.float64), np.zeros(0, np.int32),
                    np.zeros((0, na)))


def uniform_corpus(n=100000, seed=7) -> KNNInput:
    """BIGANN-like toy: uniform in [0, 255), exactly representable in
    float32, 10 labels; n stops short of the capacity, so the last
    shard's last chunk is part-empty."""
    rng = np.random.default_rng(seed)
    rows = (rng.random((n, NA), dtype=np.float32)
            * np.float32(255.0)).astype(np.float64)
    return make_corpus(rows, rng.integers(0, 10, n))


def queries(nq=24, seed=8) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.random((nq, NA), dtype=np.float32)
            * np.float32(255.0)).astype(np.float64)


def ask(port, obj):
    with socket.create_connection(("127.0.0.1", port), timeout=120) as s:
        f = s.makefile("rwb")
        f.write((json.dumps(obj) + "\n").encode())
        f.flush()
        return json.loads(f.readline())


def serve(corpus, q, k=K, merge="allgather"):
    """One debug request through a 4x1 mesh daemon; (response, stats)."""
    daemon = ServeDaemon(corpus, mesh_config(), capacity=CAP,
                         warm_buckets=[(len(q), k)],
                         mesh_shape=MESH, mesh_merge=merge)
    try:
        daemon.start()
        assert isinstance(daemon.engine, MeshResidentEngine)
        assert daemon.engine._shard_rows == SR
        resp = ask(daemon.port, {"op": "query", "id": "q", "k": k,
                                 "debug": True, "queries": q.tolist()})
        stats = ask(daemon.port, {"op": "stats"})["stats"]
    finally:
        daemon.close()
    assert resp["ok"], resp
    return resp, stats


def assert_is_the_reference(resp, corpus, q, k=K):
    refs = reference.knn_plain(corpus.data_attrs, corpus.labels, q,
                               [k] * len(q))
    for j, ref in enumerate(refs):
        assert resp["labels"][j] == ref.label, j
        assert resp["neighbors"][j] == ref.ids.tolist(), j
        assert resp["checksums"][j] == ref.checksum, j
        got = np.asarray(resp["dists"][j], np.float64)
        scale = np.maximum(ref.dists, np.finfo(np.float64).tiny)
        assert (np.abs(got - ref.dists) / scale).max() <= 1e-11, j
    return refs


# -- (a) the mesh daemon, the plain reference and one chip ---------------------

@pytest.mark.parametrize("merge", MERGES)
def test_mesh_daemon_answers_are_the_reference_and_one_chips(merge):
    corpus, q = uniform_corpus(), queries()
    resp, stats = serve(corpus, q, merge=merge)
    assert stats["device"]["mesh"] == list(MESH)
    assert stats["engine"]["paths"] and set(
        stats["engine"]["paths"].values()) == {"extract"}
    assert_is_the_reference(resp, corpus, q)
    one = ResidentEngine(corpus, EngineConfig(
        use_pallas=True, select="extract", dtype="float32"))
    solo = one.solve_batch(q, np.full(len(q), K, np.int32))
    assert resp["checksums"] == [int(r.checksum()) for r in solo]
    assert resp["labels"] == [int(r.predicted_label) for r in solo]
    assert resp["neighbors"] == [[int(i) for i in r.neighbor_ids]
                                 for r in solo]
    assert resp["dists"] == [[float(d) for d in r.neighbor_dists]
                             for r in solo]


# -- (b) the shares tie to the whole -------------------------------------------

def test_four_shards_local_lists_merge_to_the_uncut_top_k():
    """What each shard hands to the merge, merged by the reference's own
    order (distance asc, id desc), is the whole corpus's top-k: no shard
    holds back a winner, none offers a row it does not own."""
    corpus, q = uniform_corpus(), queries(nq=16, seed=9)
    eng = MeshResidentEngine(corpus, mesh_config(), mesh_shape=MESH,
                             capacity=CAP)
    seen = {}
    merge_fn_of = eng._chunk_merge_fn

    def spy(k):
        fn = merge_fn_of(k)

        def merge(cd, ci, lab):
            seen["ids"] = np.asarray(jax.device_get(ci))
            return fn(cd, ci, lab)
        return merge
    eng._chunk_merge_fn = spy
    eng.solve_batch(q, np.full(len(q), K, np.int32))
    ids = seen["ids"]                       # (shards, qpad, kcap)
    assert ids.shape[0] == MESH[0] and ids.shape[2] >= K
    sr = eng._shard_rows
    assert sr == SR
    whole = reference.knn_plain(corpus.data_attrs, corpus.labels, q,
                                [K] * len(q))
    for j in range(len(q)):
        for rr in range(MESH[0]):
            own = ids[rr, j][ids[rr, j] >= 0]
            assert ((own >= rr * sr) & (own < (rr + 1) * sr)).all()
            rows_here = min(max(corpus.params.num_data - rr * sr, 0), sr)
            assert len(own) == min(ids.shape[2], rows_here)
        cand = np.unique(ids[:, j][ids[:, j] >= 0])     # ascending ids
        part = reference.knn_plain(corpus.data_attrs[cand],
                                   corpus.labels[cand], q[j:j + 1], [K])[0]
        assert cand[part.ids].tolist() == whole[j].ids.tolist()
        assert part.label == whole[j].label
        assert np.array_equal(part.dists, whole[j].dists)


# -- (c) ties across shard boundaries ------------------------------------------

def tied_corpus():
    """Integer-valued rows filling the capacity, SR a shard. One point
    stands six times, on all four shards; k = 4 cuts through the tie, and
    the four kept (the LARGEST ids) vote 2 : 2, so the larger label
    wins."""
    rng = np.random.default_rng(11)
    n = CAP
    rows = rng.integers(0, 255, (n, NA)).astype(np.float64)
    labels = rng.integers(0, 10, n)
    point = np.full(NA, 128.0)
    copies = [10, SR + 6, SR + 16, 2 * SR + 2, 3 * SR + 8, CAP - 96]
    rows[copies] = point
    for i, lab in zip(copies, [9, 9, 3, 7, 3, 7]):
        labels[i] = lab
    # and a pair at one distance from a second probe, on shards 0 and 3
    near = point.copy()
    near[0] += 3.0
    far_a, far_b = near.copy(), near.copy()
    far_a[1] += 2.0
    far_b[1] -= 2.0
    rows[5], rows[CAP - 6] = far_a, far_b
    return make_corpus(rows, labels), point, near, copies


@pytest.mark.parametrize("merge", MERGES)
def test_ties_across_shard_boundaries_follow_the_reference(merge):
    corpus, point, near, copies = tied_corpus()
    q = np.stack([point, near, point + 0.5])
    resp, _ = serve(corpus, q, k=4, merge=merge)
    refs = assert_is_the_reference(resp, corpus, q, k=4)
    # the reference itself does what the contract says, across shards
    assert resp["neighbors"][0] == sorted(copies, reverse=True)[:4]
    assert [i // SR for i in resp["neighbors"][0]] == [3, 3, 2, 1]
    assert resp["labels"][0] == 7           # 3, 7, 3, 7 -> the larger
    # two rows at one distance on the first and the last shard, then
    # the six-fold tie again, cut after two
    assert refs[1].dists[0] == refs[1].dists[1] == 4.0
    assert resp["neighbors"][1] == [CAP - 6, 5, CAP - 96, 3 * SR + 8]


# -- (d) every winner on the last shard ----------------------------------------

def test_winners_that_all_live_on_the_last_shard_are_found():
    """A merge that kept shard 0's list would pass uniform data by luck;
    here the first three shards hold nothing near any query."""
    rng = np.random.default_rng(13)
    n = CAP
    rows = (100.0 + rng.random((n, NA), dtype=np.float32)
            * np.float32(155.0)).astype(np.float64)
    rows[3 * SR:] = (rng.random((SR, NA), dtype=np.float32)
                     * np.float32(20.0)).astype(np.float64)
    corpus = make_corpus(rows, rng.integers(0, 10, n))
    q = (rng.random((12, NA), dtype=np.float32)
         * np.float32(20.0)).astype(np.float64)
    resp, _ = serve(corpus, q)
    assert_is_the_reference(resp, corpus, q)
    assert min(min(ids) for ids in resp["neighbors"]) >= 3 * SR


# -- (e) the placement refusal -------------------------------------------------

class _OneDevice(MeshResidentEngine):
    """Stages every chunk buffer whole on the mesh's first device."""

    def _stage_chunks(self) -> None:
        first = SingleDeviceSharding(self.mesh.devices.flat[0])
        self._chunks = [jax.device_put(self._chunk_host(t), first)
                        for t in range(self._nchunks)]
        self._refresh_scalars()


def test_a_corpus_that_sits_on_one_device_is_refused():
    corpus = uniform_corpus(n=2048)
    with pytest.raises(RuntimeError, match="placement refused") as e:
        _OneDevice(corpus, mesh_config(), mesh_shape=MESH)
    # the map is shown: all four shards' 12 800-row blocks on one device
    assert f"{{'{jax.devices()[0].id}': {4 * 12800}}}" in str(e.value)


def test_a_device_with_a_smaller_share_is_refused(monkeypatch):
    corpus = uniform_corpus(n=2048)
    honest = MeshResidentEngine.corpus_rows_per_device

    def short(self):
        rows = honest(self)
        rows[sorted(rows)[-1]] -= 256
        return rows
    monkeypatch.setattr(MeshResidentEngine, "corpus_rows_per_device", short)
    with pytest.raises(RuntimeError, match="placement refused"):
        MeshResidentEngine(corpus, mesh_config(), mesh_shape=MESH)


@pytest.mark.parametrize("select", ["extract", "auto"])
def test_an_even_placement_is_accepted_on_either_layout(select):
    corpus = uniform_corpus(n=2048)
    cfg = EngineConfig(mode="sharded", use_pallas=select == "extract",
                       select=select, dtype="float32", data_block=256)
    eng = MeshResidentEngine(corpus, cfg, mesh_shape=MESH)
    rows = eng.corpus_rows_per_device()
    assert len(rows) == 4 and len(set(rows.values())) == 1
    assert sum(rows.values()) == eng.capacity_rows >= 2048


# -- spans, phase timings, the merge counter -----------------------------------

@pytest.fixture(scope="module")
def traced():
    corpus, q = uniform_corpus(), queries(nq=8, seed=21)
    tracer = obs_trace.install(obs_trace.Tracer())
    daemon = None
    try:
        daemon = ServeDaemon(corpus, mesh_config(), capacity=CAP,
                             warm_buckets=[(8, K)], mesh_shape=MESH)
        daemon.start()
        mark = len(tracer.events())
        bytes0 = telemetry.registry().counter("fleet.merge_bytes").total()
        resp = ask(daemon.port, {"op": "query", "id": "q", "k": K,
                                 "rid": "r-1", "queries": q.tolist()})
        assert resp["ok"], resp
        stats = ask(daemon.port, {"op": "stats"})["stats"]
        merged = telemetry.registry().counter(
            "fleet.merge_bytes").total() - bytes0
        comms = list(daemon.engine.last_comms)
    finally:
        if daemon is not None:
            daemon.close()
        obs_trace.uninstall()
    spans = [e for e in tracer.events() if e.get("ph") == "X"]
    return {"all": spans,
            "served": [e for e in tracer.events()[mark:]
                       if e.get("ph") == "X"],
            "stats": stats, "merge_bytes": merged, "comms": comms}


def named(events, name):
    return [e for e in events if e["name"] == name]


@pytest.mark.parametrize("name", IN_BATCH)
def test_one_mesh_micro_batch_yields_the_span_once_inside_it(traced, name):
    spans = named(traced["served"], name)
    assert len(spans) == 1, [e["name"] for e in traced["served"]]
    parent = named(traced["served"], "serve.micro_batch")[0]
    child = spans[0]
    assert child["args"]["batch"] == parent["args"]["batch"] == 1
    assert parent["ts"] <= child["ts"] and child["ts"] + child["dur"] \
        <= parent["ts"] + parent["dur"]


def test_the_mesh_spans_follow_each_other_without_overlap(traced):
    spans = sorted((e for e in traced["served"] if e["name"] in IN_BATCH),
                   key=lambda e: e["ts"])
    assert [e["name"] for e in spans] == IN_BATCH
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3


def test_the_mesh_spans_carry_what_the_metrics_read(traced):
    fold = named(traced["served"], "fleet.solve_resident")[0]["args"]
    assert fold["dispatches"] == fold["scheduled"] >= 2
    assert fold["kernel_dispatch_ms"] >= 0 and fold["throttle_wait_ms"] >= 0
    hz = named(traced["served"], "fleet.hazard")[0]["args"]
    assert hz["rows"] == 100000 and hz["flagged"] == 0
    assert hz["dn_max_cached"] is True      # warm-up's batch made the pass
    assert named(traced["served"], "fleet.finalize")[0]["args"][
        "repairs"] == 0
    merge = named(traced["served"], "fleet.merge")[0]["args"]
    assert merge["strategy"] == "allgather"
    assert merge["bytes"] == traced["merge_bytes"] \
        == sum(t.bytes_total for t in traced["comms"]) > 0


@pytest.mark.parametrize("name", SETUP)
def test_set_up_spans_are_emitted_once_and_outside_any_batch(traced, name):
    spans = named(traced["all"], name)
    assert len(spans) == 1 and "batch" not in spans[0].get("args", {})


@pytest.fixture(scope="module")
def untraced_stats():
    assert not obs_trace.sinks_active()
    return serve(uniform_corpus(n=2048), queries(nq=8, seed=22))[1]


@pytest.mark.parametrize("key", [k for k, _ in PHASE_HISTOGRAMS["batch"]])
def test_mesh_stats_report_every_batch_phase_without_a_tracer(
        untraced_stats, key):
    got = untraced_stats["phases_ms"]["batch"][key]
    assert got["count"] == 1            # the one served batch, no warm-up
    assert 0 <= got["p50"] <= got["p95"]


def test_the_merge_program_is_named_for_the_device_trace():
    corpus = uniform_corpus(n=2048)
    for merge in MERGES:
        eng = MeshResidentEngine(corpus, mesh_config(), mesh_shape=MESH,
                                 merge=merge)
        entry = eng._bucket_entry(8, K)
        cd, ci = eng._chunk_init_fn(4, entry.qpad, entry.kcap)()
        text = eng._chunk_merge_fn(entry.kcap).lower(
            cd, ci, eng._lab_dev).as_text()
        assert "jit_dmlp_mesh_merge" in text, merge


# -- admission on a mesh: one device's budget, one device's watermark ----------

def _four_chips(monkeypatch, peak, limit=16_000_000_000):
    from dmlp_tpu.obs import memwatch
    monkeypatch.setattr(memwatch, "device_memory_stats", lambda: [
        {"peak_bytes_in_use": peak, "bytes_in_use": peak,
         "bytes_limit": limit}] * 4)


@pytest.mark.parametrize("peak,admitted", [(4_440_000_000, True),
                                           (15_999_000_000, False)])
def test_auto_budget_holds_a_mesh_to_its_fullest_device(monkeypatch, peak,
                                                        admitted):
    """Four chips a quarter full are not one chip overfull: 4 x 4.44 GB
    passes one chip's 16 GB limit, and every request of the first
    full-size run on four chips was shed as ``memory``."""
    from dmlp_tpu.serve.admission import AdmissionController
    eng = MeshResidentEngine(uniform_corpus(n=2048), mesh_config(),
                             mesh_shape=MESH)
    _four_chips(monkeypatch, peak)
    adm = AdmissionController(eng)          # hbm_budget: auto
    assert adm.budget_bytes == 16_000_000_000
    assert adm.headroom_bytes() == 16_000_000_000 - peak
    verdict = adm.decide(1024, K, queued_queries=0)
    assert (verdict["verdict"] == "accept") is admitted, verdict
    if not admitted:
        assert verdict["reason"] == "memory"


def test_a_one_chip_engine_is_still_held_to_the_hosts_sum(monkeypatch):
    from dmlp_tpu.obs import memwatch
    from dmlp_tpu.serve.admission import AdmissionController
    eng = ResidentEngine(uniform_corpus(n=2048), EngineConfig())
    _four_chips(monkeypatch, 1_000_000_000)
    assert memwatch.measured_watermark()["bytes"] == 4_000_000_000
    assert memwatch.measured_watermark(per_device=True)["bytes"] \
        == 1_000_000_000
    adm = AdmissionController(eng)
    assert adm.headroom_bytes() == 16_000_000_000 - 4_000_000_000
