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
from jax.sharding import PartitionSpec as P
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
    """Stages the whole chunk stack on the mesh's first device."""

    def _stage_chunks(self) -> None:
        first = SingleDeviceSharding(self.mesh.devices.flat[0])
        self._chunks = jax.device_put(
            np.stack([self._chunk_host(t) for t in range(self._nchunks)]),
            first)


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
    if select == "extract":     # one array: every chunk of every shard
        assert eng._chunks.shape == (eng._nchunks, 4 * eng._chunk_rows, NA)
        assert eng._chunks.sharding.spec == P(None, "data", None)
        assert eng.bucket_stats()["extract_chunks"] == eng._nchunks


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
    # one program, every scheduled chunk folded inside it, nothing staged
    assert fold["dispatches"] == 1
    assert fold["chunks"] == fold["scheduled"] >= 2
    assert fold["kernel_dispatch_ms"] >= 0 and fold["throttle_wait_ms"] == 0
    assert traced["stats"]["engine"]["extract_chunks"] == fold["chunks"]
    after = named(traced["served"], "fleet.after_batch")[0]["args"]
    assert 0 <= after["gated"] <= after["tiles"] and after["tiles"] > 0
    # the kernel's two-level selection: visits that extracted at full
    # width, what select_wide_pct.mesh reads
    assert 0 <= after["wide"] <= after["tiles"] - after["gated"]
    assert after["wide_pct"] == pytest.approx(
        100.0 * after["wide"] / after["tiles"], abs=1e-3)
    hz = named(traced["served"], "fleet.hazard")[0]["args"]
    assert hz["rows"] == 100000 and hz["flagged"] == 0
    assert hz["dn_max_cached"] is True      # warm-up's batch made the pass
    fin = named(traced["served"], "fleet.finalize")[0]["args"]
    assert fin["repairs"] == 0
    # the float64 rescore gathers each query's band, not its window
    # (PR 48): rows of slots, the bytes they weigh, the share
    slots = fin["slots"] * fin["queries"]
    assert fin["queries"] == 8 and 8 * K <= fin["rows"] < slots
    assert fin["gather_bytes"] == fin["rows"] * NA * 8
    assert fin["band_pct"] == pytest.approx(100.0 * fin["rows"] / slots,
                                            abs=1e-3)
    finals = [e["args"] for e in named(traced["all"], "fleet.finalize")]
    assert traced["stats"]["engine"]["rescore"] == {
        "slots": sum(a["slots"] * a["queries"] for a in finals),
        "rows": sum(a["rows"] for a in finals)}
    merge = named(traced["served"], "fleet.merge")[0]["args"]
    assert merge["strategy"] == "allgather"
    assert merge["bytes"] == traced["merge_bytes"] \
        == sum(t.bytes_total for t in traced["comms"]) > 0


def test_the_mesh_fold_says_its_norms_were_staged(traced):
    """PR 41: ``fleet.solve_resident`` names the kernel's variant and
    where its row norms came from; ``fleet.stage_resident`` what the
    norm array weighs over the mesh; ``stats.engine`` the chunks whose
    norms were written (each once: nothing was ingested)."""
    fold = named(traced["served"], "fleet.solve_resident")[0]["args"]
    assert fold["norms"] == "staged"
    assert fold["tile_q"] >= 8 and fold["mxu_passes"] in (1, 3, 6)
    stage = named(traced["all"], "fleet.stage_resident")[0]["args"]
    eng = traced["stats"]["engine"]
    assert eng["norm_restages"] == stage["chunks"]
    assert stage["norm_bytes"] % (stage["chunks"] * 4 * 4) == 0
    assert stage["norm_bytes"] >= eng["capacity_rows"] * 4


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


# -- the one-program mesh fold -------------------------------------------------

#: three chunks a shard; the corpus stops inside the last shard's second
#: chunk, so that shard's third piece is the capacity tail (no real rows)
FOLD_CAP = 4 * 3 * 12800
FOLD_SR = FOLD_CAP // 4
FOLD_N = 3 * FOLD_SR + 12800 + 7200
FOLD_NA = 4
ALL = np.ones((4, 3), bool)


def _without(*cells):
    keep = ALL.copy()
    for rr, t in cells:
        keep[rr, t] = False
    return keep


#: what changes between micro-batches, in the order the journey makes
#: them: (step, hand-set winner histogram a chunk, hand-set (R, T) live
#: mask, ingest (start row, rows), the fold order the engine must then
#: schedule)
MESH_FOLD_STEPS = [
    ("natural", [0, 0, 0], None, None, [0, 1, 2]),
    ("hot_first", [1, 5, 3], None, None, [1, 2, 0]),
    ("piece_pruned", [1, 5, 3], _without((1, 1)), None, [1, 2, 0]),
    ("chunk_pruned", [1, 5, 3],
     _without((0, 2), (1, 2), (2, 2), (3, 2)), None, [1, 0]),
    ("capacity_tail_first", [0, 1, 9], None, None, [2, 1, 0]),
    ("restaged_chunk", [0, 0, 0], None, (100, 64), [0, 1, 2]),
    ("appended_rows", [0, 0, 0], None, (None, 9000), [0, 1, 2]),
]
MESH_FOLD_BUCKETS = {"q128": 5, "q256": 200}


def _reference_mesh_fold(eng, q_dev, order, live, n, kc, prec):
    """The fold a chunk at a time from Python, a shard at a time: the
    resolved kernel on this shard's piece of chunk ``t``, first call with
    no carry, with ``_chunk_span``'s (id_base, n_real) worked out by hand
    (real rows capped at the corpus end AND at the shard's boundary) and
    the gate counted eagerly."""
    from dmlp_tpu.ops import pallas_fused
    r = eng.mesh.devices.shape[0]
    cr, sr = eng._chunk_rows, eng._shard_rows
    q = jax.device_put(np.asarray(q_dev))
    stack = np.asarray(eng._chunks)
    kern, _ = pallas_fused.resolve_topk_kernel(
        q.shape[0], cr, eng.num_attrs, kc)
    ods, ois, gated, tiles = [], [], [], 0
    for rr in range(r):
        od = oi = None
        g = np.zeros(2, np.int64)   # gated, at full width
        for t in order:
            id_base = rr * sr + t * cr
            n_real = int(np.clip(min(n - id_base, sr - t * cr), 0, cr))
            if not live[rr, t]:
                n_real = 0
            od, oi, its, wd = kern(
                q, jax.device_put(stack[t, rr * cr:(rr + 1) * cr]), od, oi,
                n_real=n_real, id_base=id_base, kc=kc,
                interpret=eng._interpret, precision=prec, with_wide=True)
            g += np.asarray([np.count_nonzero(np.asarray(its) == 0),
                             np.asarray(wd).sum()])
            tiles += its.size
        ods.append(np.array(od))
        ois.append(np.array(oi))
        gated.append(g.tolist())
    return np.stack(ods), np.stack(ois), gated, tiles


@pytest.fixture(scope="module")
def mesh_fold_journey():
    """One 4x1 engine, two warm buckets, MESH_FOLD_STEPS in order; a
    micro-batch a bucket a step. Records what the one program was given
    and gave, what the chunk-by-chunk reference gives for the same order
    and mask on the same stack, and the compile counters."""
    calls = []

    class Recording(MeshResidentEngine):
        def _resident_fold_fn(self, kern):
            fn = super()._resident_fold_fn(kern)

            def spy(*args):
                out = fn(*args)
                calls.append({"fn": fn, "kern": kern, "args": args,
                              "out": out})
                return out
            return spy

        def _after_batch(self, pend, results):
            calls[-1]["tiles"] = pend.gate[1]
            super()._after_batch(pend, results)

    rng = np.random.default_rng(95)
    corpus = make_corpus(rng.uniform(-10, 10, (FOLD_N, FOLD_NA)),
                         rng.integers(0, 4, FOLD_N))
    eng = Recording(corpus, mesh_config(), mesh_shape=MESH,
                    capacity=FOLD_CAP)
    assert (eng._shard_rows, eng._nchunks, eng._chunk_rows) \
        == (FOLD_SR, 3, 12800)
    assert eng._block_rows()[3].tolist() == [12800, 7200, 0]
    eng.warmup([(nq, 6) for nq in MESH_FOLD_BUCKETS.values()])
    fns = {c["fn"] for c in calls}
    calls.clear()

    def counters():
        return (eng.compile_count, len(eng._fns),
                tuple(sorted(fn._cache_size() for fn in fns)))
    warm = counters()
    seen = {}
    for step, hits, keep, ingest, _want in MESH_FOLD_STEPS:
        if ingest is not None:
            start, m = ingest
            eng.ingest(rng.integers(0, 4, m).astype(np.int32),
                       rng.uniform(-10, 10, (m, FOLD_NA)), start=start)
        # (an instance attribute over the method; popped to restore it)
        eng.__dict__.pop("_prune_live", None)
        if keep is not None:
            eng._prune_live = (
                lambda inp, entry, q_dev, keep=keep: (
                    keep.copy(), {"blocks_total": 11,
                                  "blocks_pruned": int((~keep).sum())}))
        for bucket, nq in MESH_FOLD_BUCKETS.items():
            eng._block_hits[:] = hits
            eng.solve_batch(rng.uniform(-10, 10, (nq, FOLD_NA)),
                            rng.integers(1, 7, nq).astype(np.int32))
            call = calls.pop()
            assert not calls and call["fn"] in fns
            q_dev, _stack, _norms, order, nfold, n, live = call["args"]
            order = [int(t) for t in np.asarray(order)[:int(nfold)]]
            live = np.asarray(live) > 0
            od, oi, gated, _iters = call["out"]
            seen[step, bucket] = {
                "order": order, "n_real": int(n), "live": live,
                # copies: a view would keep the device array alive
                "got": (np.array(od), np.array(oi),
                        np.asarray(gated)[:, 0].tolist(), call["tiles"]),
                "want": _reference_mesh_fold(
                    eng, q_dev, order, live, int(n), call["kern"]["kc"],
                    call["kern"]["precision"]),
                "counters": counters()}
    return {"warm": warm, "steps": seen}


@pytest.mark.parametrize("bucket", sorted(MESH_FOLD_BUCKETS))
@pytest.mark.parametrize("step,want_order",
                         [(s[0], s[4]) for s in MESH_FOLD_STEPS])
def test_one_program_mesh_fold_equals_the_chunk_loop_bit_for_bit(
        mesh_fold_journey, step, want_order, bucket):
    rec = mesh_fold_journey["steps"][step, bucket]
    assert rec["order"] == want_order
    (od, oi, gated, tiles), (rod, roi, rgated, rtiles) = \
        rec["got"], rec["want"]
    assert od.dtype == rod.dtype == np.float32
    assert od.shape == rod.shape and od.shape[0] == 4
    # bit for bit, every shard's running lists, not just the answers
    assert od.tobytes() == rod.tobytes()
    assert oi.tobytes() == roi.tobytes()
    # the gate gauges: as many tiles gated on each shard, of as many
    # visited in all
    assert (gated, tiles) == (rgated, rtiles)


@pytest.mark.parametrize("step", [s[0] for s in MESH_FOLD_STEPS])
def test_mesh_fold_schedule_mask_and_ingest_never_recompile(
        mesh_fold_journey, step):
    """A new order, a pruned piece, a pruned chunk, a restaged chunk,
    more rows: the same executables (bucket builds, the engine's program
    table and each fold program's jit cache)."""
    for bucket in MESH_FOLD_BUCKETS:
        assert mesh_fold_journey["steps"][step, bucket]["counters"] \
            == mesh_fold_journey["warm"]


def test_the_journeys_masks_and_rows_reached_the_mesh_fold(
        mesh_fold_journey):
    steps = mesh_fold_journey["steps"]
    # nothing pruned: the resident all-ones mask (the empty tail needs
    # no bit, it has no rows); one piece pruned: that bit alone, beside
    # the tail's
    assert steps["natural", "q128"]["live"].all()
    assert steps["piece_pruned", "q128"]["live"].tolist() == [
        [True, True, True], [True, False, True], [True, True, True],
        [True, True, False]]
    assert not steps["chunk_pruned", "q256"]["live"][:, 2].any()
    assert steps["restaged_chunk", "q128"]["n_real"] == FOLD_N
    assert steps["appended_rows", "q128"]["n_real"] == FOLD_N + 9000
    # the last shard's tail piece holds no row of any list until rows
    # are appended into it
    tail = 3 * FOLD_SR + 2 * 12800
    assert steps["capacity_tail_first", "q256"]["got"][1].max() < tail
    assert steps["appended_rows", "q256"]["got"][1].max() >= tail


def test_the_stack_is_the_only_corpus_sized_array_on_the_mesh():
    """An extract-path mesh daemon holds the corpus once: no list of
    chunks beside the stack, no monolithic copy, nothing of that size
    left over from staging or from a solve."""
    corpus, q = uniform_corpus(), queries(nq=8, seed=23)
    size = 2 * 4 * 12800 * NA * 4            # one copy of the stack
    before = {id(a) for a in jax.live_arrays() if a.nbytes >= size}
    eng = MeshResidentEngine(corpus, mesh_config(), mesh_shape=MESH,
                             capacity=CAP)
    eng.warmup([(8, K)])
    eng.solve_batch(q, np.full(len(q), K, np.int32))
    rng = np.random.default_rng(24)
    eng.ingest(rng.integers(0, 10, 50), rng.random((50, NA)) * 255.0)
    eng.solve_batch(q, np.full(len(q), K, np.int32))
    assert eng._chunks.nbytes == size and eng._mono is None
    assert not hasattr(eng, "_sc_dev") and not hasattr(eng, "_ones_live")
    mine = {id(a) for a in jax.live_arrays() if a.nbytes >= size} - before
    assert mine == {id(eng._chunks)}
    assert eng.corpus_rows_per_device() == {
        str(d.id): 2 * 12800 for d in eng.mesh.devices.flat}


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
