"""The batcher's two-stage pipeline (PR 34; the mesh engine's since PR 42).

A micro-batch is solved in two halves (the core's ``begin_batch``: what
only enqueues; ``finish_batch``: the fence and the host's float64
work), and the one batcher thread begins batch N + 1 before it finishes
batch N whenever a batch's worth of queries is already queued. Both
resident engines run the one pair: the cases below run over the one-chip
engine's three paths and over the mesh engine's two (``mesh``: the
resident fold under ``shard_map`` and the merge across two virtual
devices; ``mesh_stream``: the merged monolithic program). Every
request is submitted BEFORE the batcher starts, one request a batch
(the batch cap is a request's size), so which batch is begun behind
which is decided by the queue and not by a race. On the CPU the kernels run interpreted:
these tests hold the order, the counters and the answers, not a time.
"""

from __future__ import annotations

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.fleet.mesh_engine import MeshPendingBatch, MeshResidentEngine
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.io.report import format_results
from dmlp_tpu.obs import telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.resilience import inject
from dmlp_tpu.resilience import stats as rs_stats
from dmlp_tpu.resilience.inject import FaultEntry, FaultSchedule
from dmlp_tpu.serve.admission import AdmissionController
from dmlp_tpu.serve.batching import MicroBatcher, Request
from dmlp_tpu.serve import engine as serve_engine
from dmlp_tpu.serve.engine import (PendingBatch, ResidentEngine,
                                   ResidentServingCore)

NA = 4
NQ = 6          # queries a request, and the batch cap: a request a batch
WAIT = 300


def corpus_of(n: int, seed: int) -> KNNInput:
    rng = np.random.default_rng(seed)
    return KNNInput(Params(n, 0, NA),
                    rng.integers(0, 5, n).astype(np.int32),
                    rng.uniform(-10, 10, (n, NA)),
                    np.zeros(0, np.int32), np.zeros((0, NA)))


#: path -> (config, corpus rows, a request's k): the streaming program,
#: the extract kernel over two resident chunks, and the wide-k multipass
#: driver (the k512 bucket's 576 slots: two passes) over one; then the
#: mesh engine on two of the suite's virtual devices: the resident fold
#: (two chunks a shard) with its merge, and the monolithic fallback
PATHS = {
    "stream": (EngineConfig(), 600, 5),
    "extract": (EngineConfig(select="extract", use_pallas=True,
                             data_block=12800), 14000, 5),
    "multipass": (EngineConfig(select="extract", use_pallas=True,
                               dtype="float32"), 900, 300),
    "mesh": (EngineConfig(mode="sharded", select="extract", use_pallas=True,
                          dtype="float32", data_block=12800), 40000, 5),
    "mesh_stream": (EngineConfig(mode="sharded"), 600, 5),
}
MESH = (2, 1)
#: the bucket path (``bucket_stats()["paths"]``) each one takes
BUCKET_PATH = {**{p: p for p in PATHS}, "mesh": "extract",
               "mesh_stream": "stream"}
#: the engine's spans of a batch's second half (fetch, finalize, gate
#: bookkeeping): the one-chip engine's and the mesh engine's names
FETCH, FINALIZE, AFTER = (
    {p: fleet if p.startswith("mesh") else chip for p in PATHS}
    for fleet, chip in (("fleet.fetch", "single.fetch"),
                        ("fleet.finalize", "single.finalize"),
                        ("fleet.after_batch", "serve.after_batch")))
#: one path of each engine's, the cheapest, for the batcher's own cases
BOTH = ["stream", "mesh_stream"]


def engine_for(path: str, seed: int = 41, n: int = 0, capacity=None):
    cfg, rows, _k = PATHS[path]
    corpus = corpus_of(n or rows, seed)
    if path.startswith("mesh"):
        return MeshResidentEngine(corpus, cfg, mesh_shape=MESH,
                                  capacity=capacity)
    return ResidentEngine(corpus, cfg, capacity=capacity)


def requests_for(path: str, count: int, seed: int = 42):
    k = PATHS[path][2]
    rng = np.random.default_rng(seed)
    return [Request(kind="query", req_id=f"r{i}", rid=f"rid-{i}",
                    query_attrs=rng.uniform(-10, 10, (NQ, NA)),
                    ks=rng.integers(max(1, k - 3), k + 1,
                                    NQ).astype(np.int32))
            for i in range(count)]


def batcher_for(eng) -> MicroBatcher:
    return MicroBatcher(eng, AdmissionController(eng),
                        max_batch_queries=NQ, tick_s=0.0)


def run_queued(eng, reqs, drain=True):
    """Queue every request, THEN start the batcher, wait, stop."""
    b = batcher_for(eng)
    for r in reqs:
        assert b.submit(r)["verdict"] == "accept"
    b.start()
    try:
        for r in reqs:
            if r.kind == "query":
                assert r.done.wait(timeout=WAIT), r.req_id
    finally:
        b.stop(drain=drain)
    return b


def overlap():
    reg = telemetry.registry()
    return (int(reg.counter("serve.batches").total()),
            int(reg.counter("serve.batches_overlapped").total()))


def text_of(results) -> str:
    return format_results(results)


@pytest.fixture(scope="module")
def piped():
    """Each path's engine fed three queued requests under a tracer: the
    requests, the spans, the overlap counts, and what a second engine
    over the same corpus answers each request alone."""
    out = {}
    for path in PATHS:
        eng, alone = engine_for(path), engine_for(path)
        reqs = requests_for(path, 3)
        tracer = obs_trace.install(obs_trace.Tracer())
        try:
            b0, o0 = overlap()
            b = run_queued(eng, reqs)
            b1, o1 = overlap()
        finally:
            obs_trace.uninstall()
        out[path] = {
            "eng": eng, "reqs": reqs, "batches": b.batches,
            "counted": (b1 - b0, o1 - o0),
            "stats": eng.bucket_stats(),
            "spans": [e for e in tracer.events() if e.get("ph") == "X"],
            "alone": [alone.solve_batch(r.query_attrs, r.ks)
                      for r in reqs]}
    return out


def named(spans, name, batch=None):
    return [e for e in spans if e["name"] == name
            and (batch is None or e["args"].get("batch") == batch)]


# -- (a) answers ---------------------------------------------------------------

@pytest.mark.parametrize("path", sorted(PATHS))
def test_queued_requests_are_answered_as_solve_batch_answers_each_alone(
        piped, path):
    side = piped[path]
    assert set(side["stats"]["paths"].values()) == {BUCKET_PATH[path]}
    assert side["batches"] == 3
    for r, want in zip(side["reqs"], side["alone"]):
        assert r.error is None
        assert text_of(r.results) == text_of(want), r.req_id
        # neighbour ids and float64 distances, not only the report's lines
        for got, ref in zip(r.results, want):
            assert got.checksum() == ref.checksum()
            assert np.array_equal(got.neighbor_ids, ref.neighbor_ids)
            assert np.array_equal(got.neighbor_dists, ref.neighbor_dists)


# -- (b) the order of the halves, and the counter ------------------------------

#: path -> the span that dispatches a batch's device work
DISPATCH = {"stream": "serve.solve_stream", "extract": "serve.solve_extract",
            "multipass": "serve.mp_merge", "mesh": "fleet.solve_resident",
            "mesh_stream": "fleet.solve_stream"}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_next_batch_is_dispatched_before_the_one_in_flight_is_finalized(
        piped, path):
    spans = piped[path]["spans"]
    for older in (1, 2):
        (dispatch,) = named(spans, DISPATCH[path], older + 1)
        (fetch,) = named(spans, FETCH[path], older)
        (final,) = named(spans, FINALIZE[path], older)
        assert dispatch["ts"] + dispatch["dur"] <= fetch["ts"], older
        assert fetch["ts"] + fetch["dur"] <= final["ts"]
    # and a batch's own halves keep their order
    for batch in (1, 2, 3):
        (dispatch,) = named(spans, DISPATCH[path], batch)
        (fetch,) = named(spans, FETCH[path], batch)
        assert dispatch["ts"] + dispatch["dur"] <= fetch["ts"]
    if path == "mesh":
        # the mesh's fence is its own pair of spans: what is left of the
        # fold, then of the merge, both in the SECOND half
        for older in (1, 2):
            (dispatch,) = named(spans, DISPATCH[path], older + 1)
            (drain,) = named(spans, "fleet.merge_drain", older)
            (merge,) = named(spans, "fleet.merge", older)
            (fetch,) = named(spans, FETCH[path], older)
            assert dispatch["ts"] + dispatch["dur"] <= drain["ts"]
            assert drain["ts"] + drain["dur"] <= merge["ts"]
            assert merge["ts"] + merge["dur"] <= fetch["ts"]
            assert {"kernel_dispatch_ms", "merge_dispatch_ms"} \
                <= set(dispatch["args"])


@pytest.mark.parametrize("path", sorted(PATHS))
def test_overlapped_batches_are_counted_and_say_so_on_their_span(piped, path):
    side = piped[path]
    assert side["counted"] == (3, 2)     # all but the first
    assert set(side["stats"]["overlap"]) == {"batches", "overlapped"}
    micro = sorted(named(side["spans"], "serve.micro_batch"),
                   key=lambda e: e["args"]["batch"])
    assert [e["args"]["overlapped"] for e in micro] == [0, 1, 1]
    # a batch begun behind another is alive while that one finishes
    for a, b in zip(micro, micro[1:]):
        assert b["ts"] < a["ts"] + a["dur"]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_engine_span_carries_its_own_batch(piped, path):
    """Spans are matched to their batch by ``batch``, and a batch's
    spans lie inside its ``serve.micro_batch``."""
    spans = piped[path]["spans"]
    micro = {e["args"]["batch"]: e
             for e in named(spans, "serve.micro_batch")}
    inner = [e for e in spans if e["name"].startswith(
        ("serve.solve_", "serve.mp_", "single.", "serve.after_batch",
         "serve.prune_score", "serve.fold_schedule", "fleet."))]
    assert inner
    if path == "mesh":
        assert {e["name"] for e in inner} >= {
            "fleet.stage_queries", "fleet.prune_score",
            "fleet.fold_schedule", "fleet.solve_resident",
            "fleet.merge_drain", "fleet.merge", "fleet.fetch",
            "fleet.hazard", "fleet.finalize", "fleet.after_batch"}
    for e in inner:
        m = micro[e["args"]["batch"]]
        assert m["ts"] <= e["ts"] + 1e-3, e["name"]
        assert e["ts"] + e["dur"] <= m["ts"] + m["dur"] + 1e-3, e["name"]
        if "rids" in m["args"]:
            assert e["args"].get("rids") == m["args"]["rids"], e["name"]
    for batch in micro:
        assert len(named(spans, FINALIZE[path], batch)) == 1
        assert len(named(spans, AFTER[path], batch)) == 1


def test_the_multipass_span_runs_from_its_enqueues_to_its_fence(piped):
    spans = piped["multipass"]["spans"]
    for batch in (1, 2, 3):
        (whole,) = named(spans, "serve.solve_multipass", batch)
        (fence,) = named(spans, "serve.mp_fetch", batch)
        passes = named(spans, "serve.mp_pass", batch)
        assert whole["args"]["passes"] == len(passes) == 2
        assert whole["args"]["queries"] == NQ
        assert {"flagged", "stalled", "shortfall", "chunks"} \
            <= set(whole["args"])
        assert whole["ts"] <= min(p["ts"] for p in passes) + 1e-3
        # it closes behind its fence (the stall and shortfall tests in
        # between) and before the readback that follows: by order, no
        # wall time is gated
        (fetch,) = named(spans, "single.fetch", batch)
        assert fence["ts"] + fence["dur"] <= whole["ts"] + whole["dur"] \
            + 1e-3 <= fetch["ts"] + 2e-3
    assert piped["multipass"]["stats"]["multipass"]["batches"] >= 3


@pytest.mark.parametrize("path", ["extract", "mesh"])
def test_one_request_at_a_time_is_todays_order_and_never_overlaps(path):
    eng = engine_for(path)
    reqs = requests_for(path, 3, seed=44)
    tracer = obs_trace.install(obs_trace.Tracer())
    b = batcher_for(eng)
    b.start()
    try:
        b0, o0 = overlap()
        for r in reqs:
            assert b.submit(r)["verdict"] == "accept"
            assert r.done.wait(timeout=WAIT)
        b1, o1 = overlap()
    finally:
        b.stop(drain=True)
        obs_trace.uninstall()
    assert (b1 - b0, o1 - o0) == (3, 0)
    spans = [e for e in tracer.events() if e.get("ph") == "X"]
    micro = sorted(named(spans, "serve.micro_batch"),
                   key=lambda e: e["ts"])
    assert [e["args"]["overlapped"] for e in micro] == [0, 0, 0]
    for a, b_ in zip(micro, micro[1:]):
        assert a["ts"] + a["dur"] <= b_["ts"] + 1e-3
    chain = (DISPATCH[path], FETCH[path], FINALIZE[path])
    order = sorted((e for e in spans if e["name"] in chain),
                   key=lambda e: e["ts"])
    assert [(e["name"], e["args"]["batch"]) for e in order] == [
        (name, batch) for batch in (1, 2, 3) for name in chain]


@pytest.mark.parametrize("path", BOTH)
def test_less_than_a_batch_queued_waits_for_the_one_in_flight_to_finish(
        path):
    """Requests that could still take company are not committed to a
    batch while another is in flight: a cap of two requests and three
    queued is a full batch and then, behind it, half of one, which is
    begun only when the first has finished (a serial batcher's order)."""
    eng = engine_for(path)
    reqs = requests_for(path, 3, seed=45)
    alone = engine_for(path)
    b = MicroBatcher(eng, AdmissionController(eng),
                     max_batch_queries=2 * NQ, tick_s=0.0)
    for r in reqs:
        assert b.submit(r)["verdict"] == "accept"
    tracer = obs_trace.install(obs_trace.Tracer())
    b0, o0 = overlap()
    b.start()
    try:
        for r in reqs:
            assert r.done.wait(timeout=WAIT)
        b1, o1 = overlap()
    finally:
        b.stop(drain=True)
        obs_trace.uninstall()
    assert (b1 - b0, o1 - o0) == (2, 0)
    assert [r.batch for r in reqs] == [1, 1, 2]
    spans = [e for e in tracer.events() if e.get("ph") == "X"]
    first, second = sorted(named(spans, "serve.micro_batch"),
                           key=lambda e: e["args"]["batch"])
    assert [first["args"]["requests"], second["args"]["requests"]] == [2, 1]
    assert first["ts"] + first["dur"] <= second["ts"] + 1e-3
    for r in reqs:
        assert text_of(r.results) == text_of(
            alone.solve_batch(r.query_attrs, r.ks))


# -- (c) an ingest between two query requests -----------------------------------

@pytest.mark.parametrize("path", BOTH)
def test_an_ingest_queued_between_two_requests_splits_old_rows_from_new(
        path):
    eng = engine_for(path, seed=51, capacity=1024)
    rng = np.random.default_rng(52)
    q = rng.uniform(-10, 10, (NQ, NA))
    ks = np.full(NQ, 4, np.int32)
    # the new rows ARE the queries: each becomes its query's nearest
    before = engine_for(path, seed=51, capacity=1024)
    want_old = text_of(before.solve_batch(q, ks))
    before.ingest(np.full(NQ, 7, np.int32), q)
    want_new = text_of(before.solve_batch(q, ks))
    assert want_old != want_new

    first = Request(kind="query", req_id="old", query_attrs=q, ks=ks)
    ingest = Request(kind="ingest", req_id="rows",
                     labels=np.full(NQ, 7, np.int32), attrs=q)
    second = Request(kind="query", req_id="new", query_attrs=q, ks=ks)
    seen = []
    real_ingest, real_finish = eng.ingest, eng.finish_batch
    eng.ingest = lambda *a, **k: (seen.append(("ingest", len(
        eng._in_flight))), real_ingest(*a, **k))[1]
    eng.finish_batch = lambda p: (seen.append(("finish", p.batch)),
                                  real_finish(p))[1]
    run_queued(eng, [first, ingest, second])
    assert ingest.done.is_set() and ingest.error is None
    assert ingest.corpus_rows == 606
    assert text_of(first.results) == want_old
    assert text_of(second.results) == want_new
    # the batch in flight was finished before the ingest ran, and the
    # second was begun only after it
    assert seen == [("finish", 1), ("ingest", 0), ("finish", 2)]


# -- (d) a failure in one half ---------------------------------------------------

@pytest.mark.parametrize("path", BOTH)
def test_a_failure_in_finish_batch_fails_that_batch_alone(path):
    eng = engine_for(path)
    reqs = requests_for(path, 3, seed=61)
    alone = engine_for(path)
    real = eng.finish_batch

    def failing(pend):
        if pend.batch == 1:
            real(pend)                  # its device work is drained
            raise RuntimeError("finalize broke")
        return real(pend)

    eng.finish_batch = failing
    errs = telemetry.registry().counter("serve.batch_errors")
    e0 = errs.total()
    b = run_queued(eng, reqs)
    assert errs.total() - e0 == 1
    assert reqs[0].error == "RuntimeError: finalize broke"
    assert reqs[0].results is None
    for r in reqs[1:]:
        assert r.error is None
        assert text_of(r.results) == text_of(
            alone.solve_batch(r.query_attrs, r.ks))
    assert b.batches == 2


@pytest.mark.parametrize("site,half", [("single.stage_put", "begin"),
                                       ("single.fetch", "finish")])
def test_an_oom_in_either_half_reruns_that_batch_whole_a_rung_down(
        site, half):
    """The ladder's meaning with two batches alive: the batch that ran
    out of memory runs again, whole, from the next rung, after the
    batch begun behind it has finished; both answer exactly."""
    eng, alone = engine_for("extract"), engine_for("extract")
    reqs = requests_for("extract", 2, seed=62)
    want = [text_of(alone.solve_batch(r.query_attrs, r.ks)) for r in reqs]
    eng.warmup([(NQ, 5)])
    order = []
    real = eng._run_finish
    eng._run_finish = lambda p: (order.append(
        (p.batch, eng._degrade_rung)), real(p))[1]
    rs_stats.reset()
    inject.install(FaultSchedule([FaultEntry(site, "oom", times=1)]))
    try:
        run_queued(eng, reqs)
    finally:
        inject.uninstall()
    for r, w in zip(reqs, want):
        assert r.error is None and text_of(r.results) == w
    assert rs_stats.snapshot()["degradations"] == ["lowp->prune"]
    assert eng._in_flight == []
    # the first batch fails (begin: its first staged put; finish: its
    # fetch), the second is finished on the top rung BEFORE the first
    # runs again on the next one (its record carries no batch: fresh)
    if half == "finish":
        assert order[0] == (1, "lowp")          # the attempt that failed
        order = order[1:]
    assert order == [(2, "lowp"), (None, "prune")]


# -- (e) stop ---------------------------------------------------------------------

@pytest.mark.parametrize("path", BOTH)
@pytest.mark.parametrize("drain", [True, False])
def test_stop_finishes_the_batch_in_flight(drain, path):
    """drain=True answers what is in flight and what is queued;
    drain=False still finishes the batch in flight (its device work is
    enqueued) and fails the queue."""
    import threading
    eng = engine_for(path)
    reqs = requests_for(path, 3, seed=71)
    begun, release = threading.Event(), threading.Event()
    real = eng.begin_batch

    def gated(*a, **k):
        out = real(*a, **k)
        if k.get("batch") == 1:
            begun.set()
            assert release.wait(timeout=WAIT)
        return out

    eng.begin_batch = gated
    b = batcher_for(eng)
    assert b.submit(reqs[0])["verdict"] == "accept"
    b.start()
    assert begun.wait(timeout=WAIT)      # batch 1 is in flight
    for r in reqs[1:]:
        assert b.submit(r)["verdict"] == "accept"
    stopper = threading.Thread(target=b.stop, kwargs={"drain": drain})
    stopper.start()
    while not b._stop:                   # stop() has taken the queue
        pass
    release.set()
    stopper.join(timeout=WAIT)
    assert not stopper.is_alive()
    assert all(r.done.is_set() for r in reqs)
    assert reqs[0].error is None and reqs[0].results is not None
    if drain:
        assert all(r.error is None for r in reqs[1:])
        assert b.batches == 3
    else:
        assert [r.error for r in reqs[1:]] == ["shutdown", "shutdown"]
        assert b.batches == 1


# -- (f) one protocol, two engines: the pair is the core's ------------------------

PAIR = ("begin_batch", "finish_batch", "solve_batch", "_outcome", "_tagged")


@pytest.mark.parametrize("cls", [ResidentEngine, MeshResidentEngine])
def test_both_engines_run_the_cores_one_pair(cls):
    """No engine keeps a pair of its own, and none solves whole in its
    second half: ``HeldBatch`` and the default bodies are gone."""
    for name in PAIR:
        assert name not in vars(cls), name
        assert getattr(cls, name) is getattr(ResidentServingCore, name)
    for half in ("_first_half", "_second_half"):
        assert half in vars(cls)
    assert cls.batches_resident == 2
    assert not hasattr(serve_engine, "HeldBatch")


@pytest.mark.parametrize("path", BOTH + ["mesh"])
def test_begun_batches_queue_oldest_first_and_leave_when_they_finish(path):
    eng, alone = engine_for(path), engine_for(path)
    reqs = requests_for(path, 2, seed=82)
    a = eng.begin_batch(reqs[0].query_attrs, reqs[0].ks, batch=9)
    b = eng.begin_batch(reqs[1].query_attrs, reqs[1].ks, batch=10)
    kind = MeshPendingBatch if path.startswith("mesh") else PendingBatch
    assert type(a) is kind and isinstance(a, PendingBatch)
    assert (a.overlapped, b.overlapped) == (False, True)
    assert (a.batch, b.batch) == (9, 10)
    assert eng._in_flight == [a, b]
    assert eng.trace_batch is None and eng.trace_rids is None
    got_a = eng.finish_batch(a)
    assert eng._in_flight == [b]
    got_b = eng.finish_batch(b)
    assert eng._in_flight == []
    assert eng.trace_batch is None and eng.trace_rids is None
    for got, r in ((got_a, reqs[0]), (got_b, reqs[1])):
        assert text_of(got) == text_of(
            alone.solve_batch(r.query_attrs, r.ks))
    # solve_batch alone is both halves back to back, nothing left behind
    assert eng._in_flight == alone._in_flight == []


def test_the_mesh_pipeline_answers_as_one_chip_and_fences_in_its_second_half(
        piped):
    """What ran whole and serial through the default pair until PR 42:
    three queued requests are three batches, the last two begun behind
    another; each answer is the one-chip engine's over the same corpus;
    batch N + 1's fold and merge are on the devices' queues before the
    host first blocks on batch N."""
    side = piped["mesh"]
    assert side["counted"] == (3, 2)
    assert side["stats"]["overlap"]["overlapped"] >= 2
    assert side["stats"]["mesh"] == list(MESH)
    one = ResidentEngine(corpus_of(PATHS["mesh"][1], 41), EngineConfig())
    for r in side["reqs"]:
        assert r.error is None
        assert text_of(r.results) == text_of(
            one.solve_batch(r.query_attrs, r.ks))
    spans = side["spans"]
    for older in (1, 2):
        (enqueue,) = named(spans, "fleet.solve_resident", older + 1)
        (score,) = named(spans, "fleet.prune_score", older + 1)
        (drain,) = named(spans, "fleet.merge_drain", older)
        # the one wait of a first half (the scorer's mask) lies before
        # its dispatch; the older batch's fence after it
        assert score["ts"] + score["dur"] <= enqueue["ts"]
        assert enqueue["ts"] + enqueue["dur"] <= drain["ts"]
    assert side["eng"].trace_batch is None
    assert side["eng"].trace_rids is None


def _mesh_report(eng):
    stats = eng.bucket_stats()
    return {"phase_ms": eng.last_phase_ms, "prune": eng.last_prune,
            "comms": eng.last_comms, "variant": eng.last_variant,
            "precision": eng.last_precision, "select": eng._last_select,
            "impl": eng.last_extract_impl, "repairs": eng.last_repairs,
            "stats": {k: stats[k] for k in (
                "last_prune", "last_prune_fraction", "last_gated_fraction",
                "last_precision")}}


def test_two_mesh_batches_alive_do_not_write_each_others_report():
    """Begin A, begin B, finish A: the engine's ``last_*`` report, the
    gate flush and ``bucket_stats()`` are A's; B's only once B has
    finished. A and B differ in bucket (q128k16 against q256k256:
    another candidate width, so another variant and other merge
    traffic, and another count of query tiles) and in what the scorer
    pruned (nothing; one shard's piece of a chunk)."""
    eng, alone = engine_for("mesh"), engine_for("mesh")
    rng = np.random.default_rng(83)
    qa, ka = rng.uniform(-10, 10, (NQ, NA)), np.full(NQ, 5, np.int32)
    # B also fills a second query tile (every list width runs 128-row
    # tiles since PR 47), so the two batches' folds visit other counts
    nb = 130
    qb, kb = rng.uniform(-10, 10, (nb, NA)), np.full(nb, 200, np.int32)
    keep = np.ones((MESH[0], eng._nchunks), bool)
    keep[1, 1] = False
    prune_b = {"blocks_total": keep.size, "blocks_pruned": 1}
    want = {}
    for name, (q, ks) in (("a", (qa, ka)), ("b", (qb, kb))):
        if name == "b":
            alone._prune_live = lambda *_: (keep.copy(), dict(prune_b))
        want[name] = text_of(alone.solve_batch(q, ks))
        want[name, "report"] = _mesh_report(alone)
    before = _mesh_report(eng)
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        a = eng.begin_batch(qa, ka, batch=1, rids="ra")
        eng._prune_live = lambda *_: (keep.copy(), dict(prune_b))
        b = eng.begin_batch(qb, kb, batch=2, rids="rb")
        # nothing finished: the engine still reports what it did before
        assert _mesh_report(eng) == before
        assert eng.trace_batch is None and eng.trace_rids is None
        tiles = {1: a.gate[1], 2: b.gate[1]}
        got_a = eng.finish_batch(a)
        rep_a = _mesh_report(eng)
        got_b = eng.finish_batch(b)
        rep_b = _mesh_report(eng)
    finally:
        obs_trace.uninstall()
    assert eng.trace_batch is None and eng.trace_rids is None
    assert text_of(got_a) == want["a"] and text_of(got_b) == want["b"]
    assert rep_a["phase_ms"] is a.phase_ms
    assert rep_b["phase_ms"] is b.phase_ms
    assert set(a.phase_ms) == set(b.phase_ms) == {
        "dispatch", "merge", "fetch", "hazard", "finalize"}
    assert rep_a["comms"] is a.comms and rep_b["comms"] is b.comms
    for name, rep in (("a", rep_a), ("b", rep_b)):
        ref = want[name, "report"]
        for key in ("prune", "variant", "precision", "select", "impl",
                    "repairs"):
            assert rep[key] == ref[key], (name, key)
        assert [t.to_dict() for t in rep["comms"]] \
            == [t.to_dict() for t in ref["comms"]], name
        for key in ("last_prune", "last_prune_fraction", "last_precision"):
            assert rep["stats"][key] == ref["stats"][key], (name, key)
    # and the two really differ where the batches do
    assert rep_a["prune"]["blocks_pruned"] == 0
    assert rep_b["prune"]["blocks_pruned"] == 1
    assert rep_a["stats"]["last_prune_fraction"] == 0.0
    assert rep_b["stats"]["last_prune_fraction"] > 0.0
    assert rep_a["variant"]["kc"] < rep_b["variant"]["kc"]
    assert [t.to_dict() for t in rep_a["comms"]] \
        != [t.to_dict() for t in rep_b["comms"]]
    # the gate flush: each batch's own count over its own tiles, on a
    # span that carries its own batch and rids
    spans = [e for e in tracer.events() if e.get("ph") == "X"]
    for batch, rids in ((1, "ra"), (2, "rb")):
        (after,) = named(spans, "fleet.after_batch", batch)
        assert after["args"]["rids"] == rids
        assert after["args"]["tiles"] == tiles[batch]
        assert 0 <= after["args"]["gated"] <= tiles[batch]
        mine = [e for e in spans if e["name"].startswith("fleet.")
                and e["args"].get("batch") == batch]
        assert {e["args"].get("rids") for e in mine} == {rids}
    assert tiles[1] != tiles[2]
    assert rep_b["stats"]["last_gated_fraction"] \
        == named(spans, "fleet.after_batch", 2)[0]["args"]["gated"] / tiles[2]


@pytest.mark.parametrize("path", BOTH)
def test_a_failure_in_either_half_is_that_batchs_alone_on_the_engine(path):
    eng, alone = engine_for(path), engine_for(path)
    reqs = requests_for(path, 3, seed=84)
    a = eng.begin_batch(reqs[0].query_attrs, reqs[0].ks, batch=1)
    b = eng.begin_batch(reqs[1].query_attrs, reqs[1].ks, batch=2)
    # a first half that raises begins nothing: the two in flight stay
    with pytest.raises(serve_engine.RequestShapeError):
        eng.begin_batch(reqs[2].query_attrs,
                        np.full(NQ, eng.max_k + 1, np.int32), batch=3)
    assert eng._in_flight == [a, b]
    real = eng._after_batch

    def failing(pend, results):
        real(pend, results)
        if pend is a:
            raise RuntimeError("bookkeeping broke")

    eng._after_batch = failing
    with pytest.raises(RuntimeError, match="bookkeeping broke"):
        eng.finish_batch(a)
    assert eng._in_flight == [b] and b.outcome is None
    assert text_of(eng.finish_batch(b)) == text_of(
        alone.solve_batch(reqs[1].query_attrs, reqs[1].ks))
    assert eng._in_flight == []
    # the failed batch keeps what it failed with
    with pytest.raises(RuntimeError, match="bookkeeping broke"):
        eng.finish_batch(a)
    assert text_of(eng.solve_batch(reqs[2].query_attrs, reqs[2].ks)) \
        == text_of(alone.solve_batch(reqs[2].query_attrs, reqs[2].ks))


# -- (g) a request's phases ---------------------------------------------------------

@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_requests_phases_tile_its_time_in_the_batcher(piped, path):
    spans = piped[path]["spans"]
    for r in piped[path]["reqs"]:
        mine = sorted((e for e in spans
                       if e["name"].startswith("serve.phase.")
                       # (admission runs on the submitter, beside queue)
                       and e["name"] != "serve.phase.admission"
                       and e["args"].get("rid") == r.rid),
                      key=lambda e: e["ts"])
        assert [e["name"] for e in mine] == [
            "serve.phase.queue", "serve.phase.coalesce",
            "serve.phase.solve", "serve.phase.finalize"]
        for a, b in zip(mine, mine[1:]):
            assert abs(a["ts"] + a["dur"] - b["ts"]) < 1.0     # us
        (micro,) = named(spans, "serve.micro_batch", r.batch)
        solve = mine[2]
        assert abs(solve["ts"] - micro["ts"]) < 1.0
        assert abs(solve["dur"] - micro["dur"]) < 1.0


@pytest.mark.parametrize("path", BOTH)
def test_admission_prices_both_batches_an_engine_keeps_resident(path):
    eng = engine_for(path)
    adm = AdmissionController(eng)
    assert eng.batches_resident == 2
    assert eng.batch_model_bytes(NQ, 5) > 0
    assert adm.batch_bytes(NQ, 5) == 2 * eng.batch_model_bytes(NQ, 5)
