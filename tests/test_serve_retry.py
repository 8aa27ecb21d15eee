"""The device retry of flagged queries (PR 38).

A query whose candidate window does not clear the hazard bound used to
cost its batch a pass over the whole float64 host corpus, on the one
batcher thread. A resident engine now solves the flagged queries again
on the device first, over the resident stack at the kernel's widest
single-pass window (512 slots), holds that list to the SAME hazard test,
lets the float64 rescore decide, and sends only what is still flagged to
the host oracle. These tests hold the answers (exact through the retry
and through the fall-through), the counts (flagged = retried = cleared +
fell through) and where the retry's programs compile. One corpus serves
them all: uniform rows in [0, 255) of 16 attributes (the benchmark
cell's toy shape, past the 8192-row switch to the extract path) with
three plants: a cluster of 300 near-duplicates (its queries flag at the
first window and clear at 512), 600 exact duplicates (a tie plateau
wider than 512: still flagged, the oracle's), and a block of
integer-valued rows that tie exactly across the fold.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from benchmark import reference
from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve.admission import AdmissionController
from dmlp_tpu.serve.batching import MicroBatcher, Request
from dmlp_tpu.serve.engine import ResidentEngine

N, NA, K = 9216, 16, 10
NQ = 8
CLUSTER, PLATEAU, GRID = 999, 2999, 5000


def planted_corpus() -> KNNInput:
    rng = np.random.default_rng(38)
    rows = rng.random((N, NA), dtype=np.float32).astype(np.float64) * 255
    rows[CLUSTER + 1:CLUSTER + 301] = rows[CLUSTER] \
        + rng.random((300, NA)) * 0.01
    rows[PLATEAU + 1:PLATEAU + 601] = rows[PLATEAU]
    rows[GRID:GRID + 400] = rng.integers(100, 103, (400, NA))
    return KNNInput(Params(N, 0, NA),
                    rng.integers(0, 10, N).astype(np.int32), rows,
                    np.zeros(0, np.int32), np.zeros((0, NA)))


def queries_at(corpus: KNNInput, anchor: int, seed: int) -> np.ndarray:
    """NQ query rows: two beside ``anchor``'s row, the rest uniform."""
    rng = np.random.default_rng(seed)
    q = rng.random((NQ, NA), dtype=np.float32).astype(np.float64) * 255
    q[0] = corpus.data_attrs[anchor] + 0.5
    q[1] = corpus.data_attrs[anchor] - 0.25
    return q


def cfg(dtype: str = "bfloat16", exact: bool = True) -> EngineConfig:
    return EngineConfig(dtype=dtype, use_pallas=True, exact=exact)


@pytest.fixture(scope="module")
def corpus() -> KNNInput:
    return planted_corpus()


@pytest.fixture(scope="module")
def warmed(corpus):
    """A bf16 engine after its warm-up, as a daemon starts one."""
    eng = ResidentEngine(corpus, cfg())
    eng.warm_report = eng.warmup([(NQ, K)])
    return eng


@pytest.fixture()
def tracer():
    t = obs_trace.install(obs_trace.Tracer())
    try:
        yield t
    finally:
        obs_trace.uninstall()


def spans(tracer, name):
    return [e for e in tracer.events()
            if e.get("ph") == "X" and e["name"] == name]


def assert_plain(results, corpus, q, k=K):
    """Every answer as the benchmark's plain float64 brute force gives
    it: label, ids, distances to the last bit."""
    want = reference.knn_plain(corpus.data_attrs, corpus.labels, q,
                               [k] * len(q))
    for got, ref in zip(results, want):
        assert got.predicted_label == ref.label
        assert np.array_equal(got.neighbor_ids, ref.ids)
        assert np.array_equal(got.neighbor_dists, ref.dists)


def solve(eng, q, k=K):
    return eng.solve_batch(q, np.full(len(q), k, np.int32))


# -- (a) exact through the retry ------------------------------------------------

@pytest.mark.parametrize("anchor", [CLUSTER, GRID],
                         ids=["near_duplicates", "tie_grid"])
def test_flagged_queries_are_answered_exactly_through_the_retry(
        warmed, corpus, tracer, anchor):
    q = queries_at(corpus, anchor, 1)
    before = dict(warmed.bucket_stats()["repairs"])
    res = solve(warmed, q)
    assert_plain(res, corpus, q)
    (retry,) = spans(tracer, "single.retry")
    args = retry["args"]
    assert args["queries"] >= 2 and args["kcap"] == 512
    assert args["cleared"] == args["queries"] and args["fell_through"] == 0
    assert not spans(tracer, "single.repair")      # no host scan
    after = warmed.bucket_stats()["repairs"]
    assert after["device"] - before["device"] == args["queries"]
    assert after["host"] == before["host"]


def test_the_pipelined_batcher_serves_flagged_batches_exactly(
        warmed, corpus, tracer):
    """Four requests queued before the batcher starts: batch N + 1 is
    begun before batch N's retry runs (the retry queues behind it), so
    every retry but the last waits through a neighbour's fold."""
    qs = [queries_at(corpus, CLUSTER, 10 + i) for i in range(4)]
    reqs = [Request(kind="query", req_id=f"r{i}", rid=f"rid-{i}",
                    query_attrs=q, ks=np.full(NQ, K, np.int32))
            for i, q in enumerate(qs)]
    b = MicroBatcher(warmed, AdmissionController(warmed),
                     max_batch_queries=NQ, tick_s=0.0)
    for r in reqs:
        assert b.submit(r)["verdict"] == "accept"
    b.start()
    try:
        for r in reqs:
            assert r.done.wait(300)
    finally:
        b.stop(drain=True)
    for r, q in zip(reqs, qs):
        assert r.error is None
        assert_plain(r.results, corpus, q)
    assert b.cycles == 4 and len(spans(tracer, "serve.cycle")) == 4
    # every batch was begun once, before the batch in front of it was
    # finalized, retried once and delivered once, in order
    finals = {e["args"]["batch"]: e for e in spans(tracer, "single.finalize")}
    begins = {e["args"]["batch"]: e
              for e in spans(tracer, "serve.batch_assemble")}
    assert sorted(begins) == sorted(finals) == [1, 2, 3, 4]
    for n in (1, 2, 3):
        assert begins[n + 1]["ts"] + begins[n + 1]["dur"] \
            <= finals[n]["ts"]
    assert [e["args"]["batch"] for e in spans(tracer, "single.retry")] \
        == [1, 2, 3, 4]
    delivered = [e["args"]["batch"]
                 for e in spans(tracer, "serve.batch_deliver")]
    assert delivered == [1, 2, 3, 4]
    assert warmed.batches_resident == 2


def test_a_drain_during_a_retry_finishes_everything_queued(
        warmed, corpus, monkeypatch):
    """``stop(drain=True)`` (the SIGTERM path) while a flagged batch
    waits for its retry, with a full batch queued during that finish:
    every request taken or queued by then is answered, exactly."""
    import threading
    qs = [queries_at(corpus, CLUSTER, 30 + i) for i in range(3)]
    reqs = [Request(kind="query", req_id=f"r{i}", rid=f"rid-{i}",
                    query_attrs=q, ks=np.full(NQ, K, np.int32))
            for i, q in enumerate(qs)]
    b = MicroBatcher(warmed, AdmissionController(warmed),
                     max_batch_queries=NQ, tick_s=0.0)
    stopper = threading.Thread(target=b.stop, kwargs={"drain": True})
    stopping = threading.Event()
    finish = warmed._retry_finish

    def late_arrival_then_stop(*args):
        if not stopping.is_set():
            # on the batcher thread, inside the first batch's finish
            assert b.submit(reqs[2])["verdict"] == "accept"
            stopper.start()
            while not b._stop:
                time.sleep(0.001)
            stopping.set()
        return finish(*args)

    monkeypatch.setattr(warmed, "_retry_finish", late_arrival_then_stop)
    for r in reqs[:2]:
        assert b.submit(r)["verdict"] == "accept"
    b.start()
    assert stopping.wait(300)
    stopper.join(300)
    assert not stopper.is_alive()
    for r, q in zip(reqs, qs):
        assert r.done.is_set() and r.error is None
        assert_plain(r.results, corpus, q)
    assert b.cycles == 3


def test_an_ingest_behind_flagged_batches_sees_none_in_flight(warmed, corpus,
                                                             tracer):
    """Queue: a flagged batch, a second batch, an ingest (row 0 written
    again as it is), a third batch. The ingest stays at the queue's
    head until both retries are read back and nothing is in flight, and
    the batch behind it is begun after it ran."""
    qs = [queries_at(corpus, CLUSTER, 20 + i) for i in range(3)]
    reqs = [Request(kind="query", req_id=f"r{i}", rid=f"rid-{i}",
                    query_attrs=q, ks=np.full(NQ, K, np.int32))
            for i, q in enumerate(qs)]
    ingest = Request(kind="ingest", req_id="rows",
                     labels=corpus.labels[:1], attrs=corpus.data_attrs[:1],
                     start=0)
    b = MicroBatcher(warmed, AdmissionController(warmed),
                     max_batch_queries=NQ, tick_s=0.0)
    for r in reqs[:2] + [ingest] + reqs[2:]:
        assert b.submit(r)["verdict"] == "accept"
    b.start()
    try:
        for r in reqs + [ingest]:
            assert r.done.wait(300)
    finally:
        b.stop(drain=True)
    assert ingest.error is None and ingest.corpus_rows == N
    for r, q in zip(reqs, qs):
        assert r.error is None
        assert_plain(r.results, corpus, q)
    (wrote,) = spans(tracer, "serve.ingest")
    begins = {e["args"]["batch"]: e
              for e in spans(tracer, "serve.batch_assemble")}
    delivers = {e["args"]["batch"]: e
                for e in spans(tracer, "serve.batch_deliver")}
    end = delivers[2]["ts"] + delivers[2]["dur"]
    assert end <= wrote["ts"]                       # nothing in flight
    assert wrote["ts"] + wrote["dur"] <= begins[3]["ts"]


# -- (b) the fall-through -----------------------------------------------------

def test_a_plateau_wider_than_the_retry_window_falls_to_the_oracle(
        warmed, corpus, tracer):
    q = queries_at(corpus, PLATEAU, 2)
    res = solve(warmed, q)
    assert_plain(res, corpus, q)
    (retry,) = spans(tracer, "single.retry")
    (repair,) = spans(tracer, "single.repair")
    assert retry["args"]["fell_through"] >= 2
    assert repair["args"]["queries"] == retry["args"]["fell_through"]


# -- (c) the counts -----------------------------------------------------------

def test_flagged_is_retried_is_cleared_plus_fell_through(warmed, corpus,
                                                         tracer):
    """A batch that holds queries of both kinds."""
    q = queries_at(corpus, CLUSTER, 3)
    q[2:4] = queries_at(corpus, PLATEAU, 4)[:2]
    before = dict(warmed.bucket_stats()["repairs"])
    res = solve(warmed, q)
    assert_plain(res, corpus, q)
    (hazard,) = spans(tracer, "single.hazard")
    (final,) = spans(tracer, "single.finalize")
    (retry,) = spans(tracer, "single.retry")
    (repair,) = spans(tracer, "single.repair")
    flagged = hazard["args"]["flagged"]
    r = retry["args"]
    assert flagged >= 4
    assert final["args"]["repairs"] == flagged == r["queries"]
    assert r["cleared"] + r["fell_through"] == r["queries"]
    assert r["cleared"] >= 2 and r["fell_through"] >= 2
    assert repair["args"]["queries"] == r["fell_through"]
    assert warmed.last_repairs == flagged
    after = warmed.bucket_stats()["repairs"]
    assert after["flagged_queries"] - before["flagged_queries"] == flagged
    assert after["device"] - before["device"] == r["cleared"]
    assert after["host"] - before["host"] == r["fell_through"]


def test_a_batch_without_flags_makes_no_retry(warmed, corpus, tracer):
    # beside eight uniform rows: a near neighbour each, then a wide gap
    q = corpus.data_attrs[7000:7000 + NQ] + 3.0
    assert_plain(solve(warmed, q), corpus, q)
    assert spans(tracer, "single.hazard")[0]["args"]["flagged"] == 0
    assert not spans(tracer, "single.retry_begin")
    assert not spans(tracer, "single.retry")
    assert "repairs" not in spans(tracer, "single.finalize")[0]["args"]


def test_the_retrys_wait_is_a_device_wait_of_its_own_site(warmed, corpus,
                                                          tracer):
    obs_trace.wait_tally().take()
    solve(warmed, queries_at(corpus, CLUSTER, 6))
    _seconds, _cpu_seconds, by_site = obs_trace.wait_tally().take()
    assert by_site.get("retry", 0) > 0
    waits = [e for e in spans(tracer, "serve.wait.device")
             if e["args"]["site"] == "retry"]
    (retry,) = spans(tracer, "single.retry")
    assert len(waits) == 1
    assert retry["ts"] <= waits[0]["ts"] and \
        waits[0]["ts"] + waits[0]["dur"] <= retry["ts"] + retry["dur"]
    # the span says which part of it was that wait (a neighbour's fold,
    # under load) and which the host's own work on the wider lists
    args = retry["args"]
    assert args["wait_ms"] > 0 and args["host_ms"] > 0
    assert abs(args["wait_ms"] - waits[0]["dur"] / 1e3) < 1.0
    assert args["wait_ms"] + args["host_ms"] <= retry["dur"] / 1e3 + 0.01


# -- where the retry's programs compile ---------------------------------------

def test_warm_up_compiles_the_retry_under_bf16_staging(warmed, corpus):
    assert set(warmed.warm_report) == {"q128k16", "retry"}
    assert "retry" in warmed.bucket_compile_ms
    count = warmed.compile_count
    solve(warmed, queries_at(corpus, CLUSTER, 7))
    assert warmed.compile_count == count       # nothing compiled to retry


def test_warm_up_compiles_the_retry_under_float32_staging_too(corpus):
    """One rule for both staging dtypes: a warmed bucket that has a
    retry has it compiled with it, flags routine or not."""
    eng = ResidentEngine(corpus, cfg("float32"))
    assert set(eng.warmup([(NQ, K)])) == {"q128k16", "retry"}
    count = eng.compile_count
    q = queries_at(corpus, PLATEAU, 8)          # exact ties flag anywhere
    assert_plain(solve(eng, q), corpus, q)
    assert eng.compile_count == count
    assert eng.bucket_stats()["repairs"]["host"] >= 2


def test_an_engine_never_warmed_compiles_the_retry_at_its_first_flag(corpus):
    """As a bucket no warm-up named compiles at its first request."""
    eng = ResidentEngine(corpus, cfg())
    q = queries_at(corpus, CLUSTER, 12)
    assert_plain(solve(eng, q), corpus, q)
    assert set(eng.bucket_compile_ms) == {"q128k16", "retry"}
    assert eng.compile_count == 2


def test_a_bucket_at_the_widest_window_keeps_the_oracle(warmed):
    assert warmed._retry_kernel(120) is not None
    assert warmed._retry_kernel(512) is None
    assert warmed._retry_kernel(1152) is None
    assert warmed._retry_kernel(120, select="topk") is None


def test_fast_mode_retries_and_answers_from_the_device_list(corpus, tracer):
    """Without the float64 rescore the wider list's own distances are
    the answer: as good as fast mode's, no exact oracle unless the
    wider list is still flagged."""
    eng = ResidentEngine(corpus, cfg(exact=False))
    eng.warmup([(NQ, K)])
    q = queries_at(corpus, CLUSTER, 9)
    res = solve(eng, q)
    (retry,) = [e for e in spans(tracer, "single.retry")
                if e["args"]["queries"] > 1]
    assert retry["args"]["fell_through"] == 0
    want = reference.knn_plain(corpus.data_attrs, corpus.labels, q,
                               [K] * NQ)
    err = max(abs(g.neighbor_dists - w.dists).max()
              for g, w in zip(res, want))
    assert 0 < err < 1e3            # bf16 distances, not float64 ones


def test_a_batch_engine_keeps_the_oracle(corpus, tracer):
    q = queries_at(corpus, CLUSTER, 11)
    inp = KNNInput(Params(N, NQ, NA), corpus.labels, corpus.data_attrs,
                   np.full(NQ, K, np.int32), q)
    res = SingleChipEngine(cfg()).run(inp)
    assert_plain(res, corpus, q)
    assert spans(tracer, "single.repair")
    assert not spans(tracer, "single.retry")


def test_boundary_retry_off_keeps_the_oracle_and_compiles_no_retry(corpus,
                                                                   tracer):
    """``EngineConfig.boundary_retry`` False (``--boundary-retry
    off``): the same exact answers from the host oracle alone, no
    retry program warmed, compiled or priced."""
    import dataclasses
    eng = ResidentEngine(corpus, dataclasses.replace(cfg(),
                                                     boundary_retry=False))
    assert "retry" not in eng.warmup([(NQ, K)])
    q = queries_at(corpus, CLUSTER, 13)
    before = dict(eng.bucket_stats()["repairs"])
    assert_plain(solve(eng, q), corpus, q)
    assert spans(tracer, "single.repair")
    assert not spans(tracer, "single.retry")
    after = eng.bucket_stats()["repairs"]
    assert after["device"] == before["device"]
    assert after["host"] - before["host"] >= 2
    assert "retry" not in eng.bucket_compile_ms
    assert "retry_lists" not in eng.mem_model(NQ, K)["terms"]


def test_the_retry_is_the_default_and_the_cells_configuration_names_it():
    """The default, stated in the configuration's file like every
    other option of the engine, so that a program without the option
    refuses the configuration (``EngineConfig(**engine)``: a TypeError
    before anything is staged) where it would serve it with a host
    scan of the whole corpus for every flagged batch."""
    from benchmark import spec
    assert EngineConfig().boundary_retry is True
    engine = spec.Cell("bigann-10m.bulk").config["engine"]
    assert engine["boundary_retry"] is True
    assert EngineConfig(**engine).boundary_retry is True


# -- the bucket's price ---------------------------------------------------------

def test_admission_prices_the_retrys_lists_with_the_bucket(warmed):
    terms = warmed.mem_model(NQ, K)["terms"]
    # one padded query block and one pair of 512-slot lists of a group
    assert terms["retry_lists"] == 16 * NA * 2 + 2 * 16 * 512 * 12
    assert warmed.batch_model_bytes(NQ, K) == terms["query_blocks"] \
        + terms["topk_carries"] + terms["retry_lists"]
    assert "retry_lists" not in warmed.mem_model(0, 0)["terms"]
    # a bucket that plans the widest window (k = 300: 576 slots) has none
    assert "retry_lists" not in warmed.mem_model(NQ, 300)["terms"]
