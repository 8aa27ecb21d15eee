"""Serving-fleet tests: mesh-resident engine parity, router failure
paths, open-loop pacing, scrape aggregation, trace validation, and the
fleet ledger family.

The byte-identity oracle everywhere is the float64 golden model — the
fleet layers (sharded residency, routing, retry, coalescing) must be
invisible in the response bytes.
"""

import json
import socket
import threading
import time

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.fleet import loadgen
from dmlp_tpu.fleet import scrape as fscrape
from dmlp_tpu.fleet.mesh_engine import MeshResidentEngine
from dmlp_tpu.fleet.router import FleetRouter
from dmlp_tpu.golden.fast import knn_golden_fast
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import telemetry
from dmlp_tpu.serve import client as sc
from dmlp_tpu.serve.daemon import ServeDaemon
from dmlp_tpu.serve.engine import ResidentEngine


def make_corpus(n=600, na=5, labels=4, seed=3, spread=50.0) -> KNNInput:
    rng = np.random.default_rng(seed)
    return KNNInput(
        Params(n, 0, na),
        rng.integers(0, labels, n).astype(np.int32),
        rng.uniform(0, spread, (n, na)),
        np.zeros(0, np.int32), np.zeros((0, na)))


def solo_and_golden(corpus: KNNInput, q, ks, config=None):
    inp = KNNInput(Params(corpus.params.num_data, len(ks),
                          corpus.params.num_attrs),
                   corpus.labels, corpus.data_attrs,
                   np.asarray(ks, np.int32), np.asarray(q, np.float64))
    solo = SingleChipEngine(config or EngineConfig())
    return ([r.checksum() for r in solo.run(inp)],
            [r.checksum() for r in knn_golden_fast(inp)], solo)


def batch(corpus, nq, seed, kmax=12):
    rng = np.random.default_rng(seed)
    q = rng.uniform(0, 50, (nq, corpus.params.num_attrs))
    ks = rng.integers(1, kmax, nq).astype(np.int32)
    return q, ks


# -- mesh-resident engine ------------------------------------------------------

def test_mesh_resident_stream_path_parity_and_compile_once():
    corpus = make_corpus()
    eng = MeshResidentEngine(corpus, EngineConfig(mode="sharded"),
                             mesh_shape=(2, 1))
    eng.warmup([(4, 12), (1, 4)])
    cc = eng.compile_count
    for seed in (11, 12):
        q, ks = batch(corpus, 4, seed)
        got = [r.checksum() for r in eng.solve_batch(q, ks)]
        solo, golden, _ = solo_and_golden(corpus, q, ks)
        assert got == solo == golden
    assert eng.compile_count == cc
    assert eng.bucket_stats()["paths"]["q8k16"] == "stream"


def test_mesh_resident_extract_path_parity_vs_solo_and_golden():
    corpus = make_corpus()
    cfg = EngineConfig(mode="sharded", select="extract",
                       use_pallas=True, data_block=256)
    eng = MeshResidentEngine(corpus, cfg, mesh_shape=(2, 1))
    eng.warmup([(4, 12)])
    cc = eng.compile_count
    q, ks = batch(corpus, 4, 21)
    got = [r.checksum() for r in eng.solve_batch(q, ks)]
    solo, golden, _ = solo_and_golden(
        corpus, q, ks, EngineConfig(select="extract", use_pallas=True,
                                    data_block=256))
    assert got == solo == golden
    assert eng.compile_count == cc
    assert "extract" in eng.bucket_stats()["paths"].values()


def test_mesh_resident_ring_merge_parity():
    corpus = make_corpus()
    eng = MeshResidentEngine(corpus, EngineConfig(mode="sharded"),
                             mesh_shape=(2, 1), merge="ring")
    eng.warmup([(3, 12)])
    q, ks = batch(corpus, 3, 31)
    got = [r.checksum() for r in eng.solve_batch(q, ks)]
    _, golden, _ = solo_and_golden(corpus, q, ks)
    assert got == golden
    assert eng.bucket_stats()["merge"] == "ring"


def test_mesh_resident_ingest_routes_rows_with_zero_recompilation():
    corpus = make_corpus()
    cfg = EngineConfig(mode="sharded", select="extract",
                       use_pallas=True, data_block=256)
    eng = MeshResidentEngine(corpus, cfg, mesh_shape=(2, 1))
    eng.warmup([(4, 12)])
    cc = eng.compile_count
    rebuilds0 = eng.summary_rebuilds
    rng = np.random.default_rng(9)
    m = 7
    newl = rng.integers(0, 4, m).astype(np.int32)
    newa = rng.uniform(0, 50, (m, eng.num_attrs))
    assert eng.ingest(newl, newa) == corpus.params.num_data + m
    grown = KNNInput(
        Params(corpus.params.num_data + m, 0, corpus.params.num_attrs),
        np.concatenate([corpus.labels, newl]),
        np.vstack([corpus.data_attrs, newa]),
        np.zeros(0, np.int32), np.zeros((0, corpus.params.num_attrs)))
    q, ks = batch(corpus, 4, 41)
    got = [r.checksum() for r in eng.solve_batch(q, ks)]
    _, golden, _ = solo_and_golden(grown, q, ks)
    assert got == golden
    assert eng.compile_count == cc        # zero solve recompilation
    if eng._summ is not None:             # summaries rebuilt in place
        assert eng.summary_rebuilds > rebuilds0


def test_mesh_resident_prune_skips_chunks_and_stays_golden(monkeypatch):
    # Norm-banded corpus over multiple per-shard chunks: far bands
    # must prune (live mask drops them) with the result still golden.
    monkeypatch.setenv("DMLP_TPU_PRUNE", "1")
    rng = np.random.default_rng(5)
    # Big enough that each 2-mesh shard spans multiple extract chunks
    # (the extract chunk granule is pallas_extract.BLOCK_ROWS = 12800
    # rows, so per-(shard, chunk) blocks need > 2 * 12800 rows total).
    n, na = 26000, 4
    base = rng.uniform(0.0, 1.0, (n, na))
    scale = np.repeat([1.0, 40.0, 400.0, 4000.0], n // 4)
    attrs = base + scale[:, None]
    corpus = KNNInput(Params(n, 0, na),
                      rng.integers(0, 4, n).astype(np.int32), attrs,
                      np.zeros(0, np.int32), np.zeros((0, na)))
    cfg = EngineConfig(mode="sharded", select="extract",
                       use_pallas=True, data_block=12800)
    eng = MeshResidentEngine(corpus, cfg, mesh_shape=(2, 1))
    assert eng._nchunks > 1               # pruning needs real blocks
    eng.warmup([(2, 6)])
    q = attrs[:2] + 0.01                  # near band 0: far bands prune
    ks = np.asarray([3, 6], np.int32)
    got = [r.checksum() for r in eng.solve_batch(q, ks)]
    inp = KNNInput(Params(n, 2, na), corpus.labels, attrs, ks,
                   np.asarray(q, np.float64))
    golden = [r.checksum() for r in knn_golden_fast(inp)]
    assert got == golden
    assert eng.last_prune is not None
    assert eng.last_prune["blocks_pruned"] > 0
    assert eng.last_prune["scanned_bytes"] \
        < eng.last_prune["dense_bytes"]


def test_mesh_resident_memory_models_positive():
    corpus = make_corpus()
    eng = MeshResidentEngine(corpus, EngineConfig(mode="sharded"),
                             mesh_shape=(2, 1))
    floor = eng.resident_model_bytes()
    marginal = eng.batch_model_bytes(8, 8)
    assert floor > 0 and marginal > 0
    model = eng.mem_model(8, 8)
    assert model["per_device"] is True
    assert model["total_bytes"] >= floor


def test_mesh_resident_lazy_monolithic_invalidates_admission_floor():
    # An extract-capable config stages the monolithic layout LAZILY
    # (first stream-path bucket); admission's cached per-device floor
    # must grow with it — a stale floor would over-admit by a full
    # corpus copy per device.
    from dmlp_tpu.serve.admission import AdmissionController
    corpus = make_corpus()
    cfg = EngineConfig(mode="sharded", select="extract",
                       use_pallas=True, data_block=256)
    eng = MeshResidentEngine(corpus, cfg, mesh_shape=(2, 1))
    assert eng._mono is None
    adm = AdmissionController(eng)
    floor_before = adm._resident_model_bytes()
    eng._ensure_monolithic()
    floor_after = adm._resident_model_bytes()
    assert floor_after > floor_before
    assert floor_after - floor_before \
        >= eng._shard_rows * eng.num_attrs * 4


# -- wide-k multipass serving --------------------------------------------------

def test_resident_wide_k_routes_through_multipass_and_stays_golden():
    corpus = make_corpus(n=1408, na=4, seed=7, spread=60.0)
    cfg = EngineConfig(select="extract", use_pallas=True,
                       data_block=512)
    eng = ResidentEngine(corpus, cfg)
    eng.warmup([(2, 600)])
    cc = eng.compile_count
    assert eng.bucket_stats()["paths"]["q128k1024"] == "multipass"
    rng = np.random.default_rng(17)
    q = rng.uniform(0, 60, (2, 4))
    ks = np.asarray([520, 600], np.int32)
    got = [r.checksum() for r in eng.solve_batch(q, ks)]
    solo, golden, solo_eng = solo_and_golden(corpus, q, ks, cfg)
    assert got == solo == golden
    assert eng.last_mp_passes > 1         # the multipass driver ran
    assert solo_eng.last_mp_passes > 1    # ...and is the solo path too
    assert eng.compile_count == cc        # no per-request compiles
    # Passes 2+ sweep the resident stack itself (a reshape inside the
    # program): no further corpus copy on the device, so the memwatch
    # serve model carries no term for one and admission's floor is the
    # one it priced when the chunks staged.
    import jax
    from dmlp_tpu.obs import memwatch
    from dmlp_tpu.serve.admission import AdmissionController
    full = (eng._ex_nchunks * eng._ex_chunk_rows, 4)
    assert eng._chunks.shape == (eng._ex_nchunks, eng._ex_chunk_rows, 4)
    assert [a for a in jax.live_arrays()
            if a.shape == full and a is not eng._d_attrs] == []
    assert eng.resident_state_key() == (True,)
    adm = AdmissionController(eng)
    total = adm._resident_model_bytes()
    model = memwatch.model_for_engine(
        eng, KNNInput(Params(eng.n_real, 2, 4),
                      eng._host_labels[:eng.n_real],
                      eng._host_attrs[:eng.n_real], ks,
                      np.asarray(q, np.float64)))
    assert "multipass_resident" not in model["terms"]
    assert total >= model["terms"]["extract_chunks"] \
        == eng._chunks.nbytes


def test_resident_wide_k_survives_ingest_invalidation():
    corpus = make_corpus(n=1408, na=4, seed=7, spread=60.0)
    cfg = EngineConfig(select="extract", use_pallas=True,
                       data_block=512)
    eng = ResidentEngine(corpus, cfg)
    eng.warmup([(2, 600)])
    cc = eng.compile_count
    rng = np.random.default_rng(23)
    newl = rng.integers(0, 4, 5).astype(np.int32)
    newa = rng.uniform(0, 60, (5, 4))
    eng.ingest(newl, newa)
    grown = KNNInput(
        Params(1408 + 5, 0, 4), np.concatenate([corpus.labels, newl]),
        np.vstack([corpus.data_attrs, newa]),
        np.zeros(0, np.int32), np.zeros((0, 4)))
    q = rng.uniform(0, 60, (2, 4))
    ks = np.asarray([520, 513], np.int32)
    got = [r.checksum() for r in eng.solve_batch(q, ks)]
    _, golden, _ = solo_and_golden(grown, q, ks)
    assert got == golden
    assert eng.compile_count == cc


# -- router --------------------------------------------------------------------

def _start_daemon(corpus, **kw):
    kw.setdefault("tick_s", 0.001)
    d = ServeDaemon(corpus, kw.pop("config", EngineConfig()), port=0,
                    **kw)
    d.start()
    return d


def _query_via(port, q, k, req_id=""):
    cli = sc.ServeClient(port)
    try:
        return cli.query(q, k=k, req_id=req_id)
    finally:
        cli.close()


def test_router_byte_identity_and_fanout_across_replicas():
    corpus = make_corpus()
    d1 = _start_daemon(corpus, warm_buckets=[(4, 8)])
    d2 = _start_daemon(corpus, warm_buckets=[(4, 8)])
    router = FleetRouter([("127.0.0.1", d1.port),
                          ("127.0.0.1", d2.port)], port=0)
    router.start()
    try:
        q, ks = batch(corpus, 4, 51, kmax=8)
        _, golden, _ = solo_and_golden(corpus, q, ks)
        for i in range(6):
            cli = sc.ServeClient(router.port)
            r = cli.query(q, ks=[int(v) for v in ks], req_id=str(i))
            cli.close()
            assert r["ok"], r
            assert r["checksums"] == golden
        st = router.stats()
        assert all(rep["requests"] > 0 for rep in st["replicas"]), st
        # Health probes are not client traffic: the per-replica counts
        # must sum to exactly the queries routed.
        assert sum(rep["requests"] for rep in st["replicas"]) == 6, st
    finally:
        router.close()
        d1.close()
        d2.close()


class _CrashingReplica:
    """Answers stats probes like a healthy daemon, then CLOSES the
    connection mid-request on any query — the crash-mid-request
    fixture (the router must classify, mark it down, and retry the
    query on a healthy replica)."""

    def __init__(self):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self.queries_seen = 0
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            with conn:
                try:
                    line = conn.makefile("rb").readline()
                    doc = json.loads(line)
                    if doc.get("op") == "stats":
                        conn.sendall(json.dumps(
                            {"ok": True, "stats": {"admission":
                             {"draining": False}}}).encode() + b"\n")
                    elif doc.get("op") == "drain":
                        conn.sendall(b'{"ok": true, "draining": true}\n')
                    else:
                        self.queries_seen += 1
                        # crash mid-request: close without responding
                except (OSError, ValueError):
                    pass

    def close(self):
        self.sock.close()


def test_router_replica_crash_mid_request_bounded_retry():
    corpus = make_corpus()
    d1 = _start_daemon(corpus, warm_buckets=[(2, 8)])
    crasher = _CrashingReplica()
    router = FleetRouter([("127.0.0.1", crasher.port),
                          ("127.0.0.1", d1.port)], port=0,
                         health_interval_s=600)  # probes only at start
    router.start()
    try:
        q, ks = batch(corpus, 2, 61, kmax=8)
        _, golden, _ = solo_and_golden(corpus, q, ks)
        responses = []
        cli = sc.ServeClient(router.port)
        for i in range(6):
            responses.append(
                cli.query(q, ks=[int(v) for v in ks], req_id=str(i)))
        cli.close()
        # Exactly one response per request, every one of them correct
        # (the crash is invisible to the client).
        assert len(responses) == 6
        assert all(r["ok"] for r in responses), responses
        assert all(r["checksums"] == golden for r in responses)
        assert crasher.queries_seen >= 1   # the crasher WAS tried
        # The retried request SAYS it was retried: the envelope
        # surfaces the replica-attempt count, and only retried
        # responses carry it (single-hop relays stay byte-verbatim).
        assert any(r.get("hops", 0) >= 2 for r in responses), responses
        assert all(r["hops"] >= 2 for r in responses if "hops" in r)
        st = router.stats()
        crashed = next(rep for rep in st["replicas"]
                       if rep["replica"].endswith(str(crasher.port)))
        assert not crashed["healthy"]
        assert sum(st["retries"].values()) >= 1
    finally:
        router.close()
        d1.close()
        crasher.close()


def test_router_drain_racing_query_wave():
    corpus = make_corpus()
    d1 = _start_daemon(corpus, warm_buckets=[(2, 8)])
    d2 = _start_daemon(corpus, warm_buckets=[(2, 8)])
    router = FleetRouter([("127.0.0.1", d1.port),
                          ("127.0.0.1", d2.port)], port=0,
                         health_interval_s=0.05)
    router.start()
    try:
        q, ks = batch(corpus, 2, 71, kmax=8)
        _, golden, _ = solo_and_golden(corpus, q, ks)
        out = [None] * 12

        def worker(i):
            cli = sc.ServeClient(router.port)
            try:
                out[i] = cli.query(q, ks=[int(v) for v in ks],
                                   req_id=str(i))
            finally:
                cli.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(12)]
        for t in threads[:4]:
            t.start()
        # Drain replica 1 IN THE MIDDLE of the wave (direct, not via
        # the router — replica-local shutdown).
        cli = sc.ServeClient(d1.port)
        cli.drain()
        cli.close()
        for t in threads[4:]:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # Every request got exactly one response; each is either the
        # correct answer (served or retried onto d2) — no silent drops.
        assert all(r is not None for r in out)
        assert all(r["ok"] for r in out), [r for r in out
                                           if not r["ok"]][:2]
        assert all(r["checksums"] == golden for r in out)
    finally:
        router.close()
        d1.close()
        d2.close()


def test_router_propagates_admission_shed_unretried():
    corpus = make_corpus()
    d1 = _start_daemon(corpus, warm_buckets=[(2, 4)], max_k=4)
    d2 = _start_daemon(corpus, warm_buckets=[(2, 4)], max_k=4)
    router = FleetRouter([("127.0.0.1", d1.port),
                          ("127.0.0.1", d2.port)], port=0)
    router.start()
    try:
        q, _ = batch(corpus, 2, 81, kmax=4)
        r = _query_via(router.port, q, k=9)
        assert not r["ok"]
        assert "rejected" in r["error"] and "k_too_large" in r["error"]
        st = router.stats()
        # An admission shed is explicit backpressure: propagated, not
        # retried onto the other replica.
        assert sum(st["retries"].values()) == 0, st["retries"]
        assert st["rejected"].get("admission", 0) >= 1
        ok = _query_via(router.port, q, k=3)
        assert ok["ok"]
    finally:
        router.close()
        d1.close()
        d2.close()


def test_router_ingest_fans_out_to_every_replica():
    corpus = make_corpus()
    d1 = _start_daemon(corpus, warm_buckets=[(2, 8)])
    d2 = _start_daemon(corpus, warm_buckets=[(2, 8)])
    router = FleetRouter([("127.0.0.1", d1.port),
                          ("127.0.0.1", d2.port)], port=0)
    router.start()
    try:
        rng = np.random.default_rng(13)
        m = 5
        newl = rng.integers(0, 4, m).astype(np.int32)
        newa = rng.uniform(0, 50, (m, corpus.params.num_attrs))
        cli = sc.ServeClient(router.port)
        r = cli.ingest([int(v) for v in newl], newa)
        cli.close()
        assert r["ok"] and r["corpus_rows"] == corpus.params.num_data + m
        for d in (d1, d2):
            assert d.engine.n_real == corpus.params.num_data + m
        grown = KNNInput(
            Params(corpus.params.num_data + m, 0,
                   corpus.params.num_attrs),
            np.concatenate([corpus.labels, newl]),
            np.vstack([corpus.data_attrs, newa]),
            np.zeros(0, np.int32),
            np.zeros((0, corpus.params.num_attrs)))
        q, ks = batch(corpus, 2, 91, kmax=8)
        _, golden, _ = solo_and_golden(grown, q, ks)
        for _ in range(4):   # both replicas see post-ingest queries
            r = _query_via(router.port, q, k=int(ks[0]))
            assert r["ok"]
        cli = sc.ServeClient(router.port)
        r = cli.query(q, ks=[int(v) for v in ks])
        cli.close()
        assert r["checksums"] == golden
    finally:
        router.close()
        d1.close()
        d2.close()


# -- open-loop paced replay ----------------------------------------------------

def test_open_loop_replay_fires_on_schedule_and_measures_queue_delay():
    corpus = make_corpus()
    d = _start_daemon(corpus, warm_buckets=[(2, 8), (1, 8)])
    try:
        header = {"serve_trace_schema": 1, "corpus": {
            "num_data": corpus.params.num_data, "num_attrs":
            corpus.params.num_attrs, "min_attr": 0.0, "max_attr": 50.0,
            "num_labels": 4}}
        reqs = [{"t_ms": i * 40, "nq": 1 + (i % 2), "k": 5,
                 "seed": 500 + i} for i in range(6)]
        t0 = time.monotonic()
        res = sc.replay_open_loop(d.port, header, reqs, speed=1.0)
        span = time.monotonic() - t0
        assert all(r.get("ok") for r in res), res
        assert all("client_ms" in r and "lag_ms" in r for r in res)
        # Open-loop pacing: the replay takes at least the trace span
        # (200 ms at speed 1), and speed=4 compresses it.
        assert span >= 0.2
        golden = sc.golden_reference(corpus, header, reqs)
        assert [r["checksums"] for r in res] == golden
    finally:
        d.close()


def test_loadgen_levels_emit_one_fleet_record_a_level(tmp_path):
    corpus = make_corpus()
    d = _start_daemon(corpus, warm_buckets=[(2, 8), (1, 8)])
    try:
        header = {"serve_trace_schema": 1, "corpus": {
            "num_data": corpus.params.num_data, "num_attrs":
            corpus.params.num_attrs, "min_attr": 0.0, "max_attr": 50.0,
            "num_labels": 4}}
        reqs = [{"t_ms": i * 20, "nq": 1, "k": 5, "seed": 600 + i}
                for i in range(5)]
        recs = loadgen.run_levels(d.port, header, reqs,
                                  speeds=[2.0, 4.0], reps=2,
                                  replicas=1, trace="unit")
        assert len(recs) == 2
        path = tmp_path / "FLEET_r99.jsonl"
        for rec in recs:
            assert rec.metrics["errors"] == 0
            assert rec.metrics["p99_ms"] > 0
            assert len(rec.metrics["p99_ms_reps"]) == 2
            rec.append_jsonl(str(path))
        from dmlp_tpu.obs.run import RunRecord
        back = RunRecord.load_all(str(path))
        assert [(r.kind, r.tool, r.config["level"]) for r in back] == [
            ("fleet", "dmlp_tpu.fleet.loadgen", "x2"),
            ("fleet", "dmlp_tpu.fleet.loadgen", "x4")]
        for r in back:
            assert r.config["mode"] == "open_loop"
            assert r.metrics["p99_ms"] > 0
            assert r.metrics["offered_qps"] > 0
            assert r.device     # the serving process's own stamp
    finally:
        d.close()


# -- scrape aggregation --------------------------------------------------------

def _registry_with(prefix_counts):
    reg = telemetry.Registry()
    for name, count in prefix_counts.items():
        reg.counter(name).inc(count)
    return reg


def test_scrape_merge_sums_counters_and_buckets_valid():
    from dmlp_tpu.obs.telemetry import validate_openmetrics
    r1 = telemetry.Registry()
    r2 = telemetry.Registry()
    for reg, base in ((r1, 3), (r2, 5)):
        reg.counter("serve.requests_completed").inc(base)
        reg.counter("serve.rejected").inc(2, label="memory")
        reg.gauge("serve.corpus_rows").set(100 * base)
        h = reg.histogram("serve.request_latency_ms", unit="ms")
        for v in (base, base * 10, base * 100):
            h.observe(v)
    merged, problems = fscrape.merge_expositions(
        [r1.to_openmetrics(), r2.to_openmetrics()], ["a", "b"])
    assert problems == []
    assert validate_openmetrics(merged) == []
    lines = merged.splitlines()
    total = next(ln for ln in lines
                 if ln.startswith("serve_requests_completed_total "))
    assert float(total.split()[-1]) == 8.0
    lab = next(ln for ln in lines
               if ln.startswith('serve_rejected_total{key="memory"}'))
    assert float(lab.split()[-1]) == 4.0
    count = next(ln for ln in lines
                 if ln.startswith("serve_request_latency_ms_count"))
    assert int(count.split()[-1]) == 6
    # Gauges stay per-replica.
    assert 'serve_corpus_rows{replica="a"} 300' in merged
    assert 'serve_corpus_rows{replica="b"} 500' in merged


def test_scrape_merge_histogram_bucketwise_not_concatenated():
    r1 = telemetry.Registry()
    r2 = telemetry.Registry()
    r1.histogram("x.ms").observe(1.0)
    r2.histogram("x.ms").observe(1.0)
    merged, _ = fscrape.merge_expositions(
        [r1.to_openmetrics(), r2.to_openmetrics()])
    # Same value in both replicas -> ONE bucket line carrying count 2,
    # not two conflicting cumulative lines.
    bucket_lines = [ln for ln in merged.splitlines()
                    if ln.startswith("x_ms_bucket") and "+Inf" not in ln]
    assert len(bucket_lines) == 1, merged
    assert bucket_lines[0].endswith(" 2")


def test_fleet_view_degrades_on_unreachable_replica(tmp_path):
    reg = telemetry.Registry()
    reg.counter("serve.requests_completed").inc(4)
    snap = tmp_path / "a.prom"
    snap.write_text(reg.to_openmetrics())
    merged, problems = fscrape.fleet_view(
        [str(snap), str(tmp_path / "missing.prom")], ["a", "b"])
    assert "serve_requests_completed_total 4" in merged
    assert any("unreachable" in p for p in problems)


# -- trace validation ----------------------------------------------------------

def test_committed_trace2_is_valid_and_bursty():
    header, reqs = sc.load_trace("inputs/serve_trace2.jsonl")
    assert sc.validate_trace(header, reqs) == []
    ts = [r["t_ms"] for r in reqs]
    assert ts == sorted(ts)
    # Bursts: several requests sharing a fire offset.
    from collections import Counter
    assert Counter(ts).most_common(1)[0][1] >= 2
    # Bucket-boundary straddling on both axes.
    nqs = {r["nq"] for r in reqs}
    assert {7, 8, 9} <= nqs and {15, 16, 17} <= nqs


def test_load_trace_rejects_non_monotonic_offsets(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text(
        json.dumps({"serve_trace_schema": 1, "corpus": {
            "num_data": 10, "num_attrs": 2, "min_attr": 0.0,
            "max_attr": 1.0, "num_labels": 2}}) + "\n"
        + '{"t_ms": 5, "nq": 1, "k": 1, "seed": 1}\n'
        + '{"t_ms": 3, "nq": 1, "k": 1, "seed": 2}\n')
    with pytest.raises(ValueError, match="monotonic"):
        sc.load_trace(str(path))


def test_validate_trace_field_checks():
    header = {"serve_trace_schema": 1, "corpus": {
        "num_data": 10, "num_attrs": 2, "min_attr": 0.0,
        "max_attr": 1.0, "num_labels": 2}}
    assert sc.validate_trace(header, [{"nq": 1, "k": 1, "seed": 0}]) \
        == []
    assert sc.validate_trace(header, [{"nq": 1, "seed": 0}])
    assert sc.validate_trace(header, [{"nq": 0, "k": 1, "seed": 0}])
    assert sc.validate_trace(header, [{"nq": 1, "k": True, "seed": 0}])
    assert sc.validate_trace(
        header, [{"nq": 1, "k": 1, "seed": 0, "t_ms": -1}])
    # A non-list "ks" is a reported problem, never a TypeError crash.
    assert sc.validate_trace(header, [{"nq": 1, "ks": 5, "seed": 0}])


# -- daemon integration (mesh replica behind the real daemon) ------------------

def test_daemon_with_mesh_engine_end_to_end():
    corpus = make_corpus()
    d = ServeDaemon(corpus, EngineConfig(), port=0, tick_s=0.001,
                    warm_buckets=[(2, 8)], mesh_shape=(2, 1))
    d.start()
    try:
        assert isinstance(d.engine, MeshResidentEngine)
        q, ks = batch(corpus, 2, 101, kmax=8)
        _, golden, _ = solo_and_golden(corpus, q, ks)
        cli = sc.ServeClient(d.port)
        r = cli.query(q, ks=[int(v) for v in ks])
        stats = cli.stats()["stats"]
        cli.close()
        assert r["ok"] and r["checksums"] == golden
        assert stats["engine"]["mesh"] == [2, 1]
        rec = d.snapshot_record()
        assert rec.config["mode"] == "mesh_resident"
    finally:
        d.close()
