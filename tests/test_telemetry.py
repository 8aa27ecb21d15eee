"""Live-telemetry tests: registry semantics, histogram quantile error
bounds vs numpy.percentile, thread safety under concurrent writers,
sampler start/stop idempotence, OpenMetrics export validity, the
analytic peak-HBM model (hand-computed per engine), watermark
reconciliation markers, the flight recorder's dump triggers (including
an injected fatal fault carrying the last N spans), registry-backed
resilience counters, and the CLI/ledger integration."""

import json
import math
import os
import threading

import numpy as np
import pytest

from dmlp_tpu.obs import memwatch, telemetry
from dmlp_tpu.obs.telemetry import (HIST_QUANTILE_REL_ERROR,
                                    FlightRecorder, Histogram, Registry,
                                    Sampler, validate_openmetrics)


@pytest.fixture(autouse=True)
def _clean_process_state():
    """Every test sees a quiet process registry and no leftover
    session (telemetry state is process-global by design)."""
    s = telemetry.session()
    if s is not None:
        s.close()
    telemetry.REGISTRY.reset()
    yield
    s = telemetry.session()
    if s is not None:
        s.close()
    telemetry.REGISTRY.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_get_or_create_returns_same_object(self):
        r = Registry()
        assert r.counter("a.b") is r.counter("a.b")

    def test_kind_conflict_raises(self):
        r = Registry()
        r.counter("a.b")
        with pytest.raises(TypeError, match="already registered"):
            r.gauge("a.b")

    def test_bad_name_rejected(self):
        r = Registry()
        for bad in ("CamelCase", "has-dash", "1leading", "dotted..twice",
                    "trailing."):
            with pytest.raises(ValueError, match="snake_case"):
                r.counter(bad)

    def test_counter_monotonic_and_labeled(self):
        c = Registry().counter("x.y")
        c.inc()
        c.inc(2, label="site_a")
        c.inc(3, label="site_b")
        assert c.total() == 6
        assert c.by_label() == {"site_a": 2, "site_b": 3}
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_last_write_wins(self):
        g = Registry().gauge("g.v")
        g.set(1)
        g.set(7.5)
        assert g.value() == 7.5

    def test_reset_prefix_scoped(self):
        r = Registry()
        r.counter("resilience.retries").inc()
        r.counter("engine.solves").inc()
        r.reset(prefix="resilience")
        assert r.get("resilience.retries") is None
        assert r.get("engine.solves").total() == 1

    def test_snapshot_shape(self):
        r = Registry()
        r.counter("c.n").inc(3)
        r.gauge("g.n").set(2)
        h = r.histogram("h.n", unit="ms")
        h.observe(5.0)
        snap = r.snapshot()
        assert snap["c.n"] == {"kind": "counter", "total": 3}
        assert snap["g.n"] == {"kind": "gauge", "value": 2.0}
        assert snap["h.n"]["count"] == 1 and snap["h.n"]["kind"] == \
            "histogram"


# ---------------------------------------------------------------------------
# histogram quantile error bound
# ---------------------------------------------------------------------------


class TestHistogramQuantiles:
    @pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
    def test_quantiles_within_documented_bound(self, dist):
        rng = np.random.RandomState(42)
        if dist == "lognormal":
            vals = rng.lognormal(3.0, 1.0, 20000)
        elif dist == "uniform":
            vals = rng.uniform(0.5, 500.0, 20000)
        else:
            # 60/40 split so no tested quantile lands in the empty
            # inter-mode gap (where ANY estimator is ambiguous: there
            # are no samples to be close to).
            vals = np.concatenate([rng.normal(10, 1, 12000),
                                   rng.normal(300, 30, 8000)])
            vals = np.clip(vals, 0.01, None)
        h = Histogram("t.ms")
        for v in vals:
            h.observe(float(v))
        # The estimate is the geometric bucket midpoint: its error vs
        # the true histogram quantile is <= HIST_QUANTILE_REL_ERROR;
        # vs numpy.percentile an extra half-bucket of rank discreteness
        # can stack, hence the 2x envelope (documented bound x2 is
        # still < 12% relative).
        tol = 2 * HIST_QUANTILE_REL_ERROR
        for q in (0.50, 0.95, 0.99):
            ref = float(np.percentile(vals, q * 100))
            est = h.quantile(q)
            assert abs(est - ref) / ref <= tol, (dist, q, est, ref)

    def test_min_max_exact_and_clamping(self):
        h = Histogram("t.ms")
        for v in (0.0001, 5.0, 123456.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["min"] == 0.0001 and snap["max"] == 123456.0
        assert h.quantile(0.0) >= snap["min"]
        assert h.quantile(1.0) <= snap["max"]

    def test_empty_and_nan_samples(self):
        h = Histogram("t.ms")
        assert math.isnan(h.quantile(0.5))
        h.observe(float("nan"))    # must not poison
        assert h.count == 0
        h.observe(2.0)
        assert h.count == 1

    def test_bucket_index_edges_consistent(self):
        # Exactly-on-boundary values must land in the bucket whose
        # upper bound they equal (le semantics), never one off.
        h = Histogram("t.ms")
        from dmlp_tpu.obs.telemetry import _BOUNDS
        for b in _BOUNDS[:50]:
            i = h.bucket_index(b)
            assert b <= _BOUNDS[i]
            assert i == 0 or b > _BOUNDS[i - 1]


# ---------------------------------------------------------------------------
# thread safety
# ---------------------------------------------------------------------------


class TestThreadSafety:
    def test_concurrent_writers_lose_nothing(self):
        r = Registry()
        n_threads, n_iters = 8, 2000

        def work(tid):
            c = r.counter("t.hits")
            h = r.histogram("t.ms")
            g = r.gauge("t.last")
            for i in range(n_iters):
                c.inc(label=f"w{tid}")
                h.observe(1.0 + (i % 100))
                g.set(i)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert r.counter("t.hits").total() == n_threads * n_iters
        assert r.histogram("t.ms").count == n_threads * n_iters
        # concurrent registration of ONE name returns one object
        assert len(r.counter("t.hits").by_label()) == n_threads

    def test_concurrent_get_or_create_one_instance(self):
        r = Registry()
        out = []

        def reg():
            out.append(r.counter("race.c"))

        threads = [threading.Thread(target=reg) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(o is out[0] for o in out)


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------


class TestSampler:
    def test_start_stop_idempotent(self):
        s = Sampler(interval_s=0.01)
        s.start()
        first = s._thread
        s.start()                       # second start: no new thread
        assert s._thread is first
        s.stop()
        s.stop()                        # second stop: no-op
        assert not s.running

    def test_sample_now_sets_mem_gauges(self):
        import jax
        keep = jax.numpy.zeros(8)     # a LIVE array while we sample
        keep.block_until_ready()
        s = Sampler(interval_s=60)
        s.sample_now()
        del keep
        # CPU backend: memory_stats is None -> honest marker gauge;
        # live arrays still measured.
        assert telemetry.REGISTRY.gauge(
            "mem.stats_unavailable").value() == 1
        assert telemetry.REGISTRY.gauge(
            "mem.live_array_bytes").value() > 0
        assert s.measured_peak()["basis"] == "live_arrays"

    def test_heartbeat_age_gauge(self, tmp_path, monkeypatch):
        hb = tmp_path / "beat"
        hb.write_text("x")
        monkeypatch.setenv("DMLP_TPU_HEARTBEAT", str(hb))
        s = Sampler(interval_s=60)
        s.sample_now()
        age = telemetry.REGISTRY.gauge("heartbeat.age_s").value()
        assert age is not None and 0 <= age < 60


# ---------------------------------------------------------------------------
# OpenMetrics export
# ---------------------------------------------------------------------------


class TestOpenMetrics:
    def test_export_validates_and_round_trips(self):
        r = Registry()
        r.counter("engine.solves").inc(3)
        r.counter("engine.retries").inc(2, label="stage_put")
        r.gauge("mem.stats_unavailable").set(1)
        h = r.histogram("span.latency_ms", unit="ms")
        for v in (1.0, 5.0, 250.0):
            h.observe(v)
        text = r.to_openmetrics()
        assert validate_openmetrics(text) == []
        assert text.endswith("# EOF\n")
        assert "engine_solves_total 3" in text
        assert 'engine_retries_total{key="stage_put"} 2' in text
        assert "span_latency_ms_count 3" in text
        assert 'span_latency_ms_bucket{le="+Inf"} 3' in text

    def test_validator_catches_breakage(self):
        assert validate_openmetrics("garbage\n") != []
        assert any("EOF" in p for p in validate_openmetrics("x 1\n"))
        # undeclared sample name
        bad = "# TYPE a counter\nb_total 1\n# EOF"
        assert any("no preceding" in p for p in validate_openmetrics(bad))
        nonnum = "# TYPE a gauge\na wat\n# EOF"
        assert any("non-numeric" in p for p in validate_openmetrics(nonnum))

    def test_validator_accepts_tiny_values_the_emitter_writes(self):
        # repr(5e-05) is '5e-05': negative-exponent scientific notation
        # must validate — a sub-100ns span once failed the whole smoke.
        r = Registry()
        r.gauge("tiny.v").set(5e-05)
        h = r.histogram("tiny.ms")
        h.observe(5e-05)
        assert validate_openmetrics(r.to_openmetrics()) == []

    def test_exemplars_render_and_validate(self):
        # The last exemplar-carrying observation per bucket is exposed
        # as a '# EXEMPLAR' comment line after its bucket sample —
        # tolerated by the validator, linking a tail bucket back to
        # one rid in the merged fleet trace.
        r = Registry()
        h = r.histogram("fleet.request_latency_ms", unit="ms")
        h.observe(2.0, exemplar="x2-0")
        h.observe(2.1, exemplar="x2-5")   # same bucket: last wins
        h.observe(400.0, exemplar="x8-3")
        h.observe(7.0)                    # no exemplar: no comment
        text = r.to_openmetrics()
        assert validate_openmetrics(text) == []
        lines = text.splitlines()
        ex = [ln for ln in lines if ln.startswith("# EXEMPLAR ")]
        assert len(ex) == 2, text
        assert any("x2-5" in ln for ln in ex)
        assert all("x2-0" not in ln for ln in ex)
        assert any("x8-3" in ln for ln in ex)
        # each exemplar comment follows its bucket sample line
        for ln in ex:
            bucket = ln.split(" ", 2)[2].rsplit(" ", 2)[0]
            i = lines.index(ln)
            assert lines[i - 1].startswith(bucket + " "), (bucket, ln)

    def test_exemplar_free_exposition_is_byte_stable(self):
        # observe() without the kwarg must render exactly as before —
        # the exemplar seam is opt-in per observation.
        r1, r2 = Registry(), Registry()
        for reg in (r1, r2):
            h = reg.histogram("span.latency_ms", unit="ms")
            for v in (1.0, 5.0, 250.0):
                h.observe(v)
        assert r1.to_openmetrics() == r2.to_openmetrics()
        assert "# EXEMPLAR" not in r1.to_openmetrics()

    def test_http_endpoint_serves_metrics(self):
        import urllib.request
        telemetry.REGISTRY.counter("http.hits").inc(5)
        s = telemetry.start(port=0, handle_signals=False)
        try:
            url = f"http://127.0.0.1:{s.http_port}/metrics"
            body = urllib.request.urlopen(url, timeout=10).read().decode()
            assert validate_openmetrics(body) == []
            assert "http_hits_total 5" in body
        finally:
            s.close()


# ---------------------------------------------------------------------------
# analytic peak-HBM model — hand-computed per engine
# ---------------------------------------------------------------------------


class TestMemwatchModel:
    def test_single_chunked_topk_hand_computed(self):
        # n=20000 a=32 q=1000 kmax=16, default config on CPU: select
        # resolves "topk" (padded 20000 > AUTO_SELECT_THRESHOLD, no
        # pallas). plan_chunks(20000, 8, None): one 20000-row chunk.
        # kcap = 16 + max(margin 16, 8-slack, k/8=2) -> 32.
        #   staged_corpus = 1 chunk * 20000 * 32 * 4      = 2_560_000
        #   labels_ids    = 20000 * 8                     =   160_000
        #   query_blocks  = 1000 * 32 * 4                 =   128_000
        #   topk_carries  = 2 * 1000 * 32 * 12            =   768_000
        m = memwatch.single_engine_model(20000, 1000, 32, 16)
        assert m["select"] == "topk" and m["kcap"] == 32
        assert m["terms"]["staged_corpus"] == 2_560_000
        assert m["terms"]["labels_ids"] == 160_000
        assert m["terms"]["query_blocks"] == 128_000
        assert m["terms"]["topk_carries"] == 768_000
        assert m["total_bytes"] == 3_616_000

    def test_single_sort_path_hand_computed(self):
        # Small dataset -> "sort": whole-dataset staging. n=1000 a=16
        # q=100 k=4: data_block = fit_blocks(1000, 2048, 8) = 1000
        # (single block), npad=1000; kcap = 4 + margin 16 -> 24
        # (round_up(20,8)=24... resolve: kmax+extra=4+16=20 -> 24).
        # qpad = round_up(100, min(1024, 104)) with qb=min(1024,104)=104
        # -> qpad=104.
        from dmlp_tpu.config import EngineConfig
        m = memwatch.single_engine_model(1000, 100, 16, 4,
                                         config=EngineConfig())
        assert m["select"] == "sort"
        assert m["terms"]["staged_corpus"] == 1000 * 16 * 4
        assert m["terms"]["labels_ids"] == 1000 * 8
        assert m["terms"]["query_blocks"] == m["qpad"] * 16 * 4
        assert m["total_bytes"] == sum(m["terms"].values())

    def test_single_extract_path_structure(self):
        # use_pallas -> extract select; kcap <= 512 single-pass:
        # carries are double-buffered od/oi (8 B/slot).
        from dmlp_tpu.config import EngineConfig
        m = memwatch.single_engine_model(
            200_000, 10_000, 64, 32,
            config=EngineConfig(use_pallas=True))
        assert m["select"] == "extract" and not m["multipass"]
        qpad = m["qpad"]
        assert m["terms"]["topk_carries"] == 2 * qpad * m["kcap"] * 8
        assert m["terms"]["labels_ids"] == 200_000 * 4
        assert m["total_bytes"] == sum(m["terms"].values())

    def test_mesh_model_allgather_vs_ring_merge_asymmetry(self):
        # Same shape, same mesh: the all-gather merge buffer scales
        # with the data-axis size, the ring's accumulator does not —
        # the ring engine's reason to exist, as a modeled number.
        kw = dict(n=100_000, nq=5_000, na=64, kmax=32,
                  mesh_shape=(4, 2))
        ms = memwatch.mesh_engine_model(mode="sharded", **kw)
        mr = memwatch.mesh_engine_model(mode="ring", **kw)
        assert ms["per_device"] and mr["per_device"]
        assert ms["terms"]["merge_buffer"] == \
            4 * ms["q_local"] * ms["kcap"] * 12
        assert mr["terms"]["merge_buffer"] == \
            2 * mr["q_local"] * mr["kcap"] * 12
        assert ms["total_bytes"] > mr["total_bytes"]

    def test_train_model_hand_computed(self):
        # dims (64, 256, 10), batch 512, adam, mesh (1, 1):
        # params = 64*256+256 + 256*10+10 = 16640+2570 = 19210 -> x4 B
        m = memwatch.train_step_model((64, 256, 10), 512,
                                      optimizer="adam")
        pbytes = 19210 * 4
        assert m["terms"]["params"] == pbytes
        assert m["terms"]["grads"] == pbytes
        assert m["terms"]["opt_moments"] == 2 * pbytes
        assert m["terms"]["batch"] == 512 * 65 * 4
        assert m["terms"]["activations"] == 512 * (256 + 10) * 4
        assert m["total_bytes"] == sum(m["terms"].values())

    def test_resident_bytes_model_dispatch(self):
        with pytest.raises(ValueError, match="unknown workload"):
            memwatch.resident_bytes_model("warp-drive")

    def test_reconcile_marker_and_tolerance(self):
        model = {"total_bytes": 1000}
        rec = memwatch.reconcile(model, {"unavailable": "no basis"})
        assert rec["mem_stats_unavailable"] == "no basis"
        ok = memwatch.reconcile(model, {"bytes": 1500,
                                        "basis": "memory_stats"})
        assert ok["within_tolerance"] and ok["ratio"] == 1.5
        off = memwatch.reconcile(model, {"bytes": 10_000,
                                         "basis": "memory_stats"})
        assert not off["within_tolerance"]
        # live_arrays basis has its own (looser) documented bounds
        live = memwatch.reconcile(model, {"bytes": 3500,
                                          "basis": "live_arrays"})
        assert live["within_tolerance"]

    def test_reconcile_scales_per_device_model(self):
        # Measured bases are process-wide sums over devices: a healthy
        # 8-device mesh run must not read as 8x over model.
        model = {"total_bytes": 1000, "per_device": True, "n_devices": 8}
        rec = memwatch.reconcile(model, {"bytes": 8000,
                                         "basis": "live_arrays"})
        assert rec["model_bytes"] == 8000
        assert rec["model_bytes_per_device"] == 1000
        assert rec["n_devices"] == 8
        assert rec["within_tolerance"] and rec["ratio"] == 1.0
        mesh = memwatch.mesh_engine_model(100_000, 5_000, 64, 32,
                                          (4, 2))
        assert mesh["n_devices"] == 8


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        fr = FlightRecorder(capacity=16)
        for i in range(100):
            fr.record("event", "e", i=i)
        evs = fr.events()
        assert len(evs) == 16
        assert evs[-1]["data"]["i"] == 99     # most recent survive

    def test_dump_contains_metrics_and_resilience(self, tmp_path):
        telemetry.REGISTRY.counter("d.hits").inc(2)
        fr = FlightRecorder()
        fr.record("span", "cli.solve", dur_ms=12.5)
        path = fr.dump(str(tmp_path), "unit_test")
        doc = json.load(open(path))
        assert doc["reason"] == "unit_test"
        assert doc["events"][0]["name"] == "cli.solve"
        assert doc["metrics"]["d.hits"]["total"] == 2
        assert "resilience" in doc

    def test_injected_fatal_fault_dumps_last_spans(self, tmp_path):
        """The satellite contract: a fatal-classified fault inside the
        retry layer dumps a flight artifact carrying the last N spans
        recorded before the fault."""
        from dmlp_tpu.obs.trace import span as obs_span
        from dmlp_tpu.resilience import retry as rs_retry

        s = telemetry.start(flight_dir=str(tmp_path),
                            handle_signals=False)
        try:
            for i in range(5):
                with obs_span(f"unit.phase{i}"):
                    pass

            def boom():
                raise RuntimeError("irrecoverable corruption")  # fatal

            with pytest.raises(RuntimeError):
                rs_retry.call_with_retry(boom, "unit.site")
        finally:
            s.close()
        flights = [f for f in os.listdir(tmp_path)
                   if f.startswith("FLIGHT_fatal_fault")]
        assert flights, "fatal fault left no flight artifact"
        doc = json.load(open(tmp_path / flights[0]))
        span_names = [e["name"] for e in doc["events"]
                      if e["kind"] == "span"]
        assert [f"unit.phase{i}" for i in range(5)] == span_names[-6:-1] \
            or all(f"unit.phase{i}" in span_names for i in range(5))
        fault = [e for e in doc["events"] if e["kind"] == "fault"]
        assert fault and fault[-1]["data"]["classification"] == "fatal"

    def test_retries_exhausted_transient_dumps_too(self, tmp_path):
        from dmlp_tpu.resilience import retry as rs_retry
        from dmlp_tpu.resilience.inject import InjectedTransientError

        s = telemetry.start(flight_dir=str(tmp_path),
                            handle_signals=False)
        try:
            def flaky():
                raise InjectedTransientError("injected transient")

            with pytest.raises(InjectedTransientError):
                rs_retry.call_with_retry(flaky, "unit.site",
                                         sleep=lambda _t: None)
        finally:
            s.close()
        assert any(f.startswith("FLIGHT_fatal_fault")
                   for f in os.listdir(tmp_path))

    def test_oom_records_event_but_no_dump(self, tmp_path):
        # oom belongs to the degradation ladder: recovery, not death.
        from dmlp_tpu.resilience import retry as rs_retry
        from dmlp_tpu.resilience.inject import SimulatedResourceExhausted

        s = telemetry.start(flight_dir=str(tmp_path),
                            handle_signals=False)
        try:
            def oom():
                raise SimulatedResourceExhausted("RESOURCE_EXHAUSTED")

            with pytest.raises(SimulatedResourceExhausted):
                rs_retry.call_with_retry(oom, "unit.site")
            kinds = [e["kind"] for e in s.flight.events()]
            assert "fault" in kinds
        finally:
            s.close()
        assert not any(f.startswith("FLIGHT_")
                       for f in os.listdir(tmp_path))


# ---------------------------------------------------------------------------
# session + span bridge + registry-backed resilience counters
# ---------------------------------------------------------------------------


class TestSession:
    def test_span_latencies_without_tracer(self):
        from dmlp_tpu.obs.trace import span as obs_span
        s = telemetry.start(handle_signals=False)
        try:
            with obs_span("unit.work"):
                pass
            h = telemetry.REGISTRY.get("unit.work.ms")
            assert h is not None and h.count == 1
            assert telemetry.REGISTRY.get("span.latency_ms").count == 1
        finally:
            s.close()

    def test_snapshot_file_rewritten_and_valid(self, tmp_path):
        path = str(tmp_path / "t.prom")
        s = telemetry.start(path=path, handle_signals=False)
        telemetry.REGISTRY.counter("unit.c").inc()
        s.close()                      # close writes the final snapshot
        text = open(path).read()
        assert validate_openmetrics(text) == []
        assert "unit_c_total 1" in text

    def test_session_restart_replaces(self):
        a = telemetry.start(handle_signals=False)
        b = telemetry.start(handle_signals=False)
        assert telemetry.session() is b
        assert a._closed
        b.close()
        assert telemetry.session() is None

    def test_resilience_counters_live_in_registry(self):
        from dmlp_tpu.resilience import stats as rs_stats
        rs_stats.reset()
        rs_stats.record_retry("single.stage_put")
        rs_stats.record_retry("single.stage_put")
        rs_stats.record_degradation("fused", "heuristic")
        rs_stats.record_rollback()
        # one source of truth: the registry counters ARE the snapshot
        assert telemetry.REGISTRY.counter(
            "resilience.retries").total() == 2
        snap = rs_stats.snapshot()
        assert snap["retries"] == 2
        assert snap["retry_sites"] == {"single.stage_put": 2}
        assert snap["degradations"] == ["fused->heuristic"]
        assert snap["rollbacks"] == 1
        assert rs_stats.any_activity()
        rs_stats.reset()
        assert not rs_stats.any_activity()
        assert rs_stats.snapshot()["retries"] == 0

    def test_snapshot_record_loads_as_runrecord(self, tmp_path):
        from dmlp_tpu.obs.run import RunRecord
        s = telemetry.start(handle_signals=False)
        try:
            telemetry.REGISTRY.counter("unit.solves").inc(4)
            telemetry.REGISTRY.histogram("unit.ms").observe(5.0)
            rec = s.snapshot_record()
        finally:
            s.close()
        assert rec.kind == "telemetry"
        path = str(tmp_path / "TEL_r99.jsonl")
        rec.append_jsonl(path)
        back = RunRecord.load(path)
        assert back.kind == "telemetry"
        assert back.metrics["unit_solves_total"] == 4
        assert "unit_ms_p50" in back.metrics


# ---------------------------------------------------------------------------
# engine + CLI integration
# ---------------------------------------------------------------------------


def _tiny_input(n=96, q=8, a=4, seed=0):
    from io import StringIO

    from dmlp_tpu.io.datagen import generate_input_text
    from dmlp_tpu.io.grammar import parse_input
    text = generate_input_text(n, q, a, 0.0, 10.0, 1, 4, 3, seed=seed)
    return parse_input(StringIO(text))


class TestEngineIntegration:
    def test_engine_publishes_model_under_session(self):
        from dmlp_tpu.config import EngineConfig
        from dmlp_tpu.engine.single import SingleChipEngine
        inp = _tiny_input()
        eng = SingleChipEngine(EngineConfig())
        s = telemetry.start(handle_signals=False)
        try:
            eng.run(inp)
            assert eng.last_mem_model is not None
            assert eng.last_mem_model["total_bytes"] > 0
            assert telemetry.REGISTRY.gauge(
                "mem.model.resident_bytes").value() == \
                eng.last_mem_model["total_bytes"]
        finally:
            s.close()

    def test_engine_model_absent_without_session(self):
        from dmlp_tpu.config import EngineConfig
        from dmlp_tpu.engine.single import SingleChipEngine
        inp = _tiny_input()
        eng = SingleChipEngine(EngineConfig())
        eng.run(inp)
        assert eng.last_mem_model is None

    def test_results_identical_with_and_without_session(self):
        from dmlp_tpu.config import EngineConfig
        from dmlp_tpu.engine.single import SingleChipEngine
        from dmlp_tpu.io.report import format_results
        inp = _tiny_input(seed=3)
        plain = format_results(SingleChipEngine(EngineConfig()).run(inp))
        s = telemetry.start(handle_signals=False)
        try:
            observed = format_results(
                SingleChipEngine(EngineConfig()).run(inp))
        finally:
            s.close()
        assert plain == observed

    def test_sharded_engine_publishes_per_device_model(self):
        from dmlp_tpu.config import EngineConfig
        from dmlp_tpu.engine.sharded import ShardedEngine
        inp = _tiny_input(n=128, q=16)
        eng = ShardedEngine(EngineConfig(mode="sharded"))
        s = telemetry.start(handle_signals=False)
        try:
            eng.run(inp)
            assert eng.last_mem_model is not None
            assert eng.last_mem_model.get("per_device")
        finally:
            s.close()


class TestCLIIntegration:
    def test_cli_telemetry_flag_end_to_end(self, tmp_path):
        from io import StringIO

        from dmlp_tpu.cli import main as cli_main
        from dmlp_tpu.io.datagen import generate_input_text
        text = generate_input_text(96, 8, 4, 0.0, 10.0, 1, 4, 3, seed=1)
        tel = str(tmp_path / "t.prom")
        met = str(tmp_path / "m.jsonl")
        out_plain, err = StringIO(), StringIO()
        rc = cli_main([], stdin=StringIO(text), stdout=out_plain,
                      stderr=err)
        assert rc == 0
        out_tel, err2 = StringIO(), StringIO()
        rc = cli_main(["--telemetry", tel, "--metrics", met],
                      stdin=StringIO(text), stdout=out_tel, stderr=err2)
        assert rc == 0
        # contract channel byte-identical with telemetry on
        assert out_plain.getvalue() == out_tel.getvalue()
        assert validate_openmetrics(open(tel).read()) == []
        summary = [json.loads(ln) for ln in open(met)
                   if json.loads(ln).get("event") == "summary"][0]
        mem = summary["mem"]
        assert mem["model_bytes"] > 0
        # CPU backend: either the live_arrays basis reconciled, or the
        # explicit marker — never silence.
        assert "mem_stats_unavailable" in mem or "basis" in mem
