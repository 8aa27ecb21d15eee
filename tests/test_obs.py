"""Tests for the unified observability subsystem (dmlp_tpu.obs).

Covers the four modules plus their wiring: the span tracer's Chrome-trace
JSON round-trips with well-formed ph/ts/dur events and nested spans nest;
cost counters resolve real FLOPs/bytes on backends with a cost model and
fall back to the explicit ``counters_unavailable`` marker otherwise;
collective-traffic accounting matches hand-computed byte counts for a
2x2 mesh; RunRecord round-trips with its schema guard; the hardened
MetricsLogger (context manager, monotonic t_ms, clear serialization
errors); the CLI ``--trace``/``--metrics`` path via a real subprocess
(contract channels byte-identical); and the ADVICE r5 multi-pass
full-array tiling guard.
"""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlp_tpu.obs import comms as obs_comms
from dmlp_tpu.obs import counters as obs_counters
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.obs.run import SCHEMA_VERSION, RunRecord
from dmlp_tpu.utils.metrics_log import MetricsLogger


# ---------------------------------------------------------------------------
# obs.trace
# ---------------------------------------------------------------------------

def test_trace_json_roundtrip_well_formed(tmp_path):
    tracer = obs_trace.Tracer()
    with tracer.span("outer", shape=[2, 3]):
        with tracer.span("inner"):
            pass
    tracer.instant("marker", n=1)
    path = str(tmp_path / "t.json")
    tracer.write(path)

    doc = json.loads(open(path).read())
    events = doc["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    assert {e["name"] for e in spans} == {"outer", "inner"}
    for e in spans:
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert "pid" in e and "tid" in e
    assert any(e.get("ph") == "i" and e["name"] == "marker" for e in events)
    # args survive the round trip
    outer = next(e for e in spans if e["name"] == "outer")
    assert outer["args"]["shape"] == [2, 3]


def test_trace_nested_spans_nest():
    """A child span's [ts, ts+dur) interval sits inside its parent's."""
    tracer = obs_trace.Tracer()
    with tracer.span("parent"):
        with tracer.span("child"):
            pass
    evs = {e["name"]: e for e in tracer.to_dict()["traceEvents"]
           if e.get("ph") == "X"}
    p, c = evs["parent"], evs["child"]
    assert p["ts"] <= c["ts"]
    assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-6
    assert p["tid"] == c["tid"]


def test_trace_span_fence_blocks_device_value():
    tracer = obs_trace.Tracer()
    with tracer.span("fenced") as sp:
        out = jax.jit(lambda x: x * 2)(jnp.arange(8))
        sp.fence(out)
    (ev,) = [e for e in tracer.to_dict()["traceEvents"]
             if e.get("ph") == "X"]
    assert ev["name"] == "fenced" and ev["dur"] >= 0


def test_trace_module_hook_noop_when_uninstalled():
    assert obs_trace.active() is None
    sp = obs_trace.span("anything", x=1)
    assert sp is obs_trace.NULL_SPAN
    with sp as s:
        s.set(y=2)
        s.fence(object())
    obs_trace.instant("nothing")  # must not raise


def test_trace_install_uninstall_and_thread_tids():
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        with obs_trace.span("main-thread"):
            pass

        def worker():
            with obs_trace.span("worker-thread"):
                pass

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    finally:
        obs_trace.uninstall()
    evs = {e["name"]: e for e in tracer.to_dict()["traceEvents"]
           if e.get("ph") == "X"}
    assert evs["main-thread"]["tid"] != evs["worker-thread"]["tid"]
    assert obs_trace.active() is None


# ---------------------------------------------------------------------------
# obs.counters
# ---------------------------------------------------------------------------

def test_normalize_cost_shapes():
    assert obs_counters.normalize_cost(None) is None
    assert obs_counters.normalize_cost([]) is None
    assert obs_counters.normalize_cost("nope") is None
    assert obs_counters.normalize_cost({"flops": 0.0}) is None
    got = obs_counters.normalize_cost(
        [{"flops": 4.0, "bytes accessed": 8.0}])
    assert got == {"flops": 4.0, "bytes_accessed": 8.0}


def test_cost_probe_counts_jitted_matmul():
    """On the CPU backend XLA reports real flops; a (64, 32) @ (32, 64)
    matmul must count >= 2*64*32*64 of them, times the dispatch count."""
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.zeros((64, 32), jnp.float32)
    b = jnp.zeros((32, 64), jnp.float32)
    probe = obs_counters.CostProbe()
    probe.record(f, (a, b), count=3, site="test.matmul")
    got = probe.collect()
    if got.get("counters_unavailable"):
        pytest.skip("backend exposes no cost model")
    assert got["flops"] >= 3 * 2 * 64 * 32 * 64
    assert got["bytes_accessed"] > 0
    assert got["dispatches_recorded"] == 3
    assert got["per_site"]["test.matmul"]["dispatches"] == 3


def test_cost_probe_dedupes_identical_signatures():
    f = jax.jit(lambda a: a + 1)
    a = jnp.zeros((8,), jnp.float32)
    probe = obs_counters.CostProbe()
    probe.record(f, (a,), site="s")
    probe.record(f, (a,), site="s")
    assert len(probe._entries) == 1
    assert next(iter(probe._entries.values()))[3] == 2


def test_cost_probe_falls_back_cleanly():
    """Unanalyzable dispatches (a plain Python callable has no .lower)
    yield the explicit counters_unavailable marker, not an exception —
    the CPU/Pallas fallback contract."""
    probe = obs_counters.CostProbe()
    probe.record(lambda x: x, (jnp.zeros((4,)),), count=2, site="opaque")
    got = probe.collect()
    assert got["counters_unavailable"] is True
    assert got["dispatches_recorded"] == 2


def test_counters_module_hook():
    assert obs_counters.active() is None
    obs_counters.record_dispatch(None, ())  # uninstalled: no-op
    probe = obs_counters.install()
    try:
        f = jax.jit(lambda a: a * a)
        obs_counters.record_dispatch(f, (jnp.ones((4,)),), site="hook")
        assert len(probe._entries) == 1
    finally:
        obs_counters.uninstall()
    assert obs_counters.active() is None


def test_roofline_summary_fields():
    rl = obs_counters.roofline(2e9, 1e9, elapsed_s=0.5, n_chips=1)
    assert rl["achieved_flops_per_s"] == pytest.approx(4e9)
    assert rl["arithmetic_intensity"] == pytest.approx(2.0)
    if "peak_flops_per_chip" in rl:
        assert rl["utilization_vs_peak"] > 0


# ---------------------------------------------------------------------------
# obs.comms — hand-computed bytes for a 2x2 mesh
# ---------------------------------------------------------------------------

def test_allgather_traffic_2x2_hand_computed():
    # 2x2 mesh: data axis r=2, query axis c=2. Per cell: q_local=4, k=8.
    # TopK triple = 12 B/candidate -> payload = 4*8*12 = 384 B.
    # all_gather: each cell sends/receives the other (r-1)=1 cell's 384 B.
    # Per-column merge -> n_groups = c = 2.
    t = obs_comms.allgather_topk_traffic(2, 4, 8, n_groups=2)
    assert t.bytes_out_per_device == 384
    assert t.bytes_in_per_device == 384
    # total = out_per_device * r * groups = 384 * 2 * 2
    assert t.bytes_total == 1536
    assert t.axis == "data" and t.axis_size == 2


def test_ring_traffic_matches_allgather_bytes_2x2():
    ag = obs_comms.allgather_topk_traffic(2, 4, 8, n_groups=2)
    ring = obs_comms.ring_topk_traffic(2, 4, 8, n_groups=2)
    # r=2: one ppermute hop of the 384 B accumulator — same wire bytes.
    assert ring.bytes_out_per_device == ag.bytes_out_per_device == 384
    assert ring.bytes_total == ag.bytes_total == 1536


def test_ring_traffic_hops_scale():
    t = obs_comms.ring_topk_traffic(4, 4, 8)  # 3 hops x 384 B
    assert t.bytes_out_per_device == 3 * 384


def test_psum_traffic_ring_bound():
    t = obs_comms.psum_traffic(1000, 4)
    assert t.bytes_out_per_device == 1500  # 2*(4-1)/4 * 1000
    assert obs_comms.psum_traffic(1000, 1).bytes_out_per_device == 0


def test_moe_a2a_traffic_hand_computed():
    # ep=2, capacity=3, hidden=8, f32: send buffer 2*3*8*4 = 192 B,
    # meta 2*3*4 = 24 B; three a2a ops move (2*192 + 24) * 1/2 = 204 B
    # off-device per cell.
    t = obs_comms.moe_a2a_traffic(2, 3, 8)
    assert t.bytes_out_per_device == 204


def test_tp_psum_activation_traffic_hand_computed():
    # tp=4, (rows=8, hidden=16) f32 block = 512 B; ring all-reduce moves
    # 2*(4-1)/4 * 512 = 768 B per psum; 2 pairs x 3 ticks = 6 psums.
    t = obs_comms.tp_psum_activation_traffic(4, 8, 16, n_pairs=2,
                                             ticks=3)
    assert t.bytes_out_per_device == 768 * 6
    assert t.axis == "tp"
    assert obs_comms.tp_psum_activation_traffic(
        1, 8, 16).bytes_out_per_device == 0  # single tp cell: no wire


def test_ep_psum_combine_traffic_hand_computed():
    # ep=2, (tokens=16, hidden=8) f32 partials = 512 B; ring bound
    # 2*(2-1)/2 * 512 = 512 B per device per step.
    t = obs_comms.ep_psum_combine_traffic(2, 16, 8)
    assert t.bytes_out_per_device == 512
    assert t.collective == "psum_ep_combine"


def test_train_step_comms_dense_moe_and_pp3_tp():
    # Dense MoE: the ep combine psum record rides moe_dense.
    out = obs_comms.train_step_comms(
        0, (2, 2), steps=3, moe_dense={"ep": 2, "tokens": 16,
                                       "hidden": 8})
    kinds = [t.collective for t in out]
    assert "psum_ep_combine" in kinds
    ep = next(t for t in out if t.collective == "psum_ep_combine")
    assert ep.count == 3 and ep.n_groups == 2  # per step, per dp group

    # dp_pp3: pipeline dict with tp adds the per-pair activation psum
    # next to the ppermute record (fwd+bwd -> count 2*steps).
    out = obs_comms.train_step_comms(
        1000, (2, 2, 2), steps=5,
        pipeline={"pp": 2, "n_micro": 4, "micro_rows": 8, "hidden": 16,
                  "tp": 2, "n_pairs": 2, "n_groups": 4})
    kinds = [t.collective for t in out]
    assert "ppermute_pipeline" in kinds and "psum_tp_activations" in kinds
    tp = next(t for t in out if t.collective == "psum_tp_activations")
    # ticks = n_micro + pp - 1 = 5; groups = dp*pp = 4; fwd+bwd count.
    assert tp.count == 10 and tp.n_groups == 4
    assert tp.bytes_out_per_device == \
        round(2 * (2 - 1) * 8 * 16 * 4 / 2) * 2 * 5


def test_every_hand_written_collective_site_has_a_live_model():
    """The static analyzer's R1 coverage check, exercised as a test:
    every traffic-bearing collective call site in engine/parallel/train
    carries a comms-model annotation naming a function that exists in
    obs/comms.py (R103/R104 both empty on the real tree)."""
    from dmlp_tpu.check.analyzer import analyze_package
    r1 = [f for f in analyze_package(["R1"])
          if f.rule in ("R103", "R104")]
    assert r1 == []


def test_engine_comms_from_dispatch_shapes():
    single = obs_comms.engine_comms("allgather", (1, 4), 16, 8)
    assert single == []  # data axis of 1: no cross-shard merge
    (t,) = obs_comms.engine_comms("ring", (2, 2), 4, 8)
    assert t.collective == "ring_allreduce_topk"
    assert t.bytes_total == 1536  # matches the hand-computed 2x2 case
    summary = obs_comms.summarize([t])
    assert summary["bytes_total"] == 1536
    assert summary["bytes_by_axis"] == {"data": 1536}


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_sharded_engine_records_comms_for_solved_shapes():
    """The mesh engine's last_comms must reflect the dispatched merge:
    validated against the shapes the solve actually used."""
    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.engine.sharded import ShardedEngine
    from dmlp_tpu.io.datagen import generate_input_text
    from dmlp_tpu.io.grammar import parse_input_text

    inp = parse_input_text(
        generate_input_text(600, 40, 8, 0.0, 50.0, 1, 6, 4, seed=11))
    eng = ShardedEngine(EngineConfig(mode="sharded", mesh_shape=(2, 2)))
    eng.run(inp)
    assert eng.last_comms, "mesh solve must account its merge traffic"
    (t,) = eng.last_comms
    r, c = eng.mesh.devices.shape
    assert (t.axis_size, t.n_groups) == (r, c)
    assert t.collective == "all_gather_merge_topk" and t.axis == "data"
    # payload derives from the dispatched (q_local, k) candidate triple:
    # per-device bytes must be a whole number of 12 B candidates from the
    # (r-1) peer cells.
    assert t.bytes_out_per_device % ((r - 1) * 12) == 0
    assert t.bytes_out_per_device > 0


# ---------------------------------------------------------------------------
# obs.run — RunRecord
# ---------------------------------------------------------------------------

def test_runrecord_roundtrip(tmp_path):
    rec = RunRecord(kind="bench", tool="test", config={"n": 4},
                    metrics={"ms": 1.5}, artifacts={"trace": "t.json"})
    path = str(tmp_path / "rec.json")
    rec.write(path)
    back = RunRecord.load(path)
    assert back.schema == SCHEMA_VERSION
    assert back.config == {"n": 4} and back.metrics == {"ms": 1.5}
    assert back.artifacts == {"trace": "t.json"}
    assert back.host.get("python")


def test_runrecord_jsonl_append(tmp_path):
    path = str(tmp_path / "runs.jsonl")
    RunRecord(kind="a", tool="t").append_jsonl(path)
    RunRecord(kind="b", tool="t").append_jsonl(path)
    lines = open(path).read().splitlines()
    assert [json.loads(ln)["kind"] for ln in lines] == ["a", "b"]


def test_runrecord_schema2_fields_roundtrip():
    rec = RunRecord(kind="bench", tool="x", round=6, device="TPU v5 lite")
    back = RunRecord.from_dict(json.loads(rec.to_json()))
    assert back.schema == SCHEMA_VERSION
    assert back.round == 6 and back.device == "TPU v5 lite"
    # a schema-1 record (no round/device) still loads
    old = RunRecord.from_dict({"kind": "bench", "tool": "x", "schema": 1})
    assert old.round is None and old.device is None


def test_runrecord_write_and_append_load_back_with_envelope(tmp_path):
    from dmlp_tpu.obs.run import round_from_name
    single = str(tmp_path / "BENCH_r06.json")
    RunRecord(kind="bench", tool="t", config={"config_id": 1},
              metrics={"engine_ms": 100,
                       "engine_ms_reps": [99, 100, 101]},
              device="cpu", round=round_from_name(single)).write(single)
    back = RunRecord.load(single)
    assert (back.kind, back.tool, back.round, back.device) \
        == ("bench", "t", 6, "cpu")
    assert back.metrics["engine_ms_reps"] == [99, 100, 101]
    multi = str(tmp_path / "runs.jsonl")
    assert round_from_name(multi) is None
    RunRecord(kind="train", tool="t2", metrics={"step_time_ms": 5.0},
              round=6).append_jsonl(multi)
    RunRecord(kind="train", tool="t2", metrics={"step_time_ms": 4.0},
              round=6).append_jsonl(multi)
    assert RunRecord.load(multi).metrics == {"step_time_ms": 5.0}
    assert [r.metrics["step_time_ms"]
            for r in RunRecord.load_all(multi)] == [5.0, 4.0]


def test_runrecord_schema_guard_and_serialization_error():
    with pytest.raises(ValueError, match="newer"):
        RunRecord.from_dict({"kind": "x", "tool": "t",
                             "schema": SCHEMA_VERSION + 1})
    bad = RunRecord(kind="x", tool="t", metrics={"arr": np.zeros(2)})
    with pytest.raises(TypeError, match="non-JSON-serializable"):
        bad.to_json()


# ---------------------------------------------------------------------------
# utils.metrics_log hardening
# ---------------------------------------------------------------------------

def test_metrics_logger_context_manager_and_t_ms(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(path=path) as log:
        log.log(step=1)
        log.log(step=2)
    assert log._fh.closed
    recs = [json.loads(ln) for ln in open(path).read().splitlines()]
    assert [r["step"] for r in recs] == [1, 2]
    assert all("t_ms" in r for r in recs)
    assert recs[0]["t_ms"] <= recs[1]["t_ms"]  # monotonic


def test_metrics_logger_clear_error_on_unserializable(tmp_path):
    with MetricsLogger(path=str(tmp_path / "m.jsonl")) as log:
        with pytest.raises(TypeError, match=r"bad_key"):
            log.log(bad_key=np.zeros(3), fine=1)


# ---------------------------------------------------------------------------
# ADVICE r5: multi-pass extract full-array tiling guard
# ---------------------------------------------------------------------------

def _widek_input(n=60_000, nq=128, na=8, k=600):
    from dmlp_tpu.io.datagen import generate_input_text
    from dmlp_tpu.io.grammar import parse_input_text
    return parse_input_text(
        generate_input_text(n, nq, na, 0.0, 100.0, k, k, 4, seed=3))


def test_multipass_full_array_supports_invariant_holds_today():
    """The carry-over the guard protects: today chunk-level tileability
    implies full-array tileability (divisibility by 128*ne survives
    multiplication). If this fails, the kernel variants changed and
    the multi-pass driver needs a real fallback."""
    from dmlp_tpu.ops.pallas_extract import supports
    assert supports(128, 38400, 8, 512)
    assert supports(128, 2 * 38400, 8, 512)


def test_multipass_guard_trips_when_full_array_untileable(monkeypatch):
    """Regression for the new guard: if the kernel resolution ever
    rejects the concatenated d_full row count while accepting the chunk
    size, the multi-pass driver must fail loudly BEFORE dispatching
    passes 2+ over a shape no kernel can tile (previously it dispatched
    anyway). The driver resolves fused-vs-two-pass through
    pallas_fused.resolve_topk_kernel (ISSUE 8) — that is the seam the
    guard actually consults, so that is what the fake rejects."""
    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.engine.single import SingleChipEngine
    from dmlp_tpu.ops import pallas_fused

    inp = _widek_input()
    eng = SingleChipEngine(EngineConfig(use_pallas=True, select="extract"))

    real = pallas_fused.resolve_topk_kernel
    chunk_sizes = []

    def fake_resolve(qb, b, a, kc, rung="fused"):
        chunk_sizes.append(b)
        if b > 38400:        # the full concatenated array — reject it
            return None, None
        return real(qb, b, a, kc, rung=rung)

    monkeypatch.setattr(pallas_fused, "resolve_topk_kernel", fake_resolve)
    with pytest.raises(AssertionError, match="full-array sweep"):
        eng._solve_extract_multipass(inp)
    # the guard saw both row counts: per-chunk then full
    assert any(b <= 38400 for b in chunk_sizes)
    assert any(b > 38400 for b in chunk_sizes)


# ---------------------------------------------------------------------------
# CLI smoke: --trace / --metrics via a real subprocess
# ---------------------------------------------------------------------------

def _cli_env():
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return env


@pytest.mark.slow
def test_cli_trace_metrics_subprocess_contract(tmp_path):
    """`--trace`/`--metrics` must leave stdout AND stderr byte-identical
    to an uninstrumented run while producing a Perfetto-loadable trace
    and a metrics JSONL whose summary carries counters (or the explicit
    unavailable marker) — the acceptance contract, via a real pipe."""
    from dmlp_tpu.io.datagen import generate_input_text

    text = generate_input_text(1200, 60, 8, 0.0, 50.0, 1, 8, 5, seed=9)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(*extra):
        proc = subprocess.run(
            [sys.executable, "-m", "dmlp_tpu", *extra],
            input=text.encode(), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=_cli_env(), cwd=repo, timeout=240)
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        return proc.stdout, proc.stderr

    out_plain, _ = run()
    trace_path = str(tmp_path / "t.json")
    metrics_path = str(tmp_path / "m.jsonl")
    out_obs, err_obs = run("--trace", trace_path, "--metrics", metrics_path)

    assert out_obs == out_plain                      # stdout byte-identical
    assert err_obs.decode().startswith("Time taken:")
    assert len(err_obs.decode().splitlines()) == 1   # no extra stderr

    # the committed checker validates both artifacts end to end
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "check_trace.py"),
         trace_path, metrics_path],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, cwd=repo,
        timeout=60)
    assert proc.returncode == 0, proc.stderr.decode()

    doc = json.loads(open(trace_path).read())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert any(n.startswith("cli.solve") for n in names)
    assert any(n.startswith("single.") for n in names)

    recs = [json.loads(ln) for ln in open(metrics_path).read().splitlines()]
    final = recs[-1]
    assert final["event"] == "summary"
    c = final["counters"]
    assert c.get("counters_unavailable") or (
        c["flops"] > 0 and c["bytes_accessed"] > 0)


def test_cli_inprocess_trace_metrics(tmp_path):
    """Same contract in-process (fast, runs in the default suite)."""
    import io

    from dmlp_tpu.cli import main
    from dmlp_tpu.io.datagen import generate_input_text

    text = generate_input_text(300, 20, 6, 0.0, 20.0, 1, 5, 3, seed=4)
    trace_path = str(tmp_path / "t.json")
    metrics_path = str(tmp_path / "m.jsonl")

    out1, err1 = io.StringIO(), io.StringIO()
    assert main([], stdin=io.StringIO(text), stdout=out1, stderr=err1) == 0
    out2, err2 = io.StringIO(), io.StringIO()
    assert main(["--trace", trace_path, "--metrics", metrics_path],
                stdin=io.StringIO(text), stdout=out2, stderr=err2) == 0

    assert out1.getvalue() == out2.getvalue()
    assert err2.getvalue().startswith("Time taken:")
    assert obs_trace.active() is None          # hooks uninstalled
    assert obs_counters.active() is None

    doc = json.loads(open(trace_path).read())
    assert [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    final = json.loads(open(metrics_path).read().splitlines()[-1])
    assert final["event"] == "summary" and "counters" in final


def test_cli_warmup_does_not_double_counters(tmp_path):
    """--warmup runs the full solve once untimed; the probe must be reset
    after it so counters cover the TIMED region only (a doubled count
    would overstate achieved FLOP/s ~2x in the roofline)."""
    import io

    from dmlp_tpu.cli import main
    from dmlp_tpu.io.datagen import generate_input_text

    text = generate_input_text(300, 20, 6, 0.0, 20.0, 1, 5, 3, seed=4)

    def counters_for(extra):
        path = str(tmp_path / f"m{len(extra)}.jsonl")
        assert main([*extra, "--metrics", path], stdin=io.StringIO(text),
                    stdout=io.StringIO(), stderr=io.StringIO()) == 0
        return json.loads(open(path).read().splitlines()[-1])["counters"]

    plain = counters_for([])
    warm = counters_for(["--warmup"])
    if plain.get("counters_unavailable"):
        pytest.skip("backend exposes no cost model")
    assert warm["flops"] == plain["flops"]
    assert warm["dispatches_recorded"] == plain["dispatches_recorded"]
