"""Perf ledger + regression sentinel (obs.ledger, dmlp_tpu.report,
tools/perf_gate.py).

Fixture-driven ingestion over the REAL repo-root artifact population
(every schema present at the root must round-trip into the ledger
without silent drops), noise-aware comparison semantics (noise band /
insufficient_trials / device_mismatch), the report CLI, and the gate's
pass / fail / insufficient-data paths — including the acceptance
requirement that a synthetic regressed RunRecord round demonstrably
fails the gate.
"""

import importlib.util
import json
import os

import pytest

from dmlp_tpu.obs.ledger import (MIN_TRIALS, build_ledger, compare_points,
                                 discover_artifacts, ingest_file,
                                 noise_band, series_deltas)
from dmlp_tpu.obs.run import SCHEMA_VERSION, RunRecord

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# ingestion over the real repo-root artifacts — every schema present
# ---------------------------------------------------------------------------

def test_ledger_covers_every_root_artifact():
    files = discover_artifacts(REPO)
    assert len(files) >= 40, "artifact discovery lost the repo root"
    ledger = build_ledger(REPO)
    cov = ledger["coverage"]
    # one entry per file, none silently dropped
    assert cov["files"] == len(files)
    assert len(ledger["entries"]) == len(files)
    # the acceptance floor: >= 90% parsed, the rest EXPLICIT
    assert cov["fraction"] >= 0.9, cov["unparseable_sources"]
    for e in ledger["entries"]:
        assert e["status"] in ("parsed", "unparseable")
        if e["status"] == "unparseable":
            assert e["error"]          # named reason, never silence


def _write_legacy_records(root):
    """The four pre-round ad-hoc shapes whose records left the repo root
    (PR 21), cut to the fields their parsers read."""
    docs = {
        "BENCH_r05.json": {
            "n": 5, "cmd": "python bench.py", "rc": 0, "tail": "",
            "parsed": {
                "metric": "knn_solve_ms", "value": 2876.263, "unit": "ms",
                "vs_baseline": 32.455, "qd_pairs_per_sec": 695346651,
                "shape": {"num_data": 200000, "num_queries": 10000,
                          "num_attrs": 64, "k": 32, "mode": "single"},
                "path": {"select": "extract", "phases_ms": {
                    "enqueue": 133.6, "fetch": 4274.7, "finalize": 66.0,
                    "device_solve_ms_extract": 99.4}},
                "device_solve_ms": 99.4}},
        "ROOFLINE_r05.json": {
            "device": "TPU v5 lite", "shape": [204800, 10240, 64],
            "k": 32, "kc": 40, "dispatch_overhead_ms": 37.82,
            "raw_ms": {"solve_with_epilogue": 87.85,
                       "kernel_only": 81.38, "mxu_matmul": 47.8},
            "corrected": {"kernel_ms": 43.56, "mxu_floor_ms": 9.98,
                          "extraction_term_ms": 33.58,
                          "pct_of_roof": 22.9},
            "extract_iters_total": 13773},
        "BENCH_BF16_r04.json": {
            "shape": {"num_data": 200000, "num_queries": 10000,
                      "num_attrs": 64, "k": 32},
            "platform": "tpu", "use_pallas": True,
            "results_identical": True,
            "runs": [
                {"staging": "f32", "median_ms": 3253.3, "min_ms": 3045.7,
                 "max_ms": 3482.8, "times_ms": [3344.5, 3216.1, 3253.3,
                                                3045.7, 3482.8],
                 "repairs": [0] * 5, "select": "extract"},
                {"staging": "bf16", "median_ms": 3238.1,
                 "min_ms": 2691.7, "max_ms": 4148.1,
                 "times_ms": [3142.4, 3238.1, 3692.0, 2691.7, 4148.1],
                 "repairs": [0] * 5, "select": "extract"}]},
        "CAPACITY_BEYOND_HBM_r04.json": {
            "device_kind": "TPU v5 lite", "num_data": 72000000,
            "num_queries": 2048, "num_attrs": 64, "kmax": 32,
            "dataset_vs_hbm": 1.09, "select": "extract", "repairs": 0,
            "solve_wall_s": 3266.4,
            "phases_ms": {"enqueue": 412162.9, "fetch": 2844708.0,
                          "finalize": 151.6},
            "validated_queries": 8, "validate_mismatches": 0},
    }
    for name, doc in docs.items():
        with open(os.path.join(str(root), name), "w") as f:
            json.dump(doc, f)


def test_ledger_parses_each_known_family(tmp_path):
    ledger = build_ledger(REPO)
    fams = {e["family"] for e in ledger["entries"]
            if e["status"] == "parsed"}
    # the families the repo root actually holds today
    assert {"harness", "sweep", "trainbench", "pipebench", "runrecord",
            "generic"} <= fams
    # ... and the two whose last root records are gone, on fixtures
    _write_legacy_records(tmp_path)
    legacy = build_ledger(str(tmp_path))
    assert legacy["coverage"]["fraction"] == 1.0
    by_src = {e["source"]: e for e in legacy["entries"]}
    assert by_src["BENCH_r05.json"]["family"] == "bench"
    assert by_src["ROOFLINE_r05.json"]["family"] == "roofline"
    assert any("pct_of_roof" in s for s in legacy["series"])
    # harness series carry per-rep trials (the gate's raw material)
    pts = ledger["series"]["harness/config1/engine_ms"]
    assert any(p.get("trials") for p in pts)
    rounds = {p["round"] for p in pts}
    assert {3, 4, 5} <= rounds


def test_ledger_runrecord_round_trip(tmp_path):
    # schema RunRecords (single + jsonl), a legacy harness shape, and
    # junk — the ledger must parse the first three and explicitly mark
    # the junk, dropping nothing.
    RunRecord(kind="bench", tool="t", config={"config_id": 1},
              metrics={"engine_ms": 100,
                       "engine_ms_reps": [99, 100, 101],
                       "obs_overhead_pct": 1.5},
              device="cpu", round=6).write(str(tmp_path / "BENCH_r06.json"))
    rec = RunRecord(kind="train", tool="t2", metrics={"step_time_ms": 5.0},
                    round=6)
    rec.append_jsonl(str(tmp_path / "TRAINBENCH_r06.jsonl"))
    with open(tmp_path / "HARNESS_r05.json", "w") as f:
        json.dump({"configs": [{"config": 1, "engine_ms": 120,
                                "engine_ms_reps": [118, 120, 125]}]}, f)
    with open(tmp_path / "BENCH_r07.json", "w") as f:
        f.write("{not json")

    ledger = build_ledger(str(tmp_path))
    by_src = {e["source"]: e for e in ledger["entries"]}
    assert by_src["BENCH_r06.json"]["status"] == "parsed"
    assert by_src["BENCH_r06.json"]["family"] == "runrecord"
    assert by_src["TRAINBENCH_r06.jsonl"]["status"] == "parsed"
    assert by_src["HARNESS_r05.json"]["status"] == "parsed"
    assert by_src["BENCH_r07.json"]["status"] == "unparseable"
    # envelope round/device flow into the points; trials captured
    (pt,) = ledger["series"]["bench:t/config1/engine_ms"]
    assert pt["round"] == 6 and pt["device"] == "cpu"
    assert pt["trials"] == [99.0, 100.0, 101.0]
    # obs overhead is its own tracked series
    assert "bench:t/config1/obs_overhead_pct" in ledger["series"]


def test_runrecord_schema2_fields_roundtrip():
    rec = RunRecord(kind="bench", tool="x", round=6, device="TPU v5 lite")
    back = RunRecord.from_dict(json.loads(rec.to_json()))
    assert back.schema == SCHEMA_VERSION
    assert back.round == 6 and back.device == "TPU v5 lite"
    # a schema-1 record (no round/device) still loads
    old = RunRecord.from_dict({"kind": "bench", "tool": "x", "schema": 1})
    assert old.round is None and old.device is None


def test_unavailable_marker_record_is_parsed_not_dropped(tmp_path):
    # e.g. ROOFLINE_r06-style records whose metrics are all markers
    RunRecord(kind="roofline", tool="t",
              metrics={"roofline_unavailable": "no TPU"},
              round=6).write(str(tmp_path / "ROOFLINE_r06.json"))
    entry = ingest_file(str(tmp_path / "ROOFLINE_r06.json"))
    assert entry["status"] == "parsed"
    assert entry["points"] == []


# ---------------------------------------------------------------------------
# noise-aware comparison semantics
# ---------------------------------------------------------------------------

def _pt(value, trials=None, device="cpu", round_=1, better="lower"):
    return {"series": "s", "value": value, "trials": trials,
            "device": device, "round": round_, "better": better}


def test_compare_within_noise_is_not_significant():
    a = _pt(100, trials=[95, 100, 105], round_=1)
    b = _pt(102, trials=[97, 102, 106], round_=2)
    cmp = compare_points(a, b)
    assert "marker" not in cmp
    assert cmp["significant"] is False
    assert cmp["regressed"] is False


def test_compare_flags_regression_beyond_band():
    a = _pt(100, trials=[99, 100, 101], round_=1)
    b = _pt(200, trials=[198, 200, 202], round_=2)
    cmp = compare_points(a, b)
    assert cmp["significant"] and cmp["regressed"]
    # and the same magnitude in the good direction is an improvement
    cmp2 = compare_points(b, a)
    assert cmp2["improved"] and not cmp2["regressed"]


def test_compare_higher_is_better_direction():
    a = _pt(100, trials=[99, 100, 101], round_=1, better="higher")
    b = _pt(50, trials=[49, 50, 51], round_=2, better="higher")
    cmp = compare_points(a, b)
    assert cmp["regressed"]  # throughput halved


def test_compare_insufficient_trials_marker():
    a = _pt(100, trials=None, round_=1)
    b = _pt(500, trials=[499, 500, 501], round_=2)
    cmp = compare_points(a, b)
    assert cmp["marker"] == "insufficient_trials"
    assert "regressed" not in cmp           # never a silent verdict
    assert cmp["delta_pct"] == 400.0        # raw delta still reported
    short = compare_points(_pt(1, trials=[1] * (MIN_TRIALS - 1)),
                           _pt(9, trials=[9] * MIN_TRIALS))
    assert short["marker"] == "insufficient_trials"


def test_compare_device_mismatch_marker():
    cmp = compare_points(_pt(100, trials=[1, 2, 3], device="cpu"),
                         _pt(900, trials=[1, 2, 3], device="TPU v5 lite"))
    assert cmp["marker"] == "device_mismatch"
    assert "regressed" not in cmp


def test_noise_band_floor_absorbs_quantized_timers():
    # 3 near-identical ms-quantized trials: MAD ~ 0, but the band must
    # not collapse below the relative floor
    assert noise_band([1000, 1000, 1001]) >= 0.02 * 1000


# ---------------------------------------------------------------------------
# the report CLI and the gate
# ---------------------------------------------------------------------------

def test_report_cli_builds_ledger_and_enforces_coverage(tmp_path):
    import dmlp_tpu.report as report
    out = tmp_path / "LEDGER.json"
    md = tmp_path / "REPORT.md"
    rc = report.main(["--root", REPO, "--out", str(out), "--md", str(md),
                      "--min-coverage", "0.9"])
    assert rc == 0
    ledger = json.loads(out.read_text())
    assert ledger["ledger_schema"] == 1
    assert ledger["coverage"]["fraction"] >= 0.9
    text = md.read_text()
    assert "Round-over-round trajectories" in text
    assert "harness/config1/engine_ms" in text
    # the roofline section, over the legacy-shape fixture (its last
    # root record is gone)
    legacy = tmp_path / "legacy"
    legacy.mkdir()
    _write_legacy_records(legacy)
    rc = report.main(["--root", str(legacy), "--out", str(out),
                      "--md", str(md), "--min-coverage", "0.9"])
    assert rc == 0
    assert "pct_of_roof" in md.read_text()


def test_perf_gate_passes_on_current_tree(capsys):
    perf_gate = _load_tool("perf_gate")
    rc = perf_gate.main(["--root", REPO])
    assert rc == 0
    out = capsys.readouterr().out
    assert "gated series checked" in out


def _write_round(tmp_path, round_, reps):
    RunRecord(kind="bench", tool="dmlp_tpu.bench",
              config={"config_id": 1},
              metrics={"engine_ms": sorted(reps)[len(reps) // 2],
                       "engine_ms_reps": reps},
              device="cpu", round=round_).append_jsonl(
        str(tmp_path / f"BENCH_r{round_:02d}.jsonl"))


def test_perf_gate_fails_on_synthetic_regressed_runrecord(tmp_path):
    perf_gate = _load_tool("perf_gate")
    _write_round(tmp_path, 6, [100, 101, 99])
    _write_round(tmp_path, 7, [205, 200, 202])   # 2x slower, tight reps
    rc = perf_gate.main(["--root", str(tmp_path)])
    assert rc == 1
    res = perf_gate.run_gate(str(tmp_path))
    (reg,) = res["regressions"]
    assert reg["series"].endswith("config1/engine_ms")
    assert reg["regressed"] and reg["cur_round"] == 7


def test_perf_gate_insufficient_data_reports_not_fails(tmp_path):
    perf_gate = _load_tool("perf_gate")
    # round 6 has trials, round 7 is single-shot: honest marker, exit 0
    _write_round(tmp_path, 6, [100, 101, 99])
    RunRecord(kind="bench", tool="dmlp_tpu.bench",
              config={"config_id": 1}, metrics={"engine_ms": 400},
              device="cpu", round=7).append_jsonl(
        str(tmp_path / "BENCH_r07.jsonl"))
    rc = perf_gate.main(["--root", str(tmp_path)])
    assert rc == 0
    res = perf_gate.run_gate(str(tmp_path))
    assert not res["regressions"]
    (unq,) = res["unqualified"]
    assert unq["marker"] == "insufficient_trials"


def test_perf_gate_device_mismatch_reports_not_fails(tmp_path):
    perf_gate = _load_tool("perf_gate")
    _write_round(tmp_path, 6, [100, 101, 99])
    RunRecord(kind="bench", tool="dmlp_tpu.bench",
              config={"config_id": 1},
              metrics={"engine_ms": 900,
                       "engine_ms_reps": [899, 900, 901]},
              device="TPU v5 lite", round=7).append_jsonl(
        str(tmp_path / "BENCH_r07.jsonl"))
    rc = perf_gate.main(["--root", str(tmp_path)])
    assert rc == 0
    res = perf_gate.run_gate(str(tmp_path))
    (unq,) = res["unqualified"]
    assert unq["marker"] == "device_mismatch"


def test_perf_gate_within_noise_passes(tmp_path):
    perf_gate = _load_tool("perf_gate")
    _write_round(tmp_path, 6, [100, 104, 96])
    _write_round(tmp_path, 7, [101, 105, 97])    # +1% inside the band
    rc = perf_gate.main(["--root", str(tmp_path)])
    assert rc == 0
    res = perf_gate.run_gate(str(tmp_path))
    (ok,) = res["within_noise"]
    assert ok["significant"] is False


def test_series_deltas_skips_single_round_series(tmp_path):
    _write_round(tmp_path, 6, [100, 101, 99])
    ledger = build_ledger(str(tmp_path))
    assert series_deltas(ledger) == []


# ---------------------------------------------------------------------------
# obs-overhead self-measurement (bench harness)
# ---------------------------------------------------------------------------

@pytest.fixture
def tiny_cfg(monkeypatch):
    """Tiny config 1 so subprocess engine runs stay cheap (the
    test_bench_harness pattern)."""
    from dmlp_tpu.bench import configs as cfgs
    cfg = cfgs.BenchConfig(1, 200, 20, 4, 0.0, 10.0, 1, 8, 4, 7, "tiny.in")
    monkeypatch.setitem(cfgs.BENCH_CONFIGS, 1, cfg)
    return cfg


def test_obs_overhead_recorded_in_runrecord(tiny_cfg, tmp_path):
    """The acceptance path: a bench config records obs_overhead_pct
    measured from real interleaved tracing+counters on/off engine
    runs, and the RunRecord round-trips through the ledger."""
    import io

    from dmlp_tpu.bench.harness import run_config

    buf = io.StringIO()
    record = tmp_path / "BENCH_r06.jsonl"
    res = run_config(1, base_dir=str(tmp_path), out=buf, reps=1,
                     obs_overhead=True, record_path=str(record),
                     timeout_s=240)
    assert res["checksums_match"]
    if "obs_overhead_unavailable" in res:
        pytest.fail(f"overhead A/B did not complete: "
                    f"{res['obs_overhead_unavailable']}")
    assert isinstance(res["obs_overhead_pct"], float)
    assert len(res["engine_ms_obs_off"]) == 1
    assert len(res["engine_ms_obs_on"]) == 1
    rec = json.loads(record.read_text().splitlines()[0])
    assert rec["schema"] == SCHEMA_VERSION
    assert "obs_overhead_pct" in rec["metrics"]
    # and the ledger picks it up as a tracked series
    ledger = build_ledger(str(tmp_path), paths=[str(record)])
    assert any("obs_overhead_pct" in s for s in ledger["series"])


# ---------------------------------------------------------------------------
# migration continuity: RunRecord rounds continue the legacy series
# ---------------------------------------------------------------------------

def test_migrated_emitters_continue_legacy_series_names(tmp_path):
    """The r05->r06 emitter migration must not sever trajectories: a
    dmlp_tpu.bench RunRecord continues harness/configN/*, and the moe/
    ladder tools continue their trainbench/* series — with their trial
    lists attached, so the gate can actually qualify them."""
    RunRecord(kind="bench", tool="dmlp_tpu.bench",
              config={"config_id": 2},
              metrics={"engine_ms": 150,
                       "engine_ms_reps": [148, 150, 153]},
              device="cpu", round=6).append_jsonl(
        str(tmp_path / "BENCH_r06.jsonl"))
    RunRecord(kind="train", tool="tools.trainbench_moe",
              metrics={"a2a_median_ms": 10.0,
                       "a2a_times_ms": [9.8, 10.0, 10.4, 10.1],
                       "dense_median_ms": 12.0,
                       "dense_times_ms": [11.9, 12.0, 12.2, 12.1],
                       "a2a_vs_dense_pct": -16.7},
              device="cpu", round=6).write(
        str(tmp_path / "TRAINBENCH_r06_moe.json"))
    RunRecord(kind="train", tool="tools.bench_offload_ladder",
              metrics={"params_step_time_ms": 5.5, "params_mfu": 0.4},
              device="cpu", round=6).write(
        str(tmp_path / "TRAINBENCH_r06_ladder.json"))

    ledger = build_ledger(str(tmp_path))
    series = ledger["series"]
    (pt,) = series["harness/config2/engine_ms"]
    assert pt["trials"] == [148.0, 150.0, 153.0]
    (moe,) = series["trainbench/moe/a2a/median_ms"]
    assert moe["trials"] == [9.8, 10.0, 10.4, 10.1]
    assert "trainbench/ladder/params/step_time_ms" in series
    assert "trainbench/ladder/params/mfu" in series
    # identifier echoes must NOT become series
    assert not any(s.endswith("/config") for s in series)


def test_migrated_series_qualify_against_legacy_rounds(tmp_path):
    """A legacy HARNESS round and a migrated RunRecord round form ONE
    series; with trials on both sides and the same device the gate
    qualifies the comparison (a regressed migration round fails)."""
    perf_gate = _load_tool("perf_gate")
    with open(tmp_path / "HARNESS_r05.json", "w") as f:
        json.dump({"configs": [{"config": 1, "engine_ms": 100,
                                "engine_ms_reps": [99, 100, 101]}]}, f)
    RunRecord(kind="bench", tool="dmlp_tpu.bench",
              config={"config_id": 1},
              metrics={"engine_ms": 300,
                       "engine_ms_reps": [297, 300, 303]},
              round=6).append_jsonl(str(tmp_path / "BENCH_r06.jsonl"))
    res = perf_gate.run_gate(str(tmp_path))
    (reg,) = res["regressions"]
    assert reg["series"] == "harness/config1/engine_ms"
    assert reg["prev_round"] == 5 and reg["cur_round"] == 6


def test_repairs_metric_is_not_higher_better():
    from dmlp_tpu.obs.ledger import _better_direction
    assert _better_direction(
        "capacity:tools.capacity_beyond_hbm/repairs") != "higher"
    assert _better_direction("bench/qd_pairs_per_sec/x") == "higher"


def test_foreign_device_round_does_not_ungate_prior_pair(tmp_path):
    """Landing one foreign-device round must not disable regression
    detection for the still-comparable earlier rounds: the deltas
    carry BOTH the adjacent (mismatched) pair and the newest
    same-device pair, and the gate still catches a regression there."""
    perf_gate = _load_tool("perf_gate")
    _write_round(tmp_path, 5, [100, 101, 99])
    _write_round(tmp_path, 6, [205, 200, 202])   # regressed, same device
    RunRecord(kind="bench", tool="dmlp_tpu.bench",
              config={"config_id": 1},
              metrics={"engine_ms": 50,
                       "engine_ms_reps": [49, 50, 51]},
              device="TPU v5 lite", round=7).append_jsonl(
        str(tmp_path / "BENCH_r07.jsonl"))
    res = perf_gate.run_gate(str(tmp_path))
    assert [u["marker"] for u in res["unqualified"]] == ["device_mismatch"]
    (reg,) = res["regressions"]          # the r5->r6 cpu pair still gates
    assert (reg["prev_round"], reg["cur_round"]) == (5, 6)
    assert perf_gate.main(["--root", str(tmp_path)]) == 1


def test_unknown_prefix_rNN_artifact_is_discovered(tmp_path):
    """README's contract: ANY _rNN-named artifact at the root is picked
    up — an unknown prefix must produce an entry, not silence."""
    RunRecord(kind="train", tool="custom.tool",
              metrics={"step_time_ms": 4.2}, round=7).write(
        str(tmp_path / "MYSERIES_r07.json"))
    ledger = build_ledger(str(tmp_path))
    (entry,) = ledger["entries"]
    assert entry["source"] == "MYSERIES_r07.json"
    assert entry["status"] == "parsed"
    assert "train:custom.tool/step_time_ms" in ledger["series"]


def test_legacy_bf16_and_capacity_continue_migrated_series(tmp_path):
    """The grandfathered r04 shapes parse under the MIGRATED emitters'
    series names, so their trajectories survive the RunRecord migration
    (with the bf16 per-arm trials attached)."""
    _write_legacy_records(tmp_path)
    ledger = build_ledger(str(tmp_path))
    pts = ledger["series"]["bench:tools.bench_bf16_staging/f32_median_ms"]
    assert any(p.get("trials") for p in pts)
    assert any(p["round"] == 4 for p in pts)
    caps = ledger["series"]["capacity:tools.capacity_beyond_hbm/solve_wall_s"]
    assert any(p["round"] == 4 for p in caps)
