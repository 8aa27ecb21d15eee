"""Bench harness: caching, checksum diff, compare_times format."""

import io
import os

import pytest

from dmlp_tpu.bench.configs import BenchConfig
from dmlp_tpu.bench.harness import (compare_times, ensure_input,
                                    ensure_oracle, run_config)


@pytest.fixture()
def tiny_cfg(monkeypatch):
    cfg = BenchConfig(1, 200, 20, 4, 0.0, 10.0, 1, 8, 4, 7, "tiny.in")
    monkeypatch.setitem(
        __import__("dmlp_tpu.bench.configs",
                   fromlist=["BENCH_CONFIGS"]).BENCH_CONFIGS, 1, cfg)
    return cfg


def test_input_generation_cached(tiny_cfg, tmp_path):
    d = str(tmp_path / "inputs")
    p1 = ensure_input(tiny_cfg, d)
    mtime = os.path.getmtime(p1)
    p2 = ensure_input(tiny_cfg, d)
    assert p1 == p2 and os.path.getmtime(p2) == mtime  # not regenerated
    with open(p1) as f:
        assert f.readline().strip() == "200 20 4"


def test_oracle_cached(tiny_cfg, tmp_path):
    inp = ensure_input(tiny_cfg, str(tmp_path / "inputs"))
    buf = io.StringIO()
    out1 = ensure_oracle(tiny_cfg, inp, str(tmp_path / "outputs"), buf)
    assert "cache" not in buf.getvalue()
    out2 = ensure_oracle(tiny_cfg, inp, str(tmp_path / "outputs"), buf)
    assert out1 == out2
    assert "Output found in cache. Skipping...\n" in buf.getvalue()


def test_run_config_end_to_end(tiny_cfg, tmp_path):
    buf = io.StringIO()
    res = run_config(1, base_dir=str(tmp_path), out=buf)
    assert res["checksums_match"], buf.getvalue()
    assert res["oracle_ms"] is not None and res["engine_ms"] is not None
    text = buf.getvalue()
    assert "Config 1: checksums PASS" in text
    assert "=== Performance Comparison ===" in text


def test_run_config_exact_mode(tiny_cfg, tmp_path):
    res = run_config(1, base_dir=str(tmp_path), fast=False,
                     out=io.StringIO())
    assert res["checksums_match"]


def test_run_config_profile_marker_on_cpu(tiny_cfg, tmp_path):
    """--profile on a CPU-pinned environment is a no-op with the explicit
    profile_unavailable marker in the config's RunRecord (ROADMAP open
    item 1: real-TPU runs get the linked jax.profiler capture instead)."""
    import json

    buf = io.StringIO()
    record_path = str(tmp_path / "runs.jsonl")
    res = run_config(1, base_dir=str(tmp_path), out=buf,
                     profile_dir=str(tmp_path / "prof"),
                     record_path=record_path)
    assert res["checksums_match"]
    assert "profile_unavailable" in buf.getvalue()
    rec = json.loads(open(record_path).read().splitlines()[-1])
    from dmlp_tpu.obs.run import SCHEMA_VERSION
    assert rec["schema"] == SCHEMA_VERSION
    assert rec["metrics"]["profile_unavailable"]
    assert "profile" not in rec.get("artifacts", {})


def test_compare_times_report_format():
    out = io.StringIO()
    pct = compare_times("Time taken: 100 ms\n", "Time taken: 80 ms\n", out)
    assert pct == pytest.approx(-20.0)
    assert "Benchmark time: 100 ms" in out.getvalue()
    assert "Engine time:    80 ms" in out.getvalue()
    assert "-20 ms (20.00% faster)" in out.getvalue()

    out = io.StringIO()
    pct = compare_times("Time taken: 80 ms\n", "Time taken: 100 ms\n", out)
    assert pct == pytest.approx(25.0)
    assert "+20 ms (25.00% slower)" in out.getvalue()

    out = io.StringIO()
    assert compare_times("nope\n", "Time taken: 1 ms\n", out) is None
    assert "Could not extract timing" in out.getvalue()


def _scrubbed_env():
    """Subprocess env for tests: CPU platform, 8 virtual devices."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return env


def test_engine_subprocess_timeout_kills(tiny_cfg, tmp_path):
    """A wedged engine must fail its config within the limit instead of
    blocking the suite — the mpirun --timeout 300 analog."""
    from dmlp_tpu.bench.harness import EngineTimeout, run_engine

    inp = ensure_input(tiny_cfg, str(tmp_path / "inputs"))
    with pytest.raises(EngineTimeout):
        # 10ms: the interpreter can't even finish importing -> guaranteed
        # timeout path, killed promptly.
        run_engine(tiny_cfg, inp, str(tmp_path), timeout_s=0.01,
                   env=_scrubbed_env())


def test_run_config_timeout_reports(tiny_cfg, tmp_path):
    buf = io.StringIO()
    res = run_config(1, base_dir=str(tmp_path), out=buf, timeout_s=0.01,
                     env=_scrubbed_env())
    assert res.get("timeout") is True
    assert not res["checksums_match"]
    assert "TIMEOUT" in buf.getvalue()


def test_mesh_shape_plumbed_to_cli(tmp_path):
    """BenchConfig.mesh_shape must reach the engine invocation (round-1 review
    missing item 4: the declared mesh was dead config)."""
    from dmlp_tpu.bench.harness import run_engine

    cfg = BenchConfig(1, 64, 8, 3, 0.0, 10.0, 1, 6, 4, 7, "mesh.in",
                      mode="sharded", mesh_shape=(4, 2))
    inp = ensure_input(cfg, str(tmp_path / "inputs"))
    out_p, err_p = run_engine(cfg, inp, str(tmp_path), env=_scrubbed_env(),
                              timeout_s=240)
    with open(out_p) as f:
        assert "checksum:" in f.read()


def test_mesh_too_big_is_an_error(tmp_path):
    """A mesh that needs more devices than the host has fails the run,
    naming both counts — never a silent solve on some other mesh."""
    from dmlp_tpu.bench.harness import run_engine

    cfg = BenchConfig(1, 64, 8, 3, 0.0, 10.0, 1, 6, 4, 7, "mesh2.in",
                      mode="sharded", mesh_shape=(64, 2))
    inp = ensure_input(cfg, str(tmp_path / "inputs"))
    with pytest.raises(RuntimeError,
                       match="needs 128 devices, have 8"):
        run_engine(cfg, inp, str(tmp_path), env=_scrubbed_env(),
                   timeout_s=240)


def test_run_config_engine_error_is_isolated(tiny_cfg, tmp_path):
    """A crashing engine fails its config but not the whole suite."""
    buf = io.StringIO()
    env = _scrubbed_env()
    env["PYTHONPATH"] = str(tmp_path)  # poison: break the subprocess import
    (tmp_path / "jax").mkdir()
    (tmp_path / "jax" / "__init__.py").write_text("raise ImportError('x')\n")
    res = run_config(1, base_dir=str(tmp_path), out=buf, env=env)
    assert res.get("error")
    assert not res["checksums_match"]
    assert "ERROR" in buf.getvalue()


def test_run_config_multiproc_cluster(monkeypatch, tmp_path):
    """Config 5 analog at tiny scale: a real 2-process Gloo cluster under
    the harness kill timeout, proc-0 stdout diffed against the oracle —
    the run_bench.sh multi-node flow end-to-end (round-2 review item 4)."""
    cfg = BenchConfig(5, 180, 16, 4, 0.0, 10.0, 1, 8, 4, 7, "mp.in",
                      mode="sharded", procs=2, virtual_devices=4)
    monkeypatch.setitem(
        __import__("dmlp_tpu.bench.configs",
                   fromlist=["BENCH_CONFIGS"]).BENCH_CONFIGS, 5, cfg)
    buf = io.StringIO()
    res = run_config(5, base_dir=str(tmp_path), out=buf, timeout_s=240,
                     env=_scrubbed_env())
    assert res["checksums_match"], buf.getvalue()
    assert "Config 5: checksums PASS" in buf.getvalue()


def test_run_engine_passes_pallas_and_select(tmp_path):
    """use_pallas/select must reach the engine argv (round-2 review item 3:
    the r2 harness always benched the default path)."""
    from dmlp_tpu.bench.harness import run_engine

    cfg = BenchConfig(1, 128, 8, 3, 0.0, 10.0, 1, 6, 4, 7, "ps.in",
                      use_pallas=True, select="seg")
    inp = ensure_input(cfg, str(tmp_path / "inputs"))
    out_p, err_p = run_engine(cfg, inp, str(tmp_path), env=_scrubbed_env(),
                              timeout_s=240)
    with open(out_p) as f:
        assert "checksum:" in f.read()


def test_oracle_capture_kit_diff_roundtrip(tmp_path):
    """round-4 review item 5 (repo side): simulate a capture directory whose
    'oracle binary' outputs come from the golden model, and assert
    tools/oracle_diff.py accepts it — and rejects a corrupted checksum
    and a mismatched input hash. (The capture script itself needs an
    x86+OpenMPI host; its manifest format is pinned here.)"""
    import hashlib
    import json
    import subprocess
    import sys

    from dmlp_tpu.bench.configs import BENCH_CONFIGS
    from dmlp_tpu.bench.harness import ensure_input
    from dmlp_tpu.golden.fast import knn_golden_fast
    from dmlp_tpu.io.grammar import parse_input
    from dmlp_tpu.io.report import format_results

    cap = tmp_path / "cap"
    cap.mkdir()
    cfg = BENCH_CONFIGS[1]
    inp_path = ensure_input(cfg, str(cap))
    with open(inp_path, "rb") as f:
        raw = f.read()
    with open(inp_path, "rb") as f:
        results = knn_golden_fast(parse_input(f))
    (cap / "oracle_1.out").write_text(format_results(results) + "\n")
    manifest = {"configs": {"1": {
        "bench": "bench_1", "input": cfg.input_name,
        "input_sha256": hashlib.sha256(raw).hexdigest(),
        "np": 8, "time_taken_ms": 1234, "out_file": "oracle_1.out"}}}
    mpath = cap / "ORACLE_GOLDEN.json"
    mpath.write_text(json.dumps(manifest))

    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "oracle_diff.py")
    env = {**os.environ}
    r = subprocess.run([sys.executable, tool, str(mpath), "--configs", "1"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "config 1: OK" in r.stdout

    # Corrupt one checksum -> must fail with a differing count.
    out = (cap / "oracle_1.out").read_text().splitlines()
    q, c = out[0].rsplit(" ", 1)[0], out[0].rsplit(" ", 1)[1]
    out[0] = f"{q} {int(c) ^ 1}"
    (cap / "oracle_1.out").write_text("\n".join(out) + "\n")
    r = subprocess.run([sys.executable, tool, str(mpath), "--configs", "1"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1 and "MISMATCH" in r.stdout

    # Wrong input hash -> generator-divergence failure.
    manifest["configs"]["1"]["input_sha256"] = "0" * 64
    mpath.write_text(json.dumps(manifest))
    r = subprocess.run([sys.executable, tool, str(mpath), "--configs", "1"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1 and "INPUT MISMATCH" in r.stdout


def test_run_config_timeout_records_marker_not_gate(tiny_cfg, tmp_path):
    """Resilience satellite: a hung config documents itself with the
    explicit `timed_out` marker (markers never gate, PR 5 convention)
    and the bench run's verdict ignores it."""
    buf = io.StringIO()
    res = run_config(1, base_dir=str(tmp_path), out=buf, timeout_s=0.01,
                     env=_scrubbed_env())
    assert res.get("timed_out") is True
    assert res.get("timeout") is True          # legacy spelling kept
    # the main() gate treats timed_out as non-gating:
    assert res["checksums_match"] or res.get("timed_out", False)


def test_per_config_timeout_override(tiny_cfg, tmp_path, monkeypatch):
    """BenchConfig.timeout_s beats the harness-wide --timeout."""
    import dataclasses

    from dmlp_tpu.bench import configs as bench_configs
    cfg = dataclasses.replace(tiny_cfg, timeout_s=0.01)
    monkeypatch.setitem(bench_configs.BENCH_CONFIGS, 1, cfg)
    buf = io.StringIO()
    res = run_config(1, base_dir=str(tmp_path), out=buf, timeout_s=600.0,
                     env=_scrubbed_env())
    assert res.get("timed_out") is True        # 600s harness limit unused
