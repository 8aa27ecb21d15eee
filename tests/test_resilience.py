"""Resilience subsystem: fault injection, retry/backoff, degradation
ladder, supervision — plus engine-level byte-identical recovery.

The chaos harness (tools/chaos_run.py, `make chaos-smoke`) proves the
end-to-end invariants through the real CLI; these tests pin the unit
semantics each mechanism is built from, fast enough for tier-1.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.datagen import generate_input_text
from dmlp_tpu.io.grammar import parse_input, parse_input_text
from dmlp_tpu.io.report import format_results
from dmlp_tpu.resilience import degrade, inject, stats
from dmlp_tpu.resilience.inject import (FaultSchedule,
                                        InjectedTransientError,
                                        SimulatedResourceExhausted)
from dmlp_tpu.resilience.retry import (OperationTimeout, RetryPolicy,
                                       backoff_ms, call_with_retry,
                                       call_with_timeout, classify)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_resilience_state(monkeypatch):
    """Every test starts with no schedule installed and zero counters;
    delay faults never really sleep."""
    monkeypatch.delenv("DMLP_TPU_FAULTS", raising=False)
    monkeypatch.delenv("DMLP_TPU_RESILIENCE", raising=False)
    stats.reset()
    inject.uninstall()
    yield
    inject.uninstall()
    stats.reset()


def sched(faults, seed=0):
    return FaultSchedule.from_dict(
        {"schema": 1, "seed": seed, "faults": faults})


# -- inject: schedule validation ---------------------------------------------

def test_schedule_rejects_unknown_site():
    with pytest.raises(ValueError, match="matches no registered"):
        sched([{"site": "engine.nope", "kind": "delay"}])


def test_schedule_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown fault kind"):
        sched([{"site": "single.fetch", "kind": "explode"}])


def test_schedule_rejects_unknown_field():
    with pytest.raises(ValueError, match="unknown field"):
        sched([{"site": "single.fetch", "kind": "delay", "mss": 5}])


def test_schedule_rejects_bad_schema():
    with pytest.raises(ValueError, match="schema must be 1"):
        FaultSchedule.from_dict({"schema": 2, "faults": []})


def test_schedule_accepts_glob_sites():
    s = sched([{"site": "single.*", "kind": "delay", "times": 2}])
    inject.install(s)
    inject.fire("single.fetch")      # delay with ms=0: no-op sleep
    inject.fire("sharded.fetch")     # glob does not match
    inject.fire("single.stage_put")
    assert [e["site"] for e in s.log if e["fired"]] == \
        ["single.fetch", "single.stage_put"]


# -- inject: fire semantics --------------------------------------------------

def test_fire_noop_without_schedule():
    assert inject.fire("single.fetch") is None


def test_transient_and_oom_raise_then_exhaust():
    inject.install(sched([
        {"site": "single.fetch", "kind": "transient"},
        {"site": "single.stage_put", "kind": "oom"},
    ]))
    with pytest.raises(InjectedTransientError):
        inject.fire("single.fetch")
    with pytest.raises(SimulatedResourceExhausted,
                       match="RESOURCE_EXHAUSTED"):
        inject.fire("single.stage_put")
    # times defaults to 1: both entries are spent
    assert inject.fire("single.fetch") == []
    assert inject.fire("single.stage_put") == []
    assert stats.snapshot()["faults_injected"] == 2


def test_after_skips_first_hits():
    s = sched([{"site": "train.step", "kind": "transient", "after": 2}])
    inject.install(s)
    assert inject.fire("train.step") == []
    assert inject.fire("train.step") == []
    with pytest.raises(InjectedTransientError):
        inject.fire("train.step")


def test_when_filters_on_context():
    inject.install(sched([
        {"site": "train.step", "kind": "nan", "when": {"step": 3}}]))
    assert inject.fire("train.step", step=2) == []
    assert inject.fire("train.step", step=3) == ["nan"]
    assert inject.fire("train.step", step=3) == []   # times=1 spent


def test_prob_draws_are_seed_deterministic():
    def run(seed):
        s = sched([{"site": "train.step", "kind": "nan", "times": 50,
                    "prob": 0.5}], seed=seed)
        inject.install(s)
        for i in range(50):
            inject.fire("train.step", step=i)
        inject.uninstall()
        return [e["fired"] for e in s.log]

    a, b, c = run(7), run(7), run(8)
    assert a == b                  # same seed -> identical decisions
    assert a != c                  # different seed -> different draws
    assert any(a) and not all(a)   # prob actually probabilistic


def test_delay_uses_injectable_sleep(monkeypatch):
    slept = []
    monkeypatch.setattr(inject, "_sleep", slept.append)
    inject.install(sched([
        {"site": "single.fetch", "kind": "delay", "ms": 40}]))
    inject.fire("single.fetch")
    assert slept == [0.04]


def test_kill_switch_disables_firing(monkeypatch):
    inject.install(sched([{"site": "single.fetch", "kind": "transient"}]))
    monkeypatch.setenv("DMLP_TPU_RESILIENCE", "0")
    assert inject.fire("single.fetch") is None


def test_log_roundtrip_and_write(tmp_path):
    s = sched([{"site": "single.fetch", "kind": "delay"}])
    inject.install(s)
    inject.fire("single.fetch")
    path = str(tmp_path / "log.json")
    s.write_log(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["seed"] == 0
    assert doc["log"][0]["site"] == "single.fetch"
    assert doc["log"][0]["fired"] is True


def test_corrupt_bytes_drops_whole_lines():
    data = b"3 1 2\n" + b"0 1.0 2.0\n" * 3 + b"Q 1 0.5 0.5\n"
    bad = inject.corrupt_bytes(data)
    assert data.startswith(bad) and bad.endswith(b"\n")
    assert bad.count(b"\n") < data.count(b"\n")   # >= 1 full line gone
    assert len(bad) <= (len(data) * 3) // 4
    # str payloads corrupt the same way; line-less input degrades empty
    assert inject.corrupt_bytes(data.decode()) == bad.decode()
    assert inject.corrupt_bytes(b"x" * 100) == b""
    assert inject.corrupt_bytes(b"") == b""


def test_corrupt_is_always_detectable():
    """Line-boundary truncation guarantees the grammar's record-count
    check raises — a corrupted payload can never silently parse."""
    from dmlp_tpu.io.grammar import ParseError
    for seed in (1, 2, 3):
        text = generate_input_text(20, 4, 3, -5, 5, 1, 4, 3, seed=seed)
        with pytest.raises(ParseError):
            parse_input_text(inject.corrupt_bytes(text))


def test_passive_not_consumed_when_raiser_fires_same_call():
    """A raising fault in the same fire() discards the actions list, so
    a passive entry fired earlier in the call rolls back (budget AND
    log) and is delivered on the retry's re-invocation instead — the
    log never claims a fault that had no effect."""
    s = sched([
        {"site": "train.step", "kind": "nan", "when": {"step": 2}},
        {"site": "train.step", "kind": "transient", "when": {"step": 2}}])
    inject.install(s)
    with pytest.raises(InjectedTransientError):
        inject.fire("train.step", step=2)
    assert [e["kind"] for e in s.log if e["fired"]] == ["transient"]
    assert inject.fire("train.step", step=2) == ["nan"]
    assert [e["kind"] for e in s.log if e["fired"]] == \
        ["transient", "nan"]


def test_passive_kind_rejected_at_non_consuming_site():
    """'corrupt'/'nan' are actions the site itself applies; scheduling
    them where fire()'s return value is discarded would count as fired
    while doing nothing — rejected at load."""
    with pytest.raises(ValueError, match="only consumed at"):
        sched([{"site": "single.fetch", "kind": "nan"}])
    with pytest.raises(ValueError, match="only consumed at"):
        sched([{"site": "*", "kind": "corrupt"}])
    sched([{"site": "io.parse", "kind": "corrupt"}])      # consumers load
    sched([{"site": "train.step", "kind": "nan"}])


# -- retry -------------------------------------------------------------------

def test_classify_three_way():
    assert classify(InjectedTransientError("x")) == "transient"
    assert classify(ConnectionError()) == "transient"
    assert classify(TimeoutError()) == "transient"
    assert classify(OperationTimeout("deadline")) == "transient"
    assert classify(RuntimeError("... UNAVAILABLE: socket closed")) == \
        "transient"
    assert classify(SimulatedResourceExhausted("x")) == "oom"
    assert classify(RuntimeError("RESOURCE_EXHAUSTED: while allocating "
                                 "1.2G")) == "oom"
    assert classify(ValueError("bad k")) == "fatal"
    assert classify(RuntimeError("plain bug")) == "fatal"


def test_backoff_deterministic_bounded_and_dethundered():
    pol = RetryPolicy(base_ms=25, cap_ms=2000, multiplier=2, jitter=0.25)
    for attempt in range(12):
        d = backoff_ms(pol, "site.a", attempt)
        raw = min(25 * 2 ** attempt, 2000)
        assert raw <= d <= raw * 1.25
        assert d == backoff_ms(pol, "site.a", attempt)   # reproducible
    # distinct sites jitter differently at the same attempt
    assert backoff_ms(pol, "site.a", 0) != backoff_ms(pol, "site.b", 0)


def test_call_with_retry_recovers_transient():
    calls = []

    def op():
        calls.append(1)
        if len(calls) < 3:
            raise InjectedTransientError("flaky")
        return "ok"

    slept = []
    assert call_with_retry(op, "t", policy=RetryPolicy(attempts=3),
                           sleep=slept.append) == "ok"
    assert len(calls) == 3 and len(slept) == 2
    assert stats.snapshot()["retries"] == 2
    assert stats.snapshot()["retry_sites"] == {"t": 2}


def test_call_with_retry_exhausts_attempts():
    def op():
        raise InjectedTransientError("always")

    with pytest.raises(InjectedTransientError):
        call_with_retry(op, "t", policy=RetryPolicy(attempts=3),
                        sleep=lambda s: None)
    assert stats.snapshot()["retries"] == 2   # attempts-1 retries


@pytest.mark.parametrize("exc", [ValueError("fatal"),
                                 SimulatedResourceExhausted("oom")])
def test_call_with_retry_propagates_nonretryable(exc):
    calls = []

    def op():
        calls.append(1)
        raise exc

    with pytest.raises(type(exc)):
        call_with_retry(op, "t", sleep=lambda s: None)
    assert len(calls) == 1                    # no second attempt
    assert stats.snapshot()["retries"] == 0


def test_retry_kill_switch(monkeypatch):
    monkeypatch.setenv("DMLP_TPU_RESILIENCE", "0")

    def op():
        raise InjectedTransientError("flaky")

    with pytest.raises(InjectedTransientError):
        call_with_retry(op, "t", sleep=lambda s: None)
    assert stats.snapshot()["retries"] == 0


def test_call_with_timeout_result_error_and_deadline():
    assert call_with_timeout(lambda: 42, 5.0, site="ok") == 42
    with pytest.raises(ValueError, match="boom"):
        call_with_timeout(lambda: (_ for _ in ()).throw(ValueError("boom")),
                          5.0, site="err")
    ev = None

    def hang():
        time.sleep(5)

    t0 = time.monotonic()
    with pytest.raises(OperationTimeout, match="exceeded"):
        call_with_timeout(hang, 0.05, site="hung")
    assert time.monotonic() - t0 < 2.0        # did not wait out the hang
    assert stats.snapshot()["timeouts"] == 1
    del ev


# -- degradation ladder ------------------------------------------------------

class _FakeEngine:
    _degrade_rung = "fused"
    last_degrade_rung = "fused"


def test_ladder_steps_down_per_oom():
    eng = _FakeEngine()
    seen = []

    def solve(inp):
        seen.append(eng._degrade_rung)
        if len(seen) < 5:
            raise SimulatedResourceExhausted("RESOURCE_EXHAUSTED")
        return "answer"

    assert degrade.run_ladder(eng, None, solve) == "answer"
    assert seen == ["lowp", "prune", "fused", "heuristic", "streaming"]
    assert eng.last_degrade_rung == "streaming"
    assert eng._degrade_rung == "fused"       # restored after the run
    assert stats.snapshot()["degradations"] == \
        ["lowp->prune", "prune->fused", "fused->heuristic",
         "heuristic->streaming"]


def test_ladder_propagates_non_oom():
    eng = _FakeEngine()

    def solve(inp):
        raise ValueError("a real bug")

    with pytest.raises(ValueError):
        degrade.run_ladder(eng, None, solve)
    assert stats.snapshot()["degradations"] == []


# -- engine-level byte-identical recovery ------------------------------------

def _small_input():
    return parse_input_text(
        generate_input_text(96, 12, 4, -5, 5, 1, 8, 3, seed=21))


def _engine():
    return SingleChipEngine(EngineConfig(data_block=32, query_block=8))


def test_engine_recovers_transients_byte_identical():
    inp = _small_input()
    golden = format_results(knn_golden(inp))
    inject.install(sched([
        {"site": "single.stage_put", "kind": "transient", "times": 2},
        {"site": "single.fetch", "kind": "transient"},
    ]))
    out = format_results(_engine().run(inp))
    assert out == golden
    snap = stats.snapshot()
    assert snap["retries"] >= 3 and snap["faults_injected"] == 3


@pytest.mark.parametrize("times,rung", [(1, "prune"),
                                        (2, "fused"),
                                        (3, "heuristic"),
                                        (4, "streaming"),
                                        (5, "host")])
def test_engine_ladder_byte_identical(times, rung):
    inp = _small_input()
    golden = format_results(knn_golden(inp))
    inject.install(sched([
        {"site": "single.stage_put", "kind": "oom", "times": times}]))
    eng = _engine()
    assert format_results(eng.run(inp)) == golden
    assert eng.last_degrade_rung == rung
    assert len(stats.snapshot()["degradations"]) == times


def test_io_parse_corrupt_recovers():
    import io as _io
    text = generate_input_text(64, 8, 3, -5, 5, 1, 8, 3, seed=4)
    golden = parse_input_text(text)
    inject.install(sched([{"site": "io.parse", "kind": "corrupt"}]))
    inp = parse_input(_io.StringIO(text))
    np.testing.assert_array_equal(inp.data_attrs, golden.data_attrs)
    np.testing.assert_array_equal(inp.ks, golden.ks)
    assert stats.snapshot()["retries"] == 1   # re-parse was recorded


def test_resilient_get_env_deadline(monkeypatch):
    """$DMLP_TPU_OP_TIMEOUT_S bounds each readback attempt; a blown
    deadline classifies transient (retried) and bumps `timeouts`."""
    import jax.numpy as jnp

    from dmlp_tpu.engine import single as eng_single
    monkeypatch.setenv("DMLP_TPU_OP_TIMEOUT_S", "30")
    np.testing.assert_array_equal(
        eng_single.resilient_get(jnp.arange(4)), [0, 1, 2, 3])

    monkeypatch.setenv("DMLP_TPU_OP_TIMEOUT_S", "0.05")
    monkeypatch.setattr(eng_single.jax, "device_get",
                        lambda v: time.sleep(0.5))
    with pytest.raises(OperationTimeout):
        eng_single.resilient_get([1])
    assert stats.snapshot()["timeouts"] >= 1

    # With the kill switch the wrapper is a DIRECT call: no worker
    # thread, no deadline — the slow get just completes.
    monkeypatch.setenv("DMLP_TPU_RESILIENCE", "0")
    before = stats.snapshot()["timeouts"]
    eng_single.resilient_get([1])
    assert stats.snapshot()["timeouts"] == before


# -- supervision -------------------------------------------------------------

def _rank_argv(body: str):
    return [sys.executable, "-c", body]


def test_supervised_healthy_cluster_returns_rank0_output(tmp_path):
    out, err, report = __import__(
        "dmlp_tpu.resilience.supervise", fromlist=["run_supervised"]
    ).run_supervised(
        lambda attempt: [_rank_argv("print('hello from rank0')"),
                         _rank_argv("pass")],
        str(tmp_path), cluster_timeout_s=60, max_launches=1)
    assert b"hello from rank0" in out
    assert report["launches"][0]["ok"] and not report["fallback"]


def test_supervised_relaunch_then_success(tmp_path):
    from dmlp_tpu.resilience.supervise import run_supervised
    marker = tmp_path / "attempt0-failed"

    def make_cluster(attempt):
        if attempt == 0:
            return [_rank_argv(f"import pathlib, sys; "
                               f"pathlib.Path(r'{marker}').touch(); "
                               "sys.exit(3)")]
        return [_rank_argv("print('recovered')")]

    out, _, report = run_supervised(make_cluster, str(tmp_path),
                                    cluster_timeout_s=60, max_launches=2)
    assert marker.exists()
    assert b"recovered" in out
    assert [l["ok"] for l in report["launches"]] == [False, True]
    assert stats.snapshot()["restarts"] == 1


def test_supervised_exhausted_falls_back(tmp_path):
    from dmlp_tpu.resilience.supervise import run_supervised
    out, _, report = run_supervised(
        lambda attempt: [_rank_argv("import sys; sys.exit(9)")],
        str(tmp_path), cluster_timeout_s=60, max_launches=2,
        fallback=lambda: (b"degraded-answer", b""))
    assert out == b"degraded-answer"
    assert report["fallback"] is True
    assert "cluster->single-process" in stats.snapshot()["degradations"]


def test_supervised_hung_rank_hits_deadline(tmp_path):
    from dmlp_tpu.resilience.supervise import ClusterFailure, run_supervised
    with pytest.raises(ClusterFailure) as ei:
        run_supervised(
            lambda attempt: [_rank_argv("import time; time.sleep(60)")],
            str(tmp_path), cluster_timeout_s=0.5, poll_s=0.05,
            max_launches=1)
    assert "deadline" in str(ei.value)


def test_heartbeat_thread_touches_file(tmp_path):
    from dmlp_tpu.resilience.supervise import start_heartbeat
    path = str(tmp_path / "hb")
    stop = start_heartbeat(path, interval_s=0.05)
    try:
        deadline = time.monotonic() + 5
        while not os.path.exists(path) and time.monotonic() < deadline:
            time.sleep(0.02)
        assert os.path.exists(path)
    finally:
        stop.set()


# -- CLI plumbing ------------------------------------------------------------

def test_cli_faults_flag_and_fault_log(tmp_path):
    """--faults through the real engine CLI: byte-identical output,
    deterministic injection log, resilience block in the metrics."""
    text = generate_input_text(128, 12, 4, -5, 5, 1, 8, 3, seed=9)
    inp_path = tmp_path / "in.txt"
    inp_path.write_text(text)
    sched_path = tmp_path / "sched.json"
    sched_path.write_text(json.dumps({"schema": 1, "seed": 3, "faults": [
        {"site": "single.fetch", "kind": "transient"}]}))

    def run(extra, env_extra):
        env = dict(os.environ)
        env.update(env_extra)
        with open(inp_path, "rb") as f:
            p = subprocess.run(
                [sys.executable, "-m", "dmlp_tpu"] + extra, stdin=f,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
                timeout=300)
        assert p.returncode == 0, p.stderr.decode()
        return p.stdout

    golden = run([], {})
    log1 = tmp_path / "log1.json"
    metrics = tmp_path / "metrics.jsonl"
    faulted = run(["--faults", str(sched_path),
                   "--metrics", str(metrics)],
                  {"DMLP_TPU_FAULT_LOG": str(log1)})
    assert faulted == golden
    with open(metrics) as f:
        summary = [json.loads(ln) for ln in f if ln.strip()][-1]
    assert summary["resilience"]["retries"] >= 1
    assert summary["resilience"]["faults_injected"] == 1
    log2 = tmp_path / "log2.json"
    run(["--faults", str(sched_path)], {"DMLP_TPU_FAULT_LOG": str(log2)})
    assert log1.read_text() == log2.read_text()   # deterministic replay


def test_distributed_entry_faults_and_log(tmp_path):
    """--faults + $DMLP_TPU_FAULT_LOG through the distributed contract
    entry: a transient rank-solve fault recovers byte-identically and
    the injection log is persisted (regression: the entry used to skip
    the log write entirely)."""
    text = generate_input_text(96, 10, 3, -5, 5, 1, 8, 3, seed=13)
    inp_path = tmp_path / "in.txt"
    inp_path.write_text(text)
    sched_path = tmp_path / "sched.json"
    sched_path.write_text(json.dumps({"schema": 1, "seed": 4, "faults": [
        {"site": "dist.rank_solve", "kind": "transient"}]}))
    log_path = tmp_path / "dlog.json"

    def run(extra, env_extra):
        env = dict(os.environ)
        env.update(env_extra)
        p = subprocess.run(
            [sys.executable, "-m", "dmlp_tpu.distributed",
             "--input", str(inp_path)] + extra,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            timeout=300)
        assert p.returncode == 0, p.stderr.decode()
        return p.stdout

    golden = run([], {})
    faulted = run(["--faults", str(sched_path)],
                  {"DMLP_TPU_FAULT_LOG": str(log_path)})
    assert faulted == golden
    log = json.loads(log_path.read_text())["log"]
    assert [e["site"] for e in log if e["fired"]] == ["dist.rank_solve"]
