"""Streaming SLO engine tests: windowed quantiles vs numpy, the
Sampler drift fix, burn-rate hysteresis / flap suppression, Theil–Sen
trends, the predictive autoscale policy, the slo.alert trace contract,
and the ramp record."""

import json
import math
import threading

import numpy as np
import pytest

from dmlp_tpu.fleet.autoscale import (predictive_target_replicas,
                                      target_replicas)
from dmlp_tpu.obs import slo as obs_slo
from dmlp_tpu.obs import telemetry
from dmlp_tpu.obs.telemetry import Histogram, Registry

REL = telemetry.HIST_QUANTILE_REL_ERROR


class FakeClock:
    """Injectable monotonic clock for deterministic window rotation."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def _windowed_hist(sub_s=1.0, max_window_s=120.0, clock=None):
    clock = clock or FakeClock()
    h = Histogram("t.lat_ms", unit="ms")
    h.enable_windows(max_window_s=max_window_s, sub_s=sub_s,
                     time_fn=clock)
    return h, clock


# ---------------------------------------------------------------------------
# windowed quantiles
# ---------------------------------------------------------------------------


def test_window_quantile_matches_numpy_within_bound():
    h, clock = _windowed_hist(sub_s=1.0)
    rng = np.random.default_rng(7)
    window = []
    # 30 s of samples, 20 per second, lognormal latencies.
    for _ in range(30):
        for v in np.exp(rng.normal(1.5, 0.6, 20)):
            h.observe(float(v))
            window.append(float(v))
        clock.advance(1.0)
    for q in (0.5, 0.95, 0.99):
        est = h.window_quantile(60.0, q)       # window covers all
        exact = float(np.percentile(window, q * 100))
        assert est == pytest.approx(exact, rel=REL + 1e-6)


def test_window_quantile_partial_window_startup():
    """A window longer than the elapsed time sees every sample — a
    cold ring must not report NaN or a truncated distribution."""
    h, clock = _windowed_hist(sub_s=1.0)
    vals = [1.0, 2.0, 3.0, 4.0, 5.0]
    for v in vals:
        h.observe(v)
        clock.advance(0.1)         # only 0.5 s elapsed, window is 60 s
    snap = h.window_snapshot(60.0)
    assert snap["count"] == len(vals)
    assert snap["min"] == 1.0 and snap["max"] == 5.0
    assert snap["p50"] == pytest.approx(3.0, rel=REL + 1e-6)


def test_window_rotation_ages_out_old_samples():
    h, clock = _windowed_hist(sub_s=1.0)
    for _ in range(10):
        h.observe(100.0)           # old: all slow
        clock.advance(1.0)
    # t=10; the 10 s window still sees them
    assert h.window_snapshot(10.0)["count"] == 10
    clock.advance(20.0)            # t=30: all aged out of a 10 s window
    for _ in range(5):
        h.observe(1.0)
        clock.advance(1.0)
    snap = h.window_snapshot(10.0)
    assert snap["count"] == 5
    assert snap["max"] == 1.0      # the 100 ms outliers are GONE
    # ...while the cumulative histogram still remembers everything
    assert h.count == 15
    assert h.quantile(1.0) == 100.0


def test_window_rotation_boundary_exact_multiple():
    """Samples landing exactly on a sub-window boundary open a new
    frame (>=, not >) and the trailing-window cutoff keeps at most one
    sub-window of slack."""
    h, clock = _windowed_hist(sub_s=2.0)
    h.observe(1.0)                 # frame [0, 2)
    clock.advance(2.0)             # exactly one sub-window
    h.observe(2.0)                 # must open frame [2, 4)
    assert len(h._frames) == 2
    assert h._frames[-1].start == pytest.approx(2.0)
    clock.advance(2.0)             # t=4
    # 2 s window: cutoff 2.0 — frame [0,2) has start+sub == cutoff,
    # fully aged; frame [2,4) remains.
    assert h.window_snapshot(2.0)["count"] == 1


def test_window_idle_gap_keeps_grid_alignment():
    """An idle gap must not stretch one frame across it (stale samples
    would then never age out)."""
    h, clock = _windowed_hist(sub_s=1.0)
    h.observe(50.0)
    clock.advance(7.3)             # idle gap
    h.observe(1.0)
    # New frame starts on the 1 s grid (t=7.0), not at 0.0
    assert h._frames[-1].start == pytest.approx(7.0)
    clock.advance(0.0)
    assert h.window_snapshot(2.0)["count"] == 1    # the old one aged


def test_window_above_splits_at_bucket_resolution():
    h, clock = _windowed_hist(sub_s=1.0)
    for v in (1.0, 2.0, 50.0, 60.0, 70.0):
        h.observe(v)
    bad, total = h.window_above(30.0, 10.0)
    assert (bad, total) == (3, 5)
    # max <= threshold short-circuits exactly: all good
    assert h.window_above(30.0, 70.0) == (0, 5)
    assert h.window_above(30.0, 1e9) == (0, 5)


def test_window_apis_require_enablement():
    h = Histogram("t.plain")
    h.observe(1.0)
    assert not h.windowed
    with pytest.raises(ValueError, match="no window ring"):
        h.window_quantile(10.0, 0.5)
    with pytest.raises(ValueError, match="no window ring"):
        h.window_above(10.0, 1.0)


def test_enable_windows_idempotent_and_validates_geometry():
    h, clock = _windowed_hist(sub_s=1.0)
    h.enable_windows(sub_s=99.0)       # second call: no-op, keeps 1.0
    assert h._sub_s == 1.0
    with pytest.raises(ValueError, match="window geometry"):
        Histogram("t.bad").enable_windows(max_window_s=1.0, sub_s=2.0)
    with pytest.raises(ValueError, match="window geometry"):
        Histogram("t.bad2").enable_windows(sub_s=0.0)


def test_windowed_histogram_concurrent_observe_and_read():
    """Writers hammer observe() while readers merge windows — the
    single-lock discipline must keep every merged state consistent
    (count equals the sum of its bucket counts; no exceptions)."""
    h, clock = _windowed_hist(sub_s=0.001)   # rotate constantly
    clock_lock = threading.Lock()
    errors = []
    N, W = 2000, 4

    def writer(seed):
        rng = np.random.default_rng(seed)
        for v in np.exp(rng.normal(1.0, 0.5, N)):
            h.observe(float(v))
            with clock_lock:
                clock.advance(1e-5)

    def reader():
        try:
            for _ in range(200):
                snap = h.window_snapshot(10.0)
                assert snap["count"] >= 0
                q = h.window_quantile(10.0, 0.99)
                assert math.isnan(q) or q > 0
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(W)] + [threading.Thread(target=reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert h.count == N * W
    # every observation landed in some frame
    assert sum(fr.count for fr in h._frames) <= N * W
    snap = h.window_snapshot(1e6)
    assert snap["count"] == N * W


# ---------------------------------------------------------------------------
# Sampler interval drift (the bugfix satellite)
# ---------------------------------------------------------------------------


def test_next_deadline_keeps_phase_under_slow_ticks():
    """Deadline-anchored schedule: sampling work that takes longer
    than the interval SKIPS the missed slots instead of drifting the
    phase or bursting to catch up."""
    nd = telemetry.Sampler._next_deadline
    # on-time: next deadline is exactly one interval later
    deadline, delay = nd(10.0, 10.2, 1.0)
    assert deadline == pytest.approx(11.0)
    assert delay == pytest.approx(0.8)
    # work overran by 2.7 intervals: the schedule skips to the next
    # FUTURE grid point (13.0 + 1.0 = 14.0), never a negative delay
    deadline, delay = nd(10.0, 13.7, 1.0)
    assert deadline == pytest.approx(14.0)
    assert delay == pytest.approx(0.3)
    assert deadline % 1.0 == pytest.approx(0.0)   # phase preserved


def test_next_deadline_no_drift_accumulation():
    """The old sleep-after-work loop drifted by the work time every
    tick; the grid schedule's deadlines stay exact multiples."""
    nd = telemetry.Sampler._next_deadline
    deadline = 0.0
    work = 0.13                    # per-tick work time
    now = 0.0
    fired = []
    for _ in range(50):
        now = deadline + work      # wake late by the work time
        deadline, delay = nd(deadline, now, 1.0)
        fired.append(deadline)
        assert delay >= 0.0
    # after 50 ticks the schedule is still on the integer grid —
    # zero accumulated drift (old behavior: 50 * 0.13 = 6.5 s late)
    assert fired[-1] == pytest.approx(50.0)


def test_next_deadline_never_negative_delay():
    nd = telemetry.Sampler._next_deadline
    deadline = 5.0
    for now in (5.0, 5.999, 6.0, 17.42, 1000.0):
        nxt, delay = nd(deadline, now, 0.5)
        assert nxt > now or delay == 0.0
        assert delay >= 0.0


# ---------------------------------------------------------------------------
# objective grammar + Theil–Sen
# ---------------------------------------------------------------------------


def test_parse_objective_latency_and_availability():
    o = obs_slo.parse_objective(
        "fleet.request_latency_ms p99 < 50 over 1m")
    assert o.kind == "latency"
    assert o.metric == "fleet.request_latency_ms"
    assert o.quantile == pytest.approx(0.99)
    assert o.threshold == 50.0
    assert o.window_s == 60.0
    assert o.budget == pytest.approx(0.01)
    assert o.name == "fleet.request_latency_ms:p99"
    a = obs_slo.parse_objective(
        "serve.ok/serve.total availability > 0.995 over 5m")
    assert a.kind == "availability"
    assert (a.good, a.total) == ("serve.ok", "serve.total")
    assert a.budget == pytest.approx(0.005)
    assert a.window_s == 300.0
    assert "availability" in a.describe()


def test_parse_objective_rejects_garbage():
    for bad in ("latency_ms p99 over 1m", "p99 < 50", "m q50 < 1",
                "a/b availability > 2 over 1m", ""):
        with pytest.raises(ValueError):
            obs_slo.parse_objective(bad)
    with pytest.raises(ValueError):
        obs_slo.parse_window("soon")
    assert obs_slo.parse_window("250ms") == pytest.approx(0.25)
    assert obs_slo.parse_window("2") == 2.0


def test_theil_sen_robust_and_degenerate():
    pts = [(float(i), 2.0 * i + 1.0) for i in range(10)]
    assert obs_slo.theil_sen(pts) == pytest.approx(2.0)
    # one wild outlier cannot bend the median of pairwise slopes much
    pts[5] = (5.0, 1000.0)
    assert obs_slo.theil_sen(pts) == pytest.approx(2.0, abs=0.5)
    assert math.isnan(obs_slo.theil_sen([]))
    assert math.isnan(obs_slo.theil_sen([(1.0, 2.0)]))
    assert math.isnan(obs_slo.theil_sen([(1.0, 2.0), (1.0, 3.0)]))


# ---------------------------------------------------------------------------
# burn-rate lifecycle: pure rule + live evaluator
# ---------------------------------------------------------------------------


def test_next_state_lifecycle_edges():
    ns = obs_slo.SLOEvaluator.next_state
    OK, P, F = obs_slo.OK, obs_slo.PENDING, obs_slo.FIRING
    # ok enters pending on a hot fast window, never jumps to firing
    assert ns(OK, True, True, 99, 0, 2, 3) == P
    assert ns(OK, False, False, 0, 99, 2, 3) == OK
    # pending -> firing needs BOTH windows hot AND the streak
    assert ns(P, True, True, 2, 0, 2, 3) == F
    assert ns(P, True, True, 1, 0, 2, 3) == P
    assert ns(P, True, False, 99, 0, 2, 3) == P
    # pending clears only after the good streak
    assert ns(P, False, True, 0, 3, 2, 3) == OK
    assert ns(P, False, True, 0, 2, 2, 3) == P
    # firing clears only on both-cold + streak; no firing -> pending
    assert ns(F, False, False, 0, 3, 2, 3) == OK
    assert ns(F, False, False, 0, 2, 2, 3) == F
    assert ns(F, False, True, 0, 99, 2, 3) == F
    assert ns(F, True, True, 5, 0, 2, 3) == F


def _make_eval(reg, clock, spec="svc.lat_ms p90 < 10 over 60s",
               **kw):
    kw.setdefault("fast_s", 10.0)
    kw.setdefault("sub_s", 1.0)
    kw.setdefault("for_ticks", 2)
    kw.setdefault("clear_ticks", 2)
    kw.setdefault("flight_dump", False)
    return obs_slo.SLOEvaluator([spec], reg, time_fn=clock, **kw)


def test_evaluator_breach_fires_and_recovers_one_cycle():
    reg = Registry()
    clock = FakeClock()
    ev = _make_eval(reg, clock)
    obj = "svc.lat_ms:p90"
    h = reg.get("svc.lat_ms")
    assert h is not None and h.windowed   # bound by the evaluator
    # healthy traffic: 5 fast samples per second for 20 s
    for _ in range(20):
        for _ in range(5):
            h.observe(1.0)
        ev.tick()
        clock.advance(1.0)
    assert ev.state(obj) == obs_slo.OK
    # overload: every sample blows the 10 ms threshold
    states = []
    for _ in range(6):
        for _ in range(5):
            h.observe(100.0)
        ev.tick()
        states.append(ev.state(obj))
        clock.advance(1.0)
    assert obs_slo.PENDING in states
    assert ev.state(obj) == obs_slo.FIRING
    sig = ev.signals(obj)
    assert sig["burn_fast"] > 1.0
    assert sig["burn_slow"] > 1.0
    # recovery: jump past the slow window so the bad samples age out
    clock.advance(120.0)
    for _ in range(5):
        for _ in range(5):
            h.observe(1.0)
        ev.tick()
        clock.advance(1.0)
    assert ev.state(obj) == obs_slo.OK
    assert ev.alert_cycles(obj) == 1
    seq = [(t["prev"], t["state"]) for t in ev.transitions]
    assert seq == [("ok", "pending"), ("pending", "firing"),
                   ("firing", "ok")]
    # transitions counter labeled by entered state
    c = reg.get("slo.transitions")
    assert c.value("pending") == 1.0
    assert c.value("firing") == 1.0
    assert c.value("ok") == 1.0


def test_evaluator_short_spike_parks_in_pending():
    """Flap suppression: a one-tick spike must go ok -> pending -> ok
    without EVER firing (for_ticks hysteresis)."""
    reg = Registry()
    clock = FakeClock()
    ev = _make_eval(reg, clock, for_ticks=3)
    obj = "svc.lat_ms:p90"
    h = reg.get("svc.lat_ms")
    for _ in range(15):
        for _ in range(5):
            h.observe(1.0)
        ev.tick()
        clock.advance(1.0)
    for _ in range(10):             # one bad burst, one tick
        h.observe(100.0)
    ev.tick()
    assert ev.state(obj) == obs_slo.PENDING
    clock.advance(15.0)             # the spike ages out of fast window
    for _ in range(4):
        for _ in range(5):
            h.observe(1.0)
        ev.tick()
        clock.advance(1.0)
    assert ev.state(obj) == obs_slo.OK
    states = [t["state"] for t in ev.transitions]
    assert obs_slo.FIRING not in states
    assert states == ["pending", "ok"]


def test_evaluator_availability_burn_from_counters():
    reg = Registry()
    clock = FakeClock()
    ev = _make_eval(reg, clock,
                    spec="svc.good/svc.req availability > 0.9 over 60s")
    obj = "svc.req:availability"
    good, total = reg.counter("svc.good"), reg.counter("svc.req")
    for _ in range(20):
        good.inc(10)
        total.inc(10)
        ev.tick()
        clock.advance(1.0)
    assert ev.state(obj) == obs_slo.OK
    assert ev.signals(obj)["burn_fast"] == 0.0
    for _ in range(6):              # outage: all requests fail
        total.inc(10)
        ev.tick()
        clock.advance(1.0)
    assert ev.state(obj) == obs_slo.FIRING
    assert ev.signals(obj)["burn_fast"] > 1.0


def test_evaluator_sample_fn_override_feeds_availability():
    """The router's merged-scrape hook: sample_fn replaces registry
    counter reads entirely."""
    reg = Registry()
    clock = FakeClock()
    cum = {"good": 0.0, "total": 0.0}
    obj = obs_slo.parse_objective(
        "f.good/f.total availability > 0.9 over 60s")
    obj.sample_fn = lambda: (cum["good"], cum["total"])
    ev = obs_slo.SLOEvaluator([obj], reg, fast_s=10.0, sub_s=1.0,
                              for_ticks=1, clear_ticks=1,
                              time_fn=clock, flight_dump=False)
    for _ in range(10):
        cum["good"] += 5
        cum["total"] += 10          # 50% failures, budget 10%
        ev.tick()
        clock.advance(1.0)
    assert ev.state("f.total:availability") == obs_slo.FIRING


def test_evaluator_gauges_and_openmetrics_family():
    reg = Registry()
    clock = FakeClock()
    ev = _make_eval(reg, clock)
    obj = "svc.lat_ms:p90"
    h = reg.get("svc.lat_ms")
    for _ in range(5):
        h.observe(1.0)
        ev.tick()
        clock.advance(1.0)
    assert reg.get("slo.state").value(obj) == 0.0
    assert reg.get("slo.ok").value(obj) == 1.0
    assert reg.get("slo.firing").value(obj) == 0.0
    assert reg.get("slo.burn_rate.fast").value(obj) == 0.0
    text = reg.to_openmetrics()
    assert "# TYPE slo_state gauge" in text
    assert "slo_burn_rate_fast" in text
    assert telemetry.validate_openmetrics(text) == []
    snap = ev.snapshot()
    assert snap["objectives"][obj]["state"] == "ok"
    assert snap["transitions"] == 0


def test_evaluator_trend_slope_and_projection():
    """A steadily degrading latency series yields a positive Theil–Sen
    slope and a finite projected crossing — the predictive signal."""
    reg = Registry()
    clock = FakeClock()
    ev = _make_eval(reg, clock, spec="svc.lat_ms p90 < 100 over 120s",
                    fast_s=5.0)
    obj = "svc.lat_ms:p90"
    h = reg.get("svc.lat_ms")
    lat = 10.0
    for _ in range(30):
        for _ in range(10):
            h.observe(lat)
        ev.tick()
        clock.advance(1.0)
        lat += 2.0                  # +2 ms every second, toward 100
    sig = ev.signals(obj)
    assert sig["slope_ms_per_s"] > 0.5
    assert math.isfinite(sig["projected_s"])
    assert 0.0 < sig["projected_s"] < 120.0
    assert ev.state(obj) == obs_slo.OK     # not yet breaching


def test_evaluator_duplicate_objective_names_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        obs_slo.SLOEvaluator(
            ["m.x p99 < 5 over 10s", "m.x p99 < 9 over 10s"],
            Registry())


# ---------------------------------------------------------------------------
# predictive autoscale policy (pure)
# ---------------------------------------------------------------------------


def _sig(**kw):
    base = {"burn_fast": 0.0, "burn_slow": 0.0,
            "slope_ms_per_s": 0.0, "projected_s": math.inf,
            "p_fast": 40.0, "threshold": 50.0}
    base.update(kw)
    return base


def test_predictive_scales_up_on_burn():
    assert predictive_target_replicas(_sig(burn_fast=2.0), 2, 1, 4) == 3


def test_predictive_scales_up_before_breach_on_projection():
    """The leading signal: no budget burnt YET, but the slope projects
    a crossing inside the lead time -> scale now."""
    s = _sig(slope_ms_per_s=1.5, projected_s=6.0, p_fast=41.0)
    assert s["burn_fast"] == 0.0
    assert predictive_target_replicas(s, 2, 1, 4, lead_time_s=10.0) == 3
    # projection beyond the horizon: hold
    s = _sig(slope_ms_per_s=0.1, projected_s=90.0)
    assert predictive_target_replicas(s, 2, 1, 4, lead_time_s=10.0) == 2


def test_predictive_flat_load_is_a_fixed_point():
    """Flat load in the dead band between the up and down triggers
    must never oscillate: the decision is current, every time."""
    s = _sig(p_fast=40.0)           # calm but above down_margin * 50
    cur = 2
    for _ in range(50):
        cur = predictive_target_replicas(s, cur, 1, 4)
    assert cur == 2


def test_predictive_synthetic_ramp_scales_before_reactive_would():
    """Synthetic ramp: latency climbing toward the threshold. The
    predictive policy steps up while p_fast is still under the
    threshold (burn 0); the reactive watermark policy, fed a
    per-replica load that has not yet crossed its high mark, holds —
    the lead the SLO signal buys."""
    p99, slope = 20.0, 4.0          # ms, ms/s
    cur_pred = cur_react = 1
    scaled_at_p99 = None
    for step in range(20):
        projected = (50.0 - p99) / slope if p99 < 50.0 else 0.0
        sig = _sig(slope_ms_per_s=slope, projected_s=projected,
                   p_fast=p99, burn_fast=0.0 if p99 < 50.0 else 5.0)
        nxt = predictive_target_replicas(sig, cur_pred, 1, 4,
                                         lead_time_s=6.0)
        if nxt > cur_pred and scaled_at_p99 is None:
            scaled_at_p99 = p99
        cur_pred = nxt
        # reactive arm: queue load stays under the watermark until the
        # breach is already happening
        load = [0.5 if p99 < 50.0 else 8.0] * 6
        cur_react = target_replicas(load, cur_react, 1, 4, 4.0, 0.25)
        p99 += slope
    assert scaled_at_p99 is not None and scaled_at_p99 < 50.0
    assert cur_pred >= 2            # predictive moved...
    # ...and it moved BEFORE the threshold; reactive only after
    assert cur_react >= 2           # (eventually, once breaching)


def test_predictive_scales_down_only_when_calm():
    calm = _sig(p_fast=10.0)        # well under 0.5 * 50
    assert predictive_target_replicas(calm, 3, 1, 4) == 2
    # any warmth blocks the down-step
    assert predictive_target_replicas(
        _sig(p_fast=10.0, burn_slow=0.2), 3, 1, 4) == 3
    assert predictive_target_replicas(
        _sig(p_fast=10.0, slope_ms_per_s=0.5), 3, 1, 4) == 3
    # clamped at the floor / ceiling
    assert predictive_target_replicas(calm, 1, 1, 4) == 1
    assert predictive_target_replicas(_sig(burn_fast=9.0), 4, 1, 4) == 4
    # NaN slope (cold trend ring) is treated as flat, not hot
    nan_sig = _sig(p_fast=10.0)
    nan_sig["slope_ms_per_s"] = math.nan
    assert predictive_target_replicas(nan_sig, 3, 1, 4) == 2


# ---------------------------------------------------------------------------
# slo.alert stream validation (tools/check_trace.py --fleet)
# ---------------------------------------------------------------------------


def _fleet_doc_with_alerts(alerts):
    evs = [{"name": "process_name", "ph": "M", "pid": 1,
            "args": {"name": "router"}},
           {"name": "fleet.clock_sync", "ph": "i", "ts": 0.0, "s": "t",
            "pid": 1, "tid": 0, "args": {"unix_ms": 0}}]
    for i, args in enumerate(alerts):
        evs.append({"name": "slo.alert", "ph": "i",
                    "ts": 100.0 + 10.0 * i, "s": "t", "pid": 1,
                    "tid": 0, "args": args})
    return {"traceEvents": evs,
            "fleet": {"processes": {"router": {"pid": 1}}}}


def _alert(prev, state, objective="lat:p99", window="1m"):
    return {"objective": objective, "prev": prev, "state": state,
            "window": window, "burn_fast": 2.0, "burn_slow": 1.5}


def _check(tmp_path, doc):
    from tools.check_trace import check_fleet_trace
    p = tmp_path / "merged.json"
    p.write_text(json.dumps(doc))
    check_fleet_trace(str(p))


def test_check_fleet_accepts_legal_alert_cycle(tmp_path, capsys):
    _check(tmp_path, _fleet_doc_with_alerts([
        _alert("ok", "pending"), _alert("pending", "firing"),
        _alert("firing", "ok"), _alert("ok", "pending"),
        _alert("pending", "ok")]))
    out = capsys.readouterr().out
    assert "5 slo.alert(s)" in out


def test_check_fleet_rejects_tampered_alert_streams(tmp_path, capsys):
    from tools.check_trace import check_fleet_trace  # noqa: F401
    # a firing with no pending before it (ok -> firing jump)
    with pytest.raises(SystemExit):
        _check(tmp_path, _fleet_doc_with_alerts([
            _alert("ok", "firing")]))
    capsys.readouterr()
    # prev does not chain (out-of-order / reordered stream)
    with pytest.raises(SystemExit):
        _check(tmp_path, _fleet_doc_with_alerts([
            _alert("ok", "pending"), _alert("ok", "pending")]))
    capsys.readouterr()
    # firing -> pending shortcut is not a legal hysteresis edge
    with pytest.raises(SystemExit):
        _check(tmp_path, _fleet_doc_with_alerts([
            _alert("ok", "pending"), _alert("pending", "firing"),
            _alert("firing", "pending")]))
    capsys.readouterr()
    # missing attribution fields
    with pytest.raises(SystemExit):
        _check(tmp_path, _fleet_doc_with_alerts([
            {"prev": "ok", "state": "pending", "window": "1m"}]))
    capsys.readouterr()
    with pytest.raises(SystemExit):
        _check(tmp_path, _fleet_doc_with_alerts([
            {"objective": "lat:p99", "prev": "ok",
             "state": "pending"}]))
    capsys.readouterr()


def test_check_fleet_alert_streams_are_per_objective(tmp_path, capsys):
    """Interleaved objectives each chain independently."""
    _check(tmp_path, _fleet_doc_with_alerts([
        _alert("ok", "pending", objective="a:p99"),
        _alert("ok", "pending", objective="b:p95"),
        _alert("pending", "firing", objective="a:p99"),
        _alert("pending", "ok", objective="b:p95"),
        _alert("firing", "ok", objective="a:p99")]))
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the ramp record
# ---------------------------------------------------------------------------


def _ramp_steps():
    def step(speed, p99, state, cycles, bf, replicas):
        return {"speed": speed, "level": f"x{speed:g}",
                "metrics": {"p99_ms": p99, "errors": 0, "rejected": 0,
                            "offered_qps": 10.0 * speed},
                "slo": {"replicas": replicas, "objectives": {
                    "lat:p99": {"state": state, "cycles": cycles,
                                "burn_fast": bf, "burn_slow": bf / 2}}}}
    return [step(1, 10.0, "ok", 0, 0.0, 1),
            step(2, 20.0, "ok", 0, 0.5, 2),
            step(4, 30.0, "ok", 0, 0.8, 2)]


def test_ramp_record_summarizes_arm():
    from dmlp_tpu.fleet.loadgen import ramp_record
    rec = ramp_record("predictive", "lat:p99", _ramp_steps(),
                      replicas=1, trace="t.jsonl")
    assert rec.kind == "slo"
    assert rec.config["arm"] == "predictive"
    assert rec.config["levels"] == ["x1", "x2", "x4"]
    m = rec.metrics
    assert m["breach_cycles"] == 0
    assert m["worst_state_level"] == 0
    assert m["max_burn_fast"] == pytest.approx(0.8)
    assert m["replicas_final"] == 2
    assert m["peak_p99_ms"] == 30.0
    # a reactive arm that fired shows it
    steps = _ramp_steps()
    steps[-1]["slo"]["objectives"]["lat:p99"].update(
        state="firing", cycles=0, burn_fast=6.0)
    rec2 = ramp_record("reactive", "lat:p99", steps)
    assert (rec2.kind, rec2.config["arm"]) == ("slo", "reactive")
    assert rec2.metrics["breach_cycles"] >= 1
    assert rec2.metrics["worst_state_level"] == 2
