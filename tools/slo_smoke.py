#!/usr/bin/env python
"""SLO smoke: prove the streaming SLO engine end to end on CPU.

The ``make slo-smoke`` checker (wired into ``make test``). Two parts,
every failure exits nonzero with the reason named:

**Part 1 — seeded breach, deterministic clock.** An in-process
``SLOEvaluator`` over a private registry is driven with a fake clock:
a healthy plateau, an 8-sample latency spike, then recovery. Exactly
one alert cycle must fire — ``ok -> pending -> firing -> ok`` — with
the ``slo.alert`` flight events captured in a ``FLIGHT_slo_breach_*``
post-mortem dump and the ``slo_*`` gauge families present in a clean
OpenMetrics exposition.

**Part 2 — predictive-vs-reactive ramp A/B over a REAL fleet.** A
``serve.solve`` delay fault (the chaos harness's straggler-solve
site) gives each replica a deterministic sleep-bound service-time
floor, so on this single-core container a second replica genuinely
doubles fleet capacity. After calibrating the real per-replica
capacity, the same escalating open-loop ramp (mid level ~1.15x one
replica's capacity, hot level ~1.6x) is replayed against two
supervised fleets that declare the same two objectives — the
CUSTOMER objective (``fleet.request_latency_ms p99 < T over 60s``)
and a tighter internal CANARY (``p95 < T_low``):

* the **reactive** arm (watermark policy, threshold pinned out of
  reach) rides one replica into the hot level: the customer p99
  objective must reach ``firing`` — the breach alert, traced as
  ``slo.alert`` instants that ``tools/check_trace.py --fleet``
  validates after the causal merge;
* the **predictive** arm follows the canary's burn rate
  (``--slo-objective``): the mid level burns the canary, the policy
  scales to two replicas during the lead window, and the hot level
  lands with the customer objective never leaving ``ok``.

Both arms first replay a closed-loop slice byte-identical to the
float64 golden oracle (observability must not perturb the contract
channel). Each arm lands one kind="slo" RunRecord, read back at the
end (``RunRecord.load_all``).

Usage::

    python tools/slo_smoke.py --out outputs/slo \
        [--record outputs/slo/SLO_SMOKE.jsonl] [--round 17]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dmlp_tpu.fleet import harness as fh                  # noqa: E402
from dmlp_tpu.fleet import loadgen                        # noqa: E402
from dmlp_tpu.io.grammar import parse_input_text          # noqa: E402
from dmlp_tpu.obs import slo as slomod                    # noqa: E402
from dmlp_tpu.obs import telemetry                        # noqa: E402
from dmlp_tpu.obs.run import RunRecord                    # noqa: E402
from dmlp_tpu.obs.telemetry import validate_openmetrics   # noqa: E402
from dmlp_tpu.serve import client as sc                   # noqa: E402

# -- part-2 capacity model ----------------------------------------------------
# The injected straggler-solve delay makes each replica's micro-batch
# consumer sleep D_MS per batch: nominal per-replica capacity is
# BATCH_CAP queries per D_MS, i.e. ~213 q/s — sleep-bound, so a
# second replica genuinely adds fleet throughput even on one CPU
# core (the real solve + router + client CPU share stays well under
# the core at every ramp level).
D_MS = 150
BATCH_CAP = 32
NQ = 16                       # queries per request (2 requests/batch)
K = 8
C0 = BATCH_CAP * 1000.0 / D_MS

CORPUS = dict(num_data=2048, num_queries=NQ, num_attrs=8,
              min_attr=0.0, max_attr=60.0, min_k=1, max_k=16,
              num_labels=10, seed=1717)
HEADER = {"serve_trace_schema": 1, "corpus": CORPUS}

MID_FACTOR = 1.15             # mid level: x calibrated capacity
HOT_FACTOR = 1.60             # hot level: x calibrated capacity
RAMP_S = 10.0                 # seconds of send per level (at x1)
LEAD_SETTLE_S = 20.0          # mid -> hot gap both arms get
OBJ_ID = "fleet.request_latency_ms:p99"
CANARY_ID = "fleet.request_latency_ms:p95"


def fail(msg: str):
    print(f"slo_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"slo_smoke: {msg}")


# -- part 1: seeded breach on a deterministic clock ---------------------------

def part1_seeded_breach(out: str) -> None:
    p1 = os.path.join(out, "part1")
    os.makedirs(p1, exist_ok=True)
    sc.clear_flight_dumps(p1)
    session = telemetry.start(
        path=os.path.join(p1, "telemetry.prom"), flight_dir=p1,
        handle_signals=False)
    try:
        clock = {"t": 0.0}
        reg = telemetry.Registry()
        ev = slomod.SLOEvaluator(
            ["svc.latency_ms p99 < 100 over 60s"], reg,
            fast_s=10.0, sub_s=1.0, for_ticks=2, clear_ticks=3,
            time_fn=lambda: clock["t"], flight_dump=True)
        h = reg.histogram("svc.latency_ms", unit="ms")  # check: allow-metric-name — smoke-local series
        obj = "svc.latency_ms:p99"

        def drive(until_s: float, value_ms: float) -> None:
            while clock["t"] < until_s:
                clock["t"] += 0.5
                h.observe(value_ms)
                ev.tick()

        drive(20.0, 10.0)                 # healthy plateau
        if ev.state(obj) != "ok" or ev.transitions:
            fail(f"healthy plateau not ok: state={ev.state(obj)} "
                 f"transitions={ev.transitions}")
        drive(26.0, 500.0)                # the seeded breach
        if ev.state(obj) != "firing":
            fail(f"seeded breach did not fire: {ev.snapshot()}")
        om_hot = reg.to_openmetrics()
        drive(100.0, 10.0)                # recovery + window drain
        if ev.state(obj) != "ok":
            fail(f"breach did not clear by t=100: {ev.snapshot()}")

        edges = [(t["prev"], t["state"]) for t in ev.transitions]
        if edges != [("ok", "pending"), ("pending", "firing"),
                     ("firing", "ok")]:
            fail(f"expected exactly one alert cycle, got {edges}")
        if ev.alert_cycles(obj) != 1:
            fail(f"alert_cycles != 1: {ev.alert_cycles(obj)}")

        probs = validate_openmetrics(om_hot)
        if probs:
            fail(f"mid-breach exposition invalid: {probs}")
        for fam in ("slo_state", "slo_firing", "slo_burn_rate_fast",
                    "slo_trend_slope_ms_per_s"):
            if fam not in om_hot:
                fail(f"family {fam} missing from the mid-breach "
                     "exposition")
        firing_lines = [ln for ln in om_hot.splitlines()
                        if ln.startswith("slo_firing")
                        and ln.rstrip().endswith(" 1")]
        if not firing_lines:
            fail("slo_firing gauge not 1 while firing")

        dumps = glob.glob(os.path.join(p1, "FLIGHT_slo_breach_*.json"))
        if len(dumps) != 1:
            fail(f"expected exactly one breach flight dump, got "
                 f"{dumps}")
        with open(dumps[0]) as f:
            doc = json.load(f)
        alerts = [e for e in doc.get("events", [])
                  if e.get("name") == "slo.alert"]
        if not alerts:
            fail(f"flight dump {dumps[0]} holds no slo.alert events")
    finally:
        session.close()
    say("part 1 OK: seeded breach fired exactly one alert cycle "
        "(ok->pending->firing->ok), flight dump + slo_* exposition "
        "captured")


# -- part 2: the ramp A/B over a real fleet -----------------------------------

def synth_trace(rate_qps: float, dur_s: float, seed0: int) -> list:
    """Evenly paced open-loop trace at ``rate_qps`` queries/s, NQ
    queries (k=K) per request."""
    req_rate = rate_qps / NQ
    n = max(int(round(dur_s * req_rate)), 1)
    return [{"t_ms": int(round(i * 1000.0 / req_rate)), "nq": NQ,
             "ks": [K] * NQ, "seed": seed0 + i} for i in range(n)]


def write_faults(out: str) -> str:
    path = os.path.join(out, "faults.json")
    with open(path, "w") as f:
        json.dump({"schema": 1, "seed": 1, "faults": [
            {"site": "serve.solve", "kind": "delay", "ms": D_MS,
             "times": 10 ** 9, "prob": 1.0}]}, f)
    return path


def calibrate(out: str, corpus_path: str, warm: str,
              faults: str) -> dict:
    """Measure the fault-slowed serving path THROUGH the router (the
    same topology the ramp arms run): healthy p95/p99 and the real
    saturated single-replica fleet capacity the ramp levels are
    scaled from — daemon-direct numbers overestimate what the routed
    path can carry."""
    proc, doc, armdir, errlog = spawn_arm(
        "calib", out, corpus_path, warm,
        spawn_flags=f"--faults {faults}", slo_specs=[],
        policy_args=["--policy", "reactive",
                     "--scale-high", "1000000000"],
        router_args=[])
    port = doc["port"]
    try:
        healthy = synth_trace(0.4 * C0, 3.0, 100_000)
        hm = loadgen.run_level(port, HEADER, healthy, 1.0)
        sat = synth_trace(2.5 * C0, 4.0, 200_000)
        sm = loadgen.run_level(port, HEADER, sat, 1.0)
    finally:
        drain_arm("calib", proc, port, errlog)
    if hm.get("errors") or hm.get("rejected"):
        fail(f"calibration healthy level had failures: {hm}")
    if sm.get("errors") or sm.get("rejected"):
        fail(f"calibration saturation level had failures: {sm}")
    c_real = float(sm.get("achieved_qps") or 0.0)
    if not (0.3 * C0 <= c_real <= 1.2 * C0):
        fail(f"calibrated capacity {c_real} q/s outside "
             f"[{0.3 * C0}, {1.2 * C0}] — the serve.solve delay "
             "fault is not bounding service time as designed")
    if hm["p95_ms"] > 1200.0:
        fail(f"healthy p95 {hm['p95_ms']} ms — the box is too loaded "
             "for a meaningful SLO baseline")
    return {"c_real": c_real, "h_p95": hm["p95_ms"],
            "h_p99": hm["p99_ms"]}


def spawn_arm(arm: str, out: str, corpus_path: str, warm: str,
              spawn_flags: str, slo_specs: list, policy_args: list,
              router_args: list):
    armdir = os.path.join(out, arm)
    os.makedirs(armdir, exist_ok=True)
    ready = os.path.join(armdir, "router_ready.json")
    errlog = os.path.join(armdir, "router.err")
    if os.path.exists(ready):
        os.remove(ready)
    cmd = [sys.executable, "-m", "dmlp_tpu.fleet",
           "--spawn-corpus", corpus_path,
           "--spawn-replicas", "1", "--max-replicas", "2",
           "--out-dir", armdir, "--spawn-warm", warm,
           "--spawn-batch-cap", str(BATCH_CAP),
           "--spawn-flags", spawn_flags,
           "--poll-s", "0.25", "--health-interval-s", "0.25",
           # The ramp is a CONTROLLED overload: pin the self-healing
           # reflexes (shard re-split, hung-replica relaunch) out of
           # reach so the only capacity change is the one the scaling
           # policy under test makes.
           "--reshard-threshold", "10",
           "--unhealthy-deadline-s", "120",
           "--port", "0", "--ready-file", ready,
           "--telemetry-port", "0"]
    for spec in slo_specs:
        cmd += ["--slo", spec]
    cmd += policy_args + router_args
    with open(errlog, "w") as ef:
        proc = subprocess.Popen(cmd, stderr=ef,
                                stdout=subprocess.DEVNULL,
                                env=fh._repo_env(), cwd=armdir)
    doc = sc.await_ready(proc, ready, timeout_s=900, errlog=errlog)
    return proc, doc, armdir, errlog


def router_stats(port: int) -> dict:
    cli = sc.ServeClient(port)
    try:
        return cli.stats()["stats"]
    finally:
        cli.close()


def drain_arm(arm: str, proc, port: int, errlog: str) -> None:
    cli = sc.ServeClient(port)
    try:
        cli.drain()
    finally:
        cli.close()
    try:
        rc = proc.wait(timeout=180)
    except subprocess.TimeoutExpired:
        proc.kill()
        fail(f"{arm} arm router did not exit after drain; "
             f"see {errlog}")
    if rc != 0:
        tail = ""
        if os.path.exists(errlog):
            tail = open(errlog).read()[-500:]
        fail(f"{arm} arm router exited {rc}: {tail}")


def run_arm(arm: str, proc, doc, errlog: str, ramp_reqs: list,
            hot_speed: float, golden_txt: str, golden_reqs: list,
            predictive: bool) -> dict:
    port = doc["port"]

    # contract channel first: closed-loop slice vs the golden oracle
    # (connections=1 keeps the good-latency slice under the canary
    # threshold — warm-up traffic must not pre-trip the policy).
    res = sc.replay(port, HEADER, golden_reqs, connections=1)
    bad = [r for r in res if not r.get("ok")]
    if bad:
        fail(f"{arm} arm closed-loop replay failed: {bad[0]}")
    if sc.contract_text([r["checksums"] for r in res]) != golden_txt:
        fail(f"{arm} arm responses differ from the golden oracle")

    steps = loadgen.run_ramp(port, HEADER, ramp_reqs, [1.0])
    mid = steps[0]
    if mid["metrics"].get("errors") or mid["metrics"].get("rejected"):
        fail(f"{arm} arm mid level had failures: {mid['metrics']}")

    # the lead window the predictive policy is buying: it must land
    # its scale-up HERE, before the hot level arrives.
    t0 = time.monotonic()
    if predictive:
        while True:
            st = router_stats(port)
            if int(st.get("healthy_replicas") or 0) >= 2:
                break
            if proc.poll() is not None:
                fail(f"{arm} arm router died mid-ramp; see {errlog}")
            if time.monotonic() - t0 > 150:
                fail(f"{arm} arm: predictive scale-up not ready "
                     f"within 150s; stats={json.dumps(st)[:500]}")
            time.sleep(1.0)
        say(f"{arm} arm: scale-up ready "
            f"{round(time.monotonic() - t0, 1)}s into the lead "
            "window")
    pad = LEAD_SETTLE_S - (time.monotonic() - t0)
    if pad > 0:
        time.sleep(pad)

    steps += loadgen.run_ramp(port, HEADER, ramp_reqs, [hot_speed])
    hot = steps[-1]
    if hot["metrics"].get("errors") or hot["metrics"].get("rejected"):
        fail(f"{arm} arm hot level had failures: {hot['metrics']}")

    om = ""
    tport = doc.get("telemetry_port")
    if tport:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{tport}/metrics", timeout=10) as r:
            om = r.read().decode()
        probs = validate_openmetrics(om)
        if probs:
            fail(f"{arm} arm /metrics invalid: {probs}")
        for fam in ("slo_state", "slo_burn_rate_fast"):
            if fam not in om:
                fail(f"{arm} arm /metrics lacks {fam}")
    st = router_stats(port)
    scale = st.get("scale") or {}
    for reflex in ("relaunches", "splits", "crashes"):
        if scale.get(reflex):
            fail(f"{arm} arm: self-healing reflex {reflex}="
                 f"{scale[reflex]} moved during the controlled ramp")
    drain_arm(arm, proc, port, errlog)
    return {"steps": steps, "stats": st, "mid": mid, "hot": hot}


def check_reactive_trace(armdir: str) -> dict:
    tools = os.path.dirname(os.path.abspath(__file__))
    merged = os.path.join(armdir, "trace-fleet-merged.json")
    rc = subprocess.call(
        [sys.executable, os.path.join(tools, "merge_traces.py"),
         armdir, "--fleet", "-o", merged], env=fh._repo_env())
    if rc != 0:
        fail("merge_traces --fleet failed on the reactive arm")
    cp = subprocess.run(
        [sys.executable, os.path.join(tools, "check_trace.py"),
         "--fleet", merged, "--json"],
        capture_output=True, text=True, env=fh._repo_env())
    if cp.returncode != 0:
        fail(f"check_trace --fleet rejected the reactive-arm trace: "
             f"{cp.stderr.strip()[-500:]}")
    verdict = json.loads(cp.stdout)
    alerts = verdict.get("slo_alerts") or {}
    n = sum(int(v) for v in alerts.values()) if isinstance(
        alerts, dict) else int(alerts or 0)
    if n < 2:
        fail(f"merged reactive trace carries {n} slo.alert events, "
             "expected the pending+firing pair at least")
    return verdict


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="outputs/slo")
    ap.add_argument("--record", default=None)
    ap.add_argument("--round", type=int, default=17)
    args = ap.parse_args(argv)
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    record = os.path.abspath(args.record) if args.record \
        else os.path.join(out, "SLO_SMOKE.jsonl")
    if os.path.exists(record):
        os.remove(record)

    part1_seeded_breach(out)

    # -- part 2 setup ---------------------------------------------------------
    corpus_txt = sc.corpus_text(HEADER)
    corpus_path = os.path.join(out, "corpus.in")
    with open(corpus_path, "w") as f:
        f.write(corpus_txt)
    corpus = parse_input_text(corpus_txt)
    faults = write_faults(out)
    probe = synth_trace(C0, 1.0, 1)
    warm = ",".join(f"{q}x{k}" for q, k in
                    sc.warm_buckets_for_trace(probe, BATCH_CAP))

    cal = calibrate(out, corpus_path, warm, faults)
    c_real = cal["c_real"]
    t_low = round(max(2.0 * cal["h_p95"], 150.0), 1)
    t_obj = round(cal["h_p99"] + 2400.0, 1)
    hot_speed = round(HOT_FACTOR / MID_FACTOR, 3)
    ramp_reqs = synth_trace(MID_FACTOR * c_real, RAMP_S, 300_000)
    golden_reqs = ramp_reqs[:8]
    golden_txt = sc.contract_text(
        sc.golden_reference(corpus, HEADER, golden_reqs))
    say(f"calibrated: capacity {c_real} q/s (nominal {C0}), healthy "
        f"p95 {cal['h_p95']} / p99 {cal['h_p99']} ms -> objective "
        f"p99<{t_obj}ms, canary p95<{t_low}ms; ramp x1="
        f"{round(MID_FACTOR * c_real, 1)} q/s, hot x{hot_speed}")

    slo_specs = [f"fleet.request_latency_ms p99 < {t_obj} over 60s",
                 f"fleet.request_latency_ms p95 < {t_low} over 60s"]

    # reactive arm: watermark policy pinned out of reach — the lagging
    # baseline that rides one replica into the breach. Fully traced.
    rdir_flags = None
    proc, doc, armdir, errlog = spawn_arm(
        "reactive", out, corpus_path, warm,
        spawn_flags=f"--faults {faults} --trace "
                    f"{os.path.join(out, 'reactive', 'trace-replica00.json')}",
        slo_specs=slo_specs,
        policy_args=["--policy", "reactive",
                     "--scale-high", "1000000000"],
        router_args=["--trace",
                     os.path.join(out, "reactive",
                                  "trace-router.json")])
    rdir_flags = armdir
    reactive = run_arm("reactive", proc, doc, errlog, ramp_reqs,
                       hot_speed, golden_txt, golden_reqs,
                       predictive=False)

    # predictive arm: follows the canary's burn rate for the lead.
    proc, doc, armdir, errlog = spawn_arm(
        "predictive", out, corpus_path, warm,
        spawn_flags=f"--faults {faults}",
        slo_specs=slo_specs,
        policy_args=["--policy", "predictive",
                     "--slo-objective", CANARY_ID,
                     "--lead-time-s", "15"],
        router_args=[])
    predictive = run_arm("predictive", proc, doc, errlog, ramp_reqs,
                         hot_speed, golden_txt, golden_reqs,
                         predictive=True)

    # -- the A/B contract -----------------------------------------------------
    recs = {}
    for arm, res in (("reactive", reactive),
                     ("predictive", predictive)):
        rec = loadgen.ramp_record(arm, OBJ_ID, res["steps"],
                                  replicas=1, trace="slo_ramp",
                                  tool="tools.slo_smoke")
        rec.round = args.round
        rec.append_jsonl(record)
        recs[arm] = rec

    rm, pm = recs["reactive"].metrics, recs["predictive"].metrics
    if rm["breach_cycles"] < 1 or rm["worst_state_level"] != 2:
        fail(f"reactive arm never fired the breach: {rm}")
    if rm["replicas_final"] != 1:
        fail(f"reactive arm scaled ({rm}) — the watermark was "
             "supposed to stay out of reach")
    if reactive["hot"]["metrics"]["p99_ms"] <= t_obj:
        fail(f"reactive hot p99 {reactive['hot']['metrics']['p99_ms']}"
             f" ms under the {t_obj} ms objective — the ramp is not "
             "saturating one replica")
    if pm["breach_cycles"] != 0 or pm["worst_state_level"] != 0:
        fail(f"predictive arm burned the customer objective: {pm}")
    if pm["max_burn_fast"] > 1.0:
        fail(f"predictive arm customer burn rate over budget: {pm}")
    if pm["replicas_final"] != 2:
        fail(f"predictive arm did not scale to 2 replicas: {pm}")
    ups = predictive["stats"].get("scale", {}).get("up", 0)
    if not ups:
        fail(f"predictive arm recorded no scale-up: "
             f"{predictive['stats'].get('scale')}")
    if predictive["hot"]["metrics"]["p99_ms"] >= t_obj:
        fail(f"predictive hot p99 "
             f"{predictive['hot']['metrics']['p99_ms']} ms breached "
             f"{t_obj} ms despite the scale-up")
    say(f"A/B OK: reactive hot p99 "
        f"{reactive['hot']['metrics']['p99_ms']} ms on 1 replica "
        f"(firing), predictive hot p99 "
        f"{predictive['hot']['metrics']['p99_ms']} ms on 2 "
        f"(ok, scale-ups {ups})")

    verdict = check_reactive_trace(rdir_flags)
    say(f"trace OK: merged reactive-arm trace passes check_trace "
        f"--fleet with slo_alerts={verdict.get('slo_alerts')}")

    # -- the arms' records read back ------------------------------------------
    back = {r.config.get("arm"): r
            for r in RunRecord.load_all(record) if r.kind == "slo"}
    for arm, metric in (("reactive", "breach_cycles"),
                        ("predictive", "breach_cycles"),
                        ("predictive", "peak_p99_ms")):
        if arm not in back or metric not in back[arm].metrics:
            fail(f"no slo RunRecord for arm {arm} with {metric} "
                 f"(got {sorted(map(str, back))})")
    say(f"run records OK: arms {sorted(map(str, back))} from "
        f"{os.path.basename(record)} (round {args.round})")
    say("PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
