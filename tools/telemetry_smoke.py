#!/usr/bin/env python
"""Telemetry smoke: prove the live-telemetry layer end to end on CPU.

The ``make telemetry-smoke`` checker (wired into ``make test``). Five
proofs, every failure exits nonzero with the reason named:

1. **Contract byte-identity** — bench config 1 runs through the real
   CLI in interleaved ``--telemetry`` OFF/ON pairs (order alternating
   per pair); every run's
   stdout must be byte-identical to the plain run's. Telemetry is
   stderr/filesystem-only by construction; this proves it.
2. **OpenMetrics validity** — the final ON-arm snapshot file passes the
   structural validator (obs.telemetry.validate_openmetrics) and
   carries the honest ``mem_stats_unavailable`` gauge (this container's
   CPU backend reports no allocator stats — the marker IS the proof
   the gauge tells the truth).
3. **Peak-HBM reconcile** — an in-process engine run under a live
   session: the analytic model (obs.memwatch.resident_bytes_model)
   must agree with the measured watermark within the basis's
   documented ratio bounds, OR the explicit ``mem_stats_unavailable``
   marker must be present (backend reports nothing at all). On this
   container the ``live_arrays`` basis measures; on TPU,
   ``memory_stats``.
4. **Flight recorder** — a fault schedule drives retries to exhaustion
   inside the CLI; the run must fail AND leave a ``FLIGHT_*.json``
   post-mortem containing recent span events.
5. **Run record** — the overhead + reconcile numbers serialize as ONE
   RunRecord (kind "telemetry", raw per-arm sample lists) that loads
   back with both arms' samples and the model's bytes.

Usage::

    python tools/telemetry_smoke.py --out outputs/telemetry \
        [--record outputs/telemetry/TELEMETRY_SMOKE.jsonl] [--pairs 2]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dmlp_tpu.bench.configs import BENCH_CONFIGS            # noqa: E402
from dmlp_tpu.bench.harness import (_extract_ms, ensure_input,  # noqa: E402
                                    run_engine)


def fail(msg: str):
    print(f"telemetry_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"telemetry_smoke: {msg}")


def run_ab(cfg, input_path, out_dir, pairs: int, telemetry_path: str):
    """Interleaved OFF/ON engine runs; returns (times dict, outputs
    dict of stdout text sets)."""
    times = {"off": [], "on": []}
    outputs = {"off": set(), "on": set()}
    for rep in range(pairs):
        order = ("off", "on") if rep % 2 == 0 else ("on", "off")
        for arm in order:
            flags = ["--telemetry", telemetry_path] if arm == "on" \
                else None
            out_path, err_path = run_engine(
                cfg, input_path, out_dir, obs_flags=flags)
            with open(out_path) as f:
                outputs[arm].add(f.read())
            with open(err_path) as f:
                ms = _extract_ms(f.read())
            if ms is None:
                fail(f"no timing line in the {arm}-arm run")
            times[arm].append(ms)
    return times, outputs


def check_openmetrics(path: str) -> None:
    from dmlp_tpu.obs.telemetry import validate_openmetrics
    with open(path) as f:
        text = f.read()
    problems = validate_openmetrics(text)
    if problems:
        fail(f"OpenMetrics snapshot invalid: {problems[:5]}")
    if "mem_stats_unavailable" not in text:
        fail("snapshot lacks the mem.stats_unavailable gauge — the "
             "honest-marker contract for backends without "
             "memory_stats")
    if "span_latency_ms" not in text:
        fail("snapshot lacks span-derived latency histograms")
    say(f"OpenMetrics snapshot valid ({len(text.splitlines())} lines)")


def reconcile_in_process(cfg, input_path):
    """In-process solve under a live session: analytic model vs
    measured watermark. Returns the reconcile dict."""
    from dmlp_tpu.cli import make_engine
    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.io.grammar import parse_input
    from dmlp_tpu.obs import memwatch, telemetry

    with open(input_path, "rb") as f:
        inp = parse_input(f)
    engine = make_engine(EngineConfig(mode=cfg.mode,
                                      use_pallas=cfg.use_pallas,
                                      select=cfg.select))
    sess = telemetry.start(handle_signals=False)
    try:
        engine.run(inp)
        model = engine.last_mem_model
        if model is None:
            fail("engine.last_mem_model unset under a live session")
        rec = memwatch.reconcile(model, sess.sampler.measured_peak())
    finally:
        sess.close()
    if "mem_stats_unavailable" in rec:
        say(f"peak-HBM reconcile: explicit marker "
            f"({rec['mem_stats_unavailable']!r}) — backend reports no "
            "memory basis")
    elif not rec["within_tolerance"]:
        fail(f"analytic peak-HBM model disagrees with the measured "
             f"watermark beyond the documented {rec['basis']} bounds: "
             f"{json.dumps(rec)}")
    else:
        say(f"peak-HBM reconcile OK: model {rec['model_bytes']} B vs "
            f"measured {rec['measured_bytes']} B "
            f"({rec['basis']}, ratio {rec['ratio']} within "
            f"{rec['ratio_bounds']})")
    return rec


def check_flight_recorder(cfg, input_path, out_dir: str) -> None:
    """Retries-to-exhaustion under a fault schedule must leave a
    FLIGHT_*.json with recent span events."""
    import subprocess

    schedule = {"schema": 1, "seed": 7, "faults": [
        {"site": "single.stage_put", "kind": "transient", "times": 8}]}
    sched_path = os.path.join(out_dir, "flight_faults.json")
    with open(sched_path, "w") as f:
        json.dump(schedule, f)
    tel_path = os.path.join(out_dir, "flight_telemetry.prom")
    for stale in os.listdir(out_dir):
        if stale.startswith("FLIGHT_"):
            os.remove(os.path.join(out_dir, stale))
    with open(input_path, "rb") as stdin:
        proc = subprocess.run(
            [sys.executable, "-m", "dmlp_tpu",
             "--telemetry", tel_path, "--faults", sched_path],
            stdin=stdin, capture_output=True, timeout=300)
    if proc.returncode == 0:
        fail("faulted run unexpectedly succeeded — the flight-recorder "
             "trigger never fired")
    flights = [f for f in os.listdir(out_dir) if f.startswith("FLIGHT_")]
    if not flights:
        fail("no FLIGHT_*.json next to the telemetry file after a "
             "retries-exhausted fault")
    with open(os.path.join(out_dir, flights[0])) as f:
        doc = json.load(f)
    kinds = {e["kind"] for e in doc.get("events", [])}
    if not doc.get("events"):
        fail(f"flight artifact {flights[0]} has no events")
    if "fault" not in kinds:
        fail(f"flight artifact lacks the fault event (kinds: {kinds})")
    say(f"flight recorder OK: {flights[0]} with "
        f"{len(doc['events'])} events (kinds {sorted(kinds)}), "
        f"reason={doc['reason']!r}")


def emit_record(record_path: str, cfg, times, rec, overhead_pct):
    import dataclasses

    from dmlp_tpu.obs.run import RunRecord, round_from_name

    metrics = {
        "engine_ms_telemetry_off": round(statistics.median(times["off"])),
        "engine_ms_telemetry_off_reps": times["off"],
        "engine_ms_telemetry_on": round(statistics.median(times["on"])),
        "engine_ms_telemetry_on_reps": times["on"],
        "peak_hbm_model_bytes": rec["model_bytes"],
    }
    if overhead_pct is not None:
        metrics["telemetry_overhead_pct"] = round(overhead_pct, 2)
    else:
        metrics["telemetry_overhead_unavailable"] = \
            "off-arm median rounded to 0 ms"
    if "measured_bytes" in rec:
        metrics["peak_hbm_measured_bytes"] = rec["measured_bytes"]
        metrics["peak_hbm_model_vs_measured_pct"] = rec["delta_pct"]
        config_basis = rec["basis"]
    else:
        # Numeric marker; the human reason rides as a separate string.
        metrics["mem_stats_unavailable"] = 1
        metrics["mem_stats_unavailable_reason"] = \
            rec["mem_stats_unavailable"]
        config_basis = "unavailable"
    record = RunRecord(
        kind="telemetry", tool="tools.telemetry_smoke",
        config={**dataclasses.asdict(cfg), "watermark_basis": config_basis},
        metrics=metrics, device="cpu",
        round=round_from_name(record_path))
    record.append_jsonl(record_path)
    return record_path


def check_record_loads(record_path: str) -> None:
    from dmlp_tpu.obs.run import RunRecord
    rec = RunRecord.load(record_path)
    if rec.kind != "telemetry":
        fail(f"telemetry RunRecord has kind {rec.kind!r}")
    for w in ("engine_ms_telemetry_on", "peak_hbm_model_bytes"):
        if w not in rec.metrics:
            fail(f"telemetry RunRecord lacks {w} "
                 f"(got {sorted(rec.metrics)})")
    if not rec.metrics.get("engine_ms_telemetry_on_reps"):
        fail("the on-arm engine_ms carries no raw trial samples")
    say(f"run record OK: kind={rec.kind}, {len(rec.metrics)} metrics, "
        "raw trials attached")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="outputs/telemetry")
    ap.add_argument("--record", default=None,
                    help="append the smoke's RunRecord (JSONL) here")
    ap.add_argument("--pairs", type=int, default=2,
                    help="interleaved OFF/ON pairs")
    ap.add_argument("--config", type=int, default=1)
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    cfg = BENCH_CONFIGS[args.config]
    if cfg.procs > 1:
        fail("telemetry smoke drives the single-process engine CLI")
    input_path = ensure_input(cfg, "inputs")

    # 1. contract byte-identity across interleaved OFF/ON pairs
    tel_path = os.path.join(args.out,
                            f"telemetry_config{args.config}.prom")
    times, outputs = run_ab(cfg, input_path, args.out, args.pairs,
                            tel_path)
    if len(outputs["off"]) != 1 or outputs["off"] != outputs["on"]:
        fail("stdout MISMATCH between telemetry-on and telemetry-off "
             "runs — the contract channel is not byte-identical")
    say(f"contract stdout byte-identical across {args.pairs} "
        f"interleaved pair(s)")

    med_off = statistics.median(times["off"])
    med_on = statistics.median(times["on"])
    overhead = ((med_on - med_off) / med_off * 100.0) if med_off > 0 \
        else None
    if overhead is not None:
        say(f"telemetry overhead {overhead:+.1f}% (median {med_off} -> "
            f"{med_on} ms; raw samples ride in the record — on a "
            "shared machine the point estimate is noise)")

    # 2. OpenMetrics validity of the ON-arm snapshot
    check_openmetrics(tel_path)

    # 3. flight recorder on a retries-exhausted fault
    check_flight_recorder(cfg, input_path, args.out)

    # 4. analytic peak-HBM model vs measured watermark. In-process, so
    # it comes after every engine subprocess: once this process has
    # touched jax it holds the chip a child would need.
    rec = reconcile_in_process(cfg, input_path)

    # 5. RunRecord + ledger round-trip
    if args.record:
        path = emit_record(args.record, cfg, times, rec, overhead)
        check_record_loads(path)

    say("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
