#!/usr/bin/env python
"""Chaos harness: prove byte-identical recovery under seeded fault
schedules (``make chaos-smoke``).

The resilience subsystem's contract is not "survives faults" but
"survives faults *without changing answers*" — recovery that perturbs
the contract checksums is a correctness bug wearing a safety vest. This
harness enforces that end to end:

1. **Golden run** — bench config 1 through the real CLI, fault-free:
   its stdout bytes are the reference.
2. **Seeded fault schedules** — three kinds, each randomized from a
   seeded PRNG (different seeds explore different fault placements,
   the same seed reproduces exactly):

   - ``straggler``  injected delays at staging/readback;
   - ``transient``  injected transient exceptions at staging/readback
     plus a corrupted parse payload (retry/backoff recovery);
   - ``oom``        simulated RESOURCE_EXHAUSTED driving the
     degradation ladder 1-3 rungs down (heuristic variant, streaming
     fold, or the float64 host oracle).

   Each faulted run must produce stdout **byte-identical** to the
   golden run, must actually FIRE faults (vacuous chaos is failure),
   and must surface its recovery in the metrics summary's
   ``resilience`` block and as ``resilience.*`` trace events.
3. **Deterministic replay** — one schedule runs twice; the two
   injection logs must be byte-identical.
4. **Train chaos** — a short ``--nan-guard`` train run with an
   injected NaN at one step must report a rollback AND finish with the
   same ``params_checksum`` + final loss as the fault-free run
   (step-identical recovery).
5. **Zero-fault overhead** — interleaved A/B pairs with the resilience
   layer disabled ($DMLP_TPU_RESILIENCE=0) vs enabled (no faults),
   recorded as ``resilience_overhead_pct`` in a RunRecord.

Usage::

    python tools/chaos_run.py [--smoke] [--base-dir .]
        [--out outputs/chaos] [--record FILE] [--seed-base N]
        [--overhead-pairs N] [--no-train] [--timeout S]

Exit 0 when every invariant holds; 1 with a message naming the first
violation.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import statistics
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIG_ID = 1


def fail(msg: str):
    print(f"chaos_run: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _extract_ms(err_text: str):
    m = re.search(r"Time taken:\s*(\d+)", err_text)
    return int(m.group(1)) if m else None


def run_engine(input_path: str, extra_argv=None, env_extra=None,
               timeout_s: float = 300.0):
    """One engine CLI subprocess; returns (stdout bytes, stderr text)."""
    argv = [sys.executable, "-m", "dmlp_tpu"] + list(extra_argv or [])
    env = dict(os.environ)
    env.update(env_extra or {})
    with open(input_path, "rb") as stdin:
        proc = subprocess.run(argv, stdin=stdin, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, env=env,
                              timeout=timeout_s)
    if proc.returncode != 0:
        fail(f"engine exited {proc.returncode}: "
             f"{proc.stderr.decode()[-2000:]}")
    return proc.stdout, proc.stderr.decode()


# -- seeded schedule generators ---------------------------------------------

def make_schedule(kind: str, seed: int) -> dict:
    """A randomized-but-seeded fault schedule of one chaos kind. The
    PRNG draws the fault placement/intensity, so different seeds
    explore the space while any one seed replays exactly."""
    rng = random.Random(seed)
    if kind == "straggler":
        faults = [
            {"site": "single.stage_put", "kind": "delay",
             "ms": rng.randint(10, 60), "times": rng.randint(1, 3)},
            {"site": "single.fetch", "kind": "delay",
             "ms": rng.randint(10, 40), "times": 1},
        ]
    elif kind == "transient":
        faults = [
            {"site": "single.stage_put", "kind": "transient",
             "times": rng.randint(1, 2)},
            {"site": "single.fetch", "kind": "transient", "times": 1},
            {"site": "io.parse", "kind": "corrupt"},
        ]
    elif kind == "oom":
        # times = how deep the ladder steps from its top (lowp) rung:
        # 1 -> pruned f32, 2 -> dense fused, 3 -> two-pass kernel
        # (heuristic).
        faults = [{"site": "single.stage_put", "kind": "oom",
                   "times": rng.randint(1, 3)}]
    else:
        raise ValueError(f"unknown chaos kind {kind!r}")
    return {"schema": 1, "seed": seed, "faults": faults}


def check_faulted_run(kind: str, golden: bytes, out_b: bytes,
                      log_path: str, metrics_path: str, trace_path: str):
    """The per-schedule invariants: byte identity, non-vacuous firing,
    visible recovery."""
    if out_b != golden:
        fail(f"{kind}: faulted stdout differs from the golden run — "
             "recovery changed answers")
    with open(log_path) as f:
        log = json.load(f)["log"]
    fired = [e for e in log if e["fired"]]
    if not fired:
        fail(f"{kind}: schedule fired no faults — vacuous chaos")
    with open(metrics_path) as f:
        summary = [json.loads(ln) for ln in f if ln.strip()][-1]
    res = summary.get("resilience")
    if not isinstance(res, dict):
        fail(f"{kind}: metrics summary has no resilience block")
    if res["faults_injected"] < len(fired):
        fail(f"{kind}: stats report {res['faults_injected']} faults, "
             f"log shows {len(fired)}")
    if kind == "transient" and res["retries"] < 1:
        fail(f"{kind}: transient faults fired but retries == 0")
    if kind == "oom" and not res["degradations"]:
        fail(f"{kind}: oom fired but the ladder recorded no degradation")
    with open(trace_path) as f:
        names = {e.get("name", "") for e in json.load(f)["traceEvents"]}
    if not any(n.startswith("resilience.") for n in names):
        fail(f"{kind}: no resilience.* events in the trace — recovery "
             "was invisible")
    return {"kind": kind, "fired": len(fired),
            "retries": res["retries"],
            "degradations": res["degradations"],
            # the engine subprocess's own device stamp: this parent
            # never asks jax where its children ran
            "device": (summary.get("device") or {}).get("device_kind")}


def measure_overhead(input_path: str, pairs: int, timeout_s: float):
    """Interleaved resilience off/on engine pairs, no faults — the
    zero-fault cost of the wrappers."""
    times = {"off": [], "on": []}
    for rep in range(max(pairs, 1)):
        order = ("off", "on") if rep % 2 == 0 else ("on", "off")
        for arm in order:
            env = {"DMLP_TPU_RESILIENCE": "0"} if arm == "off" else {}
            _, err = run_engine(input_path, env_extra=env,
                                timeout_s=timeout_s)
            ms = _extract_ms(err)
            if ms is None:
                fail(f"overhead A/B: no timing line in the {arm} arm")
            times[arm].append(ms)
    med_off = statistics.median(times["off"])
    med_on = statistics.median(times["on"])
    out = {"engine_ms_resilience_off": times["off"],
           "engine_ms_resilience_on": times["on"]}
    if med_off <= 0:
        out["resilience_overhead_unavailable"] = \
            "off-arm median rounded to 0 ms"
    else:
        out["resilience_overhead_pct"] = round(
            (med_on - med_off) / med_off * 100.0, 2)
    return out


def run_train_chaos(out_dir: str, timeout_s: float):
    """Fault-free vs NaN-faulted --nan-guard train runs must agree
    bitwise on params and final loss (step-identical rollback)."""
    sched = {"schema": 1, "seed": 5, "faults": [
        {"site": "train.step", "kind": "nan", "when": {"step": 4}}]}
    sched_path = os.path.join(out_dir, "sched_train_nan.json")
    with open(sched_path, "w") as f:
        json.dump(sched, f)

    def run(tag: str, faults: bool) -> dict:
        rec = os.path.join(out_dir, f"train_{tag}.json")
        # Fresh checkpoint dir every invocation: a stale checkpoint from
        # a previous chaos run sits AHEAD of this run's steps, and a
        # rollback that restores it would jump the loop forward instead
        # of back (the loop refuses, but the harness must not set the
        # trap in the first place).
        import shutil
        ck_dir = os.path.join(out_dir, f"ck_{tag}")
        shutil.rmtree(ck_dir, ignore_errors=True)
        argv = [sys.executable, "-m", "dmlp_tpu.train.loop",
                "--steps", "6", "--batch", "128", "--dims", "16,32,10",
                "--mesh", "1,1", "--log-every", "3", "--ckpt-every", "2",
                "--checkpoint-dir", ck_dir,
                "--nan-guard", "--record", rec]
        env = dict(os.environ)
        if faults:
            argv += ["--faults", sched_path]
            env["DMLP_TPU_FAULT_LOG"] = os.path.join(
                out_dir, "train_fault_log.json")
        proc = subprocess.run(argv, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, env=env,
                              timeout=timeout_s)
        if proc.returncode != 0:
            fail(f"train {tag} exited {proc.returncode}: "
                 f"{proc.stderr.decode()[-2000:]}")
        with open(rec) as f:
            return json.load(f)

    plain = run("plain", faults=False)
    faulted = run("faulted", faults=True)
    pm, fm = plain["metrics"], faulted["metrics"]
    if fm.get("resilience", {}).get("rollbacks") != 1:
        fail(f"train chaos: expected exactly 1 rollback, got "
             f"{fm.get('resilience')}")
    if pm["params_checksum"] != fm["params_checksum"]:
        fail("train chaos: faulted run's params differ from fault-free "
             "(rollback was not step-identical)")
    if pm["loss"] != fm["loss"]:
        fail(f"train chaos: final loss differs "
             f"({pm['loss']} != {fm['loss']})")
    return {"rollbacks": 1, "params_checksum": pm["params_checksum"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI size: 1 overhead pair (full: 3)")
    ap.add_argument("--base-dir", default=".")
    ap.add_argument("--out", default="outputs/chaos",
                    help="schedule/log/trace artifact directory")
    ap.add_argument("--record", default=None, metavar="FILE",
                    help="append the chaos RunRecord (JSONL) to FILE")
    ap.add_argument("--seed-base", type=int, default=1000)
    ap.add_argument("--overhead-pairs", type=int, default=None)
    ap.add_argument("--no-train", action="store_true")
    ap.add_argument("--timeout", type=float, default=300.0)
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    from dmlp_tpu.bench.configs import BENCH_CONFIGS
    from dmlp_tpu.bench.harness import ensure_input
    input_path = ensure_input(BENCH_CONFIGS[CONFIG_ID],
                              os.path.join(args.base_dir, "inputs"))

    print("chaos_run: golden (fault-free) run ...")
    golden, golden_err = run_engine(input_path, timeout_s=args.timeout)
    if _extract_ms(golden_err) is None:
        fail("golden run produced no 'Time taken' line")

    results = []
    replay_first = None
    for i, kind in enumerate(("straggler", "transient", "oom")):
        seed = args.seed_base + i
        sched = make_schedule(kind, seed)
        sched_path = os.path.join(args.out, f"sched_{kind}_{seed}.json")
        with open(sched_path, "w") as f:
            json.dump(sched, f, indent=1)
        log_path = os.path.join(args.out, f"log_{kind}.json")
        trace_path = os.path.join(args.out, f"trace_{kind}.json")
        metrics_path = os.path.join(args.out, f"metrics_{kind}.jsonl")
        if os.path.exists(metrics_path):
            os.remove(metrics_path)
        out_b, _ = run_engine(
            input_path,
            extra_argv=["--faults", sched_path, "--trace", trace_path,
                        "--metrics", metrics_path],
            env_extra={"DMLP_TPU_FAULT_LOG": log_path},
            timeout_s=args.timeout)
        r = check_faulted_run(kind, golden, out_b, log_path,
                              metrics_path, trace_path)
        print(f"chaos_run: {kind} ok — byte-identical, "
              f"{r['fired']} fault(s) fired, {r['retries']} retries, "
              f"degradations {r['degradations']}")
        results.append(r)
        if kind == "transient":
            replay_first = (sched_path, log_path)

    # Deterministic replay: same schedule + seed -> same injection log.
    sched_path, log_path = replay_first
    with open(log_path) as f:
        first_log = f.read()
    log2 = os.path.join(args.out, "log_transient_replay.json")
    out_b, _ = run_engine(input_path,
                          extra_argv=["--faults", sched_path],
                          env_extra={"DMLP_TPU_FAULT_LOG": log2},
                          timeout_s=args.timeout)
    if out_b != golden:
        fail("replay: stdout diverged")
    with open(log2) as f:
        second_log = f.read()
    if first_log != second_log:
        fail("replay: same schedule + seed produced a DIFFERENT "
             "injection log — injection is not deterministic")
    print("chaos_run: deterministic replay ok — injection logs "
          "byte-identical")

    train_summary = None
    if not args.no_train:
        train_summary = run_train_chaos(args.out, args.timeout)
        print("chaos_run: train NaN rollback ok — step-identical "
              f"(checksum {train_summary['params_checksum'][:16]}...)")

    pairs = args.overhead_pairs or (1 if args.smoke else 3)
    overhead = measure_overhead(input_path, pairs, args.timeout)
    print(f"chaos_run: zero-fault overhead "
          f"{overhead.get('resilience_overhead_pct', 'n/a')}% over "
          f"{pairs} interleaved pair(s) "
          f"(off {overhead['engine_ms_resilience_off']} ms, "
          f"on {overhead['engine_ms_resilience_on']} ms)")

    if args.record:
        from dmlp_tpu.obs.run import RunRecord, round_from_name
        RunRecord(
            kind="chaos", tool="tools.chaos_run",
            config={"config": CONFIG_ID, "seed_base": args.seed_base,
                    "smoke": args.smoke, "overhead_pairs": pairs},
            metrics={"byte_identical": True,
                     "replay_deterministic": True,
                     "schedules": results,
                     **({"train": train_summary} if train_summary
                        else {}),
                     **overhead},
            device=next((r["device"] for r in results
                         if r.get("device")), None),
            round=round_from_name(args.record)).append_jsonl(args.record)
    print("chaos_run: all chaos invariants hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
