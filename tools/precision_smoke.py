#!/usr/bin/env python
"""Low-precision first-pass smoke (`make precision-smoke`): CI teeth
for the bf16 first pass + bound-carrying exact rescore, through the
REAL engine CLI.

Four invariants, each a hard failure:

1. **Byte identity, bf16 forced** — a norm-banded corpus solved with
   ``DMLP_TPU_PRECISION=bf16`` must produce contract stdout
   byte-identical to the ``DMLP_TPU_PRECISION=f32`` kill-switch run
   AND to the float64 golden model.
2. **Non-vacuity** — the bf16 arm's metrics summary must carry a
   ``precision`` block reporting ``active == "bf16"`` with a strictly
   positive ``kcap_inflation`` (the candidate window really widened by
   the lowp_eps margin); the f32 arm must report the float32 form
   (``active == "bf16x3"``, or ``"f32"`` under bfloat16 staging) with
   zero inflation. A "bf16" arm that silently ran f32 is an
   identical-code A/B masquerading as a feature.
3. **Ladder recovery** — under a seeded ``oom`` schedule the solve
   must step off the top ``lowp`` rung (``lowp -> prune`` in the
   metrics resilience block) and STILL produce byte-identical
   contract stdout: the degraded pass gives up the low-precision dot,
   never the answers.
4. **Kill switch** — the f32 arm's run IS the kill-switch path
   (``DMLP_TPU_PRECISION=f32`` under the same config), so invariant 1
   doubles as its regression test.

With ``--record FILE`` the bf16/f32 A/B also lands as a
kind="precision" RunRecord.

Usage: JAX_PLATFORMS=cpu python tools/precision_smoke.py \
       --out outputs/precision [--record .../PRECISION_SMOKE.jsonl]
       [--reps 2]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fail(msg: str) -> None:
    print(f"precision_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def build_banded_input(path: str):
    """Seeded norm-banded corpus (the prune_smoke shape): 8 bands of
    2048 rows offset by +50, queries near band 0 — big enough that the
    extract path runs real multi-chunk solves, banded so the pruned
    stage the lowp rung rides stays non-vacuous too."""
    import numpy as np

    from dmlp_tpu.io.grammar import KNNInput, Params, format_input

    rng = np.random.default_rng(1807)
    n, nq, na, band = 16_384, 48, 8, 2048
    data = rng.uniform(0, 5, (n, na))
    for b in range(n // band):
        data[b * band:(b + 1) * band] += 50.0 * b
    inp = KNNInput(Params(n, nq, na),
                   rng.integers(0, 6, n).astype(np.int32), data,
                   rng.integers(1, 17, nq).astype(np.int32),
                   rng.uniform(0, 5, (nq, na)))
    with open(path, "w") as f:
        f.write(format_input(inp))


def run_cli(input_path: str, env_extra: dict, flags: list,
            timeout_s: float = 300.0, warmup: bool = True):
    """One engine CLI run; returns (stdout, stderr, wall_ms).
    ``warmup=False`` for fault-schedule runs — a warmup solve would
    consume the seeded fault before the measured solve sees it."""
    env = dict(os.environ)
    env.update(env_extra)
    argv = [sys.executable, "-m", "dmlp_tpu", "--select", "extract",
            "--data-block", "2048"] + (["--warmup"] if warmup else []) \
        + flags
    with open(input_path, "rb") as stdin:
        t0 = time.perf_counter()
        proc = subprocess.run(argv, stdin=stdin, capture_output=True,
                              env=env, timeout=timeout_s)
        wall_ms = (time.perf_counter() - t0) * 1e3
    if proc.returncode != 0:
        fail(f"engine CLI exited {proc.returncode}: "
             f"{proc.stderr.decode()[-1500:]}")
    return proc.stdout, proc.stderr.decode(), wall_ms


def last_summary(metrics_path: str) -> dict:
    with open(metrics_path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    summaries = [r for r in recs if r.get("event") == "summary"]
    if not summaries:
        fail(f"{metrics_path}: no summary record")
    return summaries[-1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="outputs/precision")
    ap.add_argument("--record", default=None, metavar="FILE",
                    help="append the bf16/f32 A/B as a "
                         "kind=\"precision\" RunRecord to FILE")
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)

    from dmlp_tpu.golden.fast import knn_golden_fast
    from dmlp_tpu.io.grammar import parse_input_text
    from dmlp_tpu.io.report import format_results

    input_path = os.path.join(args.out, "banded.in")
    build_banded_input(input_path)
    with open(input_path) as f:
        inp = parse_input_text(f.read())
    golden = format_results(knn_golden_fast(inp)).encode()

    # -- arms: interleaved bf16/f32 reps -------------------------------------
    times = {"bf16": [], "f32": []}
    outs = {"bf16": set(), "f32": set()}
    mpaths = {a: os.path.join(args.out, f"metrics_{a}.jsonl")
              for a in times}
    for p in list(mpaths.values()):
        if os.path.exists(p):
            os.remove(p)
    for rep in range(max(args.reps, 1)):
        order = ("f32", "bf16") if rep % 2 == 0 else ("bf16", "f32")
        for arm in order:
            out_b, err, _ = run_cli(
                input_path, {"DMLP_TPU_PRECISION": arm},
                ["--metrics", mpaths[arm]])
            outs[arm].add(out_b)
            m = re.search(r"Time taken:\s*(\d+)", err)
            if not m:
                fail(f"{arm}-arm run has no timing line")
            times[arm].append(int(m.group(1)))

    # 1/4. byte identity: forced-bf16 vs the f32 kill switch vs golden
    if outs["bf16"] != {golden} or outs["f32"] != {golden}:
        fail("contract stdout differs between bf16/f32/golden — the "
             "low-precision pass changed answers")
    print("precision_smoke: bf16 and f32 arms byte-identical to the "
          "golden oracle")

    # 2. non-vacuity: the bf16 arm really ran bf16 with a widened window
    prec = {a: last_summary(mpaths[a]).get("precision") or {}
            for a in times}
    if not prec["bf16"] or not prec["f32"]:
        fail("metrics summaries carry no precision block")
    if prec["bf16"].get("active") != "bf16":
        fail(f"forced-bf16 arm reports active="
             f"{prec['bf16'].get('active')!r} — the A/B is vacuous")
    if not prec["bf16"].get("kcap_inflation", 0) > 0:
        fail("bf16 arm reports zero kcap inflation — the lowp_eps "
             "margin never reached the candidate window")
    # (the f32 arm's pass is the float32 FORM: three bf16 passes over
    # split operands in exact mode at float32 staging, "bf16x3"; "f32",
    # one exact pass over the bf16 rows, where the staging is bfloat16)
    if prec["f32"].get("active") not in ("bf16x3", "f32") \
            or prec["f32"].get("kcap_inflation", 0) != 0:
        fail(f"f32 kill-switch arm reports {prec['f32']!r}")
    print(f"precision_smoke: bf16 arm active with kcap "
          f"{prec['f32'].get('kcap')} -> {prec['bf16'].get('kcap')} "
          f"(+{prec['bf16'].get('kcap_inflation')})")

    # 3. ladder recovery: seeded oom steps lowp -> prune, output intact
    sched_path = os.path.join(args.out, "oom_schedule.json")
    with open(sched_path, "w") as f:
        json.dump({"schema": 1, "seed": 7, "faults": [
            {"site": "single.stage_put", "kind": "oom", "times": 1}]}, f)
    oom_metrics = os.path.join(args.out, "metrics_oom.jsonl")
    if os.path.exists(oom_metrics):
        os.remove(oom_metrics)
    out_b, _, _ = run_cli(input_path, {"DMLP_TPU_PRECISION": "bf16"},
                          ["--metrics", oom_metrics,
                           "--faults", sched_path], warmup=False)
    if out_b != golden:
        fail("oom-schedule run stdout differs from golden — ladder "
             "recovery changed answers")
    res = last_summary(oom_metrics).get("resilience") or {}
    degs = res.get("degradations") or []
    if "lowp->prune" not in degs:
        fail(f"oom fired but the ladder recorded {degs!r}, expected a "
             "lowp->prune step")
    oom_prec = last_summary(oom_metrics).get("precision") or {}
    if oom_prec.get("active") == "bf16":
        fail("degraded run still reports an active bf16 pass — the "
             "lowp rung never actually stepped off")
    print(f"precision_smoke: seeded oom recovered via {degs} with "
          "byte-identical output")

    # -- optional run record -------------------------------------------------
    if args.record:
        from dmlp_tpu.obs.run import RunRecord, round_from_name
        RunRecord(
            kind="precision", tool="tools.precision_smoke",
            config={"config_id": "banded", "input": "banded.in",
                    "num_data": inp.params.num_data,
                    "num_queries": inp.params.num_queries,
                    "num_attrs": inp.params.num_attrs,
                    "select": "extract", "data_block": 2048},
            metrics={
                "engine_ms_bf16": round(statistics.median(
                    times["bf16"])),
                "engine_ms_bf16_reps": times["bf16"],
                "engine_ms_f32": round(statistics.median(
                    times["f32"])),
                "engine_ms_f32_reps": times["f32"],
                "precision_kcap_bf16": prec["bf16"].get("kcap"),
                "precision_kcap_f32": prec["f32"].get("kcap"),
                "precision_kcap_inflation":
                    prec["bf16"].get("kcap_inflation"),
                "precision_ab_identical": True,
            },
            device="cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
            else None,
            round=round_from_name(args.record)).append_jsonl(args.record)
        print(f"precision_smoke: banded A/B recorded to {args.record}")

    print("precision_smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
