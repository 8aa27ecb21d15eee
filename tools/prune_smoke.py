#!/usr/bin/env python
"""Pruned two-stage solve smoke (`make prune-smoke`): CI teeth for the
bound-based scan pruning (ops.summaries) through the REAL engine CLI.

Four invariants, each a hard failure:

1. **Byte identity, pruning forced on** — a norm-banded corpus (block
   bands progressively offset, queries near band 0) solved with
   ``DMLP_TPU_PRUNE=1`` must produce contract stdout byte-identical to
   the ``DMLP_TPU_PRUNE=0`` dense run AND to the float64 golden model.
2. **Non-vacuity** — on that banded corpus the pruned arm must prune
   more than half the blocks and stream < 0.5x the dense bytes (read
   from the CLI metrics summary's ``prune`` block) — a pruned path
   that never prunes is an identical-code A/B masquerading as a
   feature.
3. **Observability** — the ``--telemetry`` OpenMetrics snapshot of the
   pruned run must carry the ``scan_bytes_streamed`` counter (and the
   ``prune_*`` family), so the scanned-bytes count is scraped, not
   inferred.
4. **Ladder recovery** — under a seeded ``oom`` schedule at the
   staging site the solve must step the resilience ladder past the
   pruned rung (``prune -> fused``, after the top ``lowp`` rung steps
   first — visible in the metrics resilience block) and STILL produce
   byte-identical contract stdout.

With ``--record FILE`` the banded A/B also lands as a kind="prune"
RunRecord.

Usage: JAX_PLATFORMS=cpu python tools/prune_smoke.py --out outputs/prune
       [--record outputs/prune/PRUNE_SMOKE.jsonl] [--reps 2]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fail(msg: str) -> None:
    print(f"prune_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def build_banded_input(path: str):
    """Serialize a seeded norm-banded corpus to the input grammar:
    8 bands of 2048 rows offset by +50 each, queries near band 0 —
    blocks 1..7 provably cannot enter any top-k."""
    import numpy as np

    from dmlp_tpu.io.grammar import KNNInput, Params, format_input

    rng = np.random.default_rng(1301)
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
    return band


def run_cli(input_path: str, env_extra: dict, flags: list,
            timeout_s: float = 300.0):
    """One engine CLI run; returns (stdout, stderr, elapsed_ms)."""
    env = dict(os.environ)
    env.update(env_extra)
    argv = [sys.executable, "-m", "dmlp_tpu", "--select", "topk",
            "--data-block", "2048", "--warmup"] + flags
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
    ap.add_argument("--out", default="outputs/prune")
    ap.add_argument("--record", default=None, metavar="FILE",
                    help="append the banded A/B as a kind=\"prune\" "
                         "RunRecord to FILE")
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

    # -- arms: interleaved pruned/dense reps ---------------------------------
    times = {"pruned": [], "dense": []}
    outs = {"pruned": set(), "dense": set()}
    mpaths = {a: os.path.join(args.out, f"metrics_{a}.jsonl")
              for a in times}
    tel_path = os.path.join(args.out, "telemetry_pruned.prom")
    for p in list(mpaths.values()):
        if os.path.exists(p):
            os.remove(p)
    for rep in range(max(args.reps, 1)):
        order = ("dense", "pruned") if rep % 2 == 0 \
            else ("pruned", "dense")
        for arm in order:
            flags = ["--metrics", mpaths[arm]]
            if arm == "pruned":
                flags += ["--telemetry", tel_path]
            out_b, err, _ = run_cli(
                input_path,
                {"DMLP_TPU_PRUNE": "1" if arm == "pruned" else "0"},
                flags)
            outs[arm].add(out_b)
            import re
            m = re.search(r"Time taken:\s*(\d+)", err)
            if not m:
                fail(f"{arm}-arm run has no timing line")
            times[arm].append(int(m.group(1)))

    # 1. byte identity: arms vs each other and vs the f64 golden model
    if outs["pruned"] != {golden} or outs["dense"] != {golden}:
        fail("contract stdout differs between pruned/dense/golden — "
             "pruning changed answers")
    print("prune_smoke: pruned and dense arms byte-identical to the "
          "golden oracle")

    # 2. non-vacuity: > 0.5 of blocks pruned, < 0.5x bytes streamed
    pb = last_summary(mpaths["pruned"]).get("prune") or {}
    db = last_summary(mpaths["dense"]).get("prune") or {}
    if not pb or not db:
        fail("metrics summaries carry no prune block")
    frac = pb.get("pruned_fraction", 0)
    ratio = pb["scanned_bytes"] / max(db["scanned_bytes"], 1)
    if frac <= 0.5:
        fail(f"pruned fraction {frac} <= 0.5 on the banded corpus — "
             "vacuous pruning")
    if ratio >= 0.5:
        fail(f"pruned arm streamed {ratio:.3f}x the dense bytes "
             "(must be < 0.5)")
    print(f"prune_smoke: {pb['blocks_pruned']}/{pb['blocks_total']} "
          f"blocks pruned, scanned-bytes ratio {ratio:.3f}")

    # 3. the scanned-bytes counter is visible in the OpenMetrics scrape
    with open(tel_path) as f:
        prom = f.read()
    if "scan_bytes_streamed" not in prom:
        fail("scan_bytes_streamed missing from the OpenMetrics snapshot")
    if "prune_blocks_pruned" not in prom:
        fail("prune_blocks_pruned missing from the OpenMetrics snapshot")
    print("prune_smoke: scan.bytes_streamed + prune.* visible in the "
          "OpenMetrics scrape")

    # 4. ladder recovery: seeded ooms at staging walk the top rungs
    #    (lowp -> prune -> fused), output still byte-identical
    sched_path = os.path.join(args.out, "oom_schedule.json")
    with open(sched_path, "w") as f:
        json.dump({"schema": 1, "seed": 3, "faults": [
            {"site": "single.stage_put", "kind": "oom", "times": 2}]}, f)
    oom_metrics = os.path.join(args.out, "metrics_oom.jsonl")
    if os.path.exists(oom_metrics):
        os.remove(oom_metrics)
    out_b, _, _ = run_cli(input_path, {"DMLP_TPU_PRUNE": "1"},
                          ["--metrics", oom_metrics,
                           "--faults", sched_path])
    if out_b != golden:
        fail("oom-schedule run stdout differs from golden — ladder "
             "recovery changed answers")
    res = last_summary(oom_metrics).get("resilience") or {}
    degs = res.get("degradations") or []
    if "prune->fused" not in degs:
        fail(f"oom fired but the ladder recorded {degs!r}, expected a "
             "prune->fused step")
    print(f"prune_smoke: seeded oom recovered via {degs} with "
          "byte-identical output")

    # -- optional run record -------------------------------------------------
    if args.record:
        from dmlp_tpu.obs.run import RunRecord, round_from_name
        RunRecord(
            kind="prune", tool="tools.prune_smoke",
            config={"config_id": "banded", "input": "banded.in",
                    "num_data": inp.params.num_data,
                    "num_queries": inp.params.num_queries,
                    "num_attrs": inp.params.num_attrs,
                    "select": "topk", "data_block": 2048},
            metrics={
                "engine_ms_pruned": round(statistics.median(
                    times["pruned"])),
                "engine_ms_pruned_reps": times["pruned"],
                "engine_ms_dense": round(statistics.median(
                    times["dense"])),
                "engine_ms_dense_reps": times["dense"],
                "scanned_bytes_pruned": pb["scanned_bytes"],
                "scanned_bytes_dense": db["scanned_bytes"],
                "scanned_bytes_ratio": round(ratio, 4),
                "prune_blocks_total": pb["blocks_total"],
                "prune_blocks_pruned": pb["blocks_pruned"],
                "prune_ab_identical": True,
            },
            device="cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
            else None,
            round=round_from_name(args.record)).append_jsonl(args.record)
        print(f"prune_smoke: banded A/B recorded to {args.record}")

    print("prune_smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
