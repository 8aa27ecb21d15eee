#!/usr/bin/env python
"""Compiled-program introspection smoke (`make hlo-smoke`): CI teeth
for the HLO-derived collective/memory report (obs.hlo) on CPU.

Four invariants, each a hard failure:

1. **Byte identity** — bench input 1 through the real CLI with
   ``--hlo-report`` must produce contract stdout byte-identical to the
   plain run for every engine mode exercised (sharded, ring, auto):
   introspection is pure observation.
2. **Hand-rolled engines reconcile** — the sharded engine's compiled
   all-gather bytes and the ring engine's compiled collective-permute
   bytes (while-loop trip counts folded in) must each reconcile against
   that engine's own analytic ``# check: comms-model`` records within
   :data:`dmlp_tpu.obs.hlo.COMMS_RATIO_BOUNDS` — the models stop being
   claims and become checked statements about the compiled program.
3. **The partitioner's schedule is real** — the auto (GSPMD) engine's
   report must name at least one compiler-chosen collective with
   nonzero bytes and per-mesh-axis attribution, and its ``gspmd_*``
   traffic records must reconcile exactly (the honest-but-empty comms
   block is gone).
4. **Run record** — each ``--hlo-report`` RunRecord must load as kind
   ``hlo`` with its mode in ``config`` and ``collective_bytes_total``
   > 0 for the distributed modes, and the
   memory leg must carry either ``hlo_peak_bytes`` (this CPU backend
   populates memory_analysis) or the explicit
   ``hlo_memory_unavailable`` marker — never silence.

Usage: JAX_PLATFORMS=cpu python tools/hlo_smoke.py --out outputs/hlo
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODES = ("sharded", "ring", "auto")


def fail(msg: str) -> None:
    print(f"hlo_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def run_cli(input_path: str, flags: list, timeout_s: float = 300.0) -> str:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                       " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, "-m", "dmlp_tpu"] + flags,
        stdin=open(input_path), capture_output=True, text=True,
        env=env, timeout=timeout_s)
    if proc.returncode != 0:
        fail(f"CLI {' '.join(flags)} rc={proc.returncode}: "
             f"{proc.stderr[-800:]}")
    return proc.stdout


def check_doc(doc: dict, mode: str) -> dict:
    """Structural checks one mode's hlo RunRecord must satisfy."""
    if doc.get("kind") != "hlo":
        fail(f"{mode}: last record kind={doc.get('kind')!r}, not 'hlo'")
    comms = doc.get("comms") or {}
    rec = comms.get("reconcile") or {}
    if doc["metrics"].get("collective_bytes_total", 0) <= 0:
        fail(f"{mode}: no collective bytes in the compiled schedule")
    mem = rec.get("memory") or {}
    if "hlo_peak_bytes" not in mem and \
            "hlo_memory_unavailable" not in mem:
        fail(f"{mode}: memory leg has neither hlo_peak_bytes nor the "
             f"hlo_memory_unavailable marker: {sorted(mem)}")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="outputs/hlo")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)

    from dmlp_tpu.bench.configs import BENCH_CONFIGS
    from dmlp_tpu.bench.harness import ensure_input
    input_path = ensure_input(BENCH_CONFIGS[1], "inputs")

    # 1) byte identity per mode, plus the hlo RunRecord per mode
    reconciles = {}
    for mode in MODES:
        base = run_cli(input_path, ["--mode", mode])
        rep_path = os.path.join(args.out, f"HLO_{mode}.jsonl")
        if os.path.exists(rep_path):
            os.remove(rep_path)
        out = run_cli(input_path,
                      ["--mode", mode, "--hlo-report", rep_path])
        if out != base:
            fail(f"{mode}: --hlo-report changed contract stdout")
        with open(rep_path) as f:
            doc = json.loads(f.read().splitlines()[-1])
        reconciles[mode] = check_doc(doc, mode)
        print(f"hlo_smoke: {mode}: contract byte-identical, "
              f"{doc['metrics']['collective_bytes_total']} collective "
              f"bytes introspected")

    # 2) hand-rolled engines reconcile against their own models
    for mode, kind in (("sharded", "all-gather"),
                       ("ring", "collective-permute")):
        kinds = (reconciles[mode].get("comms_model") or {}).get("kinds",
                                                                {})
        ent = kinds.get(kind)
        if not ent:
            fail(f"{mode}: no {kind} leg in the comms reconcile: "
                 f"{sorted(kinds)}")
        if not ent.get("within_tolerance"):
            fail(f"{mode}: {kind} HLO bytes do not reconcile with the "
                 f"analytic model: {ent}")
        print(f"hlo_smoke: {mode}: {kind} model ratio "
              f"{ent['ratio']} within {ent['ratio_bounds']}")

    # 3) the partitioner's schedule named with per-axis bytes
    kinds = (reconciles["auto"].get("comms_model") or {}).get("kinds", {})
    named = [(k, e) for k, e in kinds.items()
             if e.get("hlo_bytes", 0) > 0]
    if not named:
        fail(f"auto: partitioner schedule empty: {kinds}")
    bad = [k for k, e in named
           if not (e.get("within_tolerance") or e.get("hlo_only"))]
    if bad:
        fail(f"auto: gspmd_* records do not reconcile for {bad}")
    with open(os.path.join(args.out, "HLO_auto.jsonl")) as f:
        doc = json.loads(f.read().splitlines()[-1])
    by_axis = (doc.get("comms") or {}).get("bytes_by_axis") or {}
    if not by_axis or sum(by_axis.values()) <= 0:
        fail(f"auto: no per-axis byte attribution: {by_axis}")
    print(f"hlo_smoke: auto: partitioner chose "
          f"{', '.join(k for k, _ in named)}; bytes by axis {by_axis}")

    # 4) each mode's run record loads
    from dmlp_tpu.obs.run import RunRecord
    for mode in MODES:
        rec = RunRecord.load(os.path.join(args.out, f"HLO_{mode}.jsonl"))
        if rec.kind != "hlo" or rec.config.get("mode") != mode:
            fail(f"{mode}: record is kind {rec.kind!r}, "
                 f"mode {rec.config.get('mode')!r}")
        if not rec.metrics.get("collective_bytes_total", 0) > 0:
            fail(f"{mode}: collective_bytes_total missing or 0: "
                 f"{sorted(rec.metrics)}")
    print("hlo_smoke: run records ok (kind hlo, collective_bytes_total "
          "> 0 for " + ", ".join(MODES) + ")")
    print("hlo_smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
