#!/usr/bin/env python
"""Tie-adversarial differential fuzz: golden model vs the REAL binaries.

Requires the reference checkout's stripped engines, launched in-container
via Open MPI's isolated-singleton mode (no orted needed). Generates
tie-heavy adversarial instances (integer duplicate grids, k = n,
near-duplicate clusters, plus continuous controls), runs each through
bench_1..4 AND the golden model, and diffs the checksum sets.

This is the experiment that MEASURED the reference's true tie semantics
(r5): selection ties break to the larger id, label-free — bench_1/2/3
match the golden model exactly under that comparator, while bench_4
breaks report ties id-ASCENDING, disagreeing with its own siblings (its
mismatches are recorded per-case, expected, and counted separately).
On tie-free inputs (every graded benchmark input) all five
implementations coincide.

Usage:
  python tools/fuzz_vs_binaries.py [--seeds 3000:3100]
      [--ref /root/reference] [--out outputs/TIE_SEMANTICS.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def gen(seed: int):
    from dmlp_tpu.io.grammar import KNNInput, Params, format_input, \
        parse_input_text
    rng = np.random.default_rng(seed)
    style = ["intdup", "uniform", "k_eq_n", "clustered"][seed % 4]
    n = int(rng.integers(3, 120))
    nq = int(rng.integers(1, 12))
    na = int(rng.integers(1, 6))
    if style == "intdup":
        data = rng.integers(0, 3, (n, na)).astype(np.float64)
        queries = rng.integers(0, 3, (nq, na)).astype(np.float64)
    elif style == "clustered":
        c = rng.uniform(-5, 5, (1, na))
        data = c + rng.normal(0, 1e-3, (n, na))
        queries = c + rng.normal(0, 1e-3, (nq, na))
    else:
        data = rng.uniform(-9, 9, (n, na))
        queries = rng.uniform(-9, 9, (nq, na))
    data, queries = data.round(6), queries.round(6)
    labels = rng.integers(0, int(rng.integers(1, 5)), n).astype(np.int32)
    ks = (np.full(nq, n, np.int32) if style == "k_eq_n"
          else rng.integers(1, n + 1, nq).astype(np.int32))
    return style, parse_input_text(format_input(
        KNNInput(Params(n, nq, na), labels, data, ks, queries)))


def lines(s: str):
    return sorted(l for l in s.splitlines() if l.strip())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="3000:3100")
    ap.add_argument("--ref", default="/root/reference")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    lo, hi = (int(x) for x in args.seeds.split(":"))

    from dmlp_tpu.golden.reference import knn_golden
    from dmlp_tpu.io.grammar import format_input
    from dmlp_tpu.io.report import format_results

    env = dict(os.environ, OMPI_MCA_ess_singleton_isolated="1")
    benches = {b: os.path.join(args.ref, "benchmarks", b)
               for b in ("bench_1", "bench_2", "bench_3", "bench_4")}
    for b, p in benches.items():
        if not os.path.exists(p):
            print(f"FATAL: {p} missing (need the reference checkout)")
            return 1

    mismatch = {b: 0 for b in benches}
    tie_cases = 0
    cases = 0
    for seed in range(lo, hi):
        style, inp = gen(seed)
        text = format_input(inp).encode()
        want = lines(format_results(knn_golden(inp)))
        cases += 1
        per_case = {}
        for b, p in benches.items():
            r = subprocess.run([p], input=text, capture_output=True,
                               env=env, timeout=120)
            per_case[b] = lines(r.stdout.decode()) == want
            if not per_case[b]:
                mismatch[b] += 1
        if not all(per_case.values()):
            tie_cases += 1
            if any(not per_case[b] for b in ("bench_1", "bench_2",
                                             "bench_3")):
                print(f"UNEXPECTED b1-3 mismatch seed={seed} style={style} "
                      f"{dict(per_case)}")
    summary = {
        "seeds": f"{lo}:{hi}", "cases": cases,
        "golden_mismatches_per_binary": mismatch,
        "semantics": "selection + report ties -> larger id (label-free); "
                     "vote ties -> larger label",
        "note": "bench_4 breaks report ties id-ASC — measured to disagree "
                "with bench_1/2/3 on the same inputs; its mismatch count "
                "is the tie-case count, not a golden defect. b1/2/3 "
                "mismatches should be 0.",
    }
    print(json.dumps(summary, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    return 1 if any(mismatch[b] for b in ("bench_1", "bench_2",
                                          "bench_3")) else 0


if __name__ == "__main__":
    sys.exit(main())
