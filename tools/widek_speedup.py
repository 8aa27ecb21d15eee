#!/usr/bin/env python
"""Wide-k multi-pass extract vs streaming fallback A/B (round-4 review #2).

The r4 engine dropped ALL-wide-k inputs (every query's k beyond the
kernel's 512-slot window) to the streaming selects; r5 runs the kernel in
floor-raised multi-passes. This measures the payoff at the review's
shape (200k x 1k x 64, k=4096): the multipass engine vs an engine forced
onto the streaming select, interleaved reps, identical input, both
checksum-validated against each other.

Run on the chip. CPU works too (interpret kernel) but
the numbers then measure the interpreter, not the kernel.

Usage: python tools/widek_speedup.py [--out WIDEK_MP_r05.json]
       [--n 204800 --q 1024 --a 64 --k 4096] [--reps 3]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="WIDEK_MP_r05.json")
    ap.add_argument("--n", type=int, default=204800)
    ap.add_argument("--q", type=int, default=1024)
    ap.add_argument("--a", type=int, default=64)
    ap.add_argument("--k", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    import jax

    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.engine.single import SingleChipEngine
    from dmlp_tpu.io.grammar import KNNInput, Params

    rng = np.random.default_rng(9)
    n, q, a, k = args.n, args.q, args.a, args.k
    inp = KNNInput(Params(n, q, a),
                   rng.integers(0, 10, n).astype(np.int32),
                   rng.uniform(0, 100, (n, a)),
                   np.full(q, k, np.int32),
                   rng.uniform(0, 100, (q, a)))

    engines = {
        # multipass: select="extract" + wide k routes through the
        # floor-raised passes (hetk has no bulk to keep)
        "extract_multipass": SingleChipEngine(
            EngineConfig(select="extract", use_pallas=True)),
        # the r4 behavior: streaming select (what the input used to get)
        "streaming": SingleChipEngine(
            EngineConfig(select="seg", use_pallas=True)),
    }

    results = {}
    samples = {name: [] for name in engines}
    order = list(engines)
    for r in range(args.reps + 1):  # warmup round dropped
        for name in (order if r % 2 == 0 else order[::-1]):
            eng = engines[name]
            t0 = time.perf_counter()
            res = eng.run(inp)
            dt = (time.perf_counter() - t0) * 1e3
            if r > 0:
                samples[name].append(dt)
            results[name] = res

    # cross-validate: both paths must produce identical checksums
    cs_a = [r.checksum() for r in results["extract_multipass"]]
    cs_b = [r.checksum() for r in results["streaming"]]
    assert cs_a == cs_b, "paths disagree — BUG"

    rec = {"platform": jax.devices()[0].platform,
           "shape": [n, q, a], "k": k,
           "mp_passes": engines["extract_multipass"].last_mp_passes,
           "repairs": {name: int(e.last_repairs)
                       for name, e in engines.items()},
           "checksums_identical": True, "engines": {}}
    for name, ts in samples.items():
        rec["engines"][name] = {
            "median_ms": float(np.median(ts)),
            "min_ms": float(np.min(ts)),
            "phases_ms": {p: round(v, 1) for p, v in
                          engines[name].last_phase_ms.items()},
            "select": engines[name]._last_select}
    rec["multipass_vs_streaming_pct"] = round(100.0 * (
        rec["engines"]["extract_multipass"]["median_ms"]
        / rec["engines"]["streaming"]["median_ms"] - 1), 1)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
