#!/usr/bin/env python
"""Repair-rate sweep: measure the kcap margin's generality (round-4 review #9).

The bf16 staging margin 96 + k/2 (engine.single.resolve_kcap) was
calibrated at one shape (200k x 10k x 64); the eps-aware hazard test +
oracle repair is the sound backstop, but the margin's generality across
shapes, k, staging dtypes, and distance-density regimes was asserted, not
measured. This sweep runs the engine across that grid recording
last_repairs / num_queries (the repair RATE — every run is still
checksum-exact by construction; what varies is how often the backstop has
to fire) plus the multi-pass counter for wide-k cells.

Data styles:
  uniform   — generator-style uniform draws (the benchmark regime)
  clustered — tight Gaussian clusters (dense distance spectra: the regime
              that eats margins; f32-cancellation fuzz heritage)
  intdup    — small integer grids (massive exact tie groups)

Runs on CPU (forced dtype="bfloat16" staging exercises the same margin
arithmetic; interpret-mode kernel) or TPU (native kernel + real MXU
rounding) — the platform is recorded per artifact.

Usage:
  JAX_PLATFORMS=cpu python tools/repair_rate_sweep.py \
      [--out REPAIR_SWEEP_r05_cpu.json] [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def make_case(style: str, n: int, nq: int, na: int, kmax: int, seed: int):
    from dmlp_tpu.io.grammar import KNNInput, Params
    rng = np.random.default_rng(seed)
    if style == "uniform":
        data = rng.uniform(0, 100, (n, na))
        queries = rng.uniform(0, 100, (nq, na))
    elif style == "clustered":
        nc = 16
        centers = rng.uniform(0, 100, (nc, na))
        data = centers[rng.integers(0, nc, n)] + rng.normal(
            0, 1e-3, (n, na))
        queries = centers[rng.integers(0, nc, nq)] + rng.normal(
            0, 1e-3, (nq, na))
    elif style == "intdup":
        data = rng.integers(0, 4, (n, na)).astype(np.float64)
        queries = rng.integers(0, 4, (nq, na)).astype(np.float64)
    else:
        raise ValueError(style)
    labels = rng.integers(0, 8, n).astype(np.int32)
    ks = rng.integers(max(1, kmax // 2), kmax + 1, nq).astype(np.int32)
    return KNNInput(Params(n, nq, na), labels, data, ks, queries)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="REPAIR_SWEEP_r05.json")
    ap.add_argument("--quick", action="store_true",
                    help="smaller grid (CI smoke)")
    args = ap.parse_args()

    import jax

    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.engine.single import SingleChipEngine

    platform = jax.devices()[0].platform
    # (n, nq, na, kmax): spans narrow/wide k, small/large attr counts,
    # and (on the full grid) the multi-pass wide-k regime.
    shapes = [(12800, 128, 8, 16), (12800, 128, 64, 40),
              (12800, 128, 64, 192)]
    if not args.quick:
        shapes += [(25600, 256, 16, 40), (12800, 128, 64, 768),
                   (25600, 128, 64, 1024)]
    styles = ["uniform", "clustered", "intdup"]
    dtypes = ["float32", "bfloat16"]

    records = []
    for n, nq, na, kmax in shapes:
        for style in styles:
            inp = make_case(style, n, nq, na, kmax, seed=n + kmax)
            for dtype in dtypes:
                eng = SingleChipEngine(EngineConfig(
                    select="extract", use_pallas=True, dtype=dtype))
                t0 = time.perf_counter()
                eng.run(inp)
                dt = (time.perf_counter() - t0) * 1e3
                rec = {"n": n, "nq": nq, "na": na, "kmax": kmax,
                       "style": style, "dtype": dtype,
                       "select": eng._last_select,
                       "repairs": int(eng.last_repairs),
                       "repair_rate": round(eng.last_repairs / nq, 4),
                       "mp_passes": int(eng.last_mp_passes),
                       "ms": round(dt)}
                records.append(rec)
                print(json.dumps(rec))

    agg = {}
    for r in records:
        key = f"{r['style']}/{r['dtype']}"
        agg.setdefault(key, []).append(r["repair_rate"])
    out = {"platform": platform,
           "margin": "resolve_kcap: bf16 exact margin = 96 + k/2, "
                     "f32 >= 8 slack",
           "note": "repair_rate = hazard-flagged queries / total (all runs "
                   "are checksum-exact regardless; rate measures how often "
                   "the oracle backstop fires, i.e. the margin's slack)",
           "records": records,
           "mean_rate_by_style_dtype": {k: round(float(np.mean(v)), 4)
                                        for k, v in sorted(agg.items())}}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
