#!/usr/bin/env python
"""Randomized differential fuzz campaign (committed form of the r4 hunts).

Every case solves a random KNN instance with a randomly-configured engine
and diffs run() results against the strict float64 golden model —
checksum-level equality, so any algorithmic, padding, routing, staging,
or repair bug is a hard failure, not a tolerance judgement.

Axes:
- data styles: duplicate-heavy integer grids, continuous uniform,
  CLUSTERED near-duplicates (the style that found the r4 f32
  cancellation hazard), huge magnitudes, mixed clusters+uniform, extreme
  aspect ratios (na=1 / single query / tiny n).
- k drawn over the FULL legal range [1, num_data] — exercises the
  heterogeneous-k router, the r5 MULTI-PASS wide-k extraction, and the
  wide-k f32 staging policy (staging_for_k).
- dtype auto | float32 | bfloat16; exact and fast modes; selects
  auto/extract (use_pallas on) and the streaming selects.
- engines: single, sharded, ring (the mesh engines need the virtual
  8-device CPU mesh).

Usage:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/fuzz_engines.py --seeds 10000:10100 [--out FUZZ.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def gen_case(seed: int):
    from dmlp_tpu.io.grammar import KNNInput, Params
    rng = np.random.default_rng(seed)
    style = rng.choice(["intdup", "uniform", "clustered", "huge",
                        "mixed", "aspect"])
    if style == "aspect":
        n = int(rng.choice([1, 2, 3, 9, 700]))
        nq = int(rng.choice([1, 2, 17]))
        na = int(rng.choice([1, 2, 8]))
    else:
        n = int(rng.integers(50, 2600))
        nq = int(rng.integers(1, 28))
        na = int(rng.integers(1, 9))
    if style == "intdup":
        data = rng.integers(0, 3, (n, na)).astype(np.float64)
        queries = rng.integers(0, 3, (nq, na)).astype(np.float64)
    elif style == "clustered":
        nc = int(rng.integers(1, 5))
        centers = rng.uniform(-5, 5, (nc, na))
        data = centers[rng.integers(0, nc, n)] + rng.normal(0, 1e-3, (n, na))
        queries = centers[rng.integers(0, nc, nq)] \
            + rng.normal(0, 1e-3, (nq, na))
    elif style == "huge":
        data = rng.uniform(0, 1e6, (n, na))
        queries = rng.uniform(0, 1e6, (nq, na))
    elif style == "mixed":
        c = rng.uniform(-10, 10, (1, na))
        half = n // 2
        data = np.concatenate([c + rng.normal(0, 1e-3, (half, na)),
                               rng.uniform(-20, 20, (n - half, na))])
        queries = rng.uniform(-20, 20, (nq, na))
    else:
        data = rng.uniform(-20, 20, (n, na))
        queries = rng.uniform(-20, 20, (nq, na))
    labels = rng.integers(0, int(rng.integers(1, 7)), n).astype(np.int32)
    # full legal k range, biased so wide-k (router/multipass) really fires
    if rng.random() < 0.35:
        ks = rng.integers(max(1, n // 2), n + 1, nq).astype(np.int32)
    else:
        ks = rng.integers(1, n + 1, nq).astype(np.int32)
    return style, KNNInput(Params(n, nq, na), labels, data, ks, queries)


def gen_config(seed: int):
    from dmlp_tpu.config import EngineConfig
    rng = np.random.default_rng(seed ^ 0x5EED)
    mode = rng.choice(["single", "single", "sharded", "ring"])
    dtype = rng.choice(["auto", "float32", "bfloat16"])
    exact = bool(rng.random() < 0.8)
    if rng.random() < 0.5:
        select, pallas = "extract", True
    else:
        select = rng.choice(["auto", "topk", "seg", "sort"])
        pallas = bool(rng.random() < 0.5)
    return EngineConfig(mode=mode, dtype=dtype, exact=exact,
                        select=select, use_pallas=pallas)


def run_case(seed: int):
    import jax

    from dmlp_tpu.engine.sharded import RingEngine, ShardedEngine
    from dmlp_tpu.engine.single import SingleChipEngine
    from dmlp_tpu.golden.reference import knn_golden
    from dmlp_tpu.parallel.mesh import make_mesh

    style, inp = gen_case(seed)
    cfg = gen_config(seed)
    # Fast mode's output IS the device f32 ordering — golden-checksum
    # parity is only promised there when f32 arithmetic is exact on the
    # data (integer grids); continuous styles run exact mode, like the
    # committed fast-mode tests.
    if style != "intdup" and not cfg.exact:
        import dataclasses
        cfg = dataclasses.replace(cfg, exact=True)
    if cfg.mode != "single" and len(jax.devices()) < 8:
        import dataclasses
        cfg = dataclasses.replace(cfg, mode="single")
    if cfg.mode == "single":
        eng = SingleChipEngine(cfg)
    else:
        cls = ShardedEngine if cfg.mode == "sharded" else RingEngine
        shape = [(4, 2), (2, 4), (8, 1), (1, 8)][seed % 4]
        eng = cls(cfg, mesh=make_mesh(shape))
    got = eng.run(inp)
    want = knn_golden(inp)
    ok = all(g.checksum() == w.checksum() for g, w in zip(got, want)) \
        and len(got) == len(want)
    return {"seed": seed, "style": str(style), "mode": cfg.mode,
            "dtype": str(cfg.dtype), "exact": cfg.exact,
            "select": str(cfg.select), "pallas": cfg.use_pallas,
            "n": inp.params.num_data, "nq": inp.params.num_queries,
            "kmax": int(inp.ks.max()), "ok": ok,
            "mp_passes": getattr(eng, "last_mp_passes", 0),
            "hetk": getattr(eng, "last_hetk", None) is not None,
            "repairs": int(getattr(eng, "last_repairs", 0))}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="10000:10100",
                    help="lo:hi seed range")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    lo, hi = (int(x) for x in args.seeds.split(":"))

    t0 = time.time()
    results, failures = [], []
    for seed in range(lo, hi):
        r = run_case(seed)
        results.append(r)
        if not r["ok"]:
            failures.append(r)
            print("FAIL", json.dumps(r))
        elif (seed - lo) % 10 == 0:
            print(f"{seed - lo + 1}/{hi - lo} ok "
                  f"(mp={sum(x['mp_passes'] > 1 for x in results)}, "
                  f"hetk={sum(x['hetk'] for x in results)}, "
                  f"repaired={sum(x['repairs'] > 0 for x in results)})",
                  flush=True)
    summary = {
        "seeds": f"{lo}:{hi}", "cases": len(results),
        "failures": len(failures), "failed": failures,
        "minutes": round((time.time() - t0) / 60, 1),
        "coverage": {
            "multipass_cases": sum(r["mp_passes"] > 1 for r in results),
            "hetk_routed_cases": sum(r["hetk"] for r in results),
            "repaired_cases": sum(r["repairs"] > 0 for r in results),
            "by_mode": {m: sum(r["mode"] == m for r in results)
                        for m in ("single", "sharded", "ring")},
            "by_dtype": {d: sum(r["dtype"] == d for r in results)
                         for d in ("auto", "float32", "bfloat16")},
        },
    }
    print(json.dumps(summary, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
