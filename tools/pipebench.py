#!/usr/bin/env python
"""PIPEBENCH: GPipe vs interleaved (1F1B-interleaved) schedule A/B.

Runs both dp_pp schedules at fixed shape across small microbatch counts
(the regime round-4 review item 6 targets: the GPipe bubble term
(S-1)/(M+S-1) is largest there), interleaved A/B with rotating starts
(the verify-skill methodology), and records per-config median/min step
times plus the analytic bubble fractions — one schema-1 RunRecord
(obs.run) with the sweep table under metrics, plus per-schedule
ppermute activation bytes from obs.comms.

Pipeline parallelism needs multiple devices; the container has ONE real
TPU chip, so this runs on the virtual 8-device CPU mesh (like harness
config 3) — schedule-relative numbers, not absolute TPU step times.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tools/pipebench.py [--out PIPEBENCH_r06.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def build(schedule: str, mesh, n_micro: int, n_virtual: int, dims, batch,
          seed=0):
    from dmlp_tpu.train import pipeline as pl
    from dmlp_tpu.train.step import make_optimizer

    d_in, hidden, n_classes = dims
    opt = make_optimizer("sgd", 0.05, momentum=0.0)
    if schedule == "gpipe":
        # layers_per_stage = n_virtual * layers_per_chunk so both
        # schedules train the SAME total layer count per stage.
        state = pl.build_pp_state(mesh, opt, d_in, hidden, n_classes,
                                  2 * n_virtual, seed=seed)
        step = pl.make_pp_train_step(mesh, opt, n_micro=n_micro,
                                     n_classes=n_classes)
    else:
        state = pl.build_ppi_state(mesh, opt, d_in, hidden, n_classes,
                                   n_virtual=n_virtual, layers_per_chunk=2,
                                   seed=seed)
        step = pl.make_ppi_train_step(mesh, opt, n_micro=n_micro,
                                      n_virtual=n_virtual,
                                      n_classes=n_classes)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(batch, d_in)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, n_classes, batch).astype(np.int32))
    return state, step, x, y


def time_steps(state, step, x, y, reps: int):
    state, m = step(state, x, y)  # compile + warm
    jax.block_until_ready(m["loss"])
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        state, m = step(state, x, y)
        jax.block_until_ready(m["loss"])
        times.append((time.perf_counter() - t0) * 1e3)
    return times, state


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="PIPEBENCH_r06.json")
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--virtual", type=int, default=2)
    args = ap.parse_args()

    from dmlp_tpu.obs.comms import pipeline_ppermute_traffic
    from dmlp_tpu.train.pipeline import (bubble_fraction, make_pp_mesh,
                                         schedule_ticks)
    mesh = make_pp_mesh(args.dp, args.pp)
    dims = (64, args.hidden, 16)

    records = []
    for n_micro in (1, 2, 4):
        batch = args.dp * max(n_micro, 1) * 64
        cells = {}
        for sched in ("gpipe", "interleaved"):
            cells[sched] = build(sched, mesh, n_micro, args.virtual, dims,
                                 batch)
        samples = {s: [] for s in cells}
        order = list(cells)
        for r in range(args.reps):
            for s in (order if r % 2 == 0 else order[::-1]):
                st, step, x, y = cells[s]
                ts, st = time_steps(st, step, x, y, 1)
                cells[s] = (st, step, x, y)
                samples[s].extend(ts)
        rec = {"n_micro": n_micro, "stages": args.pp, "dp": args.dp,
               "virtual": args.virtual, "hidden": args.hidden,
               "batch": batch}
        for s, ts in samples.items():
            # Analytic activation-traffic accounting (obs.comms): the
            # schedule's ppermute bytes per step, fwd + bwd mirrored.
            ppermute = pipeline_ppermute_traffic(
                args.pp, n_micro, batch // args.dp // n_micro, args.hidden,
                schedule=s, n_virtual=args.virtual, n_groups=args.dp,
                count=2)
            rec[s] = {
                "median_ms": float(np.median(ts)),
                "min_ms": float(np.min(ts)),
                "ticks": schedule_ticks(s, n_micro, args.pp, args.virtual),
                "bubble_fraction": bubble_fraction(s, n_micro, args.pp,
                                                   args.virtual),
                "ppermute_bytes_per_step": ppermute.bytes_total,
            }
        rec["interleaved_vs_gpipe_pct"] = 100.0 * (
            rec["interleaved"]["median_ms"] / rec["gpipe"]["median_ms"] - 1)
        records.append(rec)
        print(json.dumps(rec))

    # One schema-1 RunRecord (obs.run), sweeps merged in place per hidden
    # size: re-running the tool for one sweep must not clobber the other
    # sweeps already in the artifact (the small-hidden sweep is overhead-
    # dominated, the large one compute-dominated — both belong).
    from dmlp_tpu.obs.run import RunRecord
    sweeps = {}
    if os.path.exists(args.out):
        try:
            prev = json.load(open(args.out))
            # RunRecord form nests sweeps under metrics; the grandfathered
            # pre-migration artifact held them at top level.
            sweeps.update(prev.get("metrics", prev).get("sweeps", {}))
        except (json.JSONDecodeError, OSError):
            pass
    sweeps[f"hidden_{args.hidden}"] = records
    RunRecord(
        kind="pipebench", tool="tools/pipebench",
        config={"platform": jax.devices()[0].platform,
                "n_devices": len(jax.devices()),
                "dp": args.dp, "pp": args.pp, "virtual": args.virtual,
                "reps": args.reps},
        metrics={
            "note": "virtual CPU mesh (1 real TPU chip cannot host a "
                    "pipeline); schedule-relative timings + analytic "
                    "bubble fractions and ppermute activation bytes "
                    "(obs.comms). Small hidden sizes are per-tick-"
                    "overhead dominated (emulated collectives; "
                    "interleaved loses); compute-dominated sweeps show "
                    "the bubble win.",
            "sweeps": sweeps,
        },
    ).write(args.out)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
