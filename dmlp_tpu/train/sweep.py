"""Weak-scaling sweep: per-chip training throughput vs mesh size.

The BASELINE ladder's top rung (BASELINE.md "v5p-128 weak-scaling sweep on
generate_input.py synthetic data"): run the same per-chip workload on
growing dp meshes and watch samples/sec/chip — flat = perfect weak scaling,
droop = collective overhead. Global batch scales with the dp degree
(batch_per_chip stays fixed), the tp degree is constant, so the dp gradient
all-reduce is the only added cost per rung.

On a single-chip or CPU host the sweep runs on virtual devices
(XLA_FLAGS=--xla_force_host_platform_device_count=N) for correctness and
trend shape; absolute numbers come from real multi-chip meshes, where the
same code runs unchanged (the mesh is the only variable).

Usage::

    python -m dmlp_tpu.train.sweep --mesh-sizes 1,2,4,8 --steps 20 \
        --batch-per-chip 256 --dims 64,256,256,10 [--out sweep.jsonl]
        [--offload]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Optional, Sequence

import jax


def sweep_point(n_chips: int, dims: Sequence[int], batch_per_chip: int,
                steps: int, dtype: Optional[str] = "bfloat16",
                offload: bool = False, pool: int = 2) -> dict:
    """One rung: dp=n_chips mesh, global batch = batch_per_chip * n_chips."""
    import jax.numpy as jnp

    from dmlp_tpu.train.data import teacher_batches
    from dmlp_tpu.train.loop import build_sharded_state
    from dmlp_tpu.train.metrics import throughput_metrics
    from dmlp_tpu.train.sharding import batch_shardings, make_train_mesh
    from dmlp_tpu.train.step import make_optimizer, make_train_step

    devices = jax.devices()[:n_chips]
    if len(devices) < n_chips:
        raise ValueError(f"need {n_chips} devices, have {len(devices)}")
    mesh = make_train_mesh((n_chips, 1), devices)
    batch = batch_per_chip * n_chips
    optimizer = make_optimizer("sgd", 1e-2)
    state = build_sharded_state(mesh, dims, optimizer, offload=offload)
    cdtype = jnp.bfloat16 if dtype == "bfloat16" else None
    if offload:
        from dmlp_tpu.train.step import make_offload_train_step
        step_fn = make_offload_train_step(optimizer, cdtype, state)
    else:
        step_fn = make_train_step(optimizer, cdtype)
    xsh, ysh = batch_shardings(mesh)

    data = teacher_batches(dims[0], dims[-1], batch, seed=1)
    batches = [tuple(jax.device_put(a, s) for a, s in
                     zip(next(data), (xsh, ysh))) for _ in range(pool)]

    for i in range(2):  # compile + settle
        state, m = step_fn(state, *batches[i % pool])
    jax.device_get(m["loss"])
    t0 = time.perf_counter()
    for i in range(steps):
        state, m = step_fn(state, *batches[i % pool])
    jax.device_get(m["loss"])  # fence
    dt = (time.perf_counter() - t0) / steps

    tm = throughput_metrics(state["params"], batch, dt, n_chips)
    return {
        "n_chips": n_chips,
        "global_batch": batch,
        "samples_per_sec_per_chip": round(tm["samples_per_sec_per_chip"], 1),
        "step_time_ms": round(tm["step_time_ms"], 2),
        **({"mfu": round(tm["mfu"], 4)} if "mfu" in tm else {}),
        "dims": list(dims),
        "offload": offload,
        "dtype": dtype or "float32",
    }


def decompose(points: list) -> Optional[dict]:
    """Fitted communication-overhead decomposition across the rungs.

    Weak scaling keeps the per-chip workload constant, so the 1-chip rung
    is the compute-only floor and any step-time growth is collective
    overhead. The dp gradient all-reduce's ring cost scales as
    2(n-1)/n * bytes / bw, so the model is

        step_ms(n) = t_compute + t_allreduce_full * (n - 1) / n

    fitted by least squares over the rungs; per-point fields report the
    raw overhead vs rung 1. On virtual CPU devices the collectives are
    shared-memory copies, not ICI — the decomposition then characterizes
    the sweep PLUMBING (trend shape, overhead accounting), not hardware
    scaling, and is labeled as such.
    """
    if len(points) < 2:
        return None
    import numpy as np

    n = np.array([p["n_chips"] for p in points], float)
    t = np.array([p["step_time_ms"] for p in points], float)
    x = (n - 1.0) / n
    a = np.vstack([np.ones_like(x), x]).T
    (t_compute, t_ar), *_ = np.linalg.lstsq(a, t, rcond=None)
    resid = t - a @ np.array([t_compute, t_ar])
    # Compute-only floor: the measured 1-chip rung when present (its comm
    # term is exactly zero), else the fitted intercept as an extrapolated
    # fallback — the intercept alone misreports fit residual as per-rung
    # communication when the model fits poorly (virtual-device contention).
    ones = [p["step_time_ms"] for p in points if p["n_chips"] == 1]
    base = float(ones[0]) if ones else float(t_compute)
    for p in points:
        p["comm_overhead_ms"] = round(p["step_time_ms"] - base, 2)
        p["comm_fraction"] = round(
            max(p["step_time_ms"] - base, 0.0) / p["step_time_ms"], 4)
    return {"model": "step_ms = t_compute + t_allreduce_full * (n-1)/n",
            "t_compute_ms": round(float(t_compute), 2),
            "t_allreduce_full_ms": round(float(t_ar), 2),
            "max_abs_resid_ms": round(float(np.abs(resid).max()), 2)}


def run_sweep(mesh_sizes: Sequence[int], dims: Sequence[int],
              batch_per_chip: int, steps: int,
              dtype: Optional[str] = "bfloat16", offload: bool = False,
              out=None) -> list:
    results = []
    for n in mesh_sizes:
        point = sweep_point(n, dims, batch_per_chip, steps, dtype, offload)
        results.append(point)
        if out is not None:
            # Stream each rung as it lands — the largest mesh is exactly
            # where a crash/preemption happens, and earlier rungs must
            # survive it. The decomposition fields are appended to the
            # summary line instead of mutating already-written points.
            out.write(json.dumps(point) + "\n")
            out.flush()
    fit = decompose(results)
    if out is not None:
        if fit is not None:
            virtual = jax.devices()[0].platform == "cpu"
            out.write(json.dumps({
                "summary": fit,
                "per_rung_comm": [
                    {"n_chips": p["n_chips"],
                     "comm_overhead_ms": p["comm_overhead_ms"],
                     "comm_fraction": p["comm_fraction"]}
                    for p in results],
                "scope": ("plumbing-only: virtual CPU devices share the "
                          "same physical cores, so the overhead term "
                          "absorbs compute contention as well as the "
                          "shared-memory collectives (a large "
                          "max_abs_resid_ms flags exactly this); hardware "
                          "scaling needs a real multi-chip mesh"
                          if virtual else "hardware"),
            }) + "\n")
        out.flush()
    return results


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="dmlp_tpu.train.sweep",
                                description=__doc__)
    p.add_argument("--mesh-sizes", default="1,2,4,8")
    p.add_argument("--dims", default="64,256,256,10")
    p.add_argument("--batch-per-chip", type=int, default=256)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--offload", action="store_true")
    p.add_argument("--out", default=None, help="JSONL output path "
                   "(default: stdout)")
    args = p.parse_args(argv)

    sizes = [int(s) for s in args.mesh_sizes.split(",")]
    dims = tuple(int(d) for d in args.dims.split(","))
    out = open(args.out, "w") if args.out else sys.stdout
    try:
        run_sweep(sizes, dims, args.batch_per_chip, args.steps,
                  None if args.dtype == "float32" else args.dtype,
                  args.offload, out)
    finally:
        if args.out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
