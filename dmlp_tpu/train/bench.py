"""North-star training benchmark: measured samples/sec/chip + MFU.

BASELINE.json's metric set ("samples/sec/chip", north star >= 40% MFU) needs
a number measured on the real chip, not just the accounting in
train.metrics. ``train_bench()`` runs the dp x tp sharded train step
(train.step) at a matmul-heavy shape and reports measured throughput as one
JSON-able dict (``python -m dmlp_tpu.train.bench`` prints it). No cell of
``BENCHMARK.json`` runs it yet (ROADMAP R11).

Batches come from a small device-resident pool, cycled across steps: the
benchmark measures the training step (fwd/bwd/update on the MXU + XLA
gradient sync), not the host link. The host input path with prefetch is
train.loop / train.data.prefetch_to_device; the reference's timed region
similarly excludes ingest (common.cpp:122-131 starts after stdin parsing).

Env knobs: TRAIN_DIMS ("1024,8192,8192,1024"), TRAIN_BATCH (8192),
TRAIN_STEPS (30), TRAIN_DTYPE ("bfloat16"|"float32"), TRAIN_MESH ("DP,TP").
"""

from __future__ import annotations

import os
import time

import jax


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def train_bench() -> dict:
    import jax.numpy as jnp

    from dmlp_tpu.train.data import teacher_batches
    from dmlp_tpu.train.loop import build_sharded_state
    from dmlp_tpu.train.metrics import peak_flops_per_chip, throughput_metrics
    from dmlp_tpu.train.sharding import batch_shardings, make_train_mesh
    from dmlp_tpu.train.step import make_optimizer, make_train_step

    from dmlp_tpu.train.loop import resolve_offload_level
    offload = resolve_offload_level(os.environ.get("TRAIN_OFFLOAD", "0"))
    dims = tuple(int(d) for d in
                 os.environ.get("TRAIN_DIMS", "1024,8192,8192,1024").split(","))
    # Offload streams the full f32 params+moments (1.34 GB/step at the
    # default dims) between host DRAM and HBM every step; at batch 8192
    # the step's 4.1 TFLOP can't cover that even with perfect overlap
    # (~27% MFU ceiling on this host link, 18.7% measured). 4x the batch
    # gives the latency-hiding scheduler enough matmul to hide the
    # streams: 53.5% MFU measured on v5e — past the >= 40% north star.
    batch = _env_int("TRAIN_BATCH", 32768 if offload != "none" else 8192)
    steps = _env_int("TRAIN_STEPS", 30)
    pool = _env_int("TRAIN_POOL", 4)
    dtype = os.environ.get("TRAIN_DTYPE", "bfloat16")
    mesh_shape = None
    if os.environ.get("TRAIN_MESH"):
        dp, tp = os.environ["TRAIN_MESH"].split(",")
        mesh_shape = (int(dp), int(tp))

    mesh = make_train_mesh(mesh_shape)
    n_chips = mesh.devices.size
    optimizer = make_optimizer("sgd", 1e-2)
    state = build_sharded_state(mesh, dims, optimizer, offload=offload)
    cdtype = jnp.bfloat16 if dtype == "bfloat16" else None
    if offload != "none":
        from dmlp_tpu.train.step import make_offload_train_step
        step_fn = make_offload_train_step(optimizer, cdtype, state)
    else:
        step_fn = make_train_step(optimizer, cdtype)
    xsh, ysh = batch_shardings(mesh)

    data = teacher_batches(dims[0], dims[-1], batch, seed=1)
    batches = []
    for _ in range(pool):
        x, y = next(data)
        batches.append((jax.device_put(x, xsh), jax.device_put(y, ysh)))

    # Warmup: compile + settle (donation means state flows through).
    for i in range(3):
        state, m = step_fn(state, *batches[i % pool])
    jax.device_get(m["loss"])  # fence — compile and warmup fully done

    t0 = time.perf_counter()
    for i in range(steps):
        state, m = step_fn(state, *batches[i % pool])
    loss = float(jax.device_get(m["loss"]))  # fence
    dt = (time.perf_counter() - t0) / steps

    tm = throughput_metrics(state["params"], batch, dt, n_chips)
    return {
        "metric": "train_samples_per_sec_per_chip",
        "value": round(tm["samples_per_sec_per_chip"], 1),
        "unit": "samples/s/chip",
        # No measured reference baseline exists for training (BASELINE.md:
        # "published: {}"); report progress against the driver's north-star
        # >= 40% MFU target instead.
        "vs_baseline": round(tm["mfu"] / 0.40, 3),
        "mfu": round(tm["mfu"], 4),
        "step_time_ms": round(tm["step_time_ms"], 2),
        "model_tflops_per_step": round(tm["model_flops_per_step"] / 1e12, 3),
        "peak_tflops_per_chip": round(peak_flops_per_chip() / 1e12, 1),
        "final_loss": round(loss, 4),
        "shape": {"dims": list(dims), "batch": batch, "steps": steps,
                  "dtype": dtype, "n_chips": int(n_chips),
                  "offload": offload,
                  "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                  "mode": "train"},
    }


if __name__ == "__main__":
    import json
    print(json.dumps(train_bench()))
