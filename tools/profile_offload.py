"""Offload step-time decomposition (round-3 review item 7).

The r3 numbers: resident 68.5% MFU vs offload 54.5% (at 4x the batch).
This tool explains the gap with three fenced measurements at the SAME
shape:

1. ``resident``  — the regular train step, params+moments in HBM
   (pure-compute reference point);
2. ``stream``    — a transfer-only jit that round-trips the full
   params+moments pytree host DRAM -> HBM -> host DRAM, exactly the
   byte traffic the offload step adds, with no compute to hide it;
3. ``offload``   — the real in-jit offload step.

With perfect latency hiding, offload ~= max(resident, stream); with none,
offload ~= resident + stream. ``overlap_efficiency`` places the measured
step on that scale, and ``mfu_ceiling_stream`` is the best MFU any
scheduler could reach given the measured stream bandwidth — if the
measured offload MFU is at that ceiling, the gap is a hardware floor,
not scheduler headroom.

Writes OFFLOAD_DECOMP_r04.json. Env: TRAIN_DIMS/TRAIN_BATCH/TRAIN_STEPS/
TRAIN_DTYPE as in train.bench, BENCH_OUT.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fence(tree) -> None:
    import jax
    jax.block_until_ready(tree)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from dmlp_tpu.train.bench import _env_int
    from dmlp_tpu.train.data import teacher_batches
    from dmlp_tpu.train.loop import build_sharded_state
    from dmlp_tpu.train.metrics import (peak_flops_per_chip,
                                        throughput_metrics)
    from dmlp_tpu.train.sharding import batch_shardings, make_train_mesh
    from dmlp_tpu.train.step import (make_optimizer, make_train_step,
                                     supports_injit_offload)

    dims = tuple(int(d) for d in os.environ.get(
        "TRAIN_DIMS", "1024,8192,8192,1024").split(","))
    batch = _env_int("TRAIN_BATCH", 32768)
    steps = _env_int("TRAIN_STEPS", 30)
    dtype = os.environ.get("TRAIN_DTYPE", "bfloat16")
    out_path = os.environ.get("BENCH_OUT", "OFFLOAD_DECOMP_r04.json")
    cdtype = jnp.bfloat16 if dtype == "bfloat16" else None

    mesh = make_train_mesh(None)
    n_chips = mesh.devices.size
    optimizer = make_optimizer("sgd", 1e-2)
    xsh, ysh = batch_shardings(mesh)
    data = teacher_batches(dims[0], dims[-1], batch, seed=1)
    batches = []
    for _ in range(4):
        x, y = next(data)
        batches.append((jax.device_put(x, xsh), jax.device_put(y, ysh)))

    def timed_steps(step_fn, state):
        for i in range(3):
            state, m = step_fn(state, *batches[i % 4])
        jax.device_get(m["loss"])
        t0 = time.perf_counter()
        for i in range(steps):
            state, m = step_fn(state, *batches[i % 4])
        jax.device_get(m["loss"])
        return (time.perf_counter() - t0) / steps, state

    # 1. resident step at the SAME batch as offload.
    state_r = build_sharded_state(mesh, dims, optimizer, offload=False)
    dt_resident, state_r = timed_steps(make_train_step(optimizer, cdtype),
                                       state_r)
    tm_resident = throughput_metrics(state_r["params"], batch, dt_resident,
                                     n_chips)
    del state_r

    # 2. stream-only round trip of params + moments.
    state_h = build_sharded_state(mesh, dims, optimizer, offload=True)
    work = {"params": state_h["params"], "opt": state_h["opt"]}
    host_sh = jax.tree.map(lambda a: a.sharding, work)
    dev_sh = jax.tree.map(
        lambda a: a.sharding.with_memory_kind("device"), work)
    bytes_one_way = sum(a.size * a.dtype.itemsize
                        for a in jax.tree.leaves(work))

    def stream(w, eps):
        dev = jax.tree.map(jax.device_put, w, dev_sh)
        # Touch every leaf so neither copy can be elided.
        return jax.tree.map(lambda a: a + eps.astype(a.dtype), dev)

    stream_fn = jax.jit(stream, out_shardings=host_sh)
    eps = jnp.float32(0.0)
    w = stream_fn(work, eps)
    _fence(w)
    t0 = time.perf_counter()
    for _ in range(steps):
        w = stream_fn(w, eps)
    _fence(w)
    dt_stream = (time.perf_counter() - t0) / steps
    del w

    # 3. the real offload step (in-jit streaming on TPU runtimes).
    from dmlp_tpu.train.step import make_offload_train_step
    step_fn = make_offload_train_step(optimizer, cdtype, state_h)
    dt_offload, state_h = timed_steps(step_fn, state_h)
    tm_offload = throughput_metrics(state_h["params"], batch, dt_offload,
                                    n_chips)

    no_overlap = dt_resident + dt_stream
    perfect = max(dt_resident, dt_stream)
    overlap_eff = ((no_overlap - dt_offload) / (no_overlap - perfect)
                   if no_overlap > perfect else None)
    doc = {
        "note": "Offload decomposition at one shape: resident = compute "
                "reference, stream = transfer-only round trip of "
                "params+moments (no compute to hide it), offload = the "
                "real step. overlap_efficiency: 1.0 = perfect latency "
                "hiding (offload == max(resident, stream)), 0.0 = fully "
                "serial. mfu_ceiling_stream = resident MFU scaled by the "
                "best possible overlap given measured stream time.",
        "shape": {"dims": list(dims), "batch": batch, "steps": steps,
                  "dtype": dtype, "n_chips": int(n_chips),
                  "device_kind": getattr(jax.devices()[0], "device_kind",
                                         "?")},
        "injit_offload": bool(supports_injit_offload()),
        "resident_step_ms": round(dt_resident * 1e3, 2),
        "stream_roundtrip_ms": round(dt_stream * 1e3, 2),
        "offload_step_ms": round(dt_offload * 1e3, 2),
        "bytes_per_step_each_way": bytes_one_way,
        "stream_gb_per_s": round(2 * bytes_one_way / dt_stream / 1e9, 2),
        "no_overlap_ms": round(no_overlap * 1e3, 2),
        "perfect_overlap_ms": round(perfect * 1e3, 2),
        "overlap_efficiency": (round(overlap_eff, 3)
                               if overlap_eff is not None else None),
        "mfu_resident": round(tm_resident["mfu"], 4),
        "mfu_offload": round(tm_offload["mfu"], 4),
        "mfu_ceiling_stream": round(
            tm_resident["mfu"] * dt_resident / perfect, 4),
        "peak_tflops_per_chip": round(peak_flops_per_chip() / 1e12, 1),
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
