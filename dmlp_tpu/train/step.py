"""The jitted train step: fwd/bwd on the MXU, declarative gradient sync.

The north-star analog of the reference's collective layer: where MPI code
would call MPI_Allreduce on gradients, here the dp-replicated param
placement makes XLA emit the all-reduce itself when the jitted step runs
over the mesh (sharding.py). The step is a pure function over a TrainState
pytree, so it composes with orbax checkpointing (train.checkpoint) and
donation (the state buffer is reused in place).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from dmlp_tpu.train.model import mlp_apply

TrainState = Dict[str, Any]  # {"params": pytree, "opt": optax state, "step": i32}


def make_optimizer(name: str = "sgd", lr: float = 1e-2,
                   momentum: float = 0.9) -> optax.GradientTransformation:
    if name == "sgd":
        return optax.sgd(lr, momentum=momentum)
    if name == "adam":
        return optax.adam(lr)
    raise ValueError(f"unknown optimizer {name!r}")


def init_state(params, optimizer: optax.GradientTransformation) -> TrainState:
    """Build the train state; called on already-placed (sharded) params so
    the optimizer moments inherit the param shardings."""
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def make_train_step(optimizer: optax.GradientTransformation,
                    compute_dtype=None, offload_state: TrainState = None,
                    ) -> Callable[[TrainState, jax.Array, jax.Array],
                                  Tuple[TrainState, Dict[str, jax.Array]]]:
    """Jitted (state, x, y) -> (state', {loss, accuracy}).

    Donates the state: params/opt buffers are updated in place on device.
    Sharding is carried by the operands (place params with
    sharding.param_shardings and batches with batch_shardings); XLA
    propagates it through grads and inserts the dp all-reduce.

    Host offload (the bench_4 analog): pass the placed state (host-DRAM
    leaves per build_sharded_state's offload level) as ``offload_state``.
    The step streams host-resident leaves to HBM (in-jit ``device_put``
    to the ``with_memory_kind("device")`` shardings) right before use,
    and their updated values are written back to host DRAM via the jit's
    ``out_shardings``; XLA's latency-hiding scheduler overlaps the
    per-layer transfers with the matmuls, so HBM holds working copies
    only for the step's duration. Mixed states work by construction:
    an already-HBM leaf's "device" sharding equals its own, so its
    device_put and out_sharding are no-ops — the "params" offload level
    (moments HBM-resident, half the stream bytes) needs no special case
    here.

    Runtime note: XLA:CPU's SPMD partitioner rejects host-memory stores on
    multi-device shardings ("Side-effect ops cannot be replicated"), so on
    the CPU test platform offload works on (1, 1) meshes only; TPU
    runtimes own the host-offload feature.
    """
    offload = offload_state is not None
    out_shardings = None
    if offload:
        work = {"params": offload_state["params"],
                "opt": offload_state["opt"]}
        host_sh = jax.tree.map(lambda a: a.sharding, work)
        dev_sh = jax.tree.map(
            lambda a: a.sharding.with_memory_kind("device"), work)
        out_shardings = ({"params": host_sh["params"], "opt": host_sh["opt"],
                          "step": None}, None)

    def step(state: TrainState, x: jax.Array, y: jax.Array):
        params_w, opt_w = state["params"], state["opt"]
        if offload:
            params_w = jax.tree.map(jax.device_put, params_w,
                                    dev_sh["params"])
            opt_w = jax.tree.map(jax.device_put, opt_w, dev_sh["opt"])

        def loss_fn(params):
            logits = mlp_apply(params, x, compute_dtype)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
            return loss, acc

        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params_w)
        updates, opt = optimizer.update(grads, opt_w, params_w)
        params = optax.apply_updates(params_w, updates)
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        return new_state, {"loss": loss, "accuracy": acc}

    return jax.jit(step, donate_argnums=(0,), out_shardings=out_shardings)


@functools.lru_cache(maxsize=1)
def supports_injit_offload() -> bool:
    """Whether this runtime compiles host-memory placements inside jit.

    TPU runtimes do; XLA:CPU lacks the annotate_device_placement custom
    call ("No registered implementation ... for Host"), so the eager
    fallback (make_eager_offload_step) is used there. Probe-compiled once.
    """
    try:
        dev = jax.devices()[0]
        from dmlp_tpu.utils.compat import host_memory_kind
        hsh = jax.sharding.SingleDeviceSharding(
            dev, memory_kind=host_memory_kind())
        dsh = jax.sharding.SingleDeviceSharding(dev, memory_kind="device")
        w = jax.device_put(jnp.ones((8,)), hsh)
        f = jax.jit(lambda a: jax.device_put(a, dsh) * 2.0,
                    out_shardings=hsh)
        return bool(jax.device_get(f(w))[0] == 2.0)
    except Exception:
        return False


def make_eager_offload_step(optimizer: optax.GradientTransformation,
                            compute_dtype=None, host_state: TrainState = None,
                            ) -> Callable:
    """Offload fallback for runtimes without in-jit host-memory support.

    State lives in host DRAM between steps; each call eagerly streams
    params/moments to HBM, runs the regular jitted step (donated, so HBM
    copies die with the step), and evicts the updated values back. Slower
    than the in-jit form (no transfer/compute overlap) but runs everywhere,
    so CPU CI can exercise the offload semantics end-to-end.
    """
    inner = make_train_step(optimizer, compute_dtype)
    work = {"params": host_state["params"], "opt": host_state["opt"]}
    host_sh = jax.tree.map(lambda a: a.sharding, work)
    dev_sh = jax.tree.map(
        lambda a: a.sharding.with_memory_kind("device"), work)

    def step(state: TrainState, x, y):
        ws = {"params": jax.tree.map(jax.device_put, state["params"],
                                     dev_sh["params"]),
              "opt": jax.tree.map(jax.device_put, state["opt"],
                                  dev_sh["opt"]),
              "step": state["step"]}
        new, m = inner(ws, x, y)
        out = {"params": jax.tree.map(jax.device_put, new["params"],
                                      host_sh["params"]),
               "opt": jax.tree.map(jax.device_put, new["opt"],
                                   host_sh["opt"]),
               "step": new["step"]}
        return out, m

    return step


def make_offload_train_step(optimizer: optax.GradientTransformation,
                            compute_dtype=None, state: TrainState = None,
                            ) -> Callable:
    """The host-offload step for this runtime: in-jit streaming where the
    compiler supports it, the eager round-trip elsewhere."""
    if supports_injit_offload():
        return make_train_step(optimizer, compute_dtype, offload_state=state)
    return make_eager_offload_step(optimizer, compute_dtype, host_state=state)
