"""Multi-chip training dry run — called by __graft_entry__.dryrun_multichip.

Builds a (dp, tp) mesh over the given devices, jits the FULL train step
(fwd/bwd + optimizer + declarative dp gradient all-reduce + tp-sharded
weights) and runs a few steps on tiny shapes, asserting losses are finite
and the dp/tp result matches a single-device run of the same step.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np

from dmlp_tpu.train.loop import build_sharded_state
from dmlp_tpu.train.sharding import batch_shardings, make_train_mesh
from dmlp_tpu.train.step import init_state, make_optimizer, make_train_step
from dmlp_tpu.train.model import init_mlp


def dryrun_train(devices: Sequence[jax.Device]) -> None:
    n = len(devices)
    tp = 2 if n % 2 == 0 and n >= 2 else 1
    dp = n // tp
    dims = (16, 32, 32, 8)
    batch = 8 * dp
    optimizer = make_optimizer("sgd", 0.05)

    mesh = make_train_mesh((dp, tp), devices=devices)
    state = build_sharded_state(mesh, dims, optimizer, seed=3)
    step_fn = make_train_step(optimizer)
    xsh, ysh = batch_shardings(mesh)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, dims[0])).astype(np.float32)
    y = rng.integers(0, dims[-1], batch).astype(np.int32)

    state, m = step_fn(state, jax.device_put(x, xsh), jax.device_put(y, ysh))
    state, m2 = step_fn(state, jax.device_put(x, xsh), jax.device_put(y, ysh))
    loss0, loss1 = float(m["loss"]), float(m2["loss"])
    assert np.isfinite(loss0) and np.isfinite(loss1), (loss0, loss1)
    assert loss1 < loss0, "second step on same batch must reduce loss"

    # Cross-check the sharded step against a single-device run.
    sstate = init_state(init_mlp(jax.random.PRNGKey(3), dims), optimizer)
    sstep = make_train_step(optimizer)
    sstate, sm = sstep(sstate, x, y)
    np.testing.assert_allclose(float(sm["loss"]), loss0, rtol=2e-5)

    # Pipeline parallelism: one (dp, pp) microbatched step, checked
    # against the mathematically equivalent flat stack.
    if n >= 4:
        import jax.numpy as jnp
        import optax

        from dmlp_tpu.train.pipeline import (build_pp_state, flat_forward,
                                             flatten_pipeline, make_pp_mesh,
                                             make_pp_train_step)
        pp = 4
        dp_pp = n // pp
        pmesh = make_pp_mesh(dp_pp, pp, devices=devices)
        pstate = build_pp_state(pmesh, optimizer, 6, 16, 4, 2, seed=5)
        flat = flatten_pipeline(pstate["params"])
        pstep = make_pp_train_step(pmesh, optimizer, n_micro=2, n_classes=4)
        xb = rng.normal(size=(8 * dp_pp, 6)).astype(np.float32)
        yb = rng.integers(0, 4, 8 * dp_pp).astype(np.int32)
        pstate, pm = pstep(pstate, jnp.asarray(xb), jnp.asarray(yb))
        want = float(optax.softmax_cross_entropy_with_integer_labels(
            flat_forward(flat, jnp.asarray(xb)), jnp.asarray(yb)).mean())
        np.testing.assert_allclose(float(pm["loss"]), want, rtol=2e-5)

        # Expert parallelism: one (dp, ep) MoE step, checked against the
        # unsharded reference forward.
        from dmlp_tpu.train.experts import (build_moe_state, make_ep_mesh,
                                            make_moe_train_step,
                                            moe_reference_forward)
        emesh = make_ep_mesh(dp_pp, 4, devices=devices)
        estate = build_moe_state(emesh, optimizer, 6, 16, 24, 4, 8, seed=9)
        ref = {k: jnp.asarray(np.asarray(v))
               for k, v in estate["params"].items()}
        estep = make_moe_train_step(emesh, optimizer, n_experts=8,
                                    n_classes=4)
        estate, em = estep(estate, jnp.asarray(xb), jnp.asarray(yb))
        ew = float(optax.softmax_cross_entropy_with_integer_labels(
            moe_reference_forward(ref, jnp.asarray(xb)),
            jnp.asarray(yb)).mean())
        np.testing.assert_allclose(float(em["loss"]), ew, rtol=2e-5)

        # Production capacity + all-to-all MoE dispatch (round-4 review item
        # 1/4): capacity = local tokens (a2a_capacity with cf = ep) means
        # zero drops, so the loss must match the SAME unsharded reference
        # the dense dispatch was checked against.
        from dmlp_tpu.train.experts import (a2a_batch_shardings,
                                            a2a_capacity,
                                            make_moe_a2a_train_step)
        bt = xb.shape[0]
        cap = a2a_capacity(bt, dp_pp, 4, capacity_factor=4.0)
        assert cap >= bt // (dp_pp * 4), (cap, bt)  # zero-drop regime
        astate = build_moe_state(emesh, optimizer, 6, 16, 24, 4, 8, seed=9)
        astep = make_moe_a2a_train_step(emesh, optimizer, n_experts=8,
                                        n_classes=4, capacity=cap)
        xsh_a, ysh_a = a2a_batch_shardings(emesh)
        astate, am = astep(astate, jax.device_put(jnp.asarray(xb), xsh_a),
                           jax.device_put(jnp.asarray(yb), ysh_a))
        np.testing.assert_allclose(float(am["loss"]), ew, rtol=2e-5)

        # 3D dp x tp x pp composition (round-4 review item 4): one microbatched
        # step over the (dp, 2, 2) mesh vs the unpipelined, unsharded
        # reference forward.
        from dmlp_tpu.train.pipeline import (build_pp3_state, make_pp3_mesh,
                                             make_pp3_train_step,
                                             pp3_reference_forward)
        p3mesh = make_pp3_mesh(dp_pp, 2, 2, devices=devices)
        p3state = build_pp3_state(p3mesh, optimizer, 6, 16, 4, 2, seed=13)
        p3ref = {k: jnp.asarray(np.asarray(v))
                 for k, v in p3state["params"].items()}
        p3step = make_pp3_train_step(p3mesh, optimizer, n_micro=2,
                                     n_classes=4)
        p3state, p3m = p3step(p3state, jnp.asarray(xb), jnp.asarray(yb))
        p3want = float(optax.softmax_cross_entropy_with_integer_labels(
            pp3_reference_forward(p3ref, jnp.asarray(xb)),
            jnp.asarray(yb)).mean())
        np.testing.assert_allclose(float(p3m["loss"]), p3want, rtol=2e-5)
