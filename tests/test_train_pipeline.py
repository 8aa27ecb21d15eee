"""Pipeline-parallel train step vs the flat single-device stack.

The pp step's loss is the mean over the full per-dp-cell batch, so its
gradients must equal the unpipelined model's — any scheduling, masking,
ppermute-transpose, or partial-loss bug shows up as a loss/param
divergence from the flat reference within f32 tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlp_tpu.train.pipeline import (build_pp_state, flat_forward,
                                     flatten_pipeline, make_pp_mesh,
                                     make_pp_train_step)
from dmlp_tpu.train.step import make_optimizer

import optax


def _flat_step(flat, x, y, lr):
    """Plain full-batch SGD step on the flattened stack (the reference)."""
    in_w, in_b, ws, bs, out_w, out_b = [jnp.asarray(a) for a in flat]
    params = {"in_w": in_w, "in_b": in_b, "ws": ws, "bs": bs,
              "out_w": out_w, "out_b": out_b}

    def loss_fn(p):
        h = x.astype(jnp.float32) @ p["in_w"] + p["in_b"]

        def layer(h, wb):
            wi, bi = wb
            return jax.nn.relu(h @ wi + bi), None
        h, _ = jax.lax.scan(layer, h, (p["ws"], p["bs"]))
        logits = h @ p["out_w"] + p["out_b"]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    loss, grads = jax.value_and_grad(loss_fn)(params)
    new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    return float(loss), new


@pytest.mark.parametrize("dp,pp,n_micro", [(1, 4, 4), (2, 2, 2), (2, 4, 8)])
def test_pp_step_matches_flat_reference(dp, pp, n_micro):
    if len(jax.devices()) < dp * pp:
        pytest.skip(f"needs {dp * pp} devices")
    mesh = make_pp_mesh(dp, pp)
    d_in, hidden, n_classes, lps = 6, 16, 4, 2
    lr = 0.05
    optimizer = make_optimizer("sgd", lr, momentum=0.0)
    state = build_pp_state(mesh, optimizer, d_in, hidden, n_classes, lps,
                           seed=3)
    flat = flatten_pipeline(state["params"])

    rng = np.random.default_rng(0)
    batch = dp * n_micro * 8
    x = rng.normal(size=(batch, d_in)).astype(np.float32)
    y = rng.integers(0, n_classes, batch).astype(np.int32)

    step = make_pp_train_step(mesh, optimizer, n_micro=n_micro,
                              n_classes=n_classes)
    state, m = step(state, jnp.asarray(x), jnp.asarray(y))
    pp_loss = float(m["loss"])

    # Flat reference: the dp mean-of-means equals the full-batch mean
    # only when every dp shard has the same size — true here.
    flat_loss, flat_new = _flat_step(flat, jnp.asarray(x), jnp.asarray(y),
                                     lr)
    assert pp_loss == pytest.approx(flat_loss, rel=1e-5)

    got = flatten_pipeline(state["params"])
    want = (flat_new["in_w"], flat_new["in_b"], flat_new["ws"],
            flat_new["bs"], flat_new["out_w"], flat_new["out_b"])
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-6)


def test_pp_loss_decreases_over_steps():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = make_pp_mesh(1, 4)
    optimizer = make_optimizer("sgd", 0.05, momentum=0.5)
    state = build_pp_state(mesh, optimizer, 8, 32, 3, 2, seed=1)
    step = make_pp_train_step(mesh, optimizer, n_micro=4, n_classes=3)

    rng = np.random.default_rng(5)
    # Learnable teacher task: labels from a fixed random projection.
    proj = rng.normal(size=(8, 3))
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = np.argmax(x @ proj, -1).astype(np.int32)
    losses = []
    for _ in range(30):
        state, m = step(state, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.5 * losses[0]


def test_pp_forward_equals_flat_forward():
    """Inference check without training: the pipeline's collected outputs
    must be the flat stack's activations (microbatching is a pure
    reshape)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = make_pp_mesh(1, 2)
    optimizer = make_optimizer("sgd", 0.0, momentum=0.0)
    state = build_pp_state(mesh, optimizer, 5, 8, 3, 3, seed=7)
    step = make_pp_train_step(mesh, optimizer, n_micro=2, n_classes=3)

    rng = np.random.default_rng(2)
    x = rng.normal(size=(16, 5)).astype(np.float32)
    y = rng.integers(0, 3, 16).astype(np.int32)
    flat = flatten_pipeline(state["params"])  # before the donated step
    _, m = step(state, jnp.asarray(x), jnp.asarray(y))
    logits = flat_forward(flat, jnp.asarray(x))
    want = float(optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.asarray(y)).mean())
    assert float(m["loss"]) == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize("dp,tp,pp", [(1, 2, 4), (2, 2, 2)])
def test_pp3_step_matches_flat_reference(dp, tp, pp):
    """The full 3D composition — dp batch split, tp col/row-split stage
    matmuls (one psum per pair), pp microbatched schedule — must produce
    the unpipelined, unsharded model's loss and updated params."""
    from dmlp_tpu.train.pipeline import (build_pp3_state, make_pp3_mesh,
                                         make_pp3_train_step,
                                         pp3_reference_forward)

    if len(jax.devices()) < dp * tp * pp:
        pytest.skip(f"needs {dp * tp * pp} devices")
    mesh = make_pp3_mesh(dp, tp, pp)
    lr = 0.05
    optimizer = make_optimizer("sgd", lr, momentum=0.0)
    state = build_pp3_state(mesh, optimizer, 6, 16, 4, 2, seed=13)
    ref = {k: jnp.asarray(np.asarray(v)) for k, v in state["params"].items()}

    rng = np.random.default_rng(4)
    n_micro = 4
    batch = dp * n_micro * 8
    x = rng.normal(size=(batch, 6)).astype(np.float32)
    y = rng.integers(0, 4, batch).astype(np.int32)

    step = make_pp3_train_step(mesh, optimizer, n_micro=n_micro,
                               n_classes=4)
    state, m = step(state, jnp.asarray(x), jnp.asarray(y))

    def ref_loss_fn(p):
        logits = pp3_reference_forward(p, jnp.asarray(x))
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray(y)).mean()

    ref_loss, grads = jax.value_and_grad(ref_loss_fn)(ref)
    assert float(m["loss"]) == pytest.approx(float(ref_loss), rel=1e-5)
    for k in ref:
        want = np.asarray(ref[k]) - lr * np.asarray(grads[k])
        np.testing.assert_allclose(np.asarray(state["params"][k]), want,
                                   rtol=2e-4, atol=2e-6, err_msg=k)


@pytest.mark.parametrize("dp,pp,vv,n_micro", [(2, 4, 2, 4), (1, 4, 3, 2),
                                              (1, 2, 2, 2)])
def test_interleaved_step_matches_flat_reference(dp, pp, vv, n_micro):
    """round-4 review item 6: the interleaved (1F1B-interleaved / virtual
    stages) schedule must produce the unpipelined flat stack's loss and
    updated params exactly — same criterion as the GPipe equivalence."""
    from dmlp_tpu.train.pipeline import (build_ppi_state, make_pp_mesh,
                                         make_ppi_train_step)

    if len(jax.devices()) < dp * pp:
        pytest.skip(f"needs {dp * pp} devices")
    mesh = make_pp_mesh(dp, pp)
    lr = 0.05
    optimizer = make_optimizer("sgd", lr, momentum=0.0)
    state = build_ppi_state(mesh, optimizer, 6, 16, 4, n_virtual=vv,
                            layers_per_chunk=2, seed=13)
    ref = {k: jnp.asarray(np.asarray(v)) for k, v in state["params"].items()}

    rng = np.random.default_rng(4)
    batch = dp * n_micro * 8
    x = rng.normal(size=(batch, 6)).astype(np.float32)
    y = rng.integers(0, 4, batch).astype(np.int32)

    step = make_ppi_train_step(mesh, optimizer, n_micro=n_micro,
                               n_virtual=vv, n_classes=4)
    state, m = step(state, jnp.asarray(x), jnp.asarray(y))

    def ref_loss_fn(p):
        v, s, pc, h, _ = p["pp_w"].shape
        ws = p["pp_w"].reshape(v * s * pc, h, h)
        bs = p["pp_b"].reshape(v * s * pc, h)
        hh = jnp.asarray(x) @ p["in_w"] + p["in_b"]
        for i in range(v * s * pc):
            hh = jax.nn.relu(hh @ ws[i] + bs[i])
        logits = hh @ p["out_w"] + p["out_b"]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray(y)).mean()

    ref_loss, grads = jax.value_and_grad(ref_loss_fn)(ref)
    assert float(m["loss"]) == pytest.approx(float(ref_loss), rel=1e-5)
    for k in ref:
        want = np.asarray(ref[k]) - lr * np.asarray(grads[k])
        np.testing.assert_allclose(np.asarray(state["params"][k]), want,
                                   rtol=2e-4, atol=2e-6, err_msg=k)

    # flatten_interleaved's (level, stage) chunk order must agree with the
    # inline reference's layer order.
    from dmlp_tpu.train.pipeline import flat_forward, flatten_interleaved
    flat_logits = flat_forward(flatten_interleaved(ref), jnp.asarray(x))
    flat_loss = optax.softmax_cross_entropy_with_integer_labels(
        flat_logits, jnp.asarray(y)).mean()
    assert float(flat_loss) == pytest.approx(float(ref_loss), rel=1e-6)


def test_interleaved_schedule_arithmetic_and_gates():
    from dmlp_tpu.train.pipeline import (bubble_fraction, make_pp_mesh,
                                         make_ppi_train_step,
                                         schedule_ticks)
    from dmlp_tpu.train.step import make_optimizer as mo

    assert schedule_ticks("gpipe", 4, 4) == 7
    assert schedule_ticks("interleaved", 4, 4, 2) == 11
    # interleaving divides the fill/drain term by V
    assert bubble_fraction("gpipe", 4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction("interleaved", 4, 4, 2) == pytest.approx(
        1 - 4 / (3 / 2 + 4))
    assert bubble_fraction("interleaved", 4, 4, 2) \
        < bubble_fraction("gpipe", 4, 4)
    with pytest.raises(ValueError, match="n_micro <= n_stages"):
        make_ppi_train_step(make_pp_mesh(1, 2), mo("sgd", 0.1),
                            n_micro=4, n_virtual=2, n_classes=3)


def test_interleaved_via_train_loop():
    from dmlp_tpu.train.loop import train

    _, last = train(steps=6, batch=32, dims=(8, 16, 3), mesh_shape=(2, 4),
                    lr=0.05, log_every=6, parallelism="dp_pp", n_micro=2,
                    pp_schedule="interleaved", n_virtual=2)
    assert np.isfinite(last["loss"])
    with pytest.raises(ValueError, match="pp-schedule"):
        train(steps=1, batch=8, dims=(4, 8, 2), mesh_shape=(1, 1),
              parallelism="dp_tp", pp_schedule="interleaved")
