"""Training extension: learning, dp/tp parity, checkpoint/resume, metrics."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dmlp_tpu.train.data import knn_input_batches, teacher_batches
from dmlp_tpu.train.dryrun import dryrun_train
from dmlp_tpu.train.loop import build_sharded_state, train
from dmlp_tpu.train.metrics import throughput_metrics, train_step_flops
from dmlp_tpu.train.model import init_mlp, num_matmul_params
from dmlp_tpu.train.sharding import batch_shardings, make_train_mesh
from dmlp_tpu.train.step import init_state, make_optimizer, make_train_step


def test_loss_decreases_on_teacher_task():
    state, last = train(steps=60, batch=256, dims=(8, 32, 4),
                        mesh_shape=(1, 1), lr=0.1, log_every=60)
    assert last["loss"] < 1.0  # ~ln(4)=1.39 at init; must have learned
    assert last["accuracy"] > 0.5


def test_dp_tp_sharded_matches_single_device():
    dryrun_train(jax.devices())  # 8 virtual CPU devices (conftest)


@pytest.mark.parametrize("opt", ["sgd", "adam"])
def test_optimizers_step(opt):
    optimizer = make_optimizer(opt, 1e-2)
    params = init_mlp(jax.random.PRNGKey(0), (4, 8, 3))
    state = init_state(params, optimizer)
    step = make_train_step(optimizer)
    x = np.zeros((16, 4), np.float32)
    y = np.zeros(16, np.int32)
    state, m = step(state, x, y)
    assert int(state["step"]) == 1
    assert np.isfinite(float(m["loss"]))


def test_bfloat16_compute_path():
    optimizer = make_optimizer("sgd", 1e-2)
    params = init_mlp(jax.random.PRNGKey(0), (4, 16, 3))
    state = init_state(params, optimizer)
    step = make_train_step(optimizer, compute_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.integers(0, 3, 32).astype(np.int32)
    state, m = step(state, x, y)
    assert np.isfinite(float(m["loss"]))
    # params stay f32 storage
    assert state["params"]["layer0"]["w"].dtype == jnp.float32


def test_checkpoint_resume_roundtrip(tmp_path):
    ckdir = str(tmp_path / "ck")
    state1, _ = train(steps=5, batch=64, dims=(6, 16, 3), mesh_shape=(1, 1),
                      checkpoint_dir=ckdir, ckpt_every=5, log_every=5)
    # Resume and take 0 extra steps: restored state must equal saved state.
    state2, _ = train(steps=0, batch=64, dims=(6, 16, 3), mesh_shape=(1, 1),
                      checkpoint_dir=ckdir, resume=True, log_every=5)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state1["params"], state2["params"])
    assert int(state2["step"]) == 5


def test_resume_continues_counting(tmp_path):
    ckdir = str(tmp_path / "ck")
    train(steps=4, batch=32, dims=(4, 8, 2), mesh_shape=(1, 1),
          checkpoint_dir=ckdir, ckpt_every=4, log_every=4)
    state, _ = train(steps=3, batch=32, dims=(4, 8, 2), mesh_shape=(1, 1),
                     checkpoint_dir=ckdir, resume=True, log_every=3)
    assert int(state["step"]) == 7


def test_flops_and_throughput_math():
    params = init_mlp(jax.random.PRNGKey(0), (10, 20, 5))
    assert num_matmul_params(params) == 10 * 20 + 20 * 5
    assert train_step_flops(params, 2) == 6.0 * 2 * 300
    m = throughput_metrics(params, batch_size=100, step_time_s=0.5,
                           n_chips=4, peak_per_chip=1e12)
    assert m["samples_per_sec"] == 200.0
    assert m["samples_per_sec_per_chip"] == 50.0
    assert m["mfu"] == pytest.approx(6.0 * 100 * 300 / (0.5 * 4 * 1e12))


def test_knn_input_batches_cycles():
    from dmlp_tpu.io.datagen import generate_input_text
    from dmlp_tpu.io.grammar import parse_input_text
    inp = parse_input_text(generate_input_text(50, 2, 4, 0, 1, 1, 3, 4))
    it = knn_input_batches(inp, batch_size=16)
    for _ in range(5):
        x, y = next(it)
        assert x.shape == (16, 4) and y.shape == (16,)
        assert x.dtype == np.float32 and y.dtype == np.int32


def test_teacher_task_is_deterministic():
    a = next(teacher_batches(4, 3, 8, seed=7))
    b = next(teacher_batches(4, 3, 8, seed=7))
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_offload_matches_device_resident():
    """Host-DRAM param offload (bench_4 analog): same math as the
    device-resident step; on XLA:CPU the eager fallback runs (in-jit
    streaming is probe-gated to runtimes that compile host placements)."""
    from dmlp_tpu.train.step import make_offload_train_step

    dims = (6, 16, 4)
    mesh = make_train_mesh((2, 2), jax.devices()[:4])
    optimizer = make_optimizer("sgd", 1e-1)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 6)).astype(np.float32)
    y = rng.integers(0, 4, 32).astype(np.int32)

    state_a = build_sharded_state(mesh, dims, optimizer)
    step_a = make_train_step(optimizer)
    state_b = build_sharded_state(mesh, dims, optimizer, offload=True)
    assert state_b["params"]["layer0"]["w"].sharding.memory_kind == "pinned_host"
    step_b = make_offload_train_step(optimizer, state=state_b)
    for _ in range(3):
        state_a, ma = step_a(state_a, x, y)
        state_b, mb = step_b(state_b, x, y)
    assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), rel=1e-6)
    # updated params stayed in host memory across steps
    assert state_b["params"]["layer1"]["w"].sharding.memory_kind == "pinned_host"
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6),
        state_a["params"], state_b["params"])


def test_offload_via_train_loop():
    state, last = train(steps=10, batch=64, dims=(8, 16, 3),
                        mesh_shape=(2, 1), lr=0.05, log_every=10,
                        offload=True)
    assert np.isfinite(last["loss"])
    assert state["params"]["layer0"]["w"].sharding.memory_kind == "pinned_host"


def test_prefetch_to_device_preserves_stream():
    from dmlp_tpu.train.data import prefetch_to_device
    mesh = make_train_mesh((2, 1), jax.devices()[:2])
    shardings = batch_shardings(mesh)
    raw = list(next(teacher_batches(4, 3, 8, seed=3)) for _ in range(5))
    fed = prefetch_to_device(iter(raw), shardings, depth=2)
    got = list(fed)
    assert len(got) == 5
    for (x0, y0), (xd, yd) in zip(raw, got):
        np.testing.assert_array_equal(x0, np.asarray(xd))
        np.testing.assert_array_equal(y0, np.asarray(yd))


def test_weak_scaling_sweep_runs():
    from dmlp_tpu.train.sweep import run_sweep
    pts = run_sweep([1, 2, 4], dims=(8, 16, 4), batch_per_chip=32,
                    steps=3, dtype=None)
    assert [p["n_chips"] for p in pts] == [1, 2, 4]
    for p in pts:
        assert p["samples_per_sec_per_chip"] > 0
        assert p["global_batch"] == 32 * p["n_chips"]


def test_unknown_device_kind_has_no_peak_and_no_mfu():
    """A device kind the peaks table does not know raises — no fallback
    peak — and the step's rates are reported without a utilisation."""
    from dmlp_tpu.obs.counters import roofline
    from dmlp_tpu.train import metrics
    assert not hasattr(metrics, "FALLBACK_PEAK_FLOPS")
    with pytest.raises(metrics.UnknownDeviceKind, match="'cpu'"):
        metrics.peak_flops_per_chip()       # the suite runs on cpu
    params = init_mlp(jax.random.PRNGKey(0), (10, 20, 5))
    m = throughput_metrics(params, batch_size=100, step_time_s=0.5,
                           n_chips=1)
    assert m["samples_per_sec"] == 200.0 and "mfu" not in m
    rl = roofline(1e9, 1e6, 0.5)
    assert rl["achieved_flops_per_s"] == 2e9
    assert "utilization_vs_peak" not in rl
    assert "peak_flops_per_chip" not in rl


def test_train_bench_smoke(monkeypatch):
    # train_bench is a measurement path: it needs the device's peak, and
    # the cpu the suite runs on has none in the table.
    from dmlp_tpu.train import metrics
    monkeypatch.setitem(metrics.PEAK_FLOPS_BY_KIND, "cpu", 1e12)
    monkeypatch.setenv("TRAIN_DIMS", "8,16,4")
    monkeypatch.setenv("TRAIN_BATCH", "32")
    monkeypatch.setenv("TRAIN_STEPS", "3")
    monkeypatch.setenv("TRAIN_DTYPE", "float32")
    from dmlp_tpu.train.bench import train_bench
    out = train_bench()
    assert out["metric"] == "train_samples_per_sec_per_chip"
    assert out["value"] > 0 and np.isfinite(out["mfu"])


def test_offload_params_level_moments_stay_resident():
    """The "params" offload level: params live in host DRAM, optimizer
    moments stay HBM-resident (half the stream bytes of "all"), and the
    math still matches the fully resident step."""
    from dmlp_tpu.train.step import make_offload_train_step

    dims = (6, 16, 4)
    mesh = make_train_mesh((2, 1), jax.devices()[:2])
    optimizer = make_optimizer("sgd", 1e-1)
    rng = np.random.default_rng(5)
    x = rng.normal(size=(16, 6)).astype(np.float32)
    y = rng.integers(0, 4, 16).astype(np.int32)

    state_a = build_sharded_state(mesh, dims, optimizer)
    step_a = make_train_step(optimizer)
    state_b = build_sharded_state(mesh, dims, optimizer, offload="params")
    assert state_b["params"]["layer0"]["w"].sharding.memory_kind == "pinned_host"
    assert jax.tree.leaves(state_b["opt"])[0].sharding.memory_kind == "device"
    step_b = make_offload_train_step(optimizer, state=state_b)
    for _ in range(3):
        state_a, ma = step_a(state_a, x, y)
        state_b, mb = step_b(state_b, x, y)
    assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), rel=1e-6)
    # placement is preserved across steps on both sides of the split
    assert state_b["params"]["layer1"]["w"].sharding.memory_kind == "pinned_host"
    assert jax.tree.leaves(state_b["opt"])[0].sharding.memory_kind == "device"
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6),
        state_a["params"], state_b["params"])


def test_resolve_offload_level():
    from dmlp_tpu.train.loop import resolve_offload_level

    assert resolve_offload_level(False) == "none"
    assert resolve_offload_level(True) == "all"
    assert resolve_offload_level(None) == "none"
    assert resolve_offload_level("params") == "params"
    with pytest.raises(ValueError):
        resolve_offload_level("moments")


def test_resolve_offload_level_env_style():
    from dmlp_tpu.train.loop import resolve_offload_level

    assert resolve_offload_level("1") == "all"
    assert resolve_offload_level("0") == "none"


def test_train_loop_parallelism_families(tmp_path):
    """The production loop CLI path drives every mesh-parallelism family:
    dp_pp, dp_pp3, and dp_ep train with finite decreasing-ish loss and
    checkpoint/resume round-trips on the pipelined state."""
    state, last = train(steps=8, batch=32, dims=(8, 16, 3),
                        mesh_shape=(1, 4), lr=0.05, log_every=8,
                        parallelism="dp_pp", n_micro=2,
                        checkpoint_dir=str(tmp_path / "ck"), ckpt_every=8)
    assert np.isfinite(last["loss"])
    assert state["params"]["pp_w"].sharding.spec[0] == "pp"

    # resume continues the step counter on the pipelined state
    state2, last2 = train(steps=4, batch=32, dims=(8, 16, 3),
                          mesh_shape=(1, 4), lr=0.05, log_every=4,
                          parallelism="dp_pp", n_micro=2,
                          checkpoint_dir=str(tmp_path / "ck"), resume=True)
    assert last2["step"] == 12

    _, last3 = train(steps=6, batch=32, dims=(8, 16, 3),
                     mesh_shape=(1, 2, 2), lr=0.05, log_every=6,
                     parallelism="dp_pp3", n_micro=2)
    assert np.isfinite(last3["loss"])

    _, last4 = train(steps=6, batch=32, dims=(8, 16, 24, 3),
                     mesh_shape=(1, 4), lr=0.05, log_every=6,
                     parallelism="dp_ep", n_experts=4)
    assert np.isfinite(last4["loss"])


def test_train_loop_moe_a2a_dispatch():
    """round-4 review item 1: the capacity + all-to-all MoE dispatch is
    reachable from the production loop (moe_dispatch="a2a"), trains with
    finite loss, and at cf >= EP (zero drops) its first-step loss equals
    the dense dispatch's on the identical state/batch."""
    common = dict(steps=1, batch=32, dims=(8, 16, 24, 3),
                  mesh_shape=(2, 4), lr=0.05, log_every=1, seed=7,
                  parallelism="dp_ep", n_experts=4)
    _, dense = train(moe_dispatch="dense", **common)
    _, a2a = train(moe_dispatch="a2a", capacity_factor=4.0, **common)
    assert np.isfinite(a2a["loss"])
    assert a2a["loss"] == pytest.approx(dense["loss"], rel=2e-5)

    # Tight capacity (cf=1) still trains — drops go to the residual path.
    _, tight = train(steps=4, batch=32, dims=(8, 16, 24, 3),
                     mesh_shape=(1, 4), lr=0.05, log_every=4, seed=7,
                     parallelism="dp_ep", n_experts=4,
                     moe_dispatch="a2a", capacity_factor=1.0)
    assert np.isfinite(tight["loss"])


def test_train_loop_rejects_inapplicable_flags():
    with pytest.raises(ValueError, match="compute-dtype"):
        train(steps=1, batch=8, dims=(4, 8, 2), mesh_shape=(1, 2),
              parallelism="dp_pp", compute_dtype="bfloat16")
    with pytest.raises(ValueError, match="offload"):
        train(steps=1, batch=8, dims=(4, 8, 2), mesh_shape=(1, 2),
              parallelism="dp_pp", offload="all")
    from dmlp_tpu.train.pipeline import make_axes_mesh
    with pytest.raises(ValueError, match=">= 1"):
        make_axes_mesh({"dp": 1, "pp": 0})
    with pytest.raises(ValueError, match="moe-dispatch"):
        train(steps=1, batch=8, dims=(4, 8, 2), mesh_shape=(1, 2),
              parallelism="dp_pp", moe_dispatch="a2a")
    from dmlp_tpu.train.experts import a2a_capacity
    with pytest.raises(ValueError, match="divisible"):
        a2a_capacity(30, 2, 4)


def test_moe_dispatch_flags_raise_on_dp_tp():
    """--moe-dispatch/--capacity-factor must raise on EVERY non-dp_ep
    family including the default dp_tp (whose branch returns early)."""
    with pytest.raises(ValueError, match="moe-dispatch"):
        train(steps=1, batch=8, dims=(4, 8, 2), mesh_shape=(1, 1),
              parallelism="dp_tp", moe_dispatch="a2a")
    with pytest.raises(ValueError, match="capacity-factor"):
        train(steps=1, batch=8, dims=(4, 8, 2), mesh_shape=(1, 1),
              parallelism="dp_tp", capacity_factor=2.0)
    with pytest.raises(ValueError, match="capacity-factor"):
        train(steps=1, batch=32, dims=(8, 16, 24, 3), mesh_shape=(1, 4),
              parallelism="dp_ep", n_experts=4, moe_dispatch="dense",
              capacity_factor=0.25)


# -- NaN/divergence guard -> checkpoint rollback (resilience) ----------------

def _nan_sched(step, times=1):
    from dmlp_tpu.resilience.inject import FaultSchedule
    return FaultSchedule.from_dict(
        {"schema": 1, "seed": 0, "faults": [
            {"site": "train.step", "kind": "nan", "times": times,
             "when": {"step": step}}]})


@pytest.fixture()
def _resilience_clean():
    from dmlp_tpu.resilience import inject, stats
    stats.reset()
    inject.uninstall()
    yield
    inject.uninstall()
    stats.reset()


def test_nan_guard_rollback_is_step_identical(tmp_path, _resilience_clean):
    """An injected non-finite loss at step 5 rolls back to the latest
    checkpoint and replays; the run must end with EXACTLY the params an
    unfaulted run produces (the chaos harness's train invariant)."""
    from dmlp_tpu.resilience import inject, stats
    kw = dict(steps=6, batch=64, dims=(6, 16, 3), mesh_shape=(1, 1),
              ckpt_every=2, log_every=3, nan_guard=True)
    plain, plain_last = train(checkpoint_dir=str(tmp_path / "ck_a"), **kw)

    inject.install(_nan_sched(step=4))
    faulted, faulted_last = train(checkpoint_dir=str(tmp_path / "ck_b"),
                                  **kw)
    assert stats.snapshot()["rollbacks"] == 1
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), plain["params"], faulted["params"])
    assert plain_last["loss"] == faulted_last["loss"]
    assert plain_last["step"] == faulted_last["step"] == 6


def test_nan_guard_without_checkpoint_dir_raises(_resilience_clean):
    from dmlp_tpu.resilience import inject
    inject.install(_nan_sched(step=1))
    with pytest.raises(RuntimeError, match="no.*checkpoint|checkpoint.*"):
        train(steps=3, batch=32, dims=(4, 8, 2), mesh_shape=(1, 1),
              log_every=3, nan_guard=True)


def test_nan_guard_persistent_divergence_decays_lr(tmp_path,
                                                   _resilience_clean):
    """The same step diverging twice triggers LR backoff (x0.5) instead
    of an identical-replay livelock; three strikes with max_rollbacks=2
    gives up loudly."""
    from dmlp_tpu.resilience import inject, stats
    inject.install(_nan_sched(step=2, times=2))
    state, _ = train(steps=4, batch=32, dims=(4, 8, 2), mesh_shape=(1, 1),
                     checkpoint_dir=str(tmp_path / "ck"), ckpt_every=1,
                     log_every=4, nan_guard=True)
    assert stats.snapshot()["rollbacks"] == 2
    assert int(state["step"]) == 4            # recovered and finished

    inject.uninstall()
    stats.reset()
    inject.install(_nan_sched(step=2, times=5))
    with pytest.raises(RuntimeError, match="persisted through"):
        train(steps=4, batch=32, dims=(4, 8, 2), mesh_shape=(1, 1),
              checkpoint_dir=str(tmp_path / "ck2"), ckpt_every=1,
              log_every=4, nan_guard=True, max_rollbacks=2)


def test_nan_guard_recovers_before_first_periodic_checkpoint(
        tmp_path, _resilience_clean):
    """ckpt_every beyond the faulted step: the guard seeds the dir with
    the start state, so even step 1 divergence is recoverable."""
    from dmlp_tpu.resilience import inject, stats
    inject.install(_nan_sched(step=1))
    state, _ = train(steps=4, batch=32, dims=(4, 8, 2), mesh_shape=(1, 1),
                     checkpoint_dir=str(tmp_path / "ck"), ckpt_every=100,
                     log_every=4, nan_guard=True)
    assert stats.snapshot()["rollbacks"] == 1
    assert int(state["step"]) == 4


def test_nan_guard_refuses_stale_future_checkpoint(tmp_path,
                                                   _resilience_clean):
    """A checkpoint AHEAD of the faulted step (stale dir from an earlier
    run) must fail loudly — rolling back may never jump forward."""
    from dmlp_tpu.resilience import inject
    ckdir = str(tmp_path / "ck")
    train(steps=6, batch=32, dims=(4, 8, 2), mesh_shape=(1, 1),
          checkpoint_dir=ckdir, ckpt_every=6, log_every=6)  # leaves step 6
    inject.install(_nan_sched(step=2))
    with pytest.raises(RuntimeError, match="AHEAD"):
        train(steps=6, batch=32, dims=(4, 8, 2), mesh_shape=(1, 1),
              checkpoint_dir=ckdir, ckpt_every=100, log_every=6,
              nan_guard=True)
