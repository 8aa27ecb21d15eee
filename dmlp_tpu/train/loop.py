"""Training-loop CLI: the north-star benchmark entry point.

Runs a sharded train step over a device mesh with JSON metrics
(samples/sec/chip, MFU — BASELINE.json's metric set) and orbax
checkpoint/resume. ``--parallelism`` picks the mesh family: the dp x tp
MLP (default; offload ladder + compute dtype), the dp x pp /
dp x tp x pp pipelined stack, or the dp x ep MoE. Usage::

    python -m dmlp_tpu.train.loop --steps 200 --batch 4096 \
        --dims 64,512,512,10 [--mesh DP,TP] [--optimizer sgd|adam]
        [--compute-dtype bfloat16] [--offload [none|params|all]]
        [--checkpoint-dir ckpt --ckpt-every 100] [--resume]
        [--metrics-file metrics.jsonl] [--compile-cache DIR]
    python -m dmlp_tpu.train.loop --parallelism dp_pp  --mesh 2,4 \
        --dims 64,256,10 --microbatches 8
    python -m dmlp_tpu.train.loop --parallelism dp_pp3 --mesh 1,2,4 \
        --dims 64,256,10
    python -m dmlp_tpu.train.loop --parallelism dp_ep  --mesh 2,4 \
        --dims 64,256,512,10 --experts 8 \
        [--moe-dispatch dense|a2a] [--capacity-factor 1.0]
"""

from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from dmlp_tpu.obs.trace import span as obs_span
from dmlp_tpu.resilience import inject as rs_inject
from dmlp_tpu.resilience import retry as rs_retry
from dmlp_tpu.resilience import stats as rs_stats
from dmlp_tpu.train import checkpoint as ckpt_lib
from dmlp_tpu.train.data import teacher_batches
from dmlp_tpu.train.metrics import throughput_metrics
from dmlp_tpu.train.model import init_mlp
from dmlp_tpu.train.sharding import batch_shardings, make_train_mesh, param_shardings
from dmlp_tpu.train.step import init_state, make_optimizer, make_train_step
from dmlp_tpu.utils.metrics_log import MetricsLogger


def resolve_offload_level(offload) -> str:
    """Normalize the offload policy: "none" | "params" | "all".

    Bools stay accepted ("all"/"none") for the original binary API. The
    ladder trades HBM capacity against stream traffic (the step streams
    exactly the host-resident leaves, step.make_train_step):

    - "none":   everything HBM-resident — fastest, most HBM.
    - "params": params in host DRAM, optimizer moments HBM-resident —
      halves the per-step stream bytes vs "all" (params down + updated
      params up; moments never cross), so the latency-hiding scheduler
      hides the streams under the matmuls at batch sizes where "all"
      still exposes transfer (TRAINBENCH_r04 ladder).
    - "all":    params + moments in host DRAM — maximum HBM savings, the
      bench_4 "host-DRAM param offload" analog, stream-bound at ~5 GB/s.
    """
    if isinstance(offload, bool) or offload is None:
        return "all" if offload else "none"
    if offload in ("0", "1"):  # env-var style (TRAIN_OFFLOAD=1)
        return "all" if offload == "1" else "none"
    if offload not in ("none", "params", "all"):
        raise ValueError(f"unknown offload level {offload!r}")
    return offload


def build_sharded_state(mesh, dims, optimizer, seed: int = 0,
                        offload=False):
    """Init params on host, place them with the tp/dp shardings, then build
    the optimizer state on the placed params so moments inherit placement.
    ``offload`` (resolve_offload_level) picks which leaves live in host
    DRAM."""
    level = resolve_offload_level(offload)
    params = init_mlp(jax.random.PRNGKey(seed), dims)
    placed = jax.tree.map(
        lambda p, s: jax.device_put(p, s), params,
        param_shardings(params, mesh))
    state = init_state(placed, optimizer)
    if level != "none":
        # Init in HBM first, then evict: eager zeros_like on a host-memory
        # array trips a make_array_from_callback memory-kind mismatch in
        # this JAX, so optimizer moments can't be *created* there directly.
        from dmlp_tpu.utils.compat import host_memory_kind
        hk = host_memory_kind()
        to_host = lambda a: jax.device_put(  # noqa: E731
            a, a.sharding.with_memory_kind(hk))
        state["params"] = jax.tree.map(to_host, state["params"])
        if level == "all":
            state["opt"] = jax.tree.map(to_host, state["opt"])
    return state


def _build_parallel(parallelism: str, mesh_shape, dims, optimizer,
                    compute_dtype, offload, seed: int, n_micro: int,
                    n_experts: int, batch: int = 0,
                    moe_dispatch: str = "dense",
                    capacity_factor: float = 1.0,
                    pp_schedule: str = "gpipe", n_virtual: int = 2):
    """(mesh, state, step_fn, data_dims, batch_shardings) for the chosen
    parallelism family. "dp_tp" is the full-featured default (offload
    levels, compute dtype); "dp_pp"/"dp_pp3"/"dp_ep" run the pipeline/MoE
    steps — their mesh comes from --mesh (DP,PP / DP,TP,PP / DP,EP), dims
    are (in, hidden, classes) for the pipelines (layers spread uniformly
    over stages, 2 per stage) and (in, hidden, ffn, classes) for the MoE.
    ``moe_dispatch`` picks the MoE form (dp_ep only): "dense" one-hot
    (capacity-free, masked compute) or "a2a" (capacity + all-to-all
    production dispatch; ``capacity_factor`` scales the per-(source,
    destination) slot count around the uniform-routing expectation,
    train.experts.a2a_capacity). ``pp_schedule`` picks the dp_pp
    schedule: "gpipe" or "interleaved" (V = ``n_virtual`` chunks per
    stage; bubble / V at n_micro <= stages, pipeline.bubble_fraction)."""
    # MoE-dispatch flags raise when inapplicable (same no-silent-ignore
    # rule as --compute-dtype/--offload below): a benchmark invoked with
    # --moe-dispatch a2a that silently trained the dp_tp MLP would
    # misattribute its numbers.
    if moe_dispatch != "dense" and parallelism != "dp_ep":
        raise ValueError(f"--moe-dispatch applies to dp_ep only, "
                         f"not {parallelism}")
    if capacity_factor != 1.0 and not (parallelism == "dp_ep"
                                       and moe_dispatch == "a2a"):
        raise ValueError("--capacity-factor applies to the dp_ep a2a "
                         "dispatch only (dense is capacity-free)")
    if pp_schedule != "gpipe" and parallelism != "dp_pp":
        raise ValueError(f"--pp-schedule applies to dp_pp only, "
                         f"not {parallelism}")
    if n_virtual != 2 and pp_schedule != "interleaved":
        raise ValueError("--virtual-stages applies to the interleaved "
                         "dp_pp schedule only")
    if parallelism == "dp_tp":
        mesh = make_train_mesh(mesh_shape)
        offload = resolve_offload_level(offload)
        state = build_sharded_state(mesh, dims, optimizer, seed,
                                    offload=offload)
        cdtype = jnp.bfloat16 if compute_dtype == "bfloat16" else None
        if offload != "none":
            from dmlp_tpu.train.step import make_offload_train_step
            step_fn = make_offload_train_step(optimizer, cdtype, state)
        else:
            step_fn = make_train_step(optimizer, cdtype)
        return (mesh, state, step_fn, (dims[0], dims[-1]),
                batch_shardings(mesh))

    # The pipeline/MoE families run f32 without host offload; silently
    # ignoring these flags would misattribute benchmark numbers.
    if compute_dtype is not None:
        raise ValueError(f"--compute-dtype applies to dp_tp only, "
                         f"not {parallelism}")
    if resolve_offload_level(offload) != "none":
        raise ValueError(f"--offload applies to dp_tp only, "
                         f"not {parallelism}")

    if parallelism in ("dp_pp", "dp_pp3"):
        from dmlp_tpu.train import pipeline as pl
        if len(dims) != 3:
            raise ValueError(f"{parallelism} wants --dims in,hidden,classes")
        d_in, hidden, n_classes = dims
        if parallelism == "dp_pp":
            dp, pp = mesh_shape or (1, len(jax.devices()))
            mesh = pl.make_pp_mesh(dp, pp)
            if pp_schedule == "interleaved":
                # Same model as the gpipe branch (2 layers per stage, the
                # documented dp_pp architecture): V chunks of 2/V layers.
                # A V that doesn't divide it would silently change the
                # depth and make schedule A/Bs compare different models.
                lps = 2
                if lps % n_virtual:
                    raise ValueError(
                        f"--virtual-stages must divide the dp_pp model's "
                        f"{lps} layers per stage (got {n_virtual}); deeper "
                        "chunking is a library-API choice "
                        "(pipeline.build_ppi_state)")
                state = pl.build_ppi_state(mesh, optimizer, d_in, hidden,
                                           n_classes, n_virtual=n_virtual,
                                           layers_per_chunk=lps // n_virtual,
                                           seed=seed)
                step_fn = pl.make_ppi_train_step(mesh, optimizer,
                                                 n_micro=n_micro,
                                                 n_virtual=n_virtual,
                                                 n_classes=n_classes)
                return mesh, state, step_fn, (d_in, n_classes), \
                    batch_shardings(mesh)
            state = pl.build_pp_state(mesh, optimizer, d_in, hidden,
                                      n_classes, 2, seed=seed)
            step_fn = pl.make_pp_train_step(mesh, optimizer, n_micro=n_micro,
                                            n_classes=n_classes)
        else:
            dp, tp, pp = mesh_shape or (1, 2, len(jax.devices()) // 2)
            mesh = pl.make_pp3_mesh(dp, tp, pp)
            state = pl.build_pp3_state(mesh, optimizer, d_in, hidden,
                                       n_classes, 2, seed=seed)
            step_fn = pl.make_pp3_train_step(mesh, optimizer,
                                             n_micro=n_micro,
                                             n_classes=n_classes)
        return mesh, state, step_fn, (d_in, n_classes), \
            batch_shardings(mesh)

    if parallelism == "dp_ep":
        from dmlp_tpu.train import experts as ex
        if len(dims) != 4:
            raise ValueError("dp_ep wants --dims in,hidden,ffn,classes")
        d_in, hidden, ffn, n_classes = dims
        dp, ep = mesh_shape or (1, len(jax.devices()))
        mesh = ex.make_ep_mesh(dp, ep)
        state = ex.build_moe_state(mesh, optimizer, d_in, hidden, ffn,
                                   n_classes, n_experts, seed=seed)
        if moe_dispatch == "a2a":
            capacity = ex.a2a_capacity(batch, dp, ep, capacity_factor)
            step_fn = ex.make_moe_a2a_train_step(mesh, optimizer,
                                                 n_experts=n_experts,
                                                 n_classes=n_classes,
                                                 capacity=capacity)
            return mesh, state, step_fn, (d_in, n_classes), \
                ex.a2a_batch_shardings(mesh)
        step_fn = ex.make_moe_train_step(mesh, optimizer,
                                         n_experts=n_experts,
                                         n_classes=n_classes)
        return mesh, state, step_fn, (d_in, n_classes), \
            batch_shardings(mesh)

    raise ValueError(f"unknown parallelism {parallelism!r}")


def train(steps: int = 100, batch: int = 1024,
          dims: Sequence[int] = (64, 256, 256, 10),
          mesh_shape=None, optimizer_name: str = "sgd", lr: float = 1e-2,
          compute_dtype: Optional[str] = None, seed: int = 0,
          checkpoint_dir: Optional[str] = None, ckpt_every: int = 100,
          resume: bool = False, metrics: Optional[MetricsLogger] = None,
          log_every: int = 10, offload=False, parallelism: str = "dp_tp",
          n_micro: int = 4, n_experts: int = 8,
          moe_dispatch: str = "dense", capacity_factor: float = 1.0,
          pp_schedule: str = "gpipe", n_virtual: int = 2,
          sanitize: bool = False, nan_guard: bool = False,
          lr_backoff: float = 0.5, max_rollbacks: int = 3):
    optimizer = make_optimizer(optimizer_name, lr)
    mesh, state, step_fn, (d_in, n_classes), shardings = _build_parallel(
        parallelism, mesh_shape, tuple(dims), optimizer, compute_dtype,
        offload, seed, n_micro, n_experts, batch=batch,
        moe_dispatch=moe_dispatch, capacity_factor=capacity_factor,
        pp_schedule=pp_schedule, n_virtual=n_virtual)
    n_chips = mesh.devices.size
    start_step = 0
    if resume and checkpoint_dir and ckpt_lib.latest_step(checkpoint_dir) is not None:
        state = ckpt_lib.restore_checkpoint(checkpoint_dir, state)
        start_step = int(jax.device_get(state["step"]))

    from dmlp_tpu.train.data import prefetch_to_device

    def make_data(skip: int):
        """The seed-keyed batch stream positioned ``skip`` batches past
        this run's start — a NaN-guard rollback re-creates it so the
        replayed steps consume EXACTLY the batches the first pass did
        (step-identical recovery; proven in tests/test_train.py)."""
        it = teacher_batches(d_in, n_classes, batch, seed=seed + 1)
        for _ in range(skip):
            next(it)
        return prefetch_to_device(it, shardings)

    data = make_data(0)

    # LR-backoff escalation rebuilds the step with a decayed LR when the
    # SAME step produces a non-finite loss twice (deterministic replay
    # would otherwise diverge identically forever). Optimizer-state
    # structure is LR-independent (optax), so the live moments carry
    # over. dp_tp only — the pipeline/MoE step factories don't take a
    # bare optimizer swap; rollback still works there, escalation raises.
    def _rebuild_step_dp_tp(new_lr: float):
        opt2 = make_optimizer(optimizer_name, new_lr)
        cdtype = jnp.bfloat16 if compute_dtype == "bfloat16" else None
        if resolve_offload_level(offload) != "none":
            from dmlp_tpu.train.step import make_offload_train_step
            return make_offload_train_step(opt2, cdtype, state)
        return make_train_step(opt2, cdtype)

    rebuild_step = _rebuild_step_dp_tp if parallelism == "dp_tp" else None

    # Analytic collective-traffic accounting for this run's mesh
    # (obs.comms): the grad psum over dp, plus the MoE all-to-all when
    # the a2a dispatch runs — logged once so per-step records stay small.
    if metrics is not None:
        comms = _train_comms(state, mesh, parallelism, dims, batch,
                             moe_dispatch, capacity_factor, steps,
                             n_micro=n_micro, pp_schedule=pp_schedule,
                             n_virtual=n_virtual)
        if comms is not None:
            metrics.log(event="comms", **comms)

    # Sanitized training: transfer guard + leak check + debug_nans around
    # the step loop (dmlp_tpu.check.sanitize). The readbacks below are
    # explicit device_get / post-device_get floats, so a clean loop is
    # byte-identical; a NaN-producing step raises AT the op.
    from dmlp_tpu.check.sanitize import maybe_sanitized

    def san():  # fresh context per step: @contextmanager cms are one-shot
        return maybe_sanitized(train=True, force=sanitize)

    # Every step must be recoverable: a non-finite loss BEFORE the
    # first periodic checkpoint would otherwise have nothing to roll
    # back to (ckpt_every can exceed the divergence step) — seed the
    # dir with the start state, which save-at-end would overwrite only
    # at the same-or-later step anyway.
    if nan_guard and checkpoint_dir \
            and ckpt_lib.latest_step(checkpoint_dir) is None:
        ckpt_lib.save_checkpoint(checkpoint_dir, state, step=start_step)

    last = {}
    hlo_sig = None   # (step_fn, abstract arg specs) for the one-shot
    # compiled-program record logged after the loop (obs.hlo)
    t_window = time.perf_counter()
    window_steps = 0
    cur_lr = lr
    total_rollbacks = 0
    rollbacks_at: dict = {}   # step index -> rollback count at that step
    end = start_step + steps
    i = start_step
    while i < end:
        xd, yd = next(data)
        if hlo_sig is None and metrics is not None:
            # Shape specs only — no buffers kept alive across the loop.
            try:
                hlo_sig = (step_fn, jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    (state, xd, yd)))
            except Exception:  # check: no-retry
                # Introspection is best-effort: a spec-capture failure
                # must never take down a training step.
                hlo_sig = None

        def _step_op():
            # The injection fire rides INSIDE the retried op: a
            # transient fault at this site is consumed on attempt 1 and
            # the retry's re-dispatch (same state/batch — pure) lands.
            acts = rs_inject.fire("train.step", step=i) or ()
            with obs_span("train.step"), san():
                s2, m2 = step_fn(state, xd, yd)
            return acts, s2, m2

        actions, new_state, m = rs_retry.call_with_retry(
            _step_op, "train.step")

        if nan_guard:
            # Per-step loss readback (opt-in: --nan-guard; the default
            # loop keeps its async log_every cadence). An injected
            # "nan" action poisons the detector input — the rollback
            # machinery is driven without corrupting any real state.
            import math
            loss_val = (float("nan") if "nan" in actions
                        else float(jax.device_get(m["loss"])))
            if not math.isfinite(loss_val):
                if not checkpoint_dir:
                    raise RuntimeError(
                        f"non-finite loss at step {i + 1} and nowhere "
                        "to roll back to — the NaN guard needs "
                        "checkpoint_dir/--checkpoint-dir")
                total_rollbacks += 1
                rs_stats.record_rollback()
                if total_rollbacks > max_rollbacks:
                    raise RuntimeError(
                        f"non-finite loss persisted through "
                        f"{max_rollbacks} rollback(s) — giving up at "
                        f"step {i + 1}")
                seen = rollbacks_at.get(i, 0)
                rollbacks_at[i] = seen + 1
                if seen >= 1:
                    # Same step diverged twice: replay alone cannot fix
                    # a deterministic divergence — decay the LR.
                    if rebuild_step is None:
                        raise RuntimeError(
                            f"step {i + 1} diverged twice and LR "
                            f"backoff is unsupported for parallelism="
                            f"{parallelism} (dp_tp only)")
                    cur_lr *= lr_backoff
                    step_fn = rebuild_step(cur_lr)
                faulted_at = i
                state = ckpt_lib.restore_checkpoint(checkpoint_dir, state)
                i = int(jax.device_get(state["step"]))
                if i > faulted_at:
                    raise RuntimeError(
                        f"latest checkpoint is step {i}, AHEAD of the "
                        f"faulted step {faulted_at} — rolling back would "
                        f"jump forward (stale checkpoint_dir "
                        f"{checkpoint_dir!r} from an earlier run?)")
                if i < start_step:
                    raise RuntimeError(
                        f"checkpoint step {i} precedes this run's data "
                        f"stream start {start_step} — cannot replay")
                from dmlp_tpu.obs import trace as obs_trace
                obs_trace.instant("resilience.rollback", to_step=i,
                                  lr=cur_lr)
                data = make_data(i - start_step)
                t_window = time.perf_counter()
                window_steps = 0
                continue

        state = new_state
        window_steps += 1
        if (i + 1) % log_every == 0 or i + 1 == end:
            with obs_span("train.log_window", step=i + 1) as sp:
                m = jax.device_get(m)
                sp.fence(state["params"])
            dt = (time.perf_counter() - t_window) / window_steps
            t_window = time.perf_counter()
            window_steps = 0
            last = {"step": i + 1, "loss": float(m["loss"]),
                    "accuracy": float(m["accuracy"]),
                    **throughput_metrics(state["params"], batch, dt, n_chips)}
            if metrics is not None:
                metrics.log(**last)
        if checkpoint_dir and (i + 1) % ckpt_every == 0:
            with obs_span("train.checkpoint", step=i + 1):
                ckpt_lib.save_checkpoint(checkpoint_dir, state, step=i + 1)
        i += 1
    if checkpoint_dir:
        ckpt_lib.save_checkpoint(checkpoint_dir, state, step=end)
    if metrics is not None and hlo_sig is not None:
        # One-shot compiled-program record (obs.hlo): which collectives
        # the compiled step ACTUALLY dispatches, alongside the analytic
        # event="comms" summary logged before the loop. AOT lower runs
        # after the step loop (untimed) and never raises into training.
        try:
            from dmlp_tpu.obs import hlo as obs_hlo
            fn, specs = hlo_sig
            rep = obs_hlo.report_for_fn(fn, specs, label="train.step")
            if rep is None:
                metrics.log(event="hlo", hlo_unavailable=
                            "step program could not be lowered for "
                            "introspection")
            else:
                ev = {"event": "hlo", "fingerprint": rep.fingerprint}
                for kind, agg in sorted(rep.totals.items()):
                    key = kind.replace("-", "_")
                    ev[f"{key}_bytes"] = agg["bytes_moved"]
                    ev[f"{key}_count"] = agg["count"]
                if "hlo_memory_unavailable" not in rep.memory:
                    ev["hlo_temp_bytes"] = rep.memory.get("temp_bytes", 0)
                metrics.log(**ev)
        except Exception:
            pass  # check: no-retry — observability must not fail a run
    return state, last


def _train_comms(state, mesh, parallelism: str, dims, batch: int,
                 moe_dispatch: str, capacity_factor: float,
                 steps: int, n_micro: int = 4, pp_schedule: str = "gpipe",
                 n_virtual: int = 1) -> Optional[dict]:
    """obs.comms summary for this run's collective paths, from the real
    mesh/param shapes; None when the run dispatches no collectives."""
    import numpy as _np

    from dmlp_tpu.obs import comms as obs_comms

    param_bytes = int(sum(
        _np.prod(x.shape) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(state["params"])))
    moe = None
    moe_dense = None
    if parallelism == "dp_ep" and moe_dispatch == "a2a":
        from dmlp_tpu.train.experts import a2a_capacity
        dp, ep = mesh.devices.shape
        moe = {"ep": ep, "hidden": dims[1],
               "capacity": a2a_capacity(batch, dp, ep, capacity_factor)}
    elif parallelism == "dp_ep":
        # Dense one-hot dispatch: the combine is ONE ep psum of the
        # (dp-local tokens, hidden) partials per step
        # (experts._moe_body; obs.comms.ep_psum_combine_traffic).
        dp, ep = mesh.devices.shape
        moe_dense = {"ep": ep, "hidden": dims[1],
                     "tokens": max(batch // dp, 1)}
    pipeline = None
    if parallelism in ("dp_pp", "dp_pp3"):
        # Activation hand-off shapes exactly as the step dispatches them:
        # each dp cell's local batch splits into n_micro microbatches of
        # (micro_rows, hidden) f32 activations; the ppermute runs
        # independently per (dp[, tp]) cell group.
        dp, pp = mesh.devices.shape[0], mesh.devices.shape[-1]
        groups = int(_np.prod(mesh.devices.shape[:-1]))
        sched = pp_schedule if parallelism == "dp_pp" else "gpipe"
        pipeline = {"pp": pp, "n_micro": n_micro,
                    "micro_rows": max(batch // dp // max(n_micro, 1), 1),
                    "hidden": dims[1], "schedule": sched,
                    "n_virtual": n_virtual if sched == "interleaved" else 1,
                    "n_groups": groups}
        if parallelism == "dp_pp3":
            # dp_pp3 stage blocks psum each col/row pair's activation
            # over tp (pipeline._stage_block3; 2 pairs per stage).
            pipeline["tp"] = mesh.devices.shape[1]
            pipeline["n_pairs"] = 2
    traffic = obs_comms.train_step_comms(param_bytes, mesh.devices.shape,
                                         steps=steps, moe=moe,
                                         pipeline=pipeline,
                                         moe_dense=moe_dense)
    return obs_comms.summarize(traffic) if traffic else None


def _params_checksum(state) -> str:
    """sha256 over the (deterministically ordered) param leaves' bytes —
    the step-identical-recovery fingerprint in train RunRecords."""
    import hashlib

    import numpy as _np

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves(state["params"])
    for leaf in jax.device_get(leaves):
        a = _np.asarray(leaf)
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="dmlp_tpu.train", description=__doc__)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=1024)
    p.add_argument("--dims", type=str, default="64,256,256,10",
                   help="comma-separated layer dims: in,hidden...,classes "
                        "(dp_pp/dp_pp3: in,hidden,classes; dp_ep: "
                        "in,hidden,ffn,classes)")
    p.add_argument("--mesh", type=str, default=None,
                   help="DP,TP (dp_tp) / DP,PP (dp_pp) / DP,TP,PP "
                        "(dp_pp3) / DP,EP (dp_ep)")
    p.add_argument("--parallelism", default="dp_tp",
                   choices=["dp_tp", "dp_pp", "dp_pp3", "dp_ep"],
                   help="mesh-parallelism family: dp x tp MLP (default; "
                        "full feature set), dp x pp / dp x tp x pp "
                        "pipelined stack, dp x ep MoE")
    p.add_argument("--microbatches", type=int, default=4,
                   help="pipeline microbatches per step (dp_pp/dp_pp3)")
    p.add_argument("--pp-schedule", default="gpipe",
                   choices=["gpipe", "interleaved"],
                   help="dp_pp schedule: gpipe, or interleaved virtual "
                        "stages (1F1B-interleaved; bubble / V, needs "
                        "microbatches <= PP)")
    p.add_argument("--virtual-stages", type=int, default=2,
                   help="interleaved schedule: stage chunks per pp cell")
    p.add_argument("--experts", type=int, default=8,
                   help="MoE expert count (dp_ep; divisible by EP)")
    p.add_argument("--moe-dispatch", default="dense",
                   choices=["dense", "a2a"],
                   help="dp_ep dispatch: dense one-hot (capacity-free, "
                        "masked compute) or capacity + all-to-all (the "
                        "production EP form; tokens route to the "
                        "expert-owning cells over ICI, overflow drops to "
                        "the residual path)")
    p.add_argument("--capacity-factor", type=float, default=1.0,
                   help="a2a capacity factor: per-(source, destination) "
                        "slots = ceil(cf * local_tokens / EP); cf >= EP "
                        "guarantees zero drops")
    p.add_argument("--optimizer", default="sgd", choices=["sgd", "adam"])
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--compute-dtype", default=None,
                   choices=[None, "bfloat16"])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=100)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--metrics-file", default=None)
    p.add_argument("--record", metavar="FILE", default=None,
                   help="write one versioned RunRecord (obs.run) "
                        "summarizing the run to FILE")
    p.add_argument("--trace", metavar="FILE", default=None,
                   help="write a Perfetto/Chrome-trace JSON of the run's "
                        "step/checkpoint spans to FILE (obs.trace)")
    p.add_argument("--telemetry", metavar="FILE", default=None,
                   help="live telemetry (obs.telemetry): periodic "
                        "OpenMetrics snapshot rewrite of FILE (step "
                        "latency histograms, device-memory watermarks, "
                        "resilience counters) + crash flight recorder "
                        "(FLIGHT_*.json next to FILE)")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--sanitize", action="store_true",
                   help="wrap every train step in jax.transfer_guard("
                        "'disallow') + jax.checking_leaks + "
                        "jax.debug_nans (dmlp_tpu.check.sanitize); "
                        "$DMLP_TPU_SANITIZE=1 enables it too")
    p.add_argument("--nan-guard", action="store_true",
                   help="per-step non-finite-loss guard: on NaN/inf "
                        "loss, restore the latest checkpoint, replay "
                        "the stream step-identically, and decay the LR "
                        "(x0.5) if the same step diverges twice "
                        "(needs --checkpoint-dir)")
    p.add_argument("--faults", metavar="FILE", default=None,
                   help="deterministic fault-injection schedule (JSON; "
                        "dmlp_tpu.resilience.inject); $DMLP_TPU_FAULTS "
                        "sets it too")
    p.add_argument("--offload", nargs="?", const="all", default="none",
                   choices=["none", "params", "all"],
                   help="host-DRAM offload level: 'params' keeps moments "
                        "in HBM (half the stream bytes of 'all'); bare "
                        "--offload means 'all' (the bench_4 host-offload "
                        "analog)")
    p.add_argument("--compile-cache", metavar="DIR", default=None,
                   help="persistent XLA compilation cache dir; "
                        "default <checkout>/.jax_cache, and "
                        "$JAX_COMPILATION_CACHE_DIR, when set, "
                        "wins over both (utils.compile_cache)")
    args = p.parse_args(argv)

    from dmlp_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache(args.compile_cache)
    mesh_shape = None
    if args.mesh:
        mesh_shape = tuple(int(d) for d in args.mesh.split(","))
    tracer = None
    rs_stats.reset()   # resets the registry's resilience.* counters too
    telemetry_session = None
    if args.telemetry:
        from dmlp_tpu.obs import telemetry
        telemetry_session = telemetry.start(path=args.telemetry)
    if args.trace:
        from dmlp_tpu.obs import trace as obs_trace
        tracer = obs_trace.install(obs_trace.Tracer())
    schedule = rs_inject.install_from_env(args.faults)
    final_state = None
    try:
        mlog = (MetricsLogger(path=args.metrics_file)
                if args.metrics_file else MetricsLogger())
        with mlog as metrics:
            final_state, last = train(
                steps=args.steps, batch=args.batch,
                dims=tuple(int(d) for d in args.dims.split(",")),
                mesh_shape=mesh_shape, optimizer_name=args.optimizer,
                lr=args.lr, compute_dtype=args.compute_dtype,
                seed=args.seed, checkpoint_dir=args.checkpoint_dir,
                ckpt_every=args.ckpt_every, resume=args.resume,
                metrics=metrics, log_every=args.log_every,
                offload=args.offload, parallelism=args.parallelism,
                n_micro=args.microbatches, n_experts=args.experts,
                moe_dispatch=args.moe_dispatch,
                capacity_factor=args.capacity_factor,
                pp_schedule=args.pp_schedule,
                n_virtual=args.virtual_stages,
                sanitize=args.sanitize, nan_guard=args.nan_guard)
    except Exception:
        # Exception, not BaseException: a SystemExit/KeyboardInterrupt
        # is not a crash (cli.py has the same rule).
        if telemetry_session is not None:
            from dmlp_tpu.obs import telemetry
            telemetry.dump_on_crash("crash")
        raise
    finally:
        if schedule is not None:
            rs_inject.write_log_if_requested()
            rs_inject.uninstall()
        if tracer is not None:
            from dmlp_tpu.obs import trace as obs_trace
            tracer.write(args.trace)
            obs_trace.uninstall()
        if telemetry_session is not None:
            telemetry_session.close()
    if args.record:
        from dmlp_tpu.obs.run import (RunRecord, current_device,
                                      round_from_name)
        artifacts = {}
        if args.trace:
            artifacts["trace"] = args.trace
        if args.metrics_file:
            artifacts["metrics"] = args.metrics_file
        rec_metrics = dict(last)
        # Analytic per-device peak-HBM model for this run's step
        # (obs.memwatch train term set) + watermark reconcile — the mem
        # block carries the explicit marker where the backend reports
        # no memory.
        try:
            from dmlp_tpu.obs import memwatch
            model = memwatch.train_step_model(
                [int(d) for d in args.dims.split(",")], args.batch,
                optimizer=args.optimizer, mesh_shape=mesh_shape,
                compute_dtype=args.compute_dtype)
            # The (closed) session's sampler keeps its tracked peaks;
            # without a session, fall back to a one-shot basis.
            measured = (telemetry_session.sampler.measured_peak()
                        if telemetry_session is not None
                        else memwatch.measured_watermark())
            rec_metrics["mem"] = memwatch.reconcile(model, measured)
        except Exception:  # check: no-retry — obs never fails the run
            pass
        if final_state is not None:
            # Bitwise state fingerprint: the chaos harness proves a
            # NaN-faulted run resumed step-identically by comparing
            # this against the fault-free run's checksum.
            rec_metrics["params_checksum"] = _params_checksum(final_state)
        if rs_stats.any_activity() or schedule is not None:
            rec_metrics["resilience"] = rs_stats.snapshot()
        RunRecord(
            kind="train", tool="dmlp_tpu.train",
            config={"parallelism": args.parallelism,
                    "dims": [int(d) for d in args.dims.split(",")],
                    "batch": args.batch, "steps": args.steps,
                    "mesh": mesh_shape and list(mesh_shape),
                    "optimizer": args.optimizer,
                    "compute_dtype": args.compute_dtype,
                    "offload": args.offload,
                    "moe_dispatch": args.moe_dispatch,
                    "pp_schedule": args.pp_schedule,
                    "nan_guard": args.nan_guard},
            metrics=rec_metrics, artifacts=artifacts,
            device=current_device(),
            round=round_from_name(args.record)).write(args.record)
    print(f"final: {last}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
