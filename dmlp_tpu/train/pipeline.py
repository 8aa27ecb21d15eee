"""Pipeline-parallel training (GPipe-style microbatching) over a
("dp", "pp") mesh — the pp rung of the mesh-parallelism ladder next to
the dp x tp step (train.step / train.sharding).

The reference has no training at all (survey §2: TP/PP absent); this is
part of the north-star extension, built the TPU way rather than as a
port of MPMD pipeline frameworks: ONE jitted SPMD program in which

- the layer stack is split into S contiguous stages, stacked into
  uniform (S, P, H, H) arrays and sharded over the mesh's "pp" axis
  (each pp cell holds only its stage's weights);
- a ``lax.scan`` over M + S - 1 ticks runs the pipeline schedule: at
  tick t, stage s computes microbatch m = t - s and hands its
  activation to stage s+1 via ``lax.ppermute`` over ICI — the bubble
  (ticks where m is out of range) is masked, not branched, because XLA
  wants static control flow;
- the loss leaves the shard_map as per-cell PARTIALS (nonzero only on
  each dp row's last stage) summed outside in plain math — no
  collective touches the loss path, so the grad transpose is exact by
  construction — and plain ``jax.grad`` differentiates through the
  scan + ppermute (XLA emits the reverse-schedule permutes): no
  hand-written backward pass.

Input projection and readout are computed per pp cell (they are O(H)
of the O(P * H^2) stage work; only the cells whose values reach the
loss contribute gradients); batches shard over "dp", so data
parallelism composes with the pipeline in the same program.

Microbatch semantics: the loss is the mean over the full (per-dp-cell)
batch, so gradients equal the unpipelined model's — proven by the
equivalence test against a flat single-device stack
(tests/test_train_pipeline.py).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from dmlp_tpu.utils.compat import shard_map

DP_AXIS = "dp"
PP_AXIS = "pp"

PipeParams = Dict[str, jax.Array]


def make_axes_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Mesh over the leading len(axes) devices — the one mesh builder the
    pp/pp3/ep entry points share (axis names and sizes as an ordered
    dict)."""
    devices = list(devices if devices is not None else jax.devices())
    if any(v < 1 for v in axes.values()):
        raise ValueError(f"mesh axes must be >= 1, got {axes}")
    total = int(np.prod(list(axes.values())))
    if total > len(devices):
        raise ValueError(f"need {total} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:total]).reshape(*axes.values()),
                tuple(axes))


def make_pp_mesh(dp: int, pp: int, devices=None) -> Mesh:
    return make_axes_mesh({DP_AXIS: dp, PP_AXIS: pp}, devices)


def init_pipeline(key, d_in: int, hidden: int, n_classes: int,
                  stages: int, layers_per_stage: int,
                  dtype=jnp.float32) -> PipeParams:
    """Uniform pipeline body: stages x layers_per_stage (H, H) layers,
    plus replicated input projection and readout. Stacked so the stage
    axis shards with P("pp", ...)."""
    ks = jax.random.split(key, 4)
    s, p, h = stages, layers_per_stage, hidden
    scale = jnp.sqrt(2.0 / h).astype(dtype)
    return {
        "in_w": jax.random.normal(ks[0], (d_in, h), dtype)
        * jnp.sqrt(2.0 / d_in).astype(dtype),
        "in_b": jnp.zeros((h,), dtype),
        "pp_w": jax.random.normal(ks[1], (s, p, h, h), dtype) * scale,
        "pp_b": jnp.zeros((s, p, h), dtype),
        "out_w": jax.random.normal(ks[2], (h, n_classes), dtype)
        * jnp.sqrt(2.0 / h).astype(dtype),
        "out_b": jnp.zeros((n_classes,), dtype),
    }


# Single source of truth for per-param partition specs (placement and
# shard_map in_specs both derive from it).
PP_PSPECS = {
    "in_w": P(None, None), "in_b": P(None),
    "pp_w": P(PP_AXIS, None, None, None),
    "pp_b": P(PP_AXIS, None, None),
    "out_w": P(None, None), "out_b": P(None),
}


def pipeline_param_shardings(mesh: Mesh):
    return {k: NamedSharding(mesh, spec) for k, spec in PP_PSPECS.items()}


def _partials_train_step(sharded_loss, optimizer, n_dp: int):
    """Jitted donated train step over a partial-loss shard_map program:
    the per-cell partials (one nonzero cell per dp row) sum to the batch
    loss in plain math here. Shared by the 2D and 3D pipeline steps."""
    def loss_fn(params, x, y):
        loss_p, acc_p = sharded_loss(params, x, y)
        return loss_p.sum() / n_dp, acc_p.sum() / n_dp

    def step(state, x, y):
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], x, y)
        updates, opt = optimizer.update(grads, state["opt"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        return ({"params": params, "opt": opt, "step": state["step"] + 1},
                {"loss": loss, "accuracy": acc})

    return jax.jit(step, donate_argnums=(0,))


def place_state(params, shardings, optimizer):
    """device_put params per sharding table; moments inherit placement.
    Shared by the pipeline and MoE state builders. The step counter is
    placed replicated on the same mesh — a default-device scalar would
    make the jit reject mesh-committed batch arguments as an
    incompatible device set."""
    placed = {k: jax.device_put(v, shardings[k]) for k, v in params.items()}
    mesh = next(iter(shardings.values())).mesh
    step0 = jax.device_put(jnp.zeros((), jnp.int32),
                           NamedSharding(mesh, P()))
    return {"params": placed, "opt": optimizer.init(placed), "step": step0}


def _stage_block(w, b, h):
    """One stage's layers_per_stage dense+relu layers. w: (P, H, H)."""
    def layer(h, wb):
        wi, bi = wb
        return jax.nn.relu(
            jax.lax.dot_general(h, wi, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            + bi).astype(h.dtype), None
    h, _ = jax.lax.scan(layer, h, (w, b))
    return h


def _pp_body(params, x, y, *, n_stages: int, n_micro: int, n_classes: int):
    """Per-(dp, pp)-cell pipelined loss (runs inside shard_map).

    ``params["pp_w"]`` arrives as this cell's (1, P, H, H) stage slice;
    x/y are this dp cell's local batch, replicated over pp.
    """
    assert params["out_w"].shape[1] == n_classes, \
        (params["out_w"].shape, n_classes)
    s_idx = jax.lax.axis_index(PP_AXIS)
    w_s = params["pp_w"][0]
    b_s = params["pp_b"][0]

    h0 = x.astype(jnp.float32) @ params["in_w"] + params["in_b"]
    mb = h0.shape[0] // n_micro
    h_mb = h0.reshape(n_micro, mb, -1)

    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        act, ys = carry
        m = t - s_idx  # this stage's microbatch index at this tick
        # Stage 0 pulls fresh microbatches; later stages consume the
        # activation handed over at the previous tick. Bubbles (m out of
        # range) compute on zeros and are masked at collection.
        fresh = h_mb[jnp.clip(t, 0, n_micro - 1)]
        inp = jnp.where(s_idx == 0, fresh, act)
        out = _stage_block(w_s, b_s, inp)
        # Last stage collects its finished microbatch.
        take = (s_idx == n_stages - 1) & (m >= 0) & (m < n_micro)
        ys = jnp.where(
            take,
            jax.lax.dynamic_update_index_in_dim(
                ys, out, jnp.clip(m, 0, n_micro - 1), 0),
            ys)
        # Hand the activation to the next stage (stage 0 receives zeros;
        # the last stage's output is not forwarded).
        # check: comms-model=pipeline_ppermute_traffic
        act = jax.lax.ppermute(out, PP_AXIS, perm) if n_stages > 1 else out
        return (act, ys), None

    ys0 = jnp.zeros_like(h_mb)
    act0 = jnp.zeros_like(h_mb[0])
    (_, ys), _ = jax.lax.scan(tick, (act0, ys0),
                              jnp.arange(n_micro + n_stages - 1))

    # Loss as a PER-CELL PARTIAL (nonzero only on the last stage), summed
    # OUTSIDE the shard_map: no collective touches the loss path, so the
    # grad transpose is exact by construction — replicated-output specs
    # under check_vma=False are a known axis-size-overcount sharp edge,
    # and in-body psums on the loss would reintroduce it.
    h_out = ys.reshape(h0.shape)
    logits = h_out @ params["out_w"] + params["out_b"]
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    last = (s_idx == n_stages - 1).astype(loss.dtype)
    return (loss * last)[None], (acc * last)[None]


def make_pp_train_step(mesh: Mesh, optimizer: optax.GradientTransformation,
                       *, n_micro: int, n_classes: int):
    """Jitted (state, x, y) -> (state', {loss, accuracy}) over the
    ("dp", "pp") mesh. ``state`` = {"params", "opt", "step"} with params
    placed by pipeline_param_shardings."""
    n_stages = mesh.devices.shape[1]

    n_dp = mesh.devices.shape[0]
    body = functools.partial(_pp_body, n_stages=n_stages, n_micro=n_micro,
                             n_classes=n_classes)
    sharded_loss = shard_map(
        body, mesh=mesh,
        in_specs=(PP_PSPECS, P(DP_AXIS, None), P(DP_AXIS)),
        out_specs=(P((DP_AXIS, PP_AXIS)), P((DP_AXIS, PP_AXIS))),
        check_vma=False)

    return _partials_train_step(sharded_loss, optimizer, n_dp)


def build_pp_state(mesh: Mesh, optimizer, d_in: int, hidden: int,
                   n_classes: int, layers_per_stage: int, seed: int = 0):
    """Init + place pipeline params; optimizer moments inherit placement."""
    stages = mesh.devices.shape[1]
    params = init_pipeline(jax.random.PRNGKey(seed), d_in, hidden,
                           n_classes, stages, layers_per_stage)
    return place_state(params, pipeline_param_shardings(mesh), optimizer)


def flatten_pipeline(params: PipeParams) -> Tuple:
    """The mathematically equivalent single-device stack:
    in -> S*P dense+relu (H, H) layers -> readout. For the equivalence
    test and for flat-reference inference."""
    s, p, h, _ = params["pp_w"].shape
    ws = np.asarray(params["pp_w"]).reshape(s * p, h, h)
    bs = np.asarray(params["pp_b"]).reshape(s * p, h)
    return (np.asarray(params["in_w"]), np.asarray(params["in_b"]),
            ws, bs, np.asarray(params["out_w"]), np.asarray(params["out_b"]))


def flat_forward(flat, x):
    """NumPy/JAX reference forward for flatten_pipeline output."""
    in_w, in_b, ws, bs, out_w, out_b = flat
    h = x.astype(jnp.float32) @ in_w + in_b
    for wi, bi in zip(ws, bs):
        h = jax.nn.relu(h @ wi + bi)
    return h @ out_w + out_b


# ---------------------------------------------------------------------------
# Interleaved schedule (1F1B-interleaved / Megatron virtual stages): each pp
# cell holds V non-contiguous stage CHUNKS (cell s owns chunks s, s+S, ...,
# s+(V-1)S of the V*S-chunk layer sequence); the scan runs chunk c of
# microbatch m at tick m + c, activations ride a uniform +1 ring ppermute
# (the S-1 -> 0 wraparound carries the level-up hop). Each tick costs a
# 1/V stage slice, so the pipeline fill/drain shrinks: forward span
# (M - 1 + V*S) * F/V = ((M-1)/V + S) * F vs GPipe's (M - 1 + S) * F —
# the bubble term drops by V, which is the whole point at small M
# (round-4 review item 6). Backward is still jax.grad through the scan (the
# reverse schedule inherits the same 1/V tick cost).
#
# Why not plain (non-interleaved) 1F1B: in a single-jit SPMD program the
# backward schedule is XLA's reverse of the forward scan, and
# non-interleaved 1F1B has exactly GPipe's bubble ((S-1)/(M+S-1)) — its
# advantage is peak activation memory (O(S) in-flight microbatches instead
# of O(M)), which in this design is the remat lever (jax.checkpoint on the
# tick body), not a schedule change. Interleaving is the schedule lever
# that actually moves the bubble, so that is what ships.
#
# The masked schedule needs at most one active chunk per cell per tick,
# which holds when n_micro <= n_stages — exactly the small-M regime where
# GPipe's bubble hurts; larger M should use GPipe (its bubble term is
# already amortized there).
# ---------------------------------------------------------------------------


def init_pipeline_interleaved(key, d_in: int, hidden: int, n_classes: int,
                              stages: int, n_virtual: int,
                              layers_per_chunk: int,
                              dtype=jnp.float32) -> PipeParams:
    """V*S chunk layer stack: pp_w (V, S, P, H, H); chunk (l, s) holds
    layers [(l*S + s) * P, ...) of the flat sequence, so axis order
    (level, stage) IS the model's layer order under reshape."""
    ks = jax.random.split(key, 4)
    v, s, p, h = n_virtual, stages, layers_per_chunk, hidden
    scale = jnp.sqrt(2.0 / h).astype(dtype)
    return {
        "in_w": jax.random.normal(ks[0], (d_in, h), dtype)
        * jnp.sqrt(2.0 / d_in).astype(dtype),
        "in_b": jnp.zeros((h,), dtype),
        "pp_w": jax.random.normal(ks[1], (v, s, p, h, h), dtype) * scale,
        "pp_b": jnp.zeros((v, s, p, h), dtype),
        "out_w": jax.random.normal(ks[2], (h, n_classes), dtype)
        * jnp.sqrt(2.0 / h).astype(dtype),
        "out_b": jnp.zeros((n_classes,), dtype),
    }


PPI_PSPECS = {
    "in_w": P(None, None), "in_b": P(None),
    "pp_w": P(None, PP_AXIS, None, None, None),
    "pp_b": P(None, PP_AXIS, None, None),
    "out_w": P(None, None), "out_b": P(None),
}


def pipeline_interleaved_param_shardings(mesh: Mesh):
    return {k: NamedSharding(mesh, spec) for k, spec in PPI_PSPECS.items()}


def _ppi_body(params, x, y, *, n_stages: int, n_micro: int, n_virtual: int,
              n_classes: int):
    """Per-(dp, pp)-cell interleaved pipelined loss partial.

    At tick t, this cell's active chunk is the (l, s_idx) with
    r = t - s_idx, l = r // S, m = r % S (unique because M <= S); chunk
    level is a traced dynamic index into the cell's (V, P, H, H) slice.
    Bubbles compute on zeros and are masked at collection, like _pp_body.
    """
    assert params["out_w"].shape[1] == n_classes
    s_idx = jax.lax.axis_index(PP_AXIS)
    w_v = params["pp_w"][:, 0]          # (V, P, H, H) — this cell's chunks
    b_v = params["pp_b"][:, 0]

    h0 = x.astype(jnp.float32) @ params["in_w"] + params["in_b"]
    mb = h0.shape[0] // n_micro
    h_mb = h0.reshape(n_micro, mb, -1)
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        act, ys = carry
        r = t - s_idx
        lvl = jnp.where(r >= 0, r // n_stages, 0)
        m = jnp.where(r >= 0, r % n_stages, 0)
        active = (r >= 0) & (lvl < n_virtual) & (m < n_micro)
        w_l = jax.lax.dynamic_index_in_dim(
            w_v, jnp.clip(lvl, 0, n_virtual - 1), 0, keepdims=False)
        b_l = jax.lax.dynamic_index_in_dim(
            b_v, jnp.clip(lvl, 0, n_virtual - 1), 0, keepdims=False)
        fresh = h_mb[jnp.clip(m, 0, n_micro - 1)]
        inp = jnp.where((s_idx == 0) & (lvl == 0), fresh, act)
        out = _stage_block(w_l, b_l, inp)
        take = active & (s_idx == n_stages - 1) & (lvl == n_virtual - 1)
        ys = jnp.where(
            take,
            jax.lax.dynamic_update_index_in_dim(
                ys, out, jnp.clip(m, 0, n_micro - 1), 0),
            ys)
        # check: comms-model=pipeline_ppermute_traffic
        act = jax.lax.ppermute(out, PP_AXIS, ring) if n_stages > 1 else out
        return (act, ys), None

    n_ticks = n_micro - 1 + n_virtual * n_stages
    (_, ys), _ = jax.lax.scan(
        tick, (jnp.zeros_like(h_mb[0]), jnp.zeros_like(h_mb)),
        jnp.arange(n_ticks))

    logits = ys.reshape(h0.shape) @ params["out_w"] + params["out_b"]
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    last = (s_idx == n_stages - 1).astype(loss.dtype)
    return (loss * last)[None], (acc * last)[None]


def make_ppi_train_step(mesh: Mesh, optimizer: optax.GradientTransformation,
                        *, n_micro: int, n_virtual: int, n_classes: int):
    """Jitted interleaved-schedule train step over ("dp", "pp"). Requires
    n_micro <= n_stages (one active chunk per cell per tick)."""
    n_dp, n_stages = mesh.devices.shape
    if n_micro > n_stages:
        raise ValueError(
            f"interleaved schedule needs n_micro <= n_stages "
            f"({n_micro} > {n_stages}); use the gpipe schedule there")
    body = functools.partial(_ppi_body, n_stages=n_stages, n_micro=n_micro,
                             n_virtual=n_virtual, n_classes=n_classes)
    sharded_loss = shard_map(
        body, mesh=mesh,
        in_specs=(PPI_PSPECS, P(DP_AXIS, None), P(DP_AXIS)),
        out_specs=(P((DP_AXIS, PP_AXIS)), P((DP_AXIS, PP_AXIS))),
        check_vma=False)
    return _partials_train_step(sharded_loss, optimizer, n_dp)


def build_ppi_state(mesh: Mesh, optimizer, d_in: int, hidden: int,
                    n_classes: int, n_virtual: int, layers_per_chunk: int,
                    seed: int = 0):
    stages = mesh.devices.shape[1]
    params = init_pipeline_interleaved(
        jax.random.PRNGKey(seed), d_in, hidden, n_classes, stages,
        n_virtual, layers_per_chunk)
    return place_state(params, pipeline_interleaved_param_shardings(mesh),
                       optimizer)


def flatten_interleaved(params: PipeParams) -> Tuple:
    """Flat single-device stack for the interleaved layout (chunk order
    (level, stage) = the model's layer order)."""
    v, s, p, h, _ = params["pp_w"].shape
    ws = np.asarray(params["pp_w"]).reshape(v * s * p, h, h)
    bs = np.asarray(params["pp_b"]).reshape(v * s * p, h)
    return (np.asarray(params["in_w"]), np.asarray(params["in_b"]),
            ws, bs, np.asarray(params["out_w"]), np.asarray(params["out_b"]))


def schedule_ticks(schedule: str, n_micro: int, n_stages: int,
                   n_virtual: int = 1) -> int:
    """Scan tick count of each schedule — the bubble arithmetic for the
    PIPEBENCH record: each tick costs ~one stage-chunk of compute (a full
    stage for gpipe, a 1/V slice for interleaved)."""
    if schedule == "gpipe":
        return n_micro + n_stages - 1
    if schedule == "interleaved":
        return n_micro - 1 + n_virtual * n_stages
    raise ValueError(f"unknown schedule {schedule!r}")


def bubble_fraction(schedule: str, n_micro: int, n_stages: int,
                    n_virtual: int = 1) -> float:
    """Idle fraction of one device's pipeline span, in stage-work units
    (a unit = one full stage pass over one microbatch; fwd and bwd scale
    identically). Per device the useful work is always M units (its V
    chunks sum to one stage's layers); the span is the tick count times
    the per-tick cost:

    - gpipe:       (M + S - 1) ticks x 1 unit      -> span M + S - 1
    - interleaved: (M - 1 + V*S) ticks x 1/V unit  -> span (M-1)/V + S

    so interleaving divides the (S - 1)-shaped fill/drain term by V."""
    span = (schedule_ticks(schedule, n_micro, n_stages, n_virtual)
            / (n_virtual if schedule == "interleaved" else 1))
    return 1.0 - n_micro / span


# ---------------------------------------------------------------------------
# 3D composition: dp x tp x pp in one jit. Stage layers come in Megatron
# col/row pairs — the column-split matmul shards its OUTPUT dim over "tp",
# the row-split one its INPUT dim, so each pair needs exactly one tp psum —
# while the pp schedule (scan + ppermute) and the dp batch split are
# unchanged from the 2D form above. Grad-exact vs the flat stack
# (tests/test_train_pipeline.py::test_pp3_step_matches_flat_reference).
# ---------------------------------------------------------------------------

TP_AXIS = "tp"

PP3_PSPECS = {
    "in_w": P(None, None), "in_b": P(None),
    # column-parallel: output dim tp-sharded (bias follows its output)
    "wc": P(PP_AXIS, None, None, TP_AXIS),
    "bc": P(PP_AXIS, None, TP_AXIS),
    # row-parallel: input dim tp-sharded; bias replicated (added after psum)
    "wr": P(PP_AXIS, None, TP_AXIS, None),
    "br": P(PP_AXIS, None, None),
    "out_w": P(None, None), "out_b": P(None),
}


def make_pp3_mesh(dp: int, tp: int, pp: int, devices=None) -> Mesh:
    return make_axes_mesh({DP_AXIS: dp, TP_AXIS: tp, PP_AXIS: pp}, devices)


def init_pipeline3(key, d_in: int, hidden: int, n_classes: int,
                   stages: int, pairs_per_stage: int,
                   dtype=jnp.float32) -> PipeParams:
    """Col/row layer pairs per stage: h -> relu(h@Wc + bc) -> @Wr (+psum)
    -> relu(+br)."""
    ks = jax.random.split(key, 4)
    s, p2, h = stages, pairs_per_stage, hidden
    scale = jnp.sqrt(2.0 / h).astype(dtype)
    return {
        "in_w": jax.random.normal(ks[0], (d_in, h), dtype)
        * jnp.sqrt(2.0 / d_in).astype(dtype),
        "in_b": jnp.zeros((h,), dtype),
        "wc": jax.random.normal(ks[1], (s, p2, h, h), dtype) * scale,
        "bc": jnp.zeros((s, p2, h), dtype),
        "wr": jax.random.normal(ks[2], (s, p2, h, h), dtype) * scale,
        "br": jnp.zeros((s, p2, h), dtype),
        "out_w": jax.random.normal(ks[3], (h, n_classes), dtype)
        * jnp.sqrt(2.0 / h).astype(dtype),
        "out_b": jnp.zeros((n_classes,), dtype),
    }


def pipeline3_param_shardings(mesh: Mesh):
    return {k: NamedSharding(mesh, spec) for k, spec in PP3_PSPECS.items()}


def _stage_block3(wc, bc, wr, br, h):
    """One stage's col/row pairs on this tp cell's shard: wc (P2, H, Hl),
    wr (P2, Hl, H); one tp psum per pair."""
    def pair(h, wb):
        wci, bci, wri, bri = wb
        u = jax.nn.relu(
            jax.lax.dot_general(h, wci, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) + bci)
        v = jax.lax.dot_general(u, wri, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        v = jax.lax.psum(v, TP_AXIS)  # check: comms-model=tp_psum_activation_traffic
        return jax.nn.relu(v + bri).astype(h.dtype), None
    h, _ = jax.lax.scan(pair, h, (wc, bc, wr, br))
    return h


def _pp3_body(params, x, y, *, n_stages: int, n_micro: int, n_classes: int):
    """Per-(dp, tp, pp)-cell pipelined loss partial."""
    assert params["out_w"].shape[1] == n_classes
    s_idx = jax.lax.axis_index(PP_AXIS)
    t_idx = jax.lax.axis_index(TP_AXIS)
    wc, bc = params["wc"][0], params["bc"][0]
    wr, br = params["wr"][0], params["br"][0]

    h0 = x.astype(jnp.float32) @ params["in_w"] + params["in_b"]
    mb = h0.shape[0] // n_micro
    h_mb = h0.reshape(n_micro, mb, -1)
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        act, ys = carry
        m = t - s_idx
        fresh = h_mb[jnp.clip(t, 0, n_micro - 1)]
        inp = jnp.where(s_idx == 0, fresh, act)
        out = _stage_block3(wc, bc, wr, br, inp)
        take = (s_idx == n_stages - 1) & (m >= 0) & (m < n_micro)
        ys = jnp.where(
            take,
            jax.lax.dynamic_update_index_in_dim(
                ys, out, jnp.clip(m, 0, n_micro - 1), 0),
            ys)
        # check: comms-model=pipeline_ppermute_traffic
        act = jax.lax.ppermute(out, PP_AXIS, perm) if n_stages > 1 else out
        return (act, ys), None

    (_, ys), _ = jax.lax.scan(
        tick, (jnp.zeros_like(h_mb[0]), jnp.zeros_like(h_mb)),
        jnp.arange(n_micro + n_stages - 1))

    logits = ys.reshape(h0.shape) @ params["out_w"] + params["out_b"]
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    # Partial nonzero on exactly one (tp, pp) cell per dp row: the same
    # no-collective-on-the-loss-path rule as the 2D form. (Every tp cell
    # of the last stage holds identical post-psum activations; only tp 0
    # reports.)
    mine = ((s_idx == n_stages - 1) & (t_idx == 0)).astype(loss.dtype)
    return (loss * mine)[None], (acc * mine)[None]


def make_pp3_train_step(mesh: Mesh, optimizer: optax.GradientTransformation,
                        *, n_micro: int, n_classes: int):
    """Jitted (state, x, y) -> (state', metrics) over ("dp", "tp", "pp")."""
    n_dp, _n_tp, n_stages = mesh.devices.shape
    body = functools.partial(_pp3_body, n_stages=n_stages, n_micro=n_micro,
                             n_classes=n_classes)
    sharded_loss = shard_map(
        body, mesh=mesh,
        in_specs=(PP3_PSPECS, P(DP_AXIS, None), P(DP_AXIS)),
        out_specs=(P((DP_AXIS, TP_AXIS, PP_AXIS)),
                   P((DP_AXIS, TP_AXIS, PP_AXIS))),
        check_vma=False)

    return _partials_train_step(sharded_loss, optimizer, n_dp)


def build_pp3_state(mesh: Mesh, optimizer, d_in: int, hidden: int,
                    n_classes: int, pairs_per_stage: int, seed: int = 0):
    stages = mesh.devices.shape[2]
    params = init_pipeline3(jax.random.PRNGKey(seed), d_in, hidden,
                            n_classes, stages, pairs_per_stage)
    return place_state(params, pipeline3_param_shardings(mesh), optimizer)


def pp3_reference_forward(params: PipeParams, x) -> jax.Array:
    """Unsharded reference for the 3D step (equivalence oracle)."""
    h = x.astype(jnp.float32) @ params["in_w"] + params["in_b"]
    s, p2 = params["wc"].shape[:2]
    wc = params["wc"].reshape(s * p2, *params["wc"].shape[2:])
    bc = params["bc"].reshape(s * p2, -1)
    wr = params["wr"].reshape(s * p2, *params["wr"].shape[2:])
    br = params["br"].reshape(s * p2, -1)
    for i in range(s * p2):
        u = jax.nn.relu(h @ wc[i] + bc[i])
        h = jax.nn.relu(u @ wr[i] + br[i])
    return h @ params["out_w"] + params["out_b"]
