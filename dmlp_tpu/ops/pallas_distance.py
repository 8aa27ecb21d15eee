"""Pallas TPU kernel: fused masked distance tile + per-segment minima.

The "seg" selection (ops.topk.step_seg) needs two views of each distance
tile: the tile itself (to gather candidate columns from) and the minimum of
every 128-column segment (to pick the candidate segments). Computed with
stock XLA ops the segment-min pass re-reads the whole tile from HBM —
measured on TPU v5e that second pass costs more than the matmul that
produced the tile. This kernel produces both outputs in one pass: the MXU
computes the cross-term block, the VPU applies the norm expansion
``|q-d|^2 = |q|^2 + |d|^2 - 2 q.d`` + sentinel masking and reduces the
segment minima while the block is still in VMEM.

Grid: (Qb/TQ, B/TN) tiles; every tile is read/written exactly once.
Requires TN % 128 == 0 (whole lane-width segments). On non-TPU backends the
kernel runs in interpreter mode, so CPU tests exercise the identical code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from dmlp_tpu.utils.compat import tpu_compiler_params

SEG = 128  # candidate-segment width = one TPU lane row

_TQ = 1024  # query rows per tile (also the segmin lane dim -> 128-multiple)
_TN = 1024  # data columns per tile (8 segments -> valid sublane count)


def _tile(n: int, target: int, granule: int) -> int:
    """Largest granule-multiple divisor of n that is <= target (n itself if
    none exists — n is then a single tile, valid as a full-dimension block)."""
    t = min(target, n)
    t -= t % granule
    while t >= granule:
        if n % t == 0:
            return t
        t -= granule
    return n


def supports(qb: int, b: int, a: int) -> bool:
    """Shapes the kernel can tile within Mosaic's constraints + VMEM.

    The transposed segmin output needs tn/SEG sublanes divisible by 8
    (tn % 1024 == 0) unless one tile spans all of b; query tiles must be a
    multiple of 8 (engines pad to 8) and either divide into 128-multiples
    or fit a single full-dim tile small enough for VMEM. The VMEM budget
    covers the double-buffered dist, q, and d blocks (q/d scale with the
    attribute count, so wide-attribute inputs are gated out too).
    """
    if b % SEG != 0 or qb % 8 != 0:
        return False
    tn = _tile(b, _TN, 8 * SEG)
    tq = _tile(qb, _TQ, SEG)
    blocks_bytes = (tq * tn + tq * a + tn * a) * 4
    return 2 * blocks_bytes <= 12 * 2**20  # double-buffered


def _kernel(q_ref, d_ref, qn_ref, dn_ref, ids_ref, dist_ref, segmin_ref,
            *, precision: str = "f32"):
    # HIGHEST precision: default truncates f32 to bf16 on the MXU (1e-2
    # relative distance error measured on v5e — breaks neighbor selection).
    # A "bf16" FIRST PASS casts the operands instead (one MXU pass, f32
    # accumulation kept): every emitted distance then errs by at most
    # engine.finalize.lowp_eps, which the caller must fold into any
    # window/threshold decision fed by this tile.
    q = q_ref[:]
    d = d_ref[:]
    mxu = jax.lax.Precision.HIGHEST
    if precision == "bf16":
        q = q.astype(jnp.bfloat16)  # check: lowp-eps=lowp_eps
        d = d.astype(jnp.bfloat16)  # check: lowp-eps=lowp_eps
        # bf16 operands ARE the single MXU pass; Mosaic rejects an fp32
        # contract precision on them ("Bad lhs type").
        mxu = jax.lax.Precision.DEFAULT
    cross = jax.lax.dot_general(
        q, d, (((1,), (1,)), ((), ())), precision=mxu,
        preferred_element_type=jnp.float32)
    dist = qn_ref[:] + dn_ref[:] - 2.0 * cross
    dist = jnp.maximum(dist, 0.0)
    dist = jnp.where(ids_ref[:] < 0, jnp.inf, dist)
    dist_ref[:] = dist
    tq, tn = dist.shape
    # Segment minima are emitted transposed, (segments, queries): the
    # (tn/SEG, tq) block satisfies Mosaic's (8, 128) tiling where the
    # natural (tq, tn/SEG) layout's tiny lane dimension would not.
    segmin_ref[:] = dist.reshape(tq, tn // SEG, SEG).min(axis=-1).T


@functools.partial(jax.jit, static_argnames=("interpret", "precision"))
def fused_dist_segmin(q_attrs: jax.Array, d_attrs: jax.Array,
                      data_ids: jax.Array, interpret: bool = False,
                      precision: str = "f32"):
    """(queries (Qb, A), data (B, A), ids (B,)) -> (dist (Qb, B) f32,
    segmin (Qb, B/SEG) f32). Sentinel columns (id < 0) give +inf.

    Qb must divide by 8 and B by SEG; A is unconstrained (one MXU pass).
    ``precision`` ("f32" | "bf16", static — resolve OUTSIDE any jit)
    picks the first-pass dot dtype; bf16 distances carry the
    engine.finalize.lowp_eps bound.
    """
    if precision not in ("f32", "bf16"):
        raise ValueError(f"unsupported first-pass precision {precision!r}")
    qb, a = q_attrs.shape
    b = d_attrs.shape[0]
    if not supports(qb, b, a):
        # ValueError, not assert: must fail loudly under ``python -O`` too.
        raise ValueError(f"untileable shape (qb={qb}, b={b}, a={a}); "
                         "gate on supports() first")
    tq = _tile(qb, _TQ, SEG)
    tn = _tile(b, _TN, 8 * SEG)

    q32 = q_attrs.astype(jnp.float32)
    d32 = d_attrs.astype(jnp.float32)
    qn = jnp.sum(q32 * q32, axis=-1, keepdims=True)          # (Qb, 1)
    dn = jnp.sum(d32 * d32, axis=-1)[None, :]                # (1, B)
    ids2 = data_ids[None, :]                                 # (1, B)

    grid = (qb // tq, b // tn)
    dist, segmin_t = pl.pallas_call(
        functools.partial(_kernel, precision=precision),
        name="dmlp_dist_segmin",     # the event's name in a device trace
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, a), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, a), lambda i, j: (j, 0)),
            pl.BlockSpec((tq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, tn), lambda i, j: (0, j)),
            pl.BlockSpec((1, tn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tq, tn), lambda i, j: (i, j)),
            pl.BlockSpec((tn // SEG, tq), lambda i, j: (j, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qb, b), jnp.float32),
            jax.ShapeDtypeStruct((b // SEG, qb), jnp.float32),
        ],
        # HIGHEST-precision dot needs headroom past the default 16M scoped
        # limit at the full (1024, 1024) tile.
        compiler_params=tpu_compiler_params(vmem_limit_bytes=32 * 2**20),
        interpret=interpret,
    )(q32, d32, qn, dn, ids2)
    return dist, segmin_t.T


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpreter mode here: only on the
    CPU backend, where there is nothing to compile them for. A statement
    about the platform, never about whether a compile succeeded — on an
    accelerator the kernels compile natively and a Mosaic error
    propagates to the caller."""
    return jax.default_backend() == "cpu"
