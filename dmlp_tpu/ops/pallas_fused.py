"""Fused distance→top-k streaming megakernel: one HBM pass for the hot path.

This is the public face of the ``mxu_gate`` form of the extraction kernel
(ops.pallas_extract._kernel): one Pallas program computes each distance
tile on the MXU in VMEM and feeds it straight into the running top-k
carry state — the (nq, nd) distance matrix never exists in HBM — and, new
here, the current k-th-best thresholds gate the MXU TILE itself, not just
the extraction scan (the ROADMAP's "block skipping made free"). Per data
block the kernel derives a sound per-row distance lower bound from the
norms it already streams (|q - d|^2 >= (|q| - |d|)^2 over the block's
real |d| range), deflates it by the engines' staging-eps cancellation
margin (engine.finalize.staging_eps constants — the same bound the exact
pipeline already trusts for truncation hazards), and skips the matmul,
the scan, and the scratch store outright when no row's bound beats its
threshold. A gated-out block is provably a block whose extraction would
have inserted nothing, so outputs are BIT-IDENTICAL to the two-pass-era
pipeline (tests/test_pallas_fused.py fuzzes this, skip on/off).

Contrast with the pipeline it replaces where ``supports()`` holds: the
streaming "seg"/"topk" folds materialize every (Qb, B) distance tile to
HBM (ops.pallas_distance.fused_dist_segmin) and the selection re-reads
it — two passes over the dominant term of hot-path HBM traffic. The
analytic model pair ``obs.kernel_cost.fused_topk_cost`` /
``two_pass_equivalent_cost`` quantifies the eliminated write+read.

The tiles are the ungated kernel's (ops.pallas_extract.resolve_variant:
the gate adds per-block scalars and no tiling constraint).

Kill switch: ``DMLP_TPU_FUSED=0`` disables the fused path everywhere
(mirroring ``DMLP_TPU_RESILIENCE``); engines then run the two-pass
extraction kernel — also the rung the OOM degradation ladder steps
down to below "fused" (resilience.degrade: fused -> heuristic ->
streaming -> host).
"""

from __future__ import annotations

import os

import jax

from dmlp_tpu.ops import pallas_extract
from dmlp_tpu.ops.pallas_extract import extract_topk, mxu_passes, supports


def fused_enabled() -> bool:
    """The fused-path kill switch ($DMLP_TPU_FUSED=0 disables) — read
    per call so tests and operators can flip it without re-imports."""
    return os.environ.get("DMLP_TPU_FUSED", "1") != "0"


def variant_stamp(kc: int, b: int, qb: int, a: int,
                  precision: str = "f32",
                  staging: str = "float32") -> dict:
    """The device stamp's ``kernel_variant`` (obs.run.device_stamp):
    the tiles either kernel form runs with at this dispatch shape, the
    list width, and ``mxu_passes``: how many bf16 MXU passes the cross
    term takes a visit at this form over operands staged as
    ``staging`` (ops.pallas_extract.mxu_passes, the test the kernel
    branches on)."""
    return {**pallas_extract.resolve_variant(kc, b, qb, a), "kc": kc,
            "mxu_passes": mxu_passes(precision, staging)}


def fused_topk(q_attrs: jax.Array, d_attrs: jax.Array,
               carry_d: jax.Array | None = None,
               carry_i: jax.Array | None = None, *, n_real,
               id_base=0, kc: int, interpret: bool = False,
               block_skip: bool = True,
               floor: jax.Array | None = None, precision: str = "f32",
               score: str = "l2", with_wide: bool = False):
    """Drop-in for ops.pallas_extract.extract_topk with the MXU tile
    gate on. Same signature, same (dists, ids, iters) outputs (and
    ``wide`` behind them under ``with_wide``), bit-identical results;
    ``iters`` reports 0 for blocks either gate elided.
    ``precision`` ("f32" | "bf16x3" | "bf16") selects the first-pass
    form exactly as in extract_topk — the MXU-gate bound widens by the
    engine.finalize.lowp_eps margin in-kernel, so gating stays sound
    under the low-precision pass. ``score`` ("l2" | "ip") as in
    extract_topk; under "ip" the gate's bound is the inner product's
    (no entry of a block is below -|q| max|d|), deflated in-kernel by
    engine.finalize.ip_coef.

    extract_topk resolves the tiles outside its jit boundary, so the
    concrete fused/two-pass choice AND the concrete tiles are part of
    the jit cache key (the PR 3 in-jit-resolution bug class, lint
    R203). Gate on supports() first.
    """
    return extract_topk(
        q_attrs, d_attrs, carry_d, carry_i, n_real=n_real,
        id_base=id_base, kc=kc, interpret=interpret,
        block_skip=block_skip, mxu_gate=True, floor=floor,
        precision=precision, score=score, with_wide=with_wide)


def resolve_topk_kernel(qb: int, b: int, a: int, kc: int,
                        rung: str = "fused"):
    """The engine-facing selector: (kernel callable, impl label) for one
    extract-path dispatch shape, or (None, None) when neither kernel
    tiles it (callers fall back to the streaming selects).

    Preference order: the fused megakernel when the kill switch allows
    it, the engine's degradation rung is still at or above "fused"
    (the "lowp" and "prune" rungs above it compose the low-precision
    first pass and scan pruning WITH the fused kernel), and the kernel
    tiles the shape; else the two-pass extraction kernel.
    MUST be called OUTSIDE any jitted body (lint R203) and the returned
    label must key every compiled-program cache that bakes the choice
    in — the selection is part of the jit cache key by construction.
    """
    if not supports(qb, b, a, kc):
        return None, None
    if rung in ("lowp", "prune", "fused") and fused_enabled():
        return fused_topk, "fused"
    return extract_topk, "extract"
