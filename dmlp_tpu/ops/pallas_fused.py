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

Variant resolution mirrors ops.pallas_extract but reads the FUSED
namespace of the measured tune cache (``dmlp_tpu.tune``, kernel
="fused_topk"): the fused tile space (tile_q x tile_n x ne x unroll) is
swept separately because the gate changes the operating point — gated
blocks cost one VPU bound pass, so larger data blocks amortize
differently than in the ungated kernel. An absent cache resolves to the
same deterministic heuristic as the ungated kernel (bit-identical CI).

Kill switch: ``DMLP_TPU_FUSED=0`` disables the fused path everywhere
(mirroring ``DMLP_TPU_RESILIENCE``); engines then run the tuned two-pass
extraction kernel — also the first rung the OOM degradation ladder
steps down to (resilience.degrade: fused -> tuned -> heuristic ->
streaming -> host).
"""

from __future__ import annotations

import os

import jax

from dmlp_tpu.ops.pallas_extract import (_TN, _heuristic_variant,
                                         extract_topk, variant_supports)
from dmlp_tpu.ops.pallas_extract import supports as extract_supports

FUSED_KERNEL = "fused_topk"


def fused_enabled() -> bool:
    """The fused-path kill switch ($DMLP_TPU_FUSED=0 disables) — read
    per call so tests and operators can flip it without re-imports."""
    return os.environ.get("DMLP_TPU_FUSED", "1") != "0"


def _resolve_variant(kc: int, b: int, qb: int | None = None,
                     a: int | None = None,
                     precision: str = "f32") -> dict:
    """Fused-namespace variant resolution: the measured tune-cache entry
    for (device kind, bucket(b), bucket(a), kc, precision) under kernel
    "fused_topk" when one exists and still passes the full supports
    gate, else the shared deterministic heuristic — exactly the
    extract kernel's resolution contract, keyed separately because the
    MXU gate shifts which tiles win (and per first-pass precision,
    because the MXU pass count per tile does too)."""
    from dmlp_tpu.tune import lookup_variant
    cached = lookup_variant(kc, b, a=a, kernel=FUSED_KERNEL,
                            precision=precision)
    if cached is not None:
        if qb is None or a is None \
                or variant_supports(qb, b, a, kc, cached):
            return cached
    return _heuristic_variant(kc, b, qb, a)


def resolve_variant(kc: int, b: int, qb: int | None = None,
                    a: int | None = None,
                    precision: str = "f32") -> dict:
    """Public form (spans/artifacts report it): the variant fused_topk
    will run with at this dispatch shape."""
    return dict(_resolve_variant(kc, b, qb, a, precision))


def supports(qb: int, b: int, a: int, kc: int) -> bool:
    """Shapes the fused kernel can tile with ITS resolved variant (same
    tiling/VMEM constraints as the ungated kernel — the gate adds only
    per-block scalars)."""
    return variant_supports(qb, b, a, kc, _resolve_variant(kc, b, qb, a))


def variant_for(impl: str, kc: int, b: int, qb: int | None = None,
                a: int | None = None, precision: str = "f32") -> dict:
    """The variant an ``impl`` label ("fused" | "extract", from
    resolve_topk_kernel) will actually run with at this dispatch shape —
    the one helper engines use for span/artifact reporting, so the
    reported variant always comes from the SAME namespace (and
    precision key axis) the dispatch resolves through."""
    if impl == "fused":
        return resolve_variant(kc, b, qb, a, precision)
    from dmlp_tpu.ops.pallas_extract import resolve_variant as _rv
    return _rv(kc, b, qb, a, precision)


def variant_stamp(impl: str, kc: int, b: int, qb: int, a: int,
                  precision: str = "f32",
                  staging: str = "float32") -> dict:
    """:func:`variant_for` plus where the variant came from — the
    device stamp's ``kernel_variant`` (obs.run.device_stamp): the tiles
    this dispatch runs with, whether a tune-cache FILE (state outside
    the checkout) supplied them rather than the committed heuristic,
    and ``mxu_passes``: how many bf16 MXU passes the cross term takes
    a visit at this form over operands staged as ``staging``
    (ops.pallas_extract.mxu_passes, the test the kernel branches on)."""
    from dmlp_tpu.ops.pallas_extract import mxu_passes
    from dmlp_tpu.tune import lookup_variant
    v = variant_for(impl, kc, b, qb, a, precision)
    cached = lookup_variant(
        kc, b, a=a, precision=precision,
        kernel=FUSED_KERNEL if impl == "fused" else "extract_topk")
    return {**v, "kc": kc, "from_tune_cache": cached == v,
            "mxu_passes": mxu_passes(precision, staging)}


def fused_topk(q_attrs: jax.Array, d_attrs: jax.Array,
               carry_d: jax.Array | None = None,
               carry_i: jax.Array | None = None, *, n_real,
               id_base=0, kc: int, interpret: bool = False,
               block_skip: bool = True,
               floor: jax.Array | None = None, precision: str = "f32"):
    """Drop-in for ops.pallas_extract.extract_topk with the MXU tile
    gate on and variants resolved from the fused tune-cache namespace.
    Same signature, same (dists, ids, iters) outputs, bit-identical
    results; ``iters`` reports 0 for blocks either gate elided.
    ``precision`` ("f32" | "bf16x3" | "bf16") selects the first-pass
    form exactly as in extract_topk — the MXU-gate bound widens by the
    engine.finalize.lowp_eps margin in-kernel, so gating stays sound
    under the low-precision pass.

    The variant resolution happens HERE, outside the jit boundary, so
    the concrete fused/two-pass choice AND the concrete tiles are part
    of the jit cache key (the PR 3 in-jit-resolution bug class, lint
    R203). Gate on supports() first.
    """
    v = _resolve_variant(kc, d_attrs.shape[0], q_attrs.shape[0],
                         q_attrs.shape[1], precision)
    return extract_topk(
        q_attrs, d_attrs, carry_d, carry_i, n_real=n_real,
        id_base=id_base, kc=kc, interpret=interpret,
        tile_q=v["tile_q"], tile_n=v.get("tile_n", _TN), ne=v["ne"],
        unroll=v["unroll"], block_skip=block_skip, mxu_gate=True,
        floor=floor, precision=precision)


def resolve_topk_kernel(qb: int, b: int, a: int, kc: int,
                        rung: str = "fused"):
    """The engine-facing selector: (kernel callable, impl label) for one
    extract-path dispatch shape, or (None, None) when neither kernel
    tiles it (callers fall back to the streaming selects).

    Preference order: the fused megakernel when the kill switch allows
    it, the engine's degradation rung is still at or above "fused"
    (the "lowp" and "prune" rungs above it compose the low-precision
    first pass and scan pruning WITH the fused kernel), and the fused
    variant tiles the shape; else the tuned two-pass extraction kernel.
    MUST be called OUTSIDE any jitted body (lint R203) and the returned
    label must key every compiled-program cache that bakes the choice
    in — the selection is part of the jit cache key by construction.
    """
    if rung in ("lowp", "prune", "fused") and fused_enabled() \
            and supports(qb, b, a, kc):
        return fused_topk, "fused"
    if extract_supports(qb, b, a, kc):
        return extract_topk, "extract"
    return None, None
