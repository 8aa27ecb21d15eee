"""Analytic FLOPs / HBM-bytes models for the Pallas kernels.

XLA's cost analysis returns nothing for ``pallas_call`` programs, so the
flagship extract path reported ``counters_unavailable`` on TPU (ROADMAP
open item). But the kernels' work is a closed-form function of their
dispatch shapes — the grid, tile sizes, and block sweep are all decided
before launch — so this module models each kernel analytically and
:mod:`dmlp_tpu.obs.counters` consults the registry as the resolution
path for these functions (before attempting XLA cost analysis, whose
numbers for an interpret-mode Pallas program would measure the
emulation, not the kernel).

Model scope, per kernel:

- **flops** count the deterministic arithmetic: the MXU cross-term
  matmul (2*Q*B*A — the same convention XLA uses for dot), the norm
  reductions, and the elementwise norm-expansion epilogue. The extract
  kernel's while-loop passes are data-dependent, so by default the model
  is the deterministic lower bound — but the kernel reports its per-tile
  iteration counts, and callers that read them back can pass
  ``iters_total`` to :func:`extract_topk_cost` (or feed
  ``CostProbe.record_measured_iters``) to add the MEASURED extraction
  term (:func:`extract_loop_cost`); the returned dict then carries
  ``extraction_term: "measured"`` instead of ``"modeled_lower_bound"``.
  Both the single-chip engine extract paths AND the mesh engines do
  this whenever a probe is installed: the sharded programs return each
  cell's summed iters through their shard_map fold outputs
  (engine.sharded), so the sharded extraction term is measured too.
- **bytes_accessed** count HBM traffic implied by the BlockSpec sweep:
  each query tile re-reads the data panel and each data block re-reads
  the query panel (Pallas streams blocks from HBM each grid step; only
  the revisited output blocks stay VMEM-resident), plus the outputs.
  Operands are streamed as f32 (both kernels cast on entry).

The distance model's matmul term is validated against XLA's own cost
analysis of the equivalent non-Pallas ``ops.distance`` dispatch
(tests/test_obs_dist.py, 5% tolerance).

Import-light: the ops modules (and hence jax) load only when a cost is
actually resolved.
"""

from __future__ import annotations

from typing import Dict, Optional

__all__ = ["extract_topk_cost", "extract_loop_cost", "fused_topk_cost",
           "two_pass_equivalent_cost", "fused_dist_segmin_cost",
           "summaries_score_cost", "analytic_cost"]

# MXU hardware passes per dot tile by first-pass form
# (ops.pallas_extract.mxu_passes, the test the kernel branches on): the MXU
# multiplies in bf16, so an f32 dot at HIGHEST preferred precision is
# emulated in SIX bf16 product passes (Mosaic's
# ``contract_precision<fp32>``; measured on v5e in PR 36: the dot
# alone, inside a pallas_call with the kernel's BlockSpecs, took
# 13.6 us at a (128, 128) x (12 800, 128) visit and 62.1 us at
# (128, 1024) x (6 400, 1024), 6.4 and 7.3 times one pass at the
# MXU's peak, where every form of three passes or one sat on the
# data block's DMA, 9.2 and 36.1 us: PERF.md section 6), the "bf16x3"
# form (ops.pallas_extract._dot_cross: bf16 halves of both operands)
# issues THREE, and a "bf16" first pass (``precision="bf16"``, f32
# accumulation) ONE. The ``flops`` fields below deliberately do
# NOT scale by this — they keep XLA's dot convention (2*Q*B*A
# regardless of precision) so flops stay comparable across arms and
# history; the pass count is reported alongside as ``mxu_passes`` /
# ``mxu_precision`` for roofline math that wants hardware-issue terms.
# Those are the counts over FLOAT32 blocks; a dispatch whose operands
# both arrive bfloat16 streams the bf16 data block itself and takes
# ONE pass whatever the form (PR 39), and the models below weigh its
# data panel at two bytes a value (``data_dtype`` "bfloat16":
# _operand_dtype).


def extract_loop_cost(qb: int, b: int, a: int, kc: int,
                      iters_total: int, wide_iters: int = 0) -> float:
    """MEASURED extraction-loop FLOPs for ``iters_total`` recorded loop
    iterations (summed over the kernel's (Qb/tq, B/tn) ``iters`` output,
    possibly across many dispatches at the same shape).

    One recorded iteration runs ``unroll`` extraction rounds over one
    (tq, tn) tile. A FULL-WIDTH round does, per ne-quarter of width
    w = tn/ne: the quarter min (tq*w), the argmin iota-select (2*tq*w),
    the mask-out (2*tq*w), and the threshold/insert ops on the (tq, kc)
    lists (~4*tq*kc) — so ~5*tq*tn + 4*ne*tq*kc FLOPs per round. Where
    the shape takes the two-level selection (``fold`` = F slabs:
    ops.pallas_extract.fold_slabs) a round runs over the folded
    (tq, tn/F) array and takes one candidate a row: ~5*tq*tn/F +
    4*tq*kc; only the ``wide_iters`` of the iterations that a visit
    ran at full width (a bucket hid a second candidate: the kernel's
    ``wide`` output says which visits) cost the full-width round. A
    caller that cannot tell them apart passes 0 and gets the lower
    bound. (The fold pass itself is deterministic, a visit: it stands
    in :func:`_streaming_cost`.) ``a`` (the attribute width) does not
    enter the loop arithmetic but DOES enter variant resolution (the
    row width picks tile_n), so it must match the dispatch. Both
    kernel forms run the same tiles."""
    from dmlp_tpu.ops.pallas_distance import _tile
    from dmlp_tpu.ops.pallas_extract import _TN, resolve_variant

    v = resolve_variant(kc, b, qb, a)
    tq = _tile(qb, v["tile_q"], 8)
    tn = _tile(b, v.get("tile_n", _TN), 128 * v["ne"])
    wide_round = 5.0 * tq * tn + 4.0 * v["ne"] * tq * kc
    fold = v.get("fold", 0)
    if not fold:
        wide_iters = iters_total
    narrow_round = 5.0 * tq * (tn // fold) + 4.0 * tq * kc if fold else 0.0
    return v.get("unroll", 1) * (
        float(wide_iters) * wide_round
        + float(iters_total - wide_iters) * narrow_round)


def _streaming_cost(qb: int, b: int, a: int, kc: int,
                    data_dtype: str = "float32") -> Dict[str, float]:
    """The SHARED deterministic model of one streaming top-k dispatch
    (the (qb, b) distance tile lives only in VMEM): flops + HBM bytes
    at the tiles resolved for this shape. One body for both kernels —
    the fused megakernel adds only its gate term on top — so a future
    fix to any shared term cannot drift between the two models. The
    first-pass precision does not change the modeled flops/bytes (the
    in-VMEM cast is free of HBM traffic). ``data_dtype`` does: the
    kernel streams float32 blocks (a converted copy, for operands of
    mixed dtypes) unless both operands arrive bfloat16, and then the
    data panel, the term that dominates, weighs half (the query panel
    stays float32)."""
    from dmlp_tpu.ops.pallas_distance import _tile
    from dmlp_tpu.ops.pallas_extract import _TN, resolve_variant

    v = resolve_variant(kc, b, qb, a)
    tq = _tile(qb, v["tile_q"], 8)
    tn = _tile(b, v.get("tile_n", _TN), 128 * v["ne"])
    # the block-skip prefilter: one VPU min pass, or, where the
    # selection is two-level, the fold pass that stands in for it
    # (compare, two selects, max, min an element)
    prefilter = 5.0 if v.get("fold", 0) else 1.0
    flops = (2.0 * qb * b * a      # MXU cross-term block
             + 2.0 * (qb + b) * a  # |q|^2 / |d|^2 norm reductions
             + 3.0 * qb * b        # expansion (two adds) + clamp; the
             #                       sentinel mask rides in the norm row
             + prefilter * qb * b)
    width = 2.0 if data_dtype == "bfloat16" else 4.0
    byts = width * (qb // tq) * b * a   # data panel, once per query tile
    byts += 4.0 * ((b // tn) * qb * a   # query panel, once per data block
                  + (qb // tq) * b      # dn row, once per query tile
                  + (b // tn) * qb      # qn column, once per data block
                  + 2 * qb * kc         # running (dists, ids) lists out
                  + qb // tq * (b // tn))  # iteration diagnostics
    return {"flops": flops, "bytes_accessed": byts,
            "tq": tq, "tn": tn}


def extract_topk_cost(qb: int, b: int, a: int, kc: int,
                      iters_total: Optional[int] = None,
                      precision: str = "f32",
                      data_dtype: str = "float32") -> Dict[str, float]:
    """Cost of one ``ops.pallas_extract.extract_topk`` dispatch at
    (queries (qb, a), data (b, a), list width kc). Without
    ``iters_total`` the data-dependent while-loop is excluded
    (deterministic lower bound); with it, the measured extraction term
    (:func:`extract_loop_cost`) is added and the dict says so.
    ``precision`` ("f32" | "bf16x3" | "bf16") is reported back with
    its MXU pass count
    (ops.pallas_extract.mxu_passes: one where the operands arrive as
    ``data_dtype`` "bfloat16") —
    ``flops`` itself keeps the precision-independent dot convention."""
    from dmlp_tpu.ops.pallas_extract import mxu_passes
    base = _streaming_cost(qb, b, a, kc, data_dtype=data_dtype)
    out = {"flops": base["flops"], "bytes_accessed": base["bytes_accessed"],
           "extraction_term": "modeled_lower_bound",
           "mxu_precision": precision,
           "mxu_passes": mxu_passes(precision, data_dtype)}
    if iters_total is not None:
        out["flops"] += extract_loop_cost(qb, b, a, kc, iters_total)
        out["extraction_term"] = "measured"
        out["extract_iters_total"] = int(iters_total)
    return out


def fused_topk_cost(qb: int, b: int, a: int, kc: int,
                    iters_total: Optional[int] = None,
                    precision: str = "f32",
                    data_dtype: str = "float32") -> Dict[str, float]:
    """Cost of one ``ops.pallas_fused.fused_topk`` dispatch — the fused
    distance→top-k streaming megakernel. Same one-pass HBM structure as
    :func:`extract_topk_cost` (the (qb, b) distance tile lives only in
    VMEM), at the same tiles, with the per-block norm-bound MXU gate
    added to the deterministic FLOPs
    (one VPU pass over the block's dn row + a per-row bound: the price
    of being able to skip the matmul outright).

    The dict also quantifies what the fusion ELIMINATES: the two-pass
    pipeline's HBM write+read of the full (qb, b) distance matrix
    (:func:`two_pass_equivalent_cost`), as
    ``hbm_bytes_two_pass_equiv`` / ``hbm_bytes_saved_vs_two_pass`` /
    ``hbm_traffic_reduction_x`` — the ROADMAP's "one HBM pass for the
    whole hot path" claim as a checked number, not prose. Both sides of
    that delta run the same tiles, so the saved bytes are EXACTLY the
    2·4·qb·b distance round-trip. ``precision`` reports its MXU pass
    count; ``flops`` stays convention-stable.
    """
    from dmlp_tpu.ops.pallas_extract import mxu_passes
    base = _streaming_cost(qb, b, a, kc, data_dtype=data_dtype)
    tq, tn = base["tq"], base["tn"]
    flops = (base["flops"]
             # The MXU gate itself, per (tq, tn) grid cell: ~3 block
             # reductions over the dn row + ~8 scalar ops per query row
             # for the (|q|-|d|)^2 bound and its eps deflation. (The
             # cross-term block above is an upper bound: gated-out
             # blocks skip the matmul entirely.)
             + (qb // tq) * (b // tn) * (3.0 * tn + 8.0 * tq))
    byts = base["bytes_accessed"]
    tp = two_pass_equivalent_cost(qb, b, a, kc, data_dtype=data_dtype)
    out: Dict[str, float] = {
        "flops": flops, "bytes_accessed": byts,
        "extraction_term": "modeled_lower_bound",
        "mxu_precision": precision,
        "mxu_passes": mxu_passes(precision, data_dtype),
        "hbm_bytes_two_pass_equiv": tp["bytes_accessed"],
        "hbm_bytes_saved_vs_two_pass": tp["bytes_accessed"] - byts,
        "hbm_traffic_reduction_x": round(tp["bytes_accessed"] / byts, 2),
    }
    if iters_total is not None:
        out["flops"] += extract_loop_cost(qb, b, a, kc, iters_total)
        out["extraction_term"] = "measured"
        out["extract_iters_total"] = int(iters_total)
    return out


def two_pass_equivalent_cost(qb: int, b: int, a: int, kc: int,
                             data_dtype: str = "float32"
                             ) -> Dict[str, float]:
    """What the SAME dispatch costs when the (qb, b) distance matrix
    round-trips HBM between a distance kernel and a selection pass —
    the pre-fused hot path's two passes over its dominant term:
    everything the streaming kernel reads anyway, PLUS one full write
    and one full re-read of the f32 distance tile. The streaming base
    is the fused model's own, so its ``hbm_bytes_saved_vs_two_pass`` is
    exactly the round-trip delta by construction."""
    base = _streaming_cost(qb, b, a, kc, data_dtype=data_dtype)
    return {"flops": base["flops"],
            "bytes_accessed": base["bytes_accessed"]
            + 4.0 * 2.0 * qb * b}


def fused_dist_segmin_cost(qb: int, b: int, a: int) -> Dict[str, float]:
    """Deterministic cost of one ``ops.pallas_distance.fused_dist_segmin``
    dispatch: the distance tile is written to HBM (unlike extract) plus
    one 128-wide segment-min pass while the block is in VMEM."""
    from dmlp_tpu.ops.pallas_distance import _TN, _TQ, SEG, _tile

    tq = _tile(qb, _TQ, SEG)
    tn = _tile(b, _TN, 8 * SEG)
    flops = (2.0 * qb * b * a
             + 2.0 * (qb + b) * a
             + 4.0 * qb * b        # expansion + clamp + sentinel mask
             + 1.0 * qb * b)       # segment-min reduction
    byts = 4.0 * ((qb // tq) * b * a
                  + (b // tn) * qb * a
                  + (qb // tq) * 2 * b   # dn + ids rows, per query tile
                  + (b // tn) * qb       # qn column, per data block
                  + qb * b               # the (Qb, B) distance tile out
                  + qb * (b // SEG))     # the transposed segmin out
    return {"flops": flops, "bytes_accessed": byts}


def summaries_score_cost(qb: int, nblocks: int, a: int
                         ) -> Dict[str, float]:
    """Deterministic cost of one ``ops.summaries.score_blocks``
    dispatch (the pruned two-stage solve's per-batch scoring pass over
    the resident block summaries): per (query, block) the norm-band
    bound (~6 ops), the box gap + farthest-corner reductions (~6*a),
    and the threshold accumulation's sort/cumsum (~log2(B) per entry).
    Bytes are the summaries + queries in, the (B,) mask out — the
    whole point is that this is O(blocks * a), not O(corpus)."""
    import math
    logb = max(math.ceil(math.log2(max(nblocks, 2))), 1)
    flops = (2.0 * qb * a                       # query norms
             + qb * nblocks * (6.0 * a + 6.0)   # box + band bounds
             + qb * nblocks * (logb + 4.0))     # sort/cumsum/threshold
    byts = 4.0 * (qb * a                        # query panel
                  + nblocks * (2.0 * a + 3.0)   # boxes + bands + counts
                  + 3.0 * qb * nblocks          # lb/ub/order temps
                  + nblocks)                    # survivor mask out
    return {"flops": flops, "bytes_accessed": byts}


def _operand_dtype(leaves) -> str:
    """"bfloat16" where the dispatch's query and data operands BOTH
    arrive bfloat16 (the kernel then streams the bf16 rows), else
    "float32": extract_topk's own test."""
    both = all(str(getattr(x, "dtype", "")) == "bfloat16"
               for x in leaves[:2])
    return "bfloat16" if both else "float32"


def _extract_entry(specs, statics) -> Optional[Dict[str, float]]:
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(specs)
        (qb, a), (b, _) = leaves[0].shape, leaves[1].shape
        kc = int(statics["kc"])
    except Exception:
        return None
    return extract_topk_cost(qb, b, a, kc,
                             precision=str(statics.get("precision",
                                                       "f32")),
                             data_dtype=_operand_dtype(leaves))


def _fused_entry(specs, statics) -> Optional[Dict[str, float]]:
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(specs)
        (qb, a), (b, _) = leaves[0].shape, leaves[1].shape
        kc = int(statics["kc"])
    except Exception:
        return None
    return fused_topk_cost(qb, b, a, kc,
                           precision=str(statics.get("precision",
                                                     "f32")),
                           data_dtype=_operand_dtype(leaves))


def _segmin_entry(specs, statics) -> Optional[Dict[str, float]]:
    del statics
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(specs)
        (qb, a), (b, _) = leaves[0].shape, leaves[1].shape
    except Exception:
        return None
    return fused_dist_segmin_cost(qb, b, a)


def _score_entry(specs, statics) -> Optional[Dict[str, float]]:
    del statics
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(specs)
        (qb, a) = leaves[0].shape          # q (qpad, a)
        (nblocks,) = leaves[3].shape       # counts (B,)
    except Exception:
        return None
    return summaries_score_cost(qb, nblocks, a)


def analytic_cost(fn, specs, statics: Optional[dict] = None
                  ) -> Optional[Dict[str, float]]:
    """The registered analytic cost of one dispatch of ``fn`` at the
    recorded shape specs, or None when ``fn`` has no model (the caller
    then falls through to XLA cost analysis). Never raises."""
    try:
        from dmlp_tpu.ops import pallas_distance, pallas_extract, \
            pallas_fused, summaries
        models = {
            id(pallas_extract.extract_topk): _extract_entry,
            id(pallas_fused.fused_topk): _fused_entry,
            id(pallas_distance.fused_dist_segmin): _segmin_entry,
            id(summaries.score_blocks): _score_entry,
        }
        entry = models.get(id(fn))
        if entry is None:
            return None
        return entry(specs, dict(statics or {}))
    except Exception:
        return None
