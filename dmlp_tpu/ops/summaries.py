"""Per-block coarse summaries + sound bound-based scan pruning.

Stage 0/1 of the pruned two-stage solve (ROADMAP "Beyond-HBM corpus").
The fused megakernel (PR 8) made the hot path one HBM pass, but every
solve still *scans the entire corpus*: on a beyond-HBM corpus the
host->device streaming of never-competitive chunks dominates wall
clock. This module proves most blocks cannot enter the top-k *before
their bytes ever move*:

- **Stage 0 (build)** — :func:`build_summaries`: per extract-chunk-
  aligned block, the row-norm band [min |x|, max |x|] and the
  per-attribute coordinate bounding box [lo_a, hi_a]. O(n*a) once at
  staging (batch) or ingest (serve), O(blocks * a) to keep — tiny
  next to the corpus, so serving keeps them device-resident
  (:func:`stage_summaries`) while the corpus itself may live in host
  DRAM.
- **Stage 1 (prune)** — :func:`prune_mask` (host f64, the batch
  engines) / :func:`score_blocks` (jitted f32 over the resident
  summaries, the serving engine): a sound per-(query, block) distance
  LOWER bound — ``max(norm-band, box)`` with
  ``|q - x|^2 >= (|q| - |x|)^2`` and the kd-tree box gap — is compared
  against a per-query UPPER bound on the k-th-best distance, obtained
  by accumulating per-block *upper* bounds (farthest box corner ∩
  norm sum) in ascending order until >= k real rows are covered: at
  least k points provably sit within that radius, so it dominates the
  true k-th distance. A block is pruned only when its lower bound
  clears the threshold by MORE than the staging-eps margin
  (:func:`dmlp_tpu.engine.finalize.staging_eps` — the same calibrated
  bound the exact pipeline already trusts for truncation hazards),
  which covers every staging-dtype/f32 perturbation on either side of
  the comparison. Soundness over threshold-tightness: a pruned block
  provably holds no row of any query's true float64 top-k (strict
  inequality, so (dist asc, id desc) tie-breaks cannot resurrect one),
  hence the survivors-only exact stage — candidates -> f64 finalize ->
  boundary repair, all unchanged — stays byte-identical to the dense
  scan and to the golden oracle.

The threshold accumulation subsumes single-seed-block seeding (the
minimum over any one block's upper bound is one term of the running
min); the serving engine still reports its cross-request winner
histogram's hottest block as ``seed_block`` so operators can see which
block anchors the threshold.

Kill switch: ``DMLP_TPU_PRUNE=0`` disables pruning everywhere
(mirroring ``DMLP_TPU_FUSED``); the engines additionally gate on the
resilience ladder's top ``lowp``/``prune`` rungs (resilience.degrade)
and on exact mode — fast mode's output IS the device ordering and has no
repair backstop, so it always scans densely.

Import-light: jax loads only when the device scorer is actually used.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from dmlp_tpu.engine.finalize import lowp_eps, staging_eps

#: sub-block pieces per block (median split on the max-spread
#: attribute). Whole-block boxes go VACUOUS on uniform corpora — every
#: block's box is the full cube, every box gap is 0, every norm band
#: straddles the query norm — so block-level pruning is geometrically
#: impossible there. Two pieces make each box a half-cube: queries in
#: the other half see a strictly positive gap, and the per-piece
#: (count, upper-bound) entries sharpen the k-th threshold
#: accumulation. 2 (not 4/8) keeps the summary footprint ~3x the
#: whole-block one while already breaking the vacuous regime; the
#: device scorer (score_blocks) deliberately stays whole-block — the
#: serving micro-batch path is latency-bound on the scoring jit, and
#: whole-block bounds are a sound (merely looser) fallback.
PIECES = 2

#: host-scoring block chunk (blocks per vectorized slab): bounds the
#: (Q, chunk, A) f64 temp at ~tens of MB for bench-scale query counts
_SCORE_BLOCK_CHUNK = 128


def prune_enabled() -> bool:
    """The prune-path kill switch ($DMLP_TPU_PRUNE=0 disables) — read
    per call so tests and operators can flip it without re-imports."""
    return os.environ.get("DMLP_TPU_PRUNE", "1") != "0"


@dataclasses.dataclass
class BlockSummaries:
    """Coarse per-block summaries over contiguous global row ranges.

    ``ranges[b] = (lo, hi)`` is block b's real-row span (hi <= n; empty
    blocks carry count 0 and can never survive pruning). Norms are L2
    (not squared); boxes are closed per-attribute intervals. All f64 —
    the bounds must dominate the golden model's float64 distances.
    """

    ranges: List[Tuple[int, int]]
    counts: np.ndarray        # (B,)   int64 real rows per block
    nmin: np.ndarray          # (B,)   f64 min row norm (+inf if empty)
    nmax: np.ndarray          # (B,)   f64 max row norm (-inf if empty)
    lo: np.ndarray            # (B, A) f64 box lower (+inf if empty)
    hi: np.ndarray            # (B, A) f64 box upper (-inf if empty)
    # Optional 2-piece split summaries (PIECES; None = whole-block
    # only, the pre-split format — every consumer falls back):
    pcounts: Optional[np.ndarray] = None  # (B, P)    int64 rows/piece
    pnmin: Optional[np.ndarray] = None    # (B, P)    f64 min piece norm
    pnmax: Optional[np.ndarray] = None    # (B, P)    f64 max piece norm
    plo: Optional[np.ndarray] = None      # (B, P, A) f64 piece box lower
    phi: Optional[np.ndarray] = None      # (B, P, A) f64 piece box upper
    # Per-block norm median (L2, not squared) + the EXACT count of rows
    # at or below it — a disjoint (near-half, farther-half) norm split
    # that tightens the k-th threshold independently of the box split:
    nq50: Optional[np.ndarray] = None     # (B,) f64 (+inf if empty)
    nq50_cnt: Optional[np.ndarray] = None  # (B,) int64 rows with
    #                                        norm <= nq50

    @property
    def n_blocks(self) -> int:
        return len(self.ranges)

    @property
    def nbytes(self) -> int:
        base = (self.counts.nbytes + self.nmin.nbytes + self.nmax.nbytes
                + self.lo.nbytes + self.hi.nbytes)
        for extra in (self.pcounts, self.pnmin, self.pnmax, self.plo,
                      self.phi, self.nq50, self.nq50_cnt):
            if extra is not None:
                base += extra.nbytes
        return base


def summarize_rows(rows: np.ndarray, na: int):
    """(count, nmin, nmax, lo, hi) of one block's real rows — the ONE
    reduction both the full build and the per-block ingest rebuild run,
    so they cannot drift."""
    m = rows.shape[0]
    if m == 0:
        return 0, np.inf, -np.inf, np.full(na, np.inf), np.full(na, -np.inf)
    r = np.asarray(rows, np.float64)
    norms = np.sqrt(np.einsum("ia,ia->i", r, r))
    return (m, float(norms.min()), float(norms.max()),
            r.min(axis=0), r.max(axis=0))


def split_rows(rows: np.ndarray, na: int):
    """Piece-level summaries of one block: a median split on the
    max-spread attribute (the kd-tree step that costs one O(m) pass),
    plus the norm median and its EXACT cover count.

    Returns ``(pieces, nq50, nq50_cnt)`` where ``pieces`` is a PIECES-
    list of summarize_rows tuples. Any partition of the rows is sound
    (piece bounds only ever describe real rows of the piece), so the
    degenerate split — every row equal on the chosen attribute — just
    halves by position. Empty blocks yield empty pieces."""
    r = np.asarray(rows, np.float64)
    m = r.shape[0]
    if m == 0:
        empty = summarize_rows(r, na)
        return [empty] * PIECES, np.inf, 0
    norms = np.sqrt(np.einsum("ia,ia->i", r, r))
    nq50 = float(np.quantile(norms, 0.5))
    nq50_cnt = int((norms <= nq50).sum())
    spread = r.max(axis=0) - r.min(axis=0)
    ax = int(np.argmax(spread))
    left = r[:, ax] <= float(np.median(r[:, ax]))
    if left.all() or not left.any():
        left = np.arange(m) < (m // 2)
    pieces = [summarize_rows(r[left], na), summarize_rows(r[~left], na)]
    return pieces, nq50, nq50_cnt


def build_summaries(attrs: np.ndarray,
                    ranges: Sequence[Tuple[int, int]],
                    pieces: int = PIECES) -> BlockSummaries:
    """Stage 0: summaries for ``attrs`` over ``ranges`` (one O(n*a)
    pass; blocks whose span is empty or past the data end count 0).
    ``pieces`` <= 1 builds the whole-block-only format (pre-split
    consumers, and A/B baselines for the split's win).

    ``attrs`` is NOT cast wholesale: a beyond-HBM corpus is held f32 on
    host precisely because an f64 copy would double host memory
    (tools/capacity_beyond_hbm.py), so only the per-block slice inside
    summarize_rows pays the f64 conversion — O(block_rows * a) extra,
    never O(n * a)."""
    attrs = np.asarray(attrs)
    n, na = attrs.shape if attrs.ndim == 2 else (0, 1)
    nb = len(ranges)
    counts = np.zeros(nb, np.int64)
    nmin = np.full(nb, np.inf)
    nmax = np.full(nb, -np.inf)
    lo = np.full((nb, na), np.inf)
    hi = np.full((nb, na), -np.inf)
    split = pieces > 1
    pcounts = np.zeros((nb, PIECES), np.int64) if split else None
    pnmin = np.full((nb, PIECES), np.inf) if split else None
    pnmax = np.full((nb, PIECES), -np.inf) if split else None
    plo = np.full((nb, PIECES, na), np.inf) if split else None
    phi = np.full((nb, PIECES, na), -np.inf) if split else None
    nq50 = np.full(nb, np.inf) if split else None
    nq50_cnt = np.zeros(nb, np.int64) if split else None
    for b, (blo, bhi) in enumerate(ranges):
        blo, bhi = max(blo, 0), min(bhi, n)
        rows = attrs[blo:bhi]
        counts[b], nmin[b], nmax[b], lo[b], hi[b] = summarize_rows(
            rows, na)
        if split:
            pc, nq50[b], nq50_cnt[b] = split_rows(rows, na)
            for p, (cm, cn, cx, cl, ch) in enumerate(pc):
                pcounts[b, p], pnmin[b, p], pnmax[b, p] = cm, cn, cx
                plo[b, p], phi[b, p] = cl, ch
    return BlockSummaries(list((int(a), int(b)) for a, b in ranges),
                          counts, nmin, nmax, lo, hi,
                          pcounts, pnmin, pnmax, plo, phi,
                          nq50, nq50_cnt)


def update_block(summ: BlockSummaries, b: int, rows: np.ndarray,
                 lo_hi: Optional[Tuple[int, int]] = None) -> None:
    """Rebuild exactly block ``b`` from its CURRENT real rows (the
    serving ingest path: a ``dynamic_update_slice`` row append must
    invalidate/rebuild the touched blocks' summaries — a stale summary
    is silent unsoundness, the one failure mode pruning cannot repair
    after the fact). Piece summaries (when the format carries them)
    rebuild in the same call, for the same reason."""
    if lo_hi is not None:
        summ.ranges[b] = (int(lo_hi[0]), int(lo_hi[1]))
    na = summ.lo.shape[1]
    rows = np.asarray(rows, np.float64)
    (summ.counts[b], summ.nmin[b], summ.nmax[b],
     summ.lo[b], summ.hi[b]) = summarize_rows(rows, na)
    if summ.pcounts is not None:
        pc, summ.nq50[b], summ.nq50_cnt[b] = split_rows(rows, na)
        for p, (cm, cn, cx, cl, ch) in enumerate(pc):
            summ.pcounts[b, p], summ.pnmin[b, p], summ.pnmax[b, p] = \
                cm, cn, cx
            summ.plo[b, p], summ.phi[b, p] = cl, ch


def block_bounds(queries: np.ndarray, summ: BlockSummaries
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-(query, block) distance bounds, f64: ``lb[q, b]`` a LOWER
    bound on the squared distance from query q to ANY real row of
    block b (max of the norm-band and box-gap bounds), ``ub[q, b]`` an
    UPPER bound on the squared distance to EVERY real row (min of the
    farthest-box-corner and norm-sum bounds; +inf for empty blocks).
    Chunked over blocks so the (Q, chunk, A) temp stays bounded."""
    q = np.asarray(queries, np.float64)
    nq = q.shape[0]
    nb = summ.n_blocks
    qnorm = np.sqrt(np.einsum("qa,qa->q", q, q))
    lb = np.empty((nq, nb))
    ub = np.empty((nq, nb))
    chunk = _SCORE_BLOCK_CHUNK
    for b0 in range(0, nb, chunk):
        b1 = min(b0 + chunk, nb)
        nmin, nmax = summ.nmin[b0:b1], summ.nmax[b0:b1]
        band = np.maximum(nmin[None, :] - qnorm[:, None],
                          qnorm[:, None] - nmax[None, :])
        lbn = np.square(np.maximum(band, 0.0))
        dlo = summ.lo[None, b0:b1] - q[:, None, :]
        dhi = q[:, None, :] - summ.hi[None, b0:b1]
        gap = np.maximum(np.maximum(dlo, dhi), 0.0)
        lbb = np.einsum("qba,qba->qb", gap, gap)
        lb[:, b0:b1] = np.maximum(lbn, lbb)
        far = np.maximum(np.abs(q[:, None, :] - summ.lo[None, b0:b1]),
                         np.abs(q[:, None, :] - summ.hi[None, b0:b1]))
        ubb = np.einsum("qba,qba->qb", far, far)
        ub[:, b0:b1] = np.minimum(
            ubb, np.square(qnorm[:, None] + nmax[None, :]))
    empty = summ.counts <= 0
    lb[:, empty] = np.inf
    ub[:, empty] = np.inf
    return lb, ub


def piece_bounds(queries: np.ndarray, summ: BlockSummaries
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-(query, block, piece) bounds, f64: the block_bounds formulas
    over the PIECE norm bands / boxes. ``plb[q, b, p]`` lower-bounds
    the squared distance to any real row of piece p, ``pub`` upper-
    bounds it to every row (+inf for empty pieces). On a uniform
    corpus the whole-block gap is identically 0 while the half-cube
    piece gap is positive for every query in the other half — the
    non-vacuity the split buys. Requires the split format
    (``summ.pcounts is not None``)."""
    q = np.asarray(queries, np.float64)
    nq_ = q.shape[0]
    nb = summ.n_blocks
    npieces = summ.pcounts.shape[1]
    qnorm = np.sqrt(np.einsum("qa,qa->q", q, q))
    plb = np.empty((nq_, nb, npieces))
    pub = np.empty((nq_, nb, npieces))
    # Same chunking as block_bounds, halved: the (Q, chunk, P, A) temp
    # is P times the whole-block slab.
    chunk = max(1, _SCORE_BLOCK_CHUNK // npieces)
    for b0 in range(0, nb, chunk):
        b1 = min(b0 + chunk, nb)
        nmin, nmax = summ.pnmin[b0:b1], summ.pnmax[b0:b1]   # (c, P)
        band = np.maximum(nmin[None] - qnorm[:, None, None],
                          qnorm[:, None, None] - nmax[None])
        lbn = np.square(np.maximum(band, 0.0))
        dlo = summ.plo[None, b0:b1] - q[:, None, None, :]
        dhi = q[:, None, None, :] - summ.phi[None, b0:b1]
        gap = np.maximum(np.maximum(dlo, dhi), 0.0)
        lbb = np.einsum("qbpa,qbpa->qbp", gap, gap)
        plb[:, b0:b1] = np.maximum(lbn, lbb)
        far = np.maximum(
            np.abs(q[:, None, None, :] - summ.plo[None, b0:b1]),
            np.abs(q[:, None, None, :] - summ.phi[None, b0:b1]))
        ubb = np.einsum("qbpa,qbpa->qbp", far, far)
        pub[:, b0:b1] = np.minimum(
            ubb, np.square(qnorm[:, None, None] + nmax[None]))
    emptyp = summ.pcounts <= 0
    plb[:, emptyp] = np.inf
    pub[:, emptyp] = np.inf
    return plb, pub


def kth_thresholds(ub: np.ndarray, counts: np.ndarray,
                   ks: np.ndarray) -> np.ndarray:
    """Per-query upper bound on the true k-th-best squared distance:
    accumulate block upper bounds ascending until >= k real rows are
    covered — at least k points then provably sit within the last
    accumulated bound. +inf when the corpus holds fewer than k rows
    (nothing may be pruned: every real point is in the top-k)."""
    ks = np.asarray(ks, np.int64)
    order = np.argsort(ub, axis=1, kind="stable")
    sub = np.take_along_axis(ub, order, axis=1)
    csum = np.cumsum(np.asarray(counts, np.int64)[order], axis=1)
    reached = csum >= ks[:, None]
    idx = np.argmax(reached, axis=1)
    thr = np.take_along_axis(sub, idx[:, None], axis=1)[:, 0]
    return np.where(reached.any(axis=1), thr, np.inf)


def prune_mask(queries: np.ndarray, ks: np.ndarray,
               summ: BlockSummaries, *, staging: str = "float32",
               precision: str = "f32") -> Tuple[np.ndarray, Dict]:
    """Stage 1 on host (f64): the survivor mask over ``summ``'s blocks
    for this query batch, plus a stats record.

    Block b is pruned iff for EVERY query q
    ``lb(q, b) > thr(q) + eps(q)`` — strictly above the k-th-best
    upper bound widened by the staging-eps margin
    (engine.finalize.staging_eps, evaluated at the threshold), which
    dominates both the f64 rounding of the bound arithmetic and the
    staging-dtype/f32 perturbation of any distance the exact stage
    will later compare. A first pass that drops products ("bf16x3",
    the exact engines' form; "bf16" on the "lowp" rung)
    additionally widens eps by its finalize.lowp_eps bound: the
    survivor scan's device distances then err by cast + staging, and a
    pruned block must clear both. By construction at least one block
    survives per query with a finite threshold (the block/piece
    anchoring the threshold bounds itself), so a schedule is never
    empty.

    With the split format, three INDEPENDENTLY sound k-th thresholds
    combine by elementwise min — block-level, per-piece, and the
    per-block norm split ((nq50_cnt rows within (|q| + nq50)^2, the
    rest within the block ub); each accumulates DISJOINT row groups,
    which the accumulation requires (overlapping groups would double-
    count coverage) — and the block lower bound sharpens to the max of
    the whole-box bound and the min over its pieces' bounds.
    """
    q = np.asarray(queries, np.float64)
    na = q.shape[1]
    lb, ub = block_bounds(q, summ)
    thr = kth_thresholds(ub, summ.counts, ks)
    plb = None
    if summ.pcounts is not None:
        plb, pub = piece_bounds(q, summ)
        lb = np.maximum(lb, plb.min(axis=2))
        thr = np.minimum(thr, kth_thresholds(
            pub.reshape(len(q), -1), summ.pcounts.reshape(-1), ks))
        qnorm = np.sqrt(np.einsum("qa,qa->q", q, q))
        near = np.square(qnorm[:, None] + summ.nq50[None, :])
        thr = np.minimum(thr, kth_thresholds(
            np.concatenate([near, ub], axis=1),
            np.concatenate([summ.nq50_cnt,
                            summ.counts - summ.nq50_cnt]), ks))
    live = summ.counts > 0
    dn_max = float(np.square(summ.nmax[live]).max()) if live.any() else 0.0
    qn = np.einsum("qa,qa->q", q, q)
    eps = staging_eps(thr, qn, dn_max, staging, na) \
        + lowp_eps(precision, qn, dn_max)
    keep = lb <= (thr + eps)[:, None]
    survivors = live & keep.any(axis=0)
    total = int(live.sum())
    pruned = int(total - int((survivors & live).sum()))
    stats = {
        "blocks_total": total,
        "blocks_pruned": pruned,
        "pruned_fraction": round(pruned / total, 6) if total else 0.0,
        "summary_bytes": int(summ.nbytes),
    }
    if plb is not None:
        # Non-vacuity meter of the split: fraction of (query, live
        # piece) pairs whose lower bound is strictly positive. On a
        # uniform corpus the whole-block version of this is provably
        # 0.0 (full-cube boxes, straddled norm bands); the half-cube
        # pieces keep it > 0, which tests/test_prune assert.
        livep = (summ.pcounts > 0).reshape(-1)
        flat = plb.reshape(len(q), -1)[:, livep]
        stats["lb_positive_fraction"] = (
            round(float((flat > 0.0).mean()), 6) if flat.size else 0.0)
    return survivors, stats


# -- device scoring (the serving engine's resident-summary pass) --------------

def stage_summaries(summ: BlockSummaries):
    """Stage conservative f32 copies of the summaries to device (tiny:
    O(blocks * a)). Directed rounding keeps the cast sound: box lows
    and norm minima round DOWN, box highs and norm maxima round UP, so
    the f32 box/band always CONTAINS the f64 one — the device lower
    bounds can only get looser, never unsound; the residual f32
    arithmetic error of the scorer itself is the eps margin's job."""
    import jax

    def _dir(x, up: bool):
        x32 = np.asarray(x, np.float32)
        back = x32.astype(np.float64)
        bad = (back < x) if up else (back > x)
        adj = np.nextafter(x32, np.float32(np.inf if up else -np.inf))
        return np.where(bad, adj, x32).astype(np.float32)

    live = summ.counts > 0
    dn_max = float(np.square(summ.nmax[live]).max()) if live.any() else 0.0
    return {
        "counts": jax.device_put(np.asarray(summ.counts, np.int32)),
        "nmin": jax.device_put(_dir(summ.nmin, up=False)),
        "nmax": jax.device_put(_dir(summ.nmax, up=True)),
        "lo": jax.device_put(_dir(summ.lo, up=False)),
        "hi": jax.device_put(_dir(summ.hi, up=True)),
        "dn_max": jax.device_put(_dir(np.float64(dn_max), up=True)),
    }


_score_jit = None


def score_blocks(q, qvalid, ks, counts, nmin, nmax, lo, hi, dn_max,
                 eps_rel, eps_cancel):
    """Stage 1 on device (jitted, f32): the survivor mask over the
    RESIDENT summaries for one padded micro-batch — the serving
    engine's per-request scoring pass, compiled once per (qpad,
    blocks) bucket shape. Same bound/threshold/eps structure as
    :func:`prune_mask`; ``qvalid`` masks bucket-padding queries out of
    the survivor union, ``eps_rel`` / ``eps_cancel`` are the
    staging-eps constants pre-scaled on host (rel and
    EPS_CANCEL_COEF * (na + 2)). Returns the (B,) bool survivor mask.
    """
    global _score_jit
    if _score_jit is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _score(q, qvalid, ks, counts, nmin, nmax, lo, hi, dn_max,
                   eps_rel, eps_cancel):
            q32 = q.astype(jnp.float32)
            qn = jnp.einsum("qa,qa->q", q32, q32)
            qnorm = jnp.sqrt(qn)
            band = jnp.maximum(nmin[None, :] - qnorm[:, None],
                               qnorm[:, None] - nmax[None, :])
            lbn = jnp.square(jnp.maximum(band, 0.0))
            gap = jnp.maximum(jnp.maximum(lo[None] - q32[:, None, :],
                                          q32[:, None, :] - hi[None]),
                              0.0)
            lbb = jnp.einsum("qba,qba->qb", gap, gap)
            far = jnp.maximum(jnp.abs(q32[:, None, :] - lo[None]),
                              jnp.abs(q32[:, None, :] - hi[None]))
            ubb = jnp.einsum("qba,qba->qb", far, far)
            ub = jnp.minimum(ubb,
                             jnp.square(qnorm[:, None] + nmax[None, :]))
            empty = counts <= 0
            ub = jnp.where(empty[None, :], jnp.inf, ub)
            lb = jnp.where(empty[None, :], jnp.inf,
                           jnp.maximum(lbn, lbb))
            order = jnp.argsort(ub, axis=1)
            sub = jnp.take_along_axis(ub, order, axis=1)
            csum = jnp.cumsum(counts[order], axis=1)
            reached = csum >= ks[:, None]
            idx = jnp.argmax(reached, axis=1)
            thr = jnp.where(
                reached.any(axis=1),
                jnp.take_along_axis(sub, idx[:, None], axis=1)[:, 0],
                jnp.inf)
            scale = qn + dn_max
            eps = (eps_rel * jnp.sqrt(jnp.maximum(thr, 0.0) * scale)
                   + eps_cancel * scale)
            keep = qvalid[:, None] & (lb <= (thr + eps)[:, None])
            return keep.any(axis=0) & ~empty

        _score_jit = _score
    return _score_jit(q, qvalid, ks, counts, nmin, nmax, lo, hi,
                      dn_max, eps_rel, eps_cancel)


# -- scan accounting (shared by every chunked driver) -------------------------

def note_scan(engine, *, scanned_bytes: int, dense_bytes: int,
              blocks_total: int, blocks_pruned: int) -> None:
    """Fold one solve's scanned-bytes accounting into
    ``engine.last_prune`` and the live telemetry registry — the
    counters the ``--metrics`` summary and the OpenMetrics scrape carry
    (``scan.bytes_streamed`` / ``prune.blocks_pruned`` /
    ``prune.gated_fraction``). Dense solves record too (blocks_pruned
    0), so the pruned-vs-dense byte ratio is computable from either
    arm's artifact.

    ``scanned_bytes`` counts CORPUS rows read from host memory for
    scanning. On the single-chip and serve paths that equals the
    host->device traffic saved (pruned chunks are never staged); on
    the mesh path a partially-pruned chunk still ships its fixed-shape
    sharded buffer (zero-filled pieces included) — only chunks every
    shard pruned skip the link there, so mesh scanned_bytes measures
    host DRAM reads, not wire bytes."""
    from dmlp_tpu.obs import telemetry
    rec = engine.last_prune if isinstance(
        getattr(engine, "last_prune", None), dict) else {}
    rec.update(blocks_total=int(blocks_total),
               blocks_pruned=int(blocks_pruned),
               scanned_bytes=int(scanned_bytes),
               dense_bytes=int(dense_bytes))
    rec["pruned_fraction"] = (round(blocks_pruned / blocks_total, 6)
                              if blocks_total else 0.0)
    engine.last_prune = rec
    try:
        reg = telemetry.registry()
        reg.counter("scan.bytes_streamed").inc(int(scanned_bytes))
        reg.counter("prune.blocks_total").inc(int(blocks_total))
        reg.counter("prune.blocks_pruned").inc(int(blocks_pruned))
        reg.gauge("prune.gated_fraction").set(rec["pruned_fraction"])
    except Exception:  # observability never fails a solve (ops/ is
        pass           # outside the R501 resilience scope: no directive)
