"""Host-side result finalization shared by all engines.

The device engines return, per query, a selection-ordered candidate list of
size K >= max-k (+ margin). This module turns those lists into final
``QueryResult``s: optional exact float64 rescoring (restoring the reference's
double-precision ordering, engine.cpp:12 / common.h:13, without paying f64 on
the MXU), the per-query k cut, the majority vote (engine.cpp:320-332), the
report sort (engine.cpp:334-338), and -1-sentinel padding (common.cpp:66).

Everything is vectorized NumPy over (Q, K) arrays. It is no epilogue:
since the kernel's PR 47 the float64 gather of a served micro-batch (a
row a candidate slot, each at a random place of the host corpus) is
longer than the device's fold in every one-chip bulk cell (PERF.md
section 5). So where the caller read the device distances and the hazard
test's bound (boundary_band), only the slots that bound cannot order are
gathered and scored; a caller with neither rescores every slot.
"""

from __future__ import annotations

from typing import List

import numpy as np

from dmlp_tpu.golden.reference import cosine_of, row_norms
from dmlp_tpu.io.report import QueryResult


def _row_lexsort(primary: np.ndarray, *descending_ints: np.ndarray) -> np.ndarray:
    """Row-wise argsort by (primary asc, then each int key desc), stable.

    Implemented as composed stable sorts, least-significant key first (the
    radix trick), all vectorized along axis 1.
    """
    idx = np.broadcast_to(np.arange(primary.shape[1]), primary.shape).copy()
    keys = [(-k).astype(np.int64) for k in reversed(descending_ints)] + [primary]
    for key in keys:  # least-significant first; stable sorts compose
        cur = np.take_along_axis(key, idx, axis=1)
        order = np.argsort(cur, axis=1, kind="stable")
        idx = np.take_along_axis(idx, order, axis=1)
    return idx


def _vote_batch(labels: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """Vectorized majority vote with tie -> larger label; -1 if none valid."""
    q = labels.shape[0]
    masked = np.where(valid, labels, -1)
    num_labels = int(masked.max()) + 1 if masked.size and masked.max() >= 0 else 0
    if num_labels == 0:
        return np.full(q, -1, np.int64)
    counts = np.zeros((q, num_labels), np.int64)
    rows = np.broadcast_to(np.arange(q)[:, None], labels.shape)
    sel = masked >= 0
    np.add.at(counts, (rows[sel], masked[sel]), 1)
    best = counts.max(axis=1)
    is_best = counts == best[:, None]
    predicted = num_labels - 1 - np.argmax(is_best[:, ::-1], axis=1)
    return np.where(best > 0, predicted, -1)


#: What one rescore block's (block, K, A) float64 gather may weigh: 512
#: queries of 32 candidates at 128 attributes, the block every cell ran
#: before a wide row came.
RESCORE_BLOCK_BYTES = 512 * 32 * 128 * 8


def rescore_block(k: int, num_attrs: int) -> int:
    """Queries a rescore block: what RESCORE_BLOCK_BYTES holds of
    (k, num_attrs) float64 candidates, 1 to 512."""
    return max(1, min(512, RESCORE_BLOCK_BYTES // max(1, k * num_attrs * 8)))


def rescore_f64(cand_ids: np.ndarray, query_attrs: np.ndarray,
                data_attrs: np.ndarray, block: int | None = None,
                score: str = "l2",
                widths: np.ndarray | None = None,
                data_norms: np.ndarray | None = None) -> np.ndarray:
    """Exact float64 distances for candidate ids (difference form, like
    computeDistance at engine.cpp:12-18). ids < 0 map to +inf.
    Under ``score`` "ip" the ordered quantity is the NEGATED inner
    product -(q . x) of the gathered rows (so that it ascends like a
    distance; finalize_host hands the wire the product itself): one
    pass over the buffer where the difference form takes two. Under
    "cosine" it is -s, the contract's expression (golden.reference
    .cosine_of) on that product, the queries' norms and ``data_norms``,
    the rows' |x| as golden.reference.row_norms gives them (a holder of
    the corpus keeps them beside it: KNNInput.data_norms): the same one
    pass, and the ORIGINAL rows, never the normalised ones the device
    holds.

    ``widths`` (band_widths; (Q,) ints) are the leading slots of each
    list to gather and score; a slot past its query's width carries
    +inf and its row is never read. Queries of one width are scored
    together, as a rectangle of that width through the same block loop,
    so a distance is the bits the whole window's call gives it. Left
    out, every slot is scored.

    ``block`` queries are rescored at a time, in ONE (block, K, A)
    buffer that holds the gathered rows and then their difference; left
    out, it is rescore_block's. Measured on the serving host of a
    TPU v5 lite machine (PR 31; 1024 queries x 40 candidates x 960
    attributes, 314.6 MB gathered; the machine maps no huge pages):
    blocks of 512 queries, the gather and the difference each a fresh
    157 MB temporary, over glibc's 32 MB ceiling for the heap, so mapped,
    faulted in and unmapped every block: 770.8 ms a batch, 37 x the
    20.6 ms of a 128-attribute batch for 9.4 x the bytes. Blocks of
    16.6 MB, two temporaries a block: 82 ms in one process and 135 in
    the next, as the thread's heap kept or trimmed the 33 MB the two
    freed (its trim threshold is twice the 16.6), a batch every 580 or
    630 ms. One buffer a call has nothing to trim: 66.9 ms (65.95-66.97
    over six processes), a batch every 555. The 512 came from a
    pre-round sweep at 10240 x 4608 x 64 where 64-512 read alike."""
    q, k = cand_ids.shape
    na = data_attrs.shape[1]
    safe = np.clip(cand_ids, 0, data_attrs.shape[0] - 1)
    out = np.full((q, k), np.inf)
    product = score in ("ip", "cosine")
    if score == "cosine" and data_norms is None:
        data_norms = row_norms(data_attrs)
    # (queries, how many, width, queries a block) of each rectangle
    if widths is None:
        groups = [(slice(None), q, k)] if q and k else []
    else:
        widths = np.minimum(widths, k)
        members = [np.nonzero(widths == w)[0]
                   for w in np.unique(widths) if w]
        groups = [(idx, idx.size, int(widths[idx[0]])) for idx in members]
    groups = [(idx, m, w, block or rescore_block(w, na))
              for idx, m, w in groups]
    buf = np.empty(max((min(b, m) * w * na for _i, m, w, b in groups),
                       default=0), data_attrs.dtype)
    inplace = buf.dtype == np.float64
    for idx, m, w, b in groups:
        ids, qa = safe[idx, :w], query_attrs[idx]
        dst = np.empty((m, w), np.float64)
        for q0 in range(0, m, b):
            q1 = min(q0 + b, m)
            rows = buf[:(q1 - q0) * w * na].reshape(q1 - q0, w, na)
            np.take(data_attrs, ids[q0:q1], axis=0, out=rows, mode="clip")
            if product:
                np.einsum("qka,qa->qk", rows, qa[q0:q1], out=dst[q0:q1])
                continue
            diff = np.subtract(rows, qa[q0:q1, None, :],
                               out=rows if inplace else None)
            dst[q0:q1] = np.einsum("qka,qka->qk", diff, diff)
        if score == "cosine":
            dst = cosine_of(dst, row_norms(qa)[:, None], data_norms[ids])
        out[idx, :w] = np.negative(dst, out=dst) if product else dst
    out[cand_ids < 0] = np.inf
    return out


# Calibrated eps-bound constants — THE single definition, shared by the
# host hazard test below and the device-side multi-pass floor
# (engine.single._mp_floor); a recalibration here propagates to both.
EPS_REL_BF16 = 2.0 ** -6
EPS_REL_F32 = 2.0 ** -21
EPS_CANCEL_COEF = 3.0 * 2.0 ** -22

#: Low-precision FIRST-PASS coefficients (the tentpole's ``lowp_eps``
#: bound): casting the streamed q/d tiles to the pass dtype perturbs
#: each operand by a relative half-ulp u (2^-8 for bfloat16's 7
#: explicit mantissa bits), so the f32-accumulated cross term errs by
#: at most (2u + u^2) * |q||d| <= (2u + u^2) * (qn + dn)/2 per dot
#: (AM-GM), i.e. the norm-expansion distance by (2u + u^2)(qn + dn) —
#: a bound on the MAGNITUDE scale, independent of the distance itself
#: (unlike staging_eps term 1, which shrinks with sqrt(dist)). The
#: coefficient folds the 2u, the second-order u^2, and a 2x safety
#: slack: 2^-6 = 8 * 2^-9 >= (2*2^-8 + 2^-16) * 2. "f32" is the one
#: ``HIGHEST`` dot (zero cast error — the f32 accumulation itself is
#: already covered by the EPS_CANCEL_COEF term everywhere this
#: composes).
#:
#: "bf16x3" (PR 36; ops.pallas_extract.split_bf16 and _dot_cross):
#: each operand is x = hi + lo + r with hi = bf16(x), lo = bf16(x - hi)
#: (the difference is exact in float32), both round-to-nearest. With
#: 2^e <= |x| < 2^(e+1): |x - hi| <= 2^(e-8) (half a bf16 ulp), so
#: |lo| <= 2^(e-8) too (rounding is monotone and 2^(e-8) is a bf16),
#: and unless x - hi IS 2^(e-8) (then r = 0) it lies in a binade no
#: higher than e - 9, whose half ulp is 2^(e-17): |x - hi| <= u|x|,
#: |lo| <= u|x|, |r| <= (u^2 / 2)|x| with u = 2^-8 (checked over every
#: float32 of a binade: 0.9961 u, 0.9961 u, 0.9980 u^2 / 2). The kernel
#: sums q_hi.d_hi + q_hi.d_lo + q_lo.d_hi, whose bf16 x bf16 products
#: are exact in float32, and drops
#:     q_lo.d_lo + q_r.d + (q - q_r).d_r,
#: at most (u^2 + u^2/2 + (1 + u^2/2) u^2/2)|q_i||d_i| an attribute,
#: (2u^2 + u^4/4)|q||d| a dot (Cauchy-Schwarz), so the norm-expansion
#: distance errs by at most (2u^2 + u^4/4)(qn + dn) (AM-GM, as above):
#: 2^-15 of the scale, one-sided. Every test this composes into
#: compares TWO such distances (the k-th candidate's and a missed
#: row's; a threshold's and a tile's), so the coefficient is twice
#: that, 2^-14 (1 + 2^-19), rounded up to 2^-14 (1 + 2^-16) and no
#: further: it is the derivation's bound, not a calibration, and every
#: slot of clearance it takes is taken from the hazard test (6.1e-5 of
#: the scale beside the cancellation term's 9.3e-5 at 128 attributes,
#: 6.9e-4 at 960). ISSUE 36 derived 3 * 2^-15 from |r| <= u^2 |x|; the
#: half-ulp argument above halves r. The ACCUMULATION (3A products in
#: float32, partial sums at most (1 + 2u)|q||d|) stays inside
#: staging_eps term 2 even if every addition rounds the same way:
#: 3A (1 + 2u) * 2^-24 (qn + dn) one-sided for the dot, (A + 3) * 2^-24
#: for the two norms and the expansion's three additions; two-sided
#: (8A + 6)(1 + 2u) * 2^-24 <= 12 (A + 2) * 2^-24 = EPS_CANCEL_COEF *
#: (A + 2), a third to spare (the six passes' 6A products had none by
#: this count: 14A + 6). (A float32 so small that x - hi underflows is
#: flushed, an ABSOLUTE 2^-126 an attribute: no relative bound, here or
#: in term 2, counts it.)
#: tests/test_precision.py fuzzes both bounds with directed adversarial
#: magnitude-cancellation corpora. int8 has NO entry: an int8 pass
#: needs data-dependent quantization scales, so its bound cannot be a
#: static coefficient — the ROADMAP follow-on.
LOWP_COEF = {"f32": 0.0, "bf16x3": 2.0 ** -14 * (1.0 + 2.0 ** -16),
             "bf16": 2.0 ** -6}

#: The inner-product score's bounds (config.EngineConfig.score "ip";
#: the device orders by t~ = -fl32(q~ . x~), q~ and x~ the operands
#: cast to the staging dtype, the host by t = -(q . x) in float64).
#: ONE term, no cancellation: nothing of magnitude (qn + dn) is formed
#: and subtracted, so the bound is a multiple of |q||x| and does not
#: depend on the score itself.
#:
#: CAST. q~_a = q_a (1 + d_a), x~_a = x_a (1 + e_a), |d_a|, |e_a| <= u,
#: u the dtype's unit roundoff (2^-8 for bfloat16's 8 significant
#: bits: the worst case sits just above a power of two, where half a
#: spacing of 2^-7 is 2^-8 of the value; 2^-24 for float32). Then
#:     |q~ . x~ - q . x| <= (2u + u^2) sum_a |q_a x_a|
#:                       <= (2u + u^2) |q||x|        (Cauchy-Schwarz).
#: The hazard test compares TWO device scores (the k-th candidate's
#: and a row's the list may have missed: boundary_hazard), each off by
#: that much in either direction, so the coefficient is twice it:
#: 2 (2u + u^2) = 2^-6 (1 + 2^-9) for bfloat16 and 2^-22 (1 + 2^-25)
#: for float32, each rounded up to (1 + 2^-8) and no further: the
#: derivation's bound, not a calibration. |x| <= sqrt(dn_max) over
#: every row, known or missed.
#:
#: ACCUMULATION. The MXU sums the A products of a pass in float32
#: (exact products for bfloat16 operands; 3A products for the
#: "bf16x3" form, 6A for the one ``HIGHEST`` dot), every partial sum
#: at most (1 + u)^2 |q||x|: one-sided nprod * 2^-24 (1 + u)^2 |q||x|
#: if every addition rounds the same way, two-sided 12 A * 2^-24 for
#: the six passes, which is EPS_CANCEL_COEF * A: the cancellation
#: term's own count of the dot (LOWP_COEF's comment), taken here with
#: |q||x| where the distance form has (qn + dn) / 2 >= |q||x|. The
#: + 2 of (A + 2) is kept as the slack for (1 + u)^2 and for the
#: kernel's float32 norms, which the MXU gate's bound is made of.
#:
#: FIRST PASS. What "bf16x3" drops is (2u^2 + u^4/4)|q||x| a dot
#: (LOWP_COEF's derivation), two-sided LOWP_COEF["bf16x3"]: the same
#: constant. One "bf16" pass casts both blocks again: the CAST term
#: of bfloat16, EPS_IP_REL["bfloat16"] (LOWP_COEF["bf16"] = 2^-6
#: folds a slack in where this needs the u^2 too). "f32" drops
#: nothing.
#:
#: ip_coef sums the three: two device scores compare as their float64
#: values do unless they lie within ip_coef * |q| * sqrt(dn_max) of
#: each other. staging_eps(..., score="ip") is terms one and two,
#: lowp_eps(..., score="ip") the third, so every site that composes
#: the two under "l2" composes them under "ip" unchanged.
EPS_IP_REL = {"bfloat16": 2.0 ** -6 * (1.0 + 2.0 ** -8),
              "float32": 2.0 ** -22 * (1.0 + 2.0 ** -8)}

#: The cosine score (config.EngineConfig.score "cosine"): the device
#: holds x^ = fl64(x / |x|) and is handed q^ = fl64(q / |q|), each then
#: cast to the staging dtype, and runs the "ip" form over them, so the
#: bound is ip_coef at |q^| max|x^| = 1 (0 for a zero query or an
#: all-zero corpus: every device score is then an exact 0) PLUS what
#: separates q^ . x^ from the host's s, which the "ip" bound never had
#: to count because there both sides start from the same operands.
#:
#: NORMALISATION. fl(sum_a x_a^2) = |x|^2 (1 + t), |t| <= A 2^-53; the
#: square root halves that and rounds once, the division rounds once:
#: x^_a = (x_a / |x|)(1 + h_a), |h_a| <= (A / 2 + 2) 2^-53, and q^
#: alike, so |q^ . x^ - s| <= (A + 4) 2^-53 sum_a |q_a x_a| / (|q||x|)
#: <= (A + 4) 2^-53 to first order. The host's own s (the dot's A
#: products, two norms, one product and one quotient, all float64) is
#: within (2A + 4) 2^-53 of the real number. One device score against
#: one host score: (3A + 8) 2^-53; the test compares two: (3A + 8)
#: 2^-52 <= COS_NORM_COEF * (A + 4), 1.5e-12 at 1536 attributes beside
#: the float32 staging's 1.1e-3. (|x^| itself is 1 + (A / 2 + 2) 2^-53
#: at most, 2e-13 of the CAST term: inside the (1 + 2^-8) that term is
#: rounded up by.)
COS_NORM_COEF = 2.0 ** -50


def _ip_lowp_coef(precision: str) -> float:
    return EPS_IP_REL["bfloat16"] if precision == "bf16" \
        else LOWP_COEF[precision]


def _product_scale(qn: np.ndarray, dn_max: float, score: str) -> np.ndarray:
    """|q| max|x| of the operands the DEVICE multiplied, the scale of
    the "ip" and "cosine" bounds. Under "ip" the operands are the
    caller's. Under "cosine" they were normalised: ``qn`` (of the
    queries as given) says only which queries are zero, and ``dn_max``
    is the staged rows' own, 1 (0 of an all-zero corpus)."""
    if score == "cosine":
        qn = qn > 0
    return np.sqrt(qn * dn_max)


def ip_coef(staging: str, na: int, precision: str = "f32") -> float:
    """The "ip" bound's whole coefficient of |q| max|x| for operands
    staged as ``staging`` ("float32" | "bfloat16"), ``na`` attributes
    and first-pass form ``precision`` (EPS_IP_REL's comment derives
    it). The kernel's MXU gate deflates its bound by it (there the
    norms are the staged values' own: ``staging`` "float32")."""
    return (EPS_IP_REL[staging] + EPS_CANCEL_COEF * (na + 2)
            + _ip_lowp_coef(precision))


def lowp_eps(precision: str, qn: np.ndarray, dn_max: float,
             score: str = "l2") -> np.ndarray:
    """Per-query bound on the distance perturbation a low-precision
    FIRST PASS (ops.pallas_extract with ``precision != "f32"``: the
    split "bf16x3" form every exact engine runs, or one "bf16" pass) can add
    on top of the staging/f32 terms: ``LOWP_COEF[precision] * (qn +
    dn_max)``. Composes ADDITIVELY with :func:`staging_eps` (the cast
    error of the pass dtype and the staging/accumulation errors act on
    the same computed distance, so their bounds sum) at every decision
    the low-precision distances feed: the truncation-hazard test, the
    prune thresholds, the MXU-gate bound, and the multi-pass floor.
    Zero for the one-dot "f32" pass; 2^-14 of the scale for the
    three-pass "bf16x3" form, 2^-6 for one bf16 pass. Raises KeyError on
    a precision with no static bound (int8 — see LOWP_COEF). Under
    ``score`` "ip" the scale is |q| sqrt(dn_max) and the coefficient
    the form's inner-product one (EPS_IP_REL's comment); under "cosine"
    the same at unit operands (_product_scale)."""
    qn = np.asarray(qn, np.float64)
    product = score in ("ip", "cosine")
    coef = _ip_lowp_coef(precision) if product else LOWP_COEF[precision]
    if not coef:
        return np.zeros_like(qn)
    if product:
        return coef * _product_scale(qn, dn_max, score)
    return coef * (qn + dn_max)


def staging_eps(last: np.ndarray, qn: np.ndarray, dn_max: float,
                staging: str, na: int, score: str = "l2") -> np.ndarray:
    """Per-query bound on the distance perturbation the device pipeline
    can introduce, for the truncation-hazard test. Two terms:

    1. ATTR ROUNDING — casting attrs to the staging dtype perturbs each
       computed distance by at most (first order, Cauchy-Schwarz over the
       per-attr terms)

           |d~ - d| <= 2 * u * sqrt(d) * sqrt(2 * (|q|^2 + |x|^2))

       with u the half-ulp relative rounding (2^-9 for bfloat16, 2^-24
       for float32).
    2. COMPUTATION — the norm-expansion form qn + dn - 2 q.x evaluates
       three terms of magnitude ~(qn + dn) in f32 and CANCELS them, so
       its rounding error scales with the MAGNITUDES, not the result:
       ~(na + 2) * u32 * (qn + dn). When true distances are tiny against
       the coordinate scale (clustered data), this term dwarfs term 1 —
       the fuzz case the original attr-only bound missed: near-duplicate
       points at coordinate scale ~5 have gaps ~1e-6 but f32 cancellation
       error ~1e-5, silently reordering candidates past the margin.

    Neither error is monotone across points, so two points' device
    distances can swap even without an exact device tie — an
    exact-equality hazard test is sound only for exact device arithmetic.
    Comparing the k-th candidate against a potentially missed point
    doubles both bounds; the constants fold the doubling, sqrt(2), a
    >= 1.4x second-order slack, and (term 2) u32 = 2^-22 covering the
    float32 ACCUMULATION of the cross term, the norms and the
    expansion (LOWP_COEF's comment counts it for the 3A products of
    the "bf16x3" form). It does NOT cover products the first pass
    drops: an ``HIGHEST`` dot drops none (Mosaic emulates float32 in
    six bf16 passes, not the three this comment once assumed), and
    what the three-pass and one-pass forms drop is lowp_eps' to
    bound, added to this at every site. ``dn_max`` (max squared
    data-row norm, f64) bounds |x|^2 over every point, known or
    missed.

    Under ``score`` "ip" (the device orders by -q.x) there is ONE term
    and no cancellation: (EPS_IP_REL[staging] + EPS_CANCEL_COEF *
    (na + 2)) * |q| * sqrt(dn_max), the cast of both operands and the
    float32 accumulation of the dot, whatever ``last`` is (EPS_IP_REL's
    comment derives both). Under "cosine" that bound at the unit
    operands the device was given, and the normalisation's own rounding
    beside it (COS_NORM_COEF's comment).
    """
    if score in ("ip", "cosine"):
        coef = ip_coef(staging, na)
        if score == "cosine":
            coef += COS_NORM_COEF * (na + 4)
        return coef * _product_scale(np.asarray(qn, np.float64), dn_max,
                                     score)
    rel = EPS_REL_BF16 if staging == "bfloat16" else EPS_REL_F32
    scale = qn + dn_max
    return (rel * np.sqrt(np.maximum(last, 0.0) * scale)
            + EPS_CANCEL_COEF * (na + 2) * scale)


def boundary_hazard(kth: np.ndarray, last: np.ndarray,
                    eps: np.ndarray | float = 0.0) -> np.ndarray:
    """The (eps-widened) truncation-hazard predicate on the two boundary
    columns — THE single definition; boundary_overflow, the single-chip
    engine (which fetches only these columns), and the distributed
    rescore all evaluate this. +inf in the last slot means the candidate
    list wasn't even full of real points — nothing can have been
    truncated."""
    return np.isfinite(last) & (last <= kth + eps)


def boundary_clearance(kth: np.ndarray, last: np.ndarray,
                       eps: np.ndarray) -> float | None:
    """How many times its bound a batch's TIGHTEST query clears the
    window: the smallest (last - kth) / eps over the queries whose list
    is full and whose bound is positive; 1 or less is a flag
    (boundary_hazard). None where no query has both. What the hazard
    spans report as ``clear_min``."""
    full = np.isfinite(last) & (eps > 0)
    if not full.any():
        return None
    return round(float(((last - kth)[full] / eps[full]).min()), 4)


#: How many widths a batch's bands are rounded up to (band_widths):
#: queries of one width are rescored as one rectangle, so a batch costs
#: at most this many gathers' fixed parts, and a query reads at most a
#: sixteenth of the window beyond its own band.
BAND_STEPS = 16


def boundary_band(device_dists: np.ndarray, cand_ids: np.ndarray,
                  kth: np.ndarray,
                  eps: np.ndarray | float = 0.0) -> np.ndarray:
    """The slots of each candidate list that the float64 rescore has to
    read: ``(ids >= 0) & (d~ <= kth + eps)``, boundary_hazard's own
    statement turned on the rows INSIDE the list.

    The k slots before the k-th hold device distances <= ``kth`` (the
    lists are in device order), and ``eps`` bounds how far two device
    distances can swap against their float64 values (staging_eps +
    lowp_eps, or the "ip" bound: the doubling folded in). The hazard
    test trusts that of a row the list MISSED, all of them >= ``last``:
    one whose device distance is above ``kth + eps`` lies, in float64,
    beyond each of those k and cannot be among the true k nearest. That
    holds word for word of a candidate j of the list with d~_j > kth +
    eps: it is out of the float64 top k without a look at its row,
    whether or not the query is flagged. Every slot that can be
    REPORTED is in the band (a position below k has d~ <= kth), so the
    float64 order of the band's rows, cut at k, is the whole window's.

    ``eps`` is the vector the hazard test just used, as it is:
    staging_eps' first term is taken at ``last``, the largest distance
    of the list, so it bounds an in-list candidate at least as well as a
    missed row; the comparison is non-strict, the mirror of
    boundary_hazard's ``last <= kth + eps``. A caller that has no bound
    (a window that holds the whole corpus is never tested) or no device
    distances has no band: it rescores every slot.

    Args:
      device_dists: (Q, K) raw device candidate distances, device order
        (under "ip" the negated products, as kth and eps are).
      cand_ids: (Q, K) candidate ids, -1 where a list is short.
      kth: (Q,) device distance of each query's k-th candidate.
      eps: scalar or (Q,) bound of the hazard test.

    Returns:
      (Q, K) bool mask, True where the rescore must read the row.
    """
    horizon = np.asarray(kth, np.float64) + eps
    return (np.asarray(cand_ids) >= 0) \
        & (np.asarray(device_dists, np.float64) <= horizon[:, None])


def band_widths(in_band: np.ndarray) -> np.ndarray:
    """(Q,) leading slots of each list that rescore_f64 reads for the
    band ``in_band`` (boundary_band): up to the band's last slot (the
    lists are in device order, so a band is a prefix of its list; a slot
    before the last that is not in it is read too, which decides
    nothing), rounded up to one of BAND_STEPS widths of the window. 0
    where a list holds no band."""
    q, kcap = in_band.shape
    if q == 0 or kcap == 0:
        return np.zeros(q, np.int64)
    width = np.where(in_band.any(axis=1),
                     kcap - np.argmax(in_band[:, ::-1], axis=1), 0)
    step = -(-kcap // BAND_STEPS)
    return np.minimum(-(-width // step) * step, kcap)


def boundary_overflow(device_dists: np.ndarray, ks: np.ndarray,
                      eps: np.ndarray | float = 0.0) -> np.ndarray:
    """Queries whose fast-path candidate set may have truncated a tie group.

    The "topk" selection keeps the K smallest device distances with ties
    broken by position, not by the reference's larger-id preference
    (dmlp_tpu.ops.topk). A query's true top-k can then be missing
    from the candidates only if >= K entries tie at or below its k-th
    distance — which implies its k-th candidate distance equals the K-th
    (last) one. That equality is the hazard test: exact (conservative — it
    can flag safe queries, never miss an unsafe one) and computable from the
    raw device distances alone. Flagged queries are recomputed exactly on
    host (engines call dmlp_tpu.golden on just those), so parity survives
    adversarial duplicate-heavy data on the fast path too.

    ``eps`` widens the test to ``last <= kth + eps`` for staging dtypes
    whose rounding perturbs distances non-monotonically (staging_eps): a
    true neighbor can then sit up to eps ABOVE the k-th device distance,
    so the list has provably captured the true top-k only when the
    candidate horizon (last) clears the k-th distance by more than eps.
    With eps = 0 this reduces to the exact-tie test.

    Args:
      device_dists: (Q, K) raw device candidate distances, selection order.
      ks: (Q,) per-query k.
      eps: scalar or (Q,) staging-dtype perturbation bound.

    Returns:
      (Q,) bool mask of suspect queries.
    """
    q, kcap = device_dists.shape
    if q == 0 or kcap == 0:
        return np.zeros(q, bool)
    return boundary_hazard(kth_column(device_dists, ks),
                           device_dists[:, kcap - 1], eps)


def kth_column(device_dists: np.ndarray, ks: np.ndarray) -> np.ndarray:
    """(Q,) device distance of each query's k-th candidate, of the
    (Q, K) lists read back whole (what engine.single._boundary_cols
    takes on the device)."""
    q, kcap = device_dists.shape
    return device_dists[np.arange(q),
                        np.clip(np.asarray(ks) - 1, 0, kcap - 1)]


def repair_boundary_overflow(results: List[QueryResult],
                             suspect_idx: np.ndarray, inp,
                             score: str = "l2") -> None:
    """Recompute the flagged queries exactly (golden model) in place.

    ``suspect_idx`` holds local query indices (positions in ``results`` /
    ``inp`` row order); the repaired entries keep their original query ids.

    Repairs run through the vectorized oracle (golden.fast: BLAS coarse
    pass + exact f64 rescore + strict fallback), not the per-query strict
    model: staging-eps hazards can flag thousands of queries at once
    (bf16 on dense distance distributions), and the repair must stay a
    BLAS pass, not a Python loop over full-dataset solves.
    """
    from dmlp_tpu.golden.fast import knn_golden_fast
    from dmlp_tpu.io.grammar import subset_queries

    fixed_all = knn_golden_fast(subset_queries(inp, suspect_idx),
                                score=score)
    for j, qi in enumerate(np.asarray(suspect_idx)):
        fixed = fixed_all[j]
        results[qi] = QueryResult(results[qi].query_id, fixed.k,
                                  fixed.predicted_label, fixed.neighbor_ids,
                                  fixed.neighbor_dists)


def finalize_host(cand_dists: np.ndarray | None, cand_labels: np.ndarray,
                  cand_ids: np.ndarray, ks: np.ndarray,
                  query_attrs: np.ndarray, data_attrs: np.ndarray,
                  exact: bool = True,
                  query_ids: np.ndarray | None = None,
                  score: str = "l2",
                  data_norms: np.ndarray | None = None
                  ) -> List[QueryResult]:
    """Candidate lists -> final per-query results.

    Args:
      cand_dists/labels/ids: (Q, K) device candidate lists (selection order).
        ``cand_dists`` may be None when ``exact`` (distances are rescored
        from the float64 originals anyway — engines then skip fetching the
        device distance matrix entirely).
      ks: (Q,) per-query k (K >= ks.max() required).
      query_attrs/data_attrs: float64 originals, used only when ``exact``.
      exact: rescore candidates in float64 and re-select (parity mode).
      query_ids: (Q,) global query ids; defaults to arange (single process).
      score: "l2" | "ip". Under "ip" every distance in here is the
        NEGATED inner product (``cand_dists`` too), so the one
        (dist asc, id desc) order is (s desc, id desc); the results
        carry s itself, the contract's value (padding -inf), which is
        what the wire and the golden model report. Under "cosine" they
        are -s likewise and the results carry the angular distance
        d = 1 - s, ascending, padding +inf.
      data_norms: the rows' norms for an ``exact`` rescore under
        "cosine" (rescore_f64).
    """
    q, kcap = cand_ids.shape
    ks = np.asarray(ks, np.int64)
    if q and kcap < ks.max():
        raise ValueError(f"candidate width {kcap} < max k {ks.max()}")
    cand_ids = np.asarray(cand_ids, np.int64)
    cand_labels = np.asarray(cand_labels, np.int64)
    d = rescore_f64(cand_ids, query_attrs, data_attrs, score=score,
                    data_norms=data_norms) \
        if exact else np.asarray(cand_dists, np.float64)

    # Re-derive the selection order (dist asc, id desc — the measured
    # label-free oracle-binary comparator, golden.reference); after
    # float64 rescoring the device's f32 order may no longer be sorted.
    order = _row_lexsort(d, cand_ids)
    d = np.take_along_axis(d, order, axis=1)
    labels = np.take_along_axis(cand_labels, order, axis=1)
    ids = np.take_along_axis(cand_ids, order, axis=1)

    pos = np.arange(kcap)[None, :]
    in_k = pos < ks[:, None]
    valid = in_k & (ids >= 0)
    predicted = _vote_batch(labels, valid)

    # Report order == selection order under the measured label-free
    # comparator (one (dist asc, id desc) total order governs both): the
    # list is already sorted, and masking the beyond-k tail to (inf, -1)
    # preserves sortedness (the tail is contiguous at the end) — the
    # former second lexsort was an identity permutation (and measured
    # ~9.5 s at the 10240 x 4608 wide-k shape).
    rd = np.where(valid, d, np.inf)
    rids = np.where(valid, ids, -1)
    if score == "ip":
        rd = -rd          # the product itself; -(-0.0) is +0.0
    elif score == "cosine":
        rd = 1.0 + rd     # 1 - s; a padded slot stays +inf

    if query_ids is None:
        query_ids = np.arange(q, dtype=np.int64)
    results: List[QueryResult] = []
    for qi in range(q):
        k = int(ks[qi])
        results.append(QueryResult(int(query_ids[qi]), k, int(predicted[qi]),
                                   rids[qi, :k].copy(), rd[qi, :k].copy()))
    return results
