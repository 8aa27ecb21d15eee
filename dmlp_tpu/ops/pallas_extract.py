"""Pallas TPU kernel: fused distance + running top-k by iterative extraction.

The round-2 solve wrote every (Q, B) distance tile to HBM (8.4 GB at the
benchmark shape) and selected from it with segment-min + gather + lax.top_k
— measured on v5e in build round 3 the selection pipeline cost ~15x the
distance matmul. This kernel is the fix:
selection happens in VMEM while the distance block is still resident, so
the tile never exists in HBM at all.

Algorithm (replaces the reference's per-rank hot loop + nth_element,
engine.cpp:233-257, with a threshold-gated extraction):

- Grid (Qb/tq, B/tn); the (tq, kc) running top-k lives in the revisited
  output block (VMEM-resident across the data-block sweep, flash-attention
  style accumulator).
- Per data block: one MXU pass computes the (tq, tn) distance block into a
  VMEM scratch via the norm expansion |q-d|^2 = |q|^2 + |d|^2 - 2 q.d.
- A while-loop then extracts candidates: each iteration finds the minimum
  of each quarter of the block (4 candidates per row per pass), inserts
  those that beat the row's current k-th best (its threshold T = max of the
  running list) into the running list, and masks them out of the block.
  The loop ends when no row improved — for blocks that arrive after the
  running lists are warm, the expected number of iterations is ~1 + k*tn/N,
  so almost all blocks cost one scan, not a sort.
- Threshold-gated block skipping (ISSUE 3): before the loop, one cheap
  VPU reduction computes each row's block minimum; when no row's block
  min strictly beats its current threshold T (the same strict ``m < T``
  the extraction uses), the while-loop is skipped entirely (0 recorded
  iterations). A warm no-improve block then costs one (tq, tn) min pass
  instead of a full extraction round (ne argmin/insert/mask passes) —
  output-identical, because the skipped round could not have inserted
  anything. (This differs from the per-pass ``pl.when`` predication that
  measured SLOWER inside the loop — the gate is a single reduction
  before the loop, not predication of every pass.)

- Two-level selection (ISSUE 47): a warm row gains about one entry a
  block, yet every round of the loop above sweeps all (tq, tn)
  elements about eight times to take out at most ne a row. So ONE pass
  folds the block's slabs of F lane vectors (fold_slabs) to per-bucket
  minima, their block positions and the buckets' second smallest; the
  rounds then run over the folded (tq, tn / F) array, one candidate a
  row a round, at 1 / F of a round's cost, and are exact unless some
  bucket hides a second entry under its row's threshold. Such a tile
  (the first blocks of a fold, which insert tens of entries a row, and
  a few percent of the warm ones) takes the full-width loop, untouched,
  whole. The block-skip minimum is the folded array's: that pass goes.

Variant selection (tile_q / tile_n / ne / unroll / fold) is one function
of the dispatch shape (resolve_variant): ne from the list width kc
(tuned_variant), the slabs of the fold pass from the block (fold_slabs),
and the data block tile_n from the ROW width (the
double-buffered (tile_n, a) block is what fills VMEM: 12 800 rows up to
512 attributes, 6 400 at 960, 2 560 at 2048). An attribute-axis grid
that keeps 12 800 rows and accumulates the cross term over attribute
blocks was measured against this on v5e and lost by 25%
(tuned_variant's docstring).

Ties are kept by lowest global position (strict `m < T` extraction +
lowest-lane argmin; the rounds over the folded array compare block
positions, not lanes), i.e. the same semantics as the "topk"/"seg"
selects, up to the order of the full-width loop's ne sub-blocks inside
one block; the engines' boundary-overflow detection + host repair
applies unchanged.

The kernel requires affine data ids: row j of `d` has global id
``id_base + j``, rows at positions >= n_real are sentinels (masked to +inf,
reported as id -1). Both are trace-time constants, which every engine
staging path satisfies (chunks/shards are contiguous global row ranges).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dmlp_tpu.config import KERNEL_SCORES
from dmlp_tpu.utils.compat import tpu_compiler_params

from dmlp_tpu.ops.pallas_distance import _tile

# Swept on v5e at 204800 x 10240 x 64, kc=40 (r3): small query tiles win
# (the while loop runs max-over-rows extra iterations, so fewer rows per
# tile means fewer wasted passes) and two half-block minima per pass beat
# one or four. (128, 12800, 2) measured 68 ms vs 148 ms for the previous
# (512, 8192, 4) default.
_TQ = 128    # query rows per tile
_TN = 12800  # data rows per block, at most (rows past 512 attributes
#              take a shorter one: resolve_variant)
_E = 2       # extraction candidates per loop iteration (half-block minima)
_FOLD_W = 10   # lane vectors the folded array keeps, at least (fold_slabs)

# Public padding contract for callers (engine.single, bench): pad data to
# whole BLOCK_ROWS blocks and queries to whole QUERY_TILE tiles so _tile
# never degenerates (see config.resolve_granule("extract")).
BLOCK_ROWS = _TN
QUERY_TILE = _TQ


def tuned_variant(kc: int) -> dict:
    """Per-list-width kernel tuning (tile_q, ne).

    - narrow lists (kc <= 64): (tq 128, ne 2), the r3 default: 101.7 ms
      at kc 64 against ne 4's 101.3 on v5e at 204800 x 10240 x 64 (a
      pre-round sweep, its record gone), tq / ne changes within noise.
    - wide lists (kc > 64): (tq 128, ne 4). Until PR 47 tq was 64 here,
      chosen on that same sweep because "smaller query tiles cut the
      max-over-rows wasted iterations" of the full-width loop (139 vs
      151 ms at kc 136, 306 vs 373 at kc 512); those rounds now run
      over the folded array (fold_slabs) at a tenth of the cost, and a
      64-row tile reads every data block twice as often. Re-measured
      with the fold pass in (TPU v5 lite, PR 47, one resident fold,
      q1024, chunks of 51 200 x 128, host clock around five folds,
      lists equal to the bit, F 10 unless said): bf16 rows, kc 120, 196
      chunks: (64, 4) 67.32 ms, on its block's DMA; (128, 4) 50.29;
      (128, 2) 50.38 against (128, 4) 50.30 at F 20. bf16 rows at 256
      lanes under ip (``text2image-10m.bulk``), F 20: (64, 4) 119.52,
      (128, 4) 70.84. kc 144 / 256, 98 chunks: (64, 4) 41.52 / 50.84,
      (128, 4) 36.23 / 49.64. kc 512, the float32 multipass sweep over
      82 chunks: (64, 4) 87.85, (128, 4) 87.45; at F 20 (64, 4) 92.40,
      (128, 4) 93.05, (128, 2) 100.21, (64, 2) 103.76: ne 4 at every
      width past 64, and tq 128 wins or ties (0.7%) at every one, so
      the table has no row for it. WITHOUT the pass the old row still
      stands (kc 120: (64, 4) 100.14, (128, 4) 106.15; kc 512 sweep:
      105.97, 109.98): the two were chosen together. ne 8 / tq 32 /
      unroll 2 all measured worse on the pre-round sweep.

    Measured at 960 attributes (PR 31, TPU v5 lite, one fold of 20
    chunks of 51 200 x 960 float32 at q1024, kc 32, the kernel's device
    time a fold; ``chiprun_out/pr31/contest.json`` of that PR's builder,
    summarised in PERF.md section 6): the row width picks tile_n
    (resolve_variant), and (tq 128, tn 6 400) takes 88.50 ms whether
    the stack is 960 wide or zero-padded to 1 024 (the padded stack
    saves 16.8 ms a fold of chunk copies beside the kernel:
    lane_padded). tq 256 at the same tn: 87.62 (-1.0%, twice the
    compile time: not taken). An attribute-axis grid at tn 12 800 that
    accumulates the cross term into the scratch: 110.49 with 512-wide
    attribute blocks, 130.35 with 256, 97.40 at tq 256: every form
    slower than the shorter data block, so the kernel has no such axis.
    """
    return {"tile_q": _TQ, "ne": _E if kc <= 64 else 4, "unroll": 1}


def _whole_lanes(a: int) -> int:
    return -(-a // 128) * 128


def lane_padded(a: int) -> int:
    """The attribute width a RESIDENT stack holds on the device for
    rows of ``a`` attributes: whole 128-lane vectors once a row fills
    more than half of one (65..127 -> 128, 960 -> 1024; a <= 64 stays
    as it is). Zeros are exact for a squared L2 and for a norm, and
    cost nothing the chip would not spend anyway: a (tile_n, a) block
    takes whole lanes in VMEM and the MXU contracts 128 at a time (the
    kernel's time is the same at every staged width: below). What they
    buy is the layout: XLA keeps an array whose minor axis is not whole
    lanes rows-minor, and the fold then copies every chunk (the slice,
    then a relayout for the kernel), or the whole stack, where the
    kernel reads the padded stack's blocks in place (since PR 41, by a
    prefetched chunk index: extract_topk's stack form; the compiler's
    answer at 100 wide is the same whole-stack re-layout with the
    direct read as without it: tests/test_tpu_aot.py).

    Above one lane vector the rule is PR 31's (960 against 1024:
    tuned_variant). Below it, measured on the chip (PR 40, TPU v5
    lite; ``_fold_stack`` alone, q1024, kc 120, 196 of 328 chunks of
    51 200 rows, bfloat16; device time a fold from the profiler, five
    folds; PERF.md section 6; measured WITH the pass in place that
    computed the row norms and copied the chunk out of the stack a
    kernel call, which PR 41 took out of every row of this table
    alike: "norms" and ~3.9 of "beside it"), the stack as it is
    against the same values zero-padded to 128 (kernel 99.28 ms,
    everything beside it 5.0, stack 4.30 GB, no temporary):

    - 100 wide: the compiler keeps the stack attribute-major and
      re-lays-out ALL of it every fold into a temporary the size of
      the 128-wide stack (``%copy = bf16[328,51200,100]``, 12.39 ms;
      temp_size_in_bytes 4.30 GB beside a 3.36 GB stack; so at every
      width from 65 to 127 that is not a multiple of 8, by the
      compiler's account): kernel 99.56, beside it 17.4: a fold 12.2%
      longer, and MORE memory than padded.
    - 96 wide: no temporary, a slice and a transposing copy a chunk:
      kernel 99.64, beside it 9.2: 4.3% longer for a stack 25% smaller.
    - 64 wide: the same: kernel 99.54, beside it 8.1: 3.2% longer for
      HALF the stack; 20 wide: 99.59 and 10.3: 5.4% longer for a stack
      6.4 times smaller.
    - float32 staging at 100 wide (98 of 164 chunks, kc 32): no
      temporary, but the slice of the attribute-major stack alone is
      21.1 ms and the kernel 57.05 against 52.15: a fold 48% longer
      as it is (83.15 against 56.20 ms).

    Hence half a lane vector: past it whole lanes cost at most twice
    the stack and cure the widths that hold a second stack in flight;
    at 64 and below a few percent of a fold do not pay for two to six
    times the corpus."""
    return a if a <= 64 else _whole_lanes(a)


#: what the kernel may hold in VMEM by its own reckoning (vmem_bytes);
#: the compiler is given 96 MiB, the rest is for the block's temporaries
_VMEM_BOUND = 64 * 2**20


def vmem_bytes(tq: int, tn: int, a: int, kc: int, fold: int = 0) -> int:
    """VMEM the kernel's blocks take at tiles (tq, tn): the (tq, tn)
    distance scratch, the double-buffered q and d blocks, the running
    lists and, where the selection is two-level (``fold`` slabs:
    fold_slabs), the folded minima and their positions, two (tq,
    tn / fold) scratches. A block holds whole 128-lane vectors, so a
    row of ``a`` attributes weighs ``a`` rounded up to lanes
    (960 -> 1024)."""
    folded = 2 * tq * (tn // fold) if fold else 0
    return (tq * tn + folded + 2 * (tq + tn) * _whole_lanes(a)
            + 4 * tq * kc) * 4


def resolve_variant(kc: int, b: int, qb: int | None = None,
                    a: int | None = None) -> dict:
    """The tiles extract_topk runs with at this dispatch shape, for
    both kernel forms (the MXU gate adds per-block scalars, no tiling
    constraint). A pure function of its arguments: supports(),
    extract_topk, the engines' jit keys and spans and the analytic
    cost model (obs.kernel_cost) all call it with the same shape, so
    gate, kernel and counters can never disagree. Always carries
    tile_q/ne/unroll and ``fold`` (fold_slabs of the block in force: 0
    where the selection stays one-level), plus tile_n where the row
    width shortens the block.

    tile_q and ne are the kc-tuned variant's, unless ITS ne-alignment
    can't tile this b (wide-k wants ne=4 → b % 512; a caller with
    pre-shaped shards, e.g. the multi-host feed, may only satisfy the
    ne=2 alignment) — then ne 2 keeps kernel coverage rather than
    silently dropping to the streaming select.

    The data block follows the row width: ``tile_n`` is the largest
    tile of ``b`` (a 128 * ne multiple that divides it) no longer than
    _TN whose double-buffered (tile_n, a) block still fits
    :func:`vmem_bytes`' bound beside the scratch. Up to a = 512 at
    (tq 128, kc 32) that is _TN itself and the variant carries no
    ``tile_n``; a = 960 tiles 51 200 rows by 6 400, a = 2048 by 2 560.
    Without the dispatch shape (qb, a) the width is unknown and the
    block stays _TN."""
    v = tuned_variant(kc)
    if b % (128 * v["ne"]) != 0 and b % (128 * _E) == 0:
        v["ne"] = _E
    gran = 128 * v["ne"]
    tn = _tile(b, _TN, gran) if b % gran == 0 else 0
    if qb is not None and a is not None and tn:
        tq = _tile(qb, v["tile_q"], 8)
        widest = tn
        while tn > gran and vmem_bytes(tq, tn, a, kc,
                                       fold_slabs(tn)) > _VMEM_BOUND:
            tn = _tile(b, tn - gran, gran)    # the next tile of b down
        if tn < widest:
            v["tile_n"] = tn
    v["fold"] = fold_slabs(tn) if tn else 0
    return v


def fold_slabs(tn: int) -> int:
    """F, the lane vectors a bucket of the two-level selection folds
    (_kernel: a block of tn lanes is made in tn / (128 F) slabs of F
    lane vectors, each slab folds to ONE vector of per-bucket minima,
    and the extraction rounds run over the folded (tq, tn / F) array),
    or 0 where the block is one lane vector and the kernel keeps the
    full-width loop alone. A function of the dispatch shape, like every
    tile: resolve_variant carries it as ``fold``.

    The largest divisor of the block's tn / 128 lane vectors that
    leaves the folded array _FOLD_W = 10 of them (1 280 buckets a row:
    F 10 at 12 800 rows a block, 5 at 6 400, 2 at 2 560); a shorter
    block folds in two. The pass costs the same at every F, a round
    1 / F of a full-width one, and two of a row's n entries under its
    threshold share a bucket with probability ~n(n - 1) F / 2 tn, so
    the folded WIDTH is what the contest chose (TPU v5 lite, PR 47:
    one resident fold a case, q1024, chunks of 51 200 rows, host clock
    around five folds, every variant's sorted lists equal to PR 46's
    kernel's to the bit; ``wide`` = the visits that took the full-width
    loop; every figure here and in tuned_variant with all of a block's
    slabs written out, before they became the loop of _kernel, which
    adds 0-6% to each: 30.00 -> 30.71 and 50.25 -> 53.22 ms at the
    first two shapes below):

    - float32 128-d, kc 32, 82 chunks (``bigann.bulk``): PR 46's kernel
      47.55 ms; F 10 29.96 (wide 4.0%), F 20 29.83 (5.6%), F 25 29.73
      (5.9%): flat to 0.8%, the widest folded array falls back least.
    - bf16 128-d, kc 120, 196 chunks (``bigann-10m.bulk``) at (tq 128,
      ne 4): F 10 49.95 (5.9%), F 20 50.30 (8.2%), F 25 50.78 (9.3%);
      PR 46's (tq 64, ne 4) kernel 103.84.
    - float32 128-d, kc 512, the multipass sweep over 82 chunks: no
      pass 106.14, F 10 87.91, F 20 92.40 at (tq 64, ne 4); 109.98,
      87.45, 92.94 at (128, 4); the retry (q16, bf16, 196 chunks):
      8.29, F 10 7.26, F 20 7.27.
    - kc 144 / 256 (bf16, 98 chunks, tq 128): no pass 66.86 / 79.89,
      F 10 36.23 / 49.64 (wide 13.6 / 23.3%): the pass wins at every
      list width the kernel takes, so the list width does not enter.
    - 1 024 lanes a row (``gist.bulk``, 6 400 rows a block, 20 chunks):
      48.27 without, 47.51 at F 5 (wide 8.2%), 47.84 at F 10 (12.9%),
      47.99 at F 25 (21.3%): the visit is its block's DMA either way."""
    lanes = tn // 128
    f = max(f for f in range(1, max(2, lanes // _FOLD_W) + 1)
            if lanes % f == 0)
    return f if f > 1 else 0


def variant_supports(qb: int, b: int, a: int, kc: int, v: dict) -> bool:
    """supports() with an EXPLICIT variant — extract_topk's own
    validation as a predicate: whole lane-width sub-blocks
    (b % (128 * ne)), query tiles of 8, kc no wider than one block, and
    VMEM room for the distance scratch + double-buffered q/d blocks."""
    if qb % 8 != 0 or b % (128 * v["ne"]) != 0:
        return False
    tn = _tile(b, v.get("tile_n", _TN), 128 * v["ne"])
    tq = _tile(qb, v["tile_q"], 8)
    if kc > tn or kc > 512:
        return False
    return vmem_bytes(tq, tn, a, kc, v.get("fold", 0)) <= _VMEM_BOUND


def supports(qb: int, b: int, a: int, kc: int) -> bool:
    """Shapes the kernel can tile WITH the variant resolved for this
    full dispatch shape (the same resolution extract_topk uses)."""
    return variant_supports(qb, b, a, kc, resolve_variant(kc, b, qb, a))


#: first-pass forms the kernel knows (engine.finalize.LOWP_COEF has each
#: one's bound; config.EngineConfig.resolve_precision picks one)
PRECISIONS = ("f32", "bf16x3", "bf16")


def split_bf16(x):
    """``x`` (float32) as two bfloat16 halves (hi, lo): hi = bf16(x),
    lo = bf16(x - hi) (the difference is exact), so x = hi + lo + r
    with |r| <= 2^-17 |x| (engine.finalize.LOWP_COEF derives it).
    For use INSIDE a kernel: Mosaic makes both casts as written
    (measured on v5e, PR 36: residuals 0.996 * 2^-8 and 0.998 * 2^-17
    of |x|). XLA:TPU does not: it takes f32 -> bf16 -> f32 for the
    identity (excess precision), so the same lines in a jitted
    prologue gave lo = 0 on the chip, the one-pass form's error under
    the three-pass form's bound, while every CPU test passed."""
    hi = x.astype(jnp.bfloat16)  # check: lowp-eps=lowp_eps
    rest = x - hi.astype(jnp.float32)
    return hi, rest.astype(jnp.bfloat16)  # check: lowp-eps=lowp_eps


def _split_in_kernel(x, interpret: bool):
    """split_bf16 of one (16, 128) float32 tile made by a kernel of its
    own: what split_holds looks at."""
    def kern(x_ref, hi_ref, lo_ref):
        hi_ref[...], lo_ref[...] = split_bf16(x_ref[...])

    half = jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
    return pl.pallas_call(kern, out_shape=[half, half],
                          name="dmlp_split_check", interpret=interpret)(x)


@functools.lru_cache(maxsize=None)
def split_holds() -> bool:
    """Whether THIS process's backend makes split_bf16's casts as
    written inside a kernel: the split run once through a pallas_call
    (Mosaic on a chip, the interpreter on the cpu backend) over 2 048
    float32 values of seventeen binades, and |x - hi - lo| <= 2^-17 |x|
    held to on the host in float64. A compiler that folds f32 -> bf16
    -> f32 to the identity, as XLA:TPU does, leaves lo = 0 and a
    residual of up to 2^-9 |x|: the one-pass form's error under the
    three-pass form's bound, which no CPU test and no checksum would
    show (the float64 rescore hides it until a boundary is missed).
    config.EngineConfig.f32_form asks before it names "bf16x3" and
    falls back to the one HIGHEST dot, with a warning, where the
    answer is no; chip_smoke.py's ``batch.f32`` phase fails on that
    fallback."""
    import warnings

    import numpy as np

    from dmlp_tpu.ops.pallas_distance import pallas_interpret
    rng = np.random.default_rng(36)
    shape = (16, 128)                      # one bf16 tile
    x = (rng.uniform(1.0, 2.0, shape) * 2.0 ** rng.integers(-8, 9, shape)
         * rng.choice([-1.0, 1.0], shape)).astype(np.float32)
    hi, lo = jax.device_get(  # check: allow-host-sync
        _split_in_kernel(jax.device_put(x), pallas_interpret()))
    x64 = x.astype(np.float64)
    rest = x64 - hi.astype(np.float64) - lo.astype(np.float64)
    holds = bool(np.all(np.abs(rest) <= 2.0 ** -17 * np.abs(x64)))
    if not holds:
        warnings.warn(
            "this backend's compiler does not make the bf16 split as "
            f"written (residual {np.max(np.abs(rest / x64)):.3e} of |x|, "
            "bound 2^-17): the exact engines keep the one HIGHEST dot "
            "(six MXU passes) in place of the three-pass form",
            RuntimeWarning, stacklevel=2)
    return holds


def mxu_passes(precision: str, dtype) -> int:
    """bf16 MXU passes _dot_cross spends a (tq, tn) visit at first-pass
    form ``precision`` over a data block of ``dtype`` (an array dtype
    or a staging name, "float32" | "bfloat16"): 1 where the block
    arrives as bfloat16 or the form is "bf16", 3 for "bf16x3", 6 for
    the one ``HIGHEST`` dot. The ONE test of which dot runs: the
    kernel branches on it and the engines stamp it (``mxu_passes`` in
    ``last_variant`` and ``last_precision``) from their staging dtype,
    so a trace and a daemon's ``stats`` say what a fold ran."""
    if jnp.dtype(dtype) == jnp.bfloat16 or precision == "bf16":
        return 1
    return 3 if precision == "bf16x3" else 6


def _dot_cross(q, d, precision: str):
    """The (tq, tn) cross-term block of the float32 query block ``q``
    (tq, a) and the data block ``d`` (tn, a) at the requested
    FIRST-PASS form, every form accumulating in float32.

    A bfloat16 ``d`` (rows staged in bfloat16, streamed as they are:
    _extract_topk_jit hands the kernel a bfloat16 block only where the
    queries hold bfloat16 values too) takes ONE pass whatever the
    form: ``q`` is cast back (lossless for bf16 values; Mosaic makes
    the cast as written, split_bf16) and bf16 x bf16 contracts at the
    default MXU precision. The products are exact in float32 and the
    accumulation is the MXU's float32 one: the value the ``HIGHEST``
    dot computes from the same operands with five all-zero partial
    products beside it, at a sixth of the passes and half the block's
    DMA. Nothing is dropped, so this is the "f32" form's own value
    (LOWP_COEF["f32"] = 0 stands; chip_smoke.py's ``fold.bf16`` phase
    holds the chip to it). For float32 blocks:

    "f32": ONE dot at ``Precision.HIGHEST``, which Mosaic lowers to
    ``contract_precision<fp32>``: full float32 emulation, SIX bf16 MXU
    passes (measured on v5e, PR 36: the dot alone 13.6 us at a (128,
    128) x (12 800, 128) visit where one pass is 2.1; the default would
    truncate to one pass: 1e-2 relative distance error, breaks neighbor
    selection). Fast mode's form: the device ordering IS its answer.

    "bf16x3": both blocks split here into bf16 halves (split_bf16) and
    THREE passes, q_hi.d_hi + q_hi.d_lo + q_lo.d_hi. bf16 x bf16
    products are exact in float32; what is dropped (q_lo.d_lo and the
    remainders' products) is at most 2^-15 |q||d| a dot:
    engine.finalize.LOWP_COEF["bf16x3"] has the derivation. The exact
    engines' form at float32 staging: the float64 rescore decides
    every order behind it. The three are ONE dot over [q_hi | q_hi |
    q_lo] . [d_hi | d_lo | d_hi], a contraction of 3a, at every width:
    it accumulates in the MXU and pops the (tq, tn) result once where
    three dots pop it three times and sum on the VPU (measured, PR 36:
    the fold's visit at 128 attributes 18.6 us against 20.0; at 1 024
    each dot already accumulates over eight MXU tiles and the two tie,
    47.1 / 47.1). Mosaic compiles the stacked operands whether the row
    is whole lanes or not (tests/test_tpu_aot.py: 64, 96, 100, 128,
    960, 1 024 and 2 048 attributes, the wide ones at the tiles
    vmem_bytes picks; the one-pass form over bfloat16 blocks at 64, 96,
    100 and 128).
    The split is made a visit: beside the MXU's passes it is not seen
    in the visit's time, and planes staged outside the kernel measured
    slower (a shorter fold of the same call: 57.7 us a 1 024-wide visit
    against 53.5).

    "bf16": both blocks cast, ONE pass; the cast's distance
    perturbation is bounded by engine.finalize.lowp_eps too, 256 x
    wider.

    Every caller folds the form's lowp_eps into its prune threshold,
    hazard test, floor and gate bound (and, for "bf16", its candidate
    window) so the unchanged f64 rescore + boundary repair restores
    exact results."""
    def contract(a, b, mxu=jax.lax.Precision.DEFAULT):
        return jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())), precision=mxu,
            preferred_element_type=jnp.float32)

    passes = mxu_passes(precision, d.dtype)
    if passes == 1:
        # bf16 operands ARE the single MXU pass; Mosaic rejects an fp32
        # contract precision on them ("Bad lhs type"). A no-op on a
        # block that arrived bfloat16, the "bf16" form's cast otherwise.
        return contract(
            q.astype(jnp.bfloat16),  # check: lowp-eps=lowp_eps
            d.astype(jnp.bfloat16))  # check: lowp-eps=lowp_eps
    if passes == 3:
        q_hi, q_lo = split_bf16(q)
        d_hi, d_lo = split_bf16(d)
        return contract(jnp.concatenate([q_hi, q_hi, q_lo], axis=1),
                        jnp.concatenate([d_hi, d_lo, d_hi], axis=1))
    return contract(q, d, jax.lax.Precision.HIGHEST)


#: what the query block is multiplied by before the cross term, by
#: score: the block the lists order is then norms + cross ("l2") or the
#: cross term itself ("ip"), one multiply an element fewer. A power of
#: two and a sign: exact in every first-pass form (the bf16 halves of
#: -2 q are -2 times q's, every product and every float32 partial sum
#: scales with them), so the scores are the unscaled form's to the bit.
_Q_SCALE = {"l2": -2.0, "ip": -1.0}


def _score_block(qn, dn, cross, real, score: str):
    """The (tq, sw) block the running lists order, ASCENDING, from the
    cross term of the SCALED queries (_Q_SCALE), the two norm planes
    and ``real`` (1, sw), which lanes hold rows of the corpus: the
    others (sentinels, positions >= n_real) read +inf, at no cost an
    element. "l2": the squared distance by the norm expansion |q|^2 +
    |d|^2 - 2 q.d, clamped at 0 (rounding can push a near-duplicate's
    below); a sentinel's |d|^2 is read as +inf (its zero-padded row's
    cross term is 0, so the sum is +inf). "ip": -q.d alone, so that
    the largest inner product is the smallest entry and the
    extraction, the floor, the masks and every list downstream stay as
    they are: no norm plane is added and there is NO clamp (the best
    rows' entries are negative; a row orthogonal to the query reads 0);
    one ``max`` against a row of -inf (real) and +inf (sentinel) is the
    mask."""
    if score == "ip":
        return jnp.maximum(cross, jnp.where(real, -jnp.inf, jnp.inf))
    return jnp.maximum(qn + jnp.where(real, dn, jnp.inf) + cross, 0.0)


@functools.partial(jax.jit, static_argnames=("precision", "score"))
def _slab_scores(q, qn, d, dn, nlive, floor, *, precision: str, score: str):
    """One slab of a block's scores, (tq, sw), from the scaled query
    block, the slab's rows and norms: the cross term, the expansion
    with the sentinel mask riding in the norm row (lanes from ``nlive``
    on hold no row of the corpus: _score_block) and the per-row floor
    of the multi-pass extraction (engine.single
    ._solve_extract_multipass: candidates strictly below it were
    captured by an earlier pass; None where the caller has none).

    A jitted function, like _fold_slab below: the kernels a process
    traces (a fresh and a carried one a fold program, a program a
    bucket) then share ONE trace of a slab (measured, PR 47: with the
    slab's work written inline in ``jnp`` calls, ten slabs a kernel,
    the warm-up of every process took 4.7 s longer on the chip's host,
    compile cache warm, all of it tracing and lowering)."""
    sw = d.shape[0]
    cross = _dot_cross(q, d, precision)
    live = jax.lax.broadcasted_iota(jnp.int32, (1, sw), 1) < nlive
    dist = _score_block(qn, dn, cross, live, score)
    if floor is not None:
        dist = jnp.where(dist < floor, jnp.inf, dist)
    return dist


@functools.partial(jax.jit, static_argnames="fold")
def _fold_slab(dist, *, fold: int):
    """A slab's (tq, 128 * fold) scores folded to one lane vector: a
    bucket's minimum, the slab position it came from (a strict `<` over
    ascending lane vectors keeps the lowest among equals) and its
    second smallest, each (tq, 128). In lax primitives: the steps are
    unrolled, and a ``jnp`` call (an operator on a traced value too)
    is a jit trace of its own."""
    lax = jax.lax
    tq = dist.shape[0]
    r = lax.slice(dist, (0, 0), (tq, 128))
    g = lax.full((tq, 128), 0, jnp.int32)
    r2 = lax.full((tq, 128), jnp.inf, jnp.float32)
    for v in range(1, fold):
        e = lax.slice(dist, (0, v * 128), (tq, (v + 1) * 128))
        lt = lax.lt(e, r)
        r2 = lax.min(r2, lax.max(r, e))
        r = lax.select(lt, e, r)
        g = lax.select(lt, lax.full((tq, 128), v, jnp.int32), g)
    lane = lax.broadcasted_iota(jnp.int32, (tq, 128), 1)
    return r, lax.add(lax.mul(g, lax.full((tq, 128), 128, jnp.int32)),
                      lane), r2


def _kernel(sc_ref, q_ref, d_ref, qn_ref, dn_ref, f_ref, cd_ref, ci_ref,
            od_ref, oi_ref, it_ref, dist_s, *fold_s, kc: int, fresh: bool,
            ne: int, unroll: int = 1, block_skip: bool = True,
            mxu_gate: bool = False, precision: str = "f32",
            score: str = "l2", fold: int = 0, floored: bool = True):
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    tq, tn = dist_s.shape
    tq_kc = (tq, kc)
    # Runtime scalars from SMEM (static args here would recompile the
    # Mosaic kernel once per chunk — id_base differs every chunk).
    n_real = sc_ref[0, 0]
    id_base = sc_ref[0, 1]

    # Under the two-level selection the block's scores are made a SLAB
    # at a time, a dot a slab: ``fold`` lane vectors (128 * fold lanes)
    # each, tn / (128 * fold) of them (one slab, the block, without
    # it). A slab's expansion, masks and fold then sit beside the NEXT
    # slab's MXU passes in the schedule (within a round of the slab loop
    # below), where one dot over the block left the VPU's share of them
    # until the last pass had popped (the compiler's own schedule,
    # PR 47: of a float32 128-d visit's 13 244 bundles before the
    # loops, 2 000 ran with the MXU idle).
    sw = 128 * fold if fold else tn
    nslab = tn // sw
    slane = jax.lax.broadcasted_iota(jnp.int32, (tq, sw), 1)

    def scores():
        """The visit's first half: the block's scores into the scratch
        (the first block of a fresh fold seeds the lists on the way)
        and what the selection needs to know of them against the rows'
        thresholds AT THE VISIT'S START, t0 (a threshold only falls
        during a visit, so every entry the visit can insert is strictly
        under it), two int32 scalars:

        - ``go``: some row has an entry under its threshold (else the
          block is skipped: 0 recorded iterations);
        - ``hid``: the rounds must run at FULL width. Always, without
          the fold pass. With it, a slab's ``fold`` lane vectors fold
          to ONE: bucket l of slab f holds the entries at block
          positions f sw + l, f sw + 128 + l, ...; the pass keeps its
          minimum ``r``, the block position it came from ``g`` (a
          strict `<` over ascending vectors keeps the lowest among
          equals) and its second smallest ``r2``, all three in
          registers while the slab lasts (accumulators that live
          across the block go through VMEM every step, and the one
          store a cycle then bounds the pass). No bucket with r2 < t0:
          every entry under the threshold is its bucket's minimum, the
          rounds over the folded (tq, tn / fold) array are exact and a
          masked bucket needs no refill. Some bucket hides a second
          entry under its minimum (as many entries under t0 in the
          block as in ``r`` is the same test, counted; ``max`` and
          ``min`` are one operation an element fewer): the tile takes
          the full-width loop over the untouched scratch, whole (its
          rows share one loop)."""
        q = q_ref[:] * _Q_SCALE[score]
        qn = qn_ref[:]
        floor = f_ref[:] if floored else None
        nlive = n_real - j * tn     # the block's rows of the corpus
        def slab(f, acc):
            """Slab ``f`` (a Python int, or the index of the loop below)
            into the scratch and, folded, into the two folded arrays;
            ``acc``: the minima of r and r2 over the slabs so far."""
            static = isinstance(f, int)
            lo = f * sw if static else pl.multiple_of(f * sw, sw)
            cols = slice(lo, lo + sw) if static else pl.ds(lo, sw)
            # Sentinel rows are the block positions from n_real on
            dist = _slab_scores(q, qn, d_ref[cols, :], dn_ref[:, cols],
                                nlive - lo, floor, precision=precision,
                                score=score)
            if fresh and static and lo < kc:
                # The first block seeds the running lists with its first
                # kc columns (cheaper than extracting kc entries one loop
                # pass at a time): this slab's share of them, all of
                # them unless the list is wider than a slab.
                n = min(kc - lo, sw)

                @pl.when(j == 0)
                def _():
                    kpos = lo + jax.lax.broadcasted_iota(jnp.int32, (tq, n),
                                                         1)
                    od_ref[:, lo:lo + n] = jax.lax.slice(dist, (0, 0),
                                                         (tq, n))
                    oi_ref[:, lo:lo + n] = jnp.where(kpos < n_real,
                                                     id_base + kpos, -1)
                dist = jnp.where((j == 0) & (slane < n), jnp.inf, dist)
            dist_s[:, cols] = dist
            if not fold:
                return dist, None
            r, pos, r2 = _fold_slab(dist, fold=fold)
            r_s, g_s = fold_s
            at = slice(f * 128, (f + 1) * 128) if static \
                else pl.ds(pl.multiple_of(f * 128, 128), 128)
            r_s[:, at] = r
            g_s[:, at] = pos + lo
            if acc is None:
                return r, r2
            return jax.lax.min(acc[0], r), jax.lax.min(acc[1], r2)

        # The slabs that seed a fresh fold's lists are written out (the
        # first, unless the list is wider than a slab); the others run
        # as a loop, up to five a round, its body traced and lowered
        # once. Written out ten times a kernel they doubled the time a
        # process spends in Python on its programs, compile cache warm
        # (PR 47, the chip's host: +0.8 s a fold program, +4.3 s of
        # ``bigann.steady``'s set-up of 48), and the compiler overlaps
        # one slab's VPU work with the next one's MXU passes only inside
        # a round: ten slabs in two rounds are ~5% more bundles a visit
        # than all ten written out, one a round 23-49% more
        # (tools/kernel_bundles.py).
        peeled = min(-(-kc // sw), nslab) if fresh else 0 if fold else 1
        inf = jnp.full((tq, 128), jnp.inf, jnp.float32)
        acc = None if peeled else (inf, inf)
        for f in range(peeled):
            acc = slab(f, acc)
        rest = nslab - peeled
        if fold and rest > 0:
            group = next(g for g in (5, 4, 3, 2, 1) if rest % g == 0)

            def slabs(i, acc):
                for f in range(group):
                    acc = slab(peeled + i * group + f, acc)
                return acc

            acc = jax.lax.fori_loop(0, rest // group, slabs, acc)
        rmin, r2min = acc
        t0 = jnp.max(od_ref[:], axis=1, keepdims=True)      # (tq, 1)
        if fold:
            hid = jnp.max((jnp.min(r2min, axis=1, keepdims=True)
                           < t0).astype(jnp.int32))
        else:
            # the block minimum of the block-skip test is the block's
            # (under the fold it is the folded array's: that pass goes)
            hid = jnp.int32(1)
        if not block_skip:
            return jnp.int32(1), hid
        # Threshold-gated block skipping: strict `<` matches the
        # extraction's `m < T`, so a skipped block is exactly a block
        # whose first round would have inserted nothing.
        bmin = jnp.min(rmin, axis=1, keepdims=True)         # (tq, 1)
        return jnp.max((bmin < t0).astype(jnp.int32)), hid

    if not fresh:
        @pl.when(j == 0)
        def _():
            od_ref[:] = cd_ref[:]
            oi_ref[:] = ci_ref[:]
    if not mxu_gate:
        go, hid = scores()
    else:
        # Fused streaming megakernel (ops.pallas_fused): the current
        # k-th-best thresholds gate the MXU TILE itself, not just the
        # extraction scan. A sound per-row lower bound on every distance
        # in the block needs only the norms already streamed in:
        # |q - d|^2 >= (|q| - |d|)^2, minimized over the block's real
        # |d| range [mn, mx] — zero when |q| falls inside it. The bound
        # is deflated by the engines' staging-eps cancellation margin
        # (engine.finalize.staging_eps, same constants) so f32 rounding
        # in the norm-expansion distance can never make a computed
        # distance fall below it: a gated-out block is exactly a block
        # whose extraction would have inserted nothing, and the kernel
        # skips the matmul, the scan, and the scratch store outright
        # (0 recorded iterations) — block skipping made free.
        from dmlp_tpu.engine.finalize import (EPS_CANCEL_COEF,
                                              EPS_REL_F32, LOWP_COEF,
                                              ip_coef)
        na = q_ref.shape[1]
        lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1)
        real = (j * tn + lane1) < n_real
        dn = dn_ref[:]
        sdn = jnp.sqrt(jnp.maximum(dn, 0.0))
        if score == "ip":
            # No entry of the block is below -|q| max|d| (Cauchy-
            # Schwarz over the block's real rows), deflated by what the
            # pass can add to a COMPUTED inner product. The norms are
            # the staged values' own, so that is the float32
            # coefficient (the norms' and the dot's accumulation) and
            # the form's: engine.finalize.ip_coef, the host bound's
            # constants. An all-sentinel block reads +inf (NaN for a
            # zero query): either compares False below, the correct
            # skip.
            mx = jnp.max(jnp.where(real, sdn, -jnp.inf))
            sq = jnp.sqrt(jnp.maximum(qn_ref[:], 0.0))
            lb_safe = -(sq * mx) * (1.0 + ip_coef("float32", na,
                                                  precision))
        else:
            mn = jnp.min(jnp.where(real, sdn, jnp.inf))
            mx = jnp.max(jnp.where(real, sdn, -jnp.inf))
            dn_hi = jnp.max(jnp.where(real, dn, 0.0))
            qn = qn_ref[:]
            sq = jnp.sqrt(jnp.maximum(qn, 0.0))
            gap = jnp.maximum(jnp.maximum(mn - sq, sq - mx), 0.0)
            lb = gap * gap                                     # (tq, 1)
            scale = jnp.maximum(qn, 0.0) + dn_hi
            # A low-precision pass perturbs the COMPUTED distances the
            # gate reasons about by up to lowp_eps more than f32
            # rounding alone, so the deflation margin widens by
            # LOWP_COEF * scale (the device form of
            # engine.finalize.lowp_eps — same composition the host
            # prune/hazard tests apply).
            eps = (EPS_REL_F32 * jnp.sqrt(lb * scale)
                   + (EPS_CANCEL_COEF * (na + 2)
                      + LOWP_COEF[precision]) * scale)
            # All-sentinel blocks drive lb (and hence eps) to +inf; the
            # inf - inf NaN compares False below, which IS the correct
            # skip.
            lb_safe = jnp.maximum(lb - eps, 0.0)
        t_cur = jnp.max(od_ref[:], axis=1, keepdims=True)  # (tq, 1)
        gate_on = jnp.max((lb_safe < t_cur).astype(jnp.int32)) > 0
        if fresh:
            # The first block must compute: it seeds the running lists
            # (and od_ref holds garbage before that, making t_cur
            # meaningless — forced on, its value never matters).
            gate_on = gate_on | (j == 0)

        go, hid = jax.lax.cond(
            gate_on, scores, lambda: (jnp.int32(0), jnp.int32(0)))

    w = tn // ne
    wlane = jax.lax.broadcasted_iota(jnp.int32, (tq, w), 1)
    kiota = jax.lax.broadcasted_iota(jnp.int32, tq_kc, 1)

    def insert(m, pos):
        """The running lists take the candidate ``m`` (tq, 1), at block
        position ``pos``, in place of a row's current k-th best wherever
        it is strictly better; returns where it was."""
        rd = od_ref[:]
        t = jnp.max(rd, axis=1, keepdims=True)              # (tq, 1)
        better = m < t                                      # (tq, 1)
        wi = jnp.min(jnp.where(rd == t, kiota, kc), axis=1,
                     keepdims=True)
        ins = better & (kiota == wi)
        od_ref[:] = jnp.where(ins, m, rd)
        oi_ref[:] = jnp.where(ins, id_base + j * tn + pos, oi_ref[:])
        return better

    def round_():
        # Each quarter independently: find its min, insert if it beats the
        # row's current k-th best, mask it out. All ops are 2D with
        # lane-aligned static slices — 3D reshapes / lane-offset slices
        # blow up the Mosaic compile.
        # (A pl.when skip of the argmin/insert/mask passes for no-improve
        # halves measured SLOWER — 79.5 vs 68.3 ms — the predication
        # overhead beats the saved passes; keep the straight-line form.)
        go = jnp.int32(0)
        for e in range(ne):
            qd = dist_s[:, e * w:(e + 1) * w]               # (tq, w)
            m = jnp.min(qd, axis=1, keepdims=True)          # (tq, 1)
            am = jnp.min(jnp.where(qd == m, wlane, w), axis=1,
                         keepdims=True)                     # (tq, 1)
            better = insert(m, e * w + am)
            dist_s[:, e * w:(e + 1) * w] = jnp.where(
                better & (wlane == am), jnp.inf, qd)
            go = go + jnp.max(better.astype(jnp.int32))
        return go

    def narrow_round():
        # The same round over the folded array: the row minimum, the
        # lowest BLOCK POSITION among equals (not the lowest lane),
        # insert, mask the bucket. One candidate a row a round.
        r_s, g_s = fold_s
        r, g = r_s[:], g_s[:]
        m = jnp.min(r, axis=1, keepdims=True)
        am = jnp.min(jnp.where(r == m, g, tn), axis=1, keepdims=True)
        better = insert(m, am)
        r_s[:] = jnp.where(better & (g == am), jnp.inf, r)
        return jnp.max(better.astype(jnp.int32))

    def loop(one_round, go0, width):
        """Rounds of ``one_round`` until one inserts nothing (at most
        ``width`` + 1: a round that inserts masks an entry); never
        entered where ``go0`` is false. Returns the rounds run."""
        def body(state):
            it, _ = state
            # `unroll` extraction rounds per loop-condition sync.
            # Correctness needs only the LAST round's found-any flag: if
            # that round found nothing, no remaining candidate beats any
            # row's threshold.
            for _u in range(unroll - 1):
                one_round()
            return it + 1, one_round() > 0

        return jax.lax.while_loop(
            lambda s: s[1] & (s[0] <= width), body, (jnp.int32(0), go0))[0]

    # One of the two loops runs, or neither (0 recorded iterations: a
    # gate or the block-skip test let the visit run no round).
    wide = ((go > 0) & (hid > 0)).astype(jnp.int32)
    iters = loop(round_, wide > 0, tn)
    if fold:
        iters = iters + loop(narrow_round, (go > 0) & (hid == 0),
                             tn // fold)
    # Diagnostics: lane j of this tile's block holds, in row 0, the
    # rounds the visit ran and, in row 1, whether it ran them at full
    # width (an iota-select avoids dynamic-lane scalar stores).
    njs = it_ref.shape[1]
    ji = jax.lax.broadcasted_iota(jnp.int32, (tq, njs), 1)
    ri = jax.lax.broadcasted_iota(jnp.int32, (tq, njs), 0)

    @pl.when(j == 0)
    def _():
        it_ref[:] = jnp.zeros((tq, njs), jnp.int32)
    it_ref[:] = jnp.where(ji == j, jnp.where(ri == 1, wide, iters),
                          it_ref[:])

    # Output blocks map to (i, 0): they stay VMEM-resident across the
    # data-block sweep and flush once after the last block.
    del nj


def row_norms(d: jax.Array) -> jax.Array:
    """Squared L2 norms of the rows of ``d`` (..., A) as the kernel's
    distance expansion reads them: float32, of the STAGED values (under
    bfloat16 staging the norm of the bf16 row, which is what the cross
    term sees; never the host's float64 norm). The ONE expression: a
    resident engine stages these beside its stack with each chunk
    (serve.engine._update_chunk) and extract_topk computes them for a
    caller that hands none in."""
    d32 = d.astype(jnp.float32)
    return jnp.sum(d32 * d32, axis=-1)


def extract_topk(q_attrs: jax.Array, d_attrs: jax.Array,
                 carry_d: jax.Array | None = None,
                 carry_i: jax.Array | None = None, *, n_real,
                 id_base=0, chunk=None, d_norms: jax.Array | None = None,
                 kc: int, interpret: bool = False,
                 tile_q: int | None = None, tile_n: int | None = None,
                 ne: int | None = None, unroll: int | None = None,
                 fold: int | None = None,
                 block_skip: bool = True, mxu_gate: bool = False,
                 floor: jax.Array | None = None, precision: str = "f32",
                 score: str = "l2", with_wide: bool = False):
    """(queries (Qb, A), data (B, A)) -> (dists (Qb, kc) f32 ascending-ish
    unsorted, ids (Qb, kc) i32, iters (Qb/tq, B/tn) i32 loop counts; 0 =
    the threshold prefilter skipped that block) and, under
    ``with_wide``, a fourth: wide (Qb/tq, B/tn) i32, 1 where the visit's
    rounds ran at FULL width (the two-level selection fell back, or the
    shape takes no fold pass), 0 where they ran over the folded array
    or not at all.
    Rows >= n_real are sentinels; data row j has global id id_base + j.

    The data comes in one of two forms, told apart by its rank. A
    (B, A) block, as a caller that stages a chunk a call holds it (the
    batch engines). Or a RESIDENT (nchunks, B, A) stack
    with ``chunk``, a traced index into it (the resident engines'
    fold): the index rides with ``n_real`` and ``id_base`` in the
    grid's scalar prefetch and the data BlockSpec's index map reads it,
    so the kernel's DMA fetches its (tile_n, A) blocks straight out of
    the stack and the program holds no copy of the chunk (``stack[c]``
    cost one, every chunk of every fold: PERF.md section 6, PR 41). A
    block is the stack form with one chunk and index 0: one
    pallas_call either way.
    ``d_norms`` are the data rows' squared norms (:func:`row_norms` of
    the staged values): (B,) beside a block, (nchunks, 1, B) beside a
    stack, read by the same chunk index. (The unit axis keeps a chunk's
    norms one dense row on the chip, ``T(1,128)``, 4 B a row: as
    (nchunks, B) eight chunks share a tile and Mosaic cannot cut the
    kernel's (1, tile_n) block out of it.) A resident engine stages
    them once with each chunk; without them they are computed here
    from the chunk, a pass over it a call.
    Optional carry (prior running lists, e.g. from a previous chunk) is
    folded in; without it slots pad (+inf, -1). Optional ``floor``
    ((Qb, 1) f32): per-row distance floor — candidates with
    dist < floor are masked out (the multi-pass wide-k driver raises it
    to the previous pass's max − eps each pass).

    tile_q/tile_n/ne/unroll/fold default to the resolved variant
    (resolve_variant; ``fold`` to fold_slabs of the block the tiles in
    force make); pass them explicitly only to override (a
    resident engine's program passes the statics that key its jit, a
    test a tiling of its own). The resolution happens OUT HERE, before
    the jit boundary, so the CONCRETE variant is part of the jit cache
    key.
    ``block_skip`` toggles the threshold-gated block prefilter
    (output-identical either way; off only for A/B measurement).
    ``mxu_gate`` enables the fused
    megakernel's norm-bound MXU tile gating (output-identical;
    ops.pallas_fused.fused_topk is the public face). ``precision``
    ("f32" | "bf16x3" | "bf16": PRECISIONS) selects the FIRST-PASS
    form (_dot_cross): "f32" is one ``HIGHEST`` dot, six MXU passes
    (measured on v5e, PR 36: PERF.md section 6); "bf16x3" splits the
    streamed q/d tiles into bf16 halves in the kernel and spends
    three; "bf16" casts them and spends one; all accumulate in f32.
    Where BOTH operands arrive bfloat16 (rows and queries staged in
    bfloat16) the data BlockSpec streams the bf16 rows as they are,
    half the bytes a visit and no float32 copy of the chunk, and the
    cross term takes ONE pass whatever the form: the "f32" form's own
    value, nothing dropped (mxu_passes; any other pair of dtypes is
    converted to float32 and takes the form as named).
    The candidate lists of the last two deviate from the f32 pass by
    at most that form's
    engine.finalize.lowp_eps per distance, and callers MUST widen
    their prune / hazard / floor bounds by that margin (and, for
    "bf16", the candidate window: resolve_kcap) for the exact pipeline
    to stay byte-identical. Static: part of the jit cache key, resolved
    by callers OUTSIDE every jit (R2 discipline).
    ``score`` ("l2" | "ip": KERNEL_SCORES) is what the lists are ordered by
    (_score_block): the squared distance, or -q.d under "ip", where
    "dists" holds the NEGATED inner products, ascending like any other
    list (the norm planes are still read: the MXU gate's bound is made
    of them). Static like ``precision``: a corpus has one score, its
    engine passes it to every program.

    Gate on supports() first. Output lists are NOT sorted; callers sort by
    the composite key (ops.topk.select_topk) if order matters.
    """
    if (d_attrs.ndim == 3) != (chunk is not None):
        raise ValueError("a (nchunks, B, A) stack takes a chunk index, a "
                         f"(B, A) block none (got {d_attrs.shape}, "
                         f"chunk={chunk!r})")
    v = resolve_variant(kc, d_attrs.shape[-2], q_attrs.shape[0],
                        q_attrs.shape[1])
    # Eager callers pass plain ints for the traced SMEM scalars; under
    # the sanitizer's transfer guard the jit argument conversion would
    # be an implicit host->device transfer — make it explicit here (a
    # traced value, e.g. from the mesh engines' shard_map bodies, passes
    # through untouched).
    import numpy as _onp
    if isinstance(n_real, (int, _onp.integer)):
        n_real = jax.device_put(_onp.int32(n_real))
    if isinstance(id_base, (int, _onp.integer)):
        id_base = jax.device_put(_onp.int32(id_base))
    if isinstance(chunk, (int, _onp.integer)):
        chunk = jax.device_put(_onp.int32(chunk))
    tile_q = v["tile_q"] if tile_q is None else tile_q
    tile_n = v.get("tile_n", _TN) if tile_n is None else tile_n
    ne = v["ne"] if ne is None else ne
    if fold is None:
        # of the block the tiles in force make of this dispatch (the
        # resolved variant's, unless the caller passed tiles of its own)
        b = d_attrs.shape[-2]
        fold = fold_slabs(_tile(b, tile_n, 128 * ne)) \
            if b % (128 * ne) == 0 else 0
    if precision not in PRECISIONS:
        raise ValueError(f"unsupported first-pass precision {precision!r} "
                         "(int8 is the gated follow-on — see ROADMAP)")
    if score not in KERNEL_SCORES:
        raise ValueError(f"unknown kernel score {score!r} (one of "
                         f"{KERNEL_SCORES}; config.kernel_score maps an "
                         "engine's)")
    out = _extract_topk_jit(
        q_attrs, d_attrs, carry_d, carry_i, n_real=n_real,
        id_base=id_base, chunk=chunk, d_norms=d_norms, kc=kc,
        interpret=interpret,
        tile_q=tile_q, tile_n=tile_n, ne=ne,
        unroll=v["unroll"] if unroll is None else unroll, fold=fold,
        block_skip=block_skip, mxu_gate=mxu_gate, floor=floor,
        precision=precision, score=score)
    return out if with_wide else out[:3]


@functools.partial(
    jax.jit, static_argnames=("kc", "interpret", "tile_q", "tile_n", "ne",
                              "unroll", "fold", "block_skip", "mxu_gate",
                              "precision", "score"))
def _extract_topk_jit(q_attrs, d_attrs, carry_d, carry_i, *, n_real,
                      id_base, kc: int, interpret: bool, tile_q: int,
                      tile_n: int, ne: int, unroll: int, block_skip: bool,
                      mxu_gate: bool, floor, precision: str = "f32",
                      chunk=None, d_norms=None, score: str = "l2",
                      fold: int = 0):
    qb, a = q_attrs.shape
    b = d_attrs.shape[-2]
    tq = _tile(qb, tile_q, 8)
    tn = _tile(b, tile_n, 128 * ne)
    # Validate the ACTUAL tiling (supports() only covers the defaults):
    # the fresh-seed slice and quarter layout need kc <= tn, and the
    # distance scratch + double-buffered blocks must fit VMEM.
    if not (qb % 8 == 0 and b % (128 * ne) == 0 and kc <= tn
            and kc <= 512 and tn % (128 * max(fold, 1)) == 0
            and vmem_bytes(tq, tn, a, kc, fold) <= _VMEM_BOUND):
        # ValueError, not assert: a caller that skipped supports() must
        # fail loudly under ``python -O`` too, not compute garbage.
        raise ValueError(
            f"untileable (qb={qb}, b={b}, kc={kc}, tq={tq}, tn={tn}, "
            f"ne={ne}, fold={fold})")

    # One form inside: a (nchunks, B, A) stack, its (nchunks, 1, B)
    # norms and a chunk index. A (B, A) block is a stack of one, index 0.
    stack, norms = d_attrs, d_norms
    if stack.ndim == 2:
        stack, chunk = stack[None], 0
    if norms is not None:
        norms = norms.reshape(stack.shape[0], 1, b)
    # Rows staged in bfloat16 reach the kernel AS bfloat16 where the
    # queries hold bfloat16 values too (both operands arrive bfloat16:
    # the engines stage both through one dtype): half the block's DMA,
    # one MXU pass (_dot_cross), no float32 copy of the chunk. The
    # query block is small and resident across the data axis: it stays
    # float32 in HBM (lossless) and is cast back a visit, which spares
    # short query tiles bfloat16's 16-sublane tile. Float32 rows are
    # read as they are. Any other pair of dtypes is converted, as
    # before.
    q32 = q_attrs.astype(jnp.float32)
    qn = jnp.sum(q32 * q32, axis=-1, keepdims=True)
    convert = stack.dtype != jnp.float32 \
        and not q_attrs.dtype == stack.dtype == jnp.bfloat16
    if convert or norms is None:
        # Nothing staged to read in place (no norms handed in, or a
        # pair of dtypes no engine stages): the chunk alone is taken
        # out of the stack, a stack of one again, never the stack.
        blk = stack[chunk]
        norms = row_norms(blk) if norms is None else norms[chunk, 0]
        if convert:
            blk = blk.astype(jnp.float32)
        stack, norms, chunk = blk[None], norms[None, None], 0

    fresh, floored = carry_d is None, floor is not None
    if fresh:
        carry_d = jnp.full((qb, kc), jnp.inf, jnp.float32)
        carry_i = jnp.full((qb, kc), -1, jnp.int32)
    if floor is None:
        floor = jnp.full((qb, 1), -jnp.inf, jnp.float32)

    # The grid's scalar prefetch (SMEM): what the kernel reads (n_real,
    # id_base) and what the data's and the norms' index maps read (chunk).
    scalars = jnp.asarray([[n_real, id_base, chunk]], jnp.int32)
    grid = (qb // tq, b // tn)
    kern = functools.partial(_kernel, kc=kc, fresh=fresh, ne=ne,
                             unroll=unroll, block_skip=block_skip,
                             mxu_gate=mxu_gate, precision=precision,
                             score=score, fold=fold, floored=floored)
    # The name is what a profiler capture and the compiled HLO show for
    # this custom call (``%dmlp_topk_fused.1 = ... custom-call(...)``):
    # it states the form, so a trace tells the MXU-gated kernel from the
    # ungated one and a chunk's first fold (no carry) from the carried.
    name = ("dmlp_topk_fused" if mxu_gate else "dmlp_topk_extract") \
        + ("_fresh" if fresh else "")
    out_d, out_i, out_iters = pl.pallas_call(
        kern,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tq, a), lambda i, j, sc: (i, 0)),
                pl.BlockSpec((None, tn, a),
                             lambda i, j, sc: (sc[0, 2], j, 0)),
                pl.BlockSpec((tq, 1), lambda i, j, sc: (i, 0)),
                pl.BlockSpec((None, 1, tn),
                             lambda i, j, sc: (sc[0, 2], 0, j)),
                pl.BlockSpec((tq, 1), lambda i, j, sc: (i, 0)),
                pl.BlockSpec((tq, kc), lambda i, j, sc: (i, 0)),
                pl.BlockSpec((tq, kc), lambda i, j, sc: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((tq, kc), lambda i, j, sc: (i, 0)),
                pl.BlockSpec((tq, kc), lambda i, j, sc: (i, 0)),
                # One iters block per query tile (row 0 carries the
                # counts) keeps dim 0 safely "parallel" — a single shared
                # block would be clobbered across megacore cores.
                pl.BlockSpec((tq, b // tn), lambda i, j, sc: (i, 0)),
            ],
            # the distance block and, under the two-level selection,
            # its folded minima and their block positions
            scratch_shapes=[pltpu.VMEM((tq, tn), jnp.float32)] + (
                [pltpu.VMEM((tq, tn // fold), jnp.float32),
                 pltpu.VMEM((tq, tn // fold), jnp.int32)] if fold else []),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((qb, kc), jnp.float32),
            jax.ShapeDtypeStruct((qb, kc), jnp.int32),
            jax.ShapeDtypeStruct((qb, b // tn), jnp.int32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=96 * 2**20),
        interpret=interpret,
    )(scalars, q32, stack, qn, norms, floor, carry_d, carry_i)
    return out_d, out_i, out_iters[::tq], out_iters[1::tq]
