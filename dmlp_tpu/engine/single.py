"""Single-chip KNN engine — the minimum end-to-end slice (survey §7 L1).

One jitted function does what the reference's whole MPI choreography does on
a grid of CPU ranks (engine.cpp:20-351): distances ride the MXU as a matmul
(dmlp_tpu.ops.distance), selection is an exact-tie-break sort
(dmlp_tpu.ops.topk), and queries/data stream in blocks so the (Q, N) distance
matrix never materializes. The scatter/bcast phases (engine.cpp:62-209)
vanish: one chip holds the (padded) arrays in HBM.

Two output paths:

- ``candidates()`` + host finalize (default, ``run()``): the device returns
  top-(kmax + margin) candidate lists; the host rescores them in float64 and
  applies vote/report semantics — checksum parity with the float64 golden
  model while the MXU does the O(Q*N*A) work in f32/bf16.
- ``run_device_full()``: vote + report ordering on-device too (benchmark
  path; no float64 rescue).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.finalize import (band_widths, boundary_band,
                                      boundary_clearance,
                                      boundary_hazard, finalize_host,
                                      lowp_eps, repair_boundary_overflow,
                                      rescore_f64, staging_eps)
from dmlp_tpu.io.grammar import KNNInput, subset_queries
from dmlp_tpu.io.report import QueryResult
from dmlp_tpu.obs import counters as obs_counters
from dmlp_tpu.obs import memwatch, telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.obs.trace import span as obs_span
from dmlp_tpu.ops.topk import TopK, init_topk, make_block_step, streaming_topk
from dmlp_tpu.ops.vote import majority_vote, report_order
from dmlp_tpu.resilience import degrade as rs_degrade
from dmlp_tpu.resilience import inject as rs_inject
from dmlp_tpu.resilience import retry as rs_retry

# Per-chunk distance-tile budget for the pipelined driver (bytes). The live
# tile is (query_rows x chunk_rows) f32; chunk/query blocking keeps it under
# this so HBM never holds a Q x N matrix.
_TILE_BUDGET = 1 << 30

# Max staged-but-unfolded chunks in flight. The enqueue loop runs far
# ahead of device execution (staging, not the host, is the bottleneck),
# and every jnp.asarray allocates its device buffer immediately — without
# backpressure a dataset LARGER than HBM would stage itself to death
# before the first folds free their chunks. Blocking on the fold output
# W chunks back caps device residency at ~W chunks while still keeping
# the transfer pipe full (W * 51200 * 64 * 4B ~= 105 MB at the default
# chunk plan).
_CHUNK_WINDOW = 8


class ChunkThrottle:
    """Sliding-window backpressure for chunked staging loops: feed each
    chunk's fold output to tick(); it blocks on the output from
    _CHUNK_WINDOW chunks ago, so at most that many staged chunks (plus
    their folds) are ever in flight on device."""

    def __init__(self, window: int = _CHUNK_WINDOW):
        self._window = window
        self._pending: list = []

    def tick(self, fold_out) -> None:
        self._pending.append(fold_out)
        if len(self._pending) > self._window:
            jax.block_until_ready(self._pending.pop(0))


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def np_staging_dtype(staging: str):
    """Host wire dtype for a staging mode ("float32" | "bfloat16").

    The engines convert on HOST and stage with explicit
    ``jax.device_put``: the sanitizer's transfer guard
    (``--sanitize`` / dmlp_tpu.check.sanitize) disallows *implicit*
    transfers, and staging is the one transfer that is the engines'
    explicit job — ``jnp.asarray`` staging would trip the guard on TPU.
    """
    if staging == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    return np.float32


def stage_put(arr: np.ndarray, staging: str = "float32"):
    """Explicit (async) host->device put in the staging wire dtype —
    the transfer-guard-proof spelling of ``jnp.asarray(arr, dtype)``.

    The one staging chokepoint every chunked driver feeds through, so
    it is a registered injection site (``single.stage_put``) and the
    put carries the transient-retry wrapper: re-staging the same host
    array is idempotent by construction. The fire rides INSIDE the
    retried op so an injected transient is consumed by attempt 1 and
    the retry's re-put lands."""
    host = np.asarray(arr, np_staging_dtype(staging))

    def _op():
        rs_inject.fire("single.stage_put")
        return jax.device_put(host)

    return rs_retry.call_with_retry(_op, "single.stage_put")


def resilient_get(values, site: str = "single.fetch"):
    """Fenced device readback (the fetch IS the fence) with fault
    injection + bounded transient retry — ``jax.device_get`` of
    already-enqueued values is idempotent, so a flaky readback retries
    without re-dispatching the solve. ``$DMLP_TPU_OP_TIMEOUT_S`` (off
    by default — the readback IS the solve fence, so its normal
    duration is the solve's) additionally bounds each attempt with a
    worker-thread deadline; the resulting ``OperationTimeout``
    classifies transient, so a slow-but-recoverable fetch retries and
    the ``timeouts`` counter records it."""
    deadline = float(os.environ.get("DMLP_TPU_OP_TIMEOUT_S", "0") or 0)

    def _get():
        rs_inject.fire(site)
        return jax.device_get(values)  # check: allow-host-sync

    def _op():
        # The deadline is part of the resilience layer: with the
        # DMLP_TPU_RESILIENCE=0 kill switch the wrapper must be a
        # direct call (no worker thread, no unretried OperationTimeout).
        if deadline > 0 and rs_retry.resilience_enabled():
            return rs_retry.call_with_timeout(_get, deadline, site=site)
        return _get()

    return rs_retry.call_with_retry(_op, site)


def plan_chunks(n: int, granule: int, target: int | None) -> Tuple[int, int, int]:
    """Chunked-staging plan shared by the pipelined and extract drivers:
    (npad, nchunks, chunk_rows) — ~``target``-row chunks (default 51200,
    a pre-round choice, unverifiable: big enough that per-chunk merge
    work stays negligible, small enough that the first fold starts while
    later chunks are still in flight) of whole ``granule`` blocks covering
    ``n``. Large granules can make the final chunk all padding; drivers
    skip staging it.

    A chunk is 51 200 ROWS whatever they weigh: 26 MB at 128 float32
    attributes, 210 MB at 960 staged on 1 024 lanes. Measured on the
    resident fold at that width (PR 31, TPU v5 lite, 20 chunks a fold at
    q1024): the pass that computes a chunk's row norms and indexes it
    out of the stack takes 12.2 ms a fold beside 88.5 ms of kernel
    (3.0 beside 61.9 ms at 128 attributes over 82 chunks); the rows a
    chunk holds were not re-tuned at this width."""
    npad = round_up(max(n, 1), granule)
    t = round_up(target or 51200, granule)
    nchunks = max(1, -(-npad // t))
    chunk_rows = round_up(-(-npad // nchunks), granule)
    return npad, nchunks, chunk_rows


def fit_blocks(n: int, target_block: int, granule: int = 8) -> int:
    """A data_block (multiple of ``granule``, <= ~target_block) whose
    round_up padding wastes < granule * nblocks rows of n.

    Plain round_up(n, target_block) can waste up to target_block - 1 rows
    (31% at n=200k, target=64k) — real compute, since padded rows still ride
    the matmul. Shrinking the block to ~n/nblocks keeps the scan length and
    the waste both minimal. The "seg" selection needs granule=128 (whole
    lane-width segments).
    """
    n = max(n, 1)
    nblocks = max(1, -(-n // max(target_block, granule)))
    return round_up(-(-n // nblocks), granule)


def resolve_kcap(cfg: EngineConfig, kmax: int, select: str, cap: int,
                 staging: str = "float32",
                 precision: str | None = None,
                 na: int | None = None) -> int:
    """Device candidate-list width: kmax + margin, rounded to 8, clamped to
    [kmax, cap]. The fast selection paths get >= 8 slack beyond kmax even
    with margin 0: the tie-overflow detector compares the k-th and last
    candidate, which coincide without slack (degenerate all-repair).

    bfloat16 staging deepens the margin with k (96 + k/2): its rounding
    reorders device distances non-monotonically by up to
    finalize.staging_eps, and the eps-aware hazard test only stays quiet
    (no oracle-repair fallback) when the candidate horizon clears the
    k-th distance by more than eps — deeper lists buy that clearance
    where distances grow dense. Measured at the 200k x 10k x 64 benchmark
    shape: a 32-slot window leaves 3453/10000 queries flagged, 64 slots
    71, 96 slots 0 — the constant is that measurement plus headroom; the
    (vectorized-oracle) repair stays as the sound backstop for inputs
    whose distance density outruns it. Measured again where the default
    dtype serves a published slice (PR 38, TPU v5 lite, 10^7 uniform
    [0, 255) rows of 128 attributes, k = 10 in the k16 bucket's
    120-slot window, 16 384 distinct queries a seed): 0 to 5 queries
    flagged by seed, 12 of 147 456 over nine seeds (about 1 in 10^4),
    and the batch's tightest query clears its bound 1.06-1.07 x in the
    median batch of 1024 (0.99-1.00 x in the worst): the window sits AT
    the bound there, not 2.3 x over it as the reckoning from a normal
    tail had it. The constant stays: 96 slots more would clear ~1.5 x
    and triple the float64 gather of every query to spare one in 10^4
    a retry, and the serving engine now repairs a flagged query on the
    device at 512 slots (serve.engine.ResidentEngine._retry_begin: every
    flagged query of those runs cleared there) for about a fifth of a
    batch's cycle (~35 ms of 188: PERF.md section 5).

    ``precision`` is the first-pass dot precision the window must clear
    (config.resolve_precision when None — the inflation is planned from
    the CONFIGURED precision, not the active rung: a bf16-sized window
    fed by an f32 pass is merely generous, never unsound, and planning
    it once keeps the window static across ladder steps). "bf16" reuses
    the bf16-staging depth (96 + k/2): the cast perturbs every distance
    by at most finalize.lowp_eps, the same coef * (qn + dn_max) shape
    as the staging cancellation term that margin was calibrated for.

    ``na`` (the row width; the resident serving engines give it, where a
    repair stalls the one batcher thread) deepens the float32 window
    with the bound it must clear: staging_eps' cancellation term is
    3 * 2^-22 * (na + 2) * (qn + dn_max), so (na + 2) // 40 slots, which
    leaves every row of up to 677 attributes at the 16-slot margin.
    Measured at 960 attributes (PR 31, TPU v5 lite, 10^6 uniform [0, 1)
    rows, k = 10 in a 32-slot window, 57 344 queries): the batch's
    tightest query cleared its bound 1.36-1.48 x in the median batch and
    1.08 x in the worst, none flagged; the k-th to last gap as a sum of
    order-statistic spacings, fitted to those two quantiles, puts a flag
    at 5e-6 a query with 32 slots (one run in twelve of the cell would
    hold one, a host pass over 8 GB of float64), 4e-9 with 40 and, at
    2048 attributes, 2e-8 with the 72 this rule gives. Fast mode takes
    the term too (its hazard test and repair are the same): with its 8
    slots of slack the cell's control repaired about a query a batch,
    ~4.5 s each."""
    if precision is None:
        precision = cfg.resolve_precision(staging)
    extra = cfg.margin if cfg.exact else 0
    if select in ("sort", "topk", "seg", "extract"):
        extra = max(extra, 8)
    if na is not None:
        # In fast mode too: the hazard test and its repair run there.
        extra = max(extra, (na + 2) // 40)
    if precision == "bf16" and cfg.exact:
        extra = max(extra, 96 + kmax // 2)
    if staging == "bfloat16" and cfg.exact:
        extra = max(extra, 96 + kmax // 2)
    elif cfg.exact:
        # f32 staging: the cancellation eps (finalize.staging_eps term 2)
        # scales with qn + dn_max, not with k — at wide k the candidate
        # horizon sits in a DENSE part of the distance spectrum and a
        # constant 8-slot margin stops clearing it (pre-round,
        # unverifiable: at 204800 x 1024 x 64, k=4096 on v5e 809/1024
        # queries flagged, with k/8 extra slots none). Slots are cheap;
        # oracle repairs are not.
        extra = max(extra, kmax // 8)
    return max(min(round_up(kmax + extra, 8), cap), kmax)


def pad_dataset(inp: KNNInput, multiple: int, dtype: np.dtype
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad (attrs, labels, ids) to a multiple of ``multiple`` rows.

    Sentinel rows carry label = -1 and id = -1; the distance kernel masks
    them to +inf (masked_pairwise_sq_l2). This replaces the reference's
    uneven remainder shards (engine.cpp:62-63) — XLA wants static, uniform
    shapes.

    ``dtype`` should be the host-side staging dtype: padding straight into
    float32 halves the memcpy and the host->device bytes relative to staging
    in the parser's float64 (the f64 originals stay available for the exact
    host rescore).
    """
    n = inp.params.num_data
    npad = round_up(max(n, 1), multiple)
    attrs = np.zeros((npad, inp.params.num_attrs), dtype)
    attrs[:n] = inp.data_attrs
    labels = np.full(npad, -1, np.int32)
    labels[:n] = inp.labels
    ids = np.full(npad, -1, np.int32)
    ids[:n] = np.arange(n, dtype=np.int32)
    return attrs, labels, ids


def hetk_split(cfg: EngineConfig, staging: str, ks: np.ndarray,
               num_data: int, gate_rows: int):
    """Heterogeneous-k split plan: (bulk_idx, out_idx) or None.

    k is legal up to num_data (generate_input.py:19) but the extraction
    kernel's running lists cap at kc <= 512 (ops.pallas_extract.supports).
    Without routing, ONE huge-k query pushes every query off the flagship
    kernel onto the streaming select. The split keeps queries whose kcap
    fits on the kernel ("bulk") and streams only the wide-k outliers —
    each query is solved exactly once, on the best path its k admits.
    ``gate_rows`` is the row count the auto-select gate sees (whole
    dataset for the single-chip engine, one shard for the mesh engines).
    """
    if len(ks) == 0 or num_data == 0 or not cfg.use_pallas:
        return None
    if cfg.select not in ("auto", "extract"):
        return None
    if cfg.resolve_select(gate_rows) != "extract":
        return None
    # Largest per-query k whose candidate width still fits the kernel's
    # kc cap (the margin is k- and staging-dependent, resolve_kcap).
    k_fit = next((k for k in range(512, 0, -1)
                  if resolve_kcap(cfg, k, "extract", 1 << 30,
                                  staging) <= 512), 0)
    if k_fit == 0 or int(ks.max()) <= k_fit:
        return None      # everything fits: no routing needed
    bulk = np.nonzero(ks <= k_fit)[0]
    out = np.nonzero(ks > k_fit)[0]
    if bulk.size == 0:
        return None      # nothing the kernel could take
    return bulk, out


class MeasuredIters:
    """Lazy per-site accumulator for the extract/fused kernels'
    iteration diagnostics: ``add()`` chains a tiny on-device ``jnp.sum``
    per dispatch (no-op unless a cost probe is installed), ``done()``
    queues the site's device scalar on ``engine._pending_iters`` for the
    post-fence flush (engine._flush_measured_iters) — ONE copy of the
    protocol for the extract paths instead of one per path."""

    def __init__(self, engine, site: str,
                 shape: Tuple[int, int, int, int]):
        self._on = obs_counters.active() is not None
        self._engine, self._site = engine, site
        self._shape = tuple(shape)
        self._sum = None

    def add(self, iters, wide_iters=None) -> None:
        """``iters``: a dispatch's recorded iterations; ``wide_iters``:
        those of them that ran at full width (the kernel's two-level
        selection: ``iters * wide``), where the caller has them."""
        if self._on:
            s = jnp.stack([jnp.sum(iters),
                           0 if wide_iters is None else jnp.sum(wide_iters)])
            self._sum = s if self._sum is None else self._sum + s

    def done(self) -> None:
        if self._sum is not None:
            self._engine._pending_iters.append(
                (self._site, self._sum, self._shape))


def flush_measured_iters(engine) -> None:
    """Read back an engine's queued extract-loop iters sums (the solve
    is already fenced by the result fetch, so this is a scalar readback,
    not a sync) and hand them to the installed cost probe — the
    MEASURED extraction term of obs.kernel_cost. No-op when nothing was
    queued (no probe, or a non-extract path ran). Shared by the
    single-chip engine and the mesh engines (both queue through
    MeasuredIters onto ``engine._pending_iters``)."""
    pend = getattr(engine, "_pending_iters", [])
    engine._pending_iters = []
    if not pend:
        return
    for site, s, shape in pend:
        try:
            total, wide = jax.device_get(s)  # check: allow-host-sync
            obs_counters.record_measured_iters(site, int(total), shape,
                                               int(wide))
        except Exception:  # check: no-retry
            pass  # observability must never fail the solve


@contextlib.contextmanager
def no_auto_coarsen(engine):
    """Device-full output IS the device ordering (no f64 rescore or host
    repair licenses a coarser dtype there), so dtype="auto" resolves to
    float32 for the duration of a run_device_full; an EXPLICIT
    dtype="bfloat16" is honored — the caller asked for it."""
    if engine.config.dtype == "auto" and engine._staging == "bfloat16":
        engine._staging, engine._dtype = "float32", jnp.float32
        try:
            yield
        finally:
            engine._staging, engine._dtype = "bfloat16", jnp.bfloat16
    else:
        yield


# Widest kmax dtype="auto" may stage bf16 for. The bf16 kcap margin
# (96 + k/2, resolve_kcap) was calibrated inside the extraction kernel's
# window; far beyond it the margin stops clearing the bf16 eps on dense
# distance spectra — pre-round, unverifiable: on v5e at 204800 x 1024 x
# 64, k=4096 EVERY query flagged and the oracle repair swamped what
# bf16 staging saves. Auto therefore prefers
# exact-margin f32 staging for wide-k solves; an EXPLICIT
# dtype="bfloat16" is still honored.
_BF16_AUTO_K_CAP = 512


def staging_for_k(engine, kmax: int):
    """no_auto_coarsen-shaped context: swap dtype="auto" bf16 staging to
    float32 for the duration of a wide-k solve (kmax > _BF16_AUTO_K_CAP)."""
    if kmax > _BF16_AUTO_K_CAP:
        return no_auto_coarsen(engine)
    return contextlib.nullcontext()


def active_precision(engine) -> str:
    """First-pass form a solve ON the resilience ladder runs at
    ("f32" | "bf16x3" | "bf16"; ops.pallas_extract._dot_cross): what
    run() and the serving engines hand their solve.

    A form that drops products needs the backstop that makes it sound,
    the f64 rescore + boundary repair of an exact run.
    candidates() and run_device_full() have none (their output is the
    device ordering): they do not ask here and solve at the default,
    the one ``HIGHEST`` dot, as fast mode does. An exact run's float32
    pass takes config.f32_form's answer for the engine's staging:
    three bf16 passes at float32 staging, on every rung (bfloat16
    staging keeps the name "f32", and its kernel spends one exact
    pass over the bf16 rows: ops.pallas_extract.mxu_passes). "bf16", ONE
    pass, needs more: the config resolves to it
    (config.resolve_precision — ``$DMLP_TPU_PRECISION`` included) and
    the ladder still sits on its top "lowp" rung — the first OOM
    step-down gives the one-pass form (and, on the next plan, its
    inflated window) back before anything else, for the float32 form.
    Resolved OUTSIDE every jit and passed as a static argument, so
    every compiled program keys on the result (R2 discipline).
    Candidate windows deliberately do NOT consult this: resolve_kcap
    plans from the CONFIGURED precision so the window stays static
    across rungs.

    Engines that freeze a precision PLAN at construction (the resident
    serving engines — their bucket kcaps and staged summary-eps
    constants derive from it) expose ``_precision_plan``; the active
    cast clamps to it, so flipping ``$DMLP_TPU_PRECISION`` to "bf16"
    under a server whose windows were planned f32 cannot run a lossy
    pass against uninflated windows. (The f32 flip under a bf16 plan
    is always safe: wider-than-needed windows only.)"""
    return engine.config.resolve_precision(
        engine._staging,
        allow_bf16=getattr(engine, "_degrade_rung", "fused") == "lowp"
        and getattr(engine, "_precision_plan", "bf16") == "bf16")


@functools.partial(jax.jit,
                   static_argnames=("chunk_rows", "k", "select", "use_pallas"))
def _outlier_fold(carry: TopK, q_attrs, battrs, labels_all, lo, n_real, *,
                  chunk_rows, k, select, use_pallas=False) -> TopK:
    """Fold one already-staged data chunk into the huge-k outlier queries'
    running top-k (heterogeneous-k routing). The chunk's labels/ids are
    derived ON DEVICE (labels by dynamic_slice of the once-staged full
    label vector, ids from the chunk's row range) so the outlier path adds
    zero host->device attr traffic — it rides the exact same chunk arrays
    the extraction kernel consumes. ``lo``/``n_real`` are traced scalars:
    one compile serves every chunk."""
    blabels = jax.lax.dynamic_slice(labels_all, (lo,), (chunk_rows,))
    ri = lo + jnp.arange(chunk_rows, dtype=jnp.int32)
    bids = jnp.where(ri < n_real, ri, -1)
    step = make_block_step(select, k, use_pallas, carry.dists.dtype)
    return step(carry, q_attrs, battrs, blabels, bids)


@functools.partial(jax.jit, static_argnames=("k", "select", "use_pallas"))
def _chunk_fold(carry: TopK, q_attrs, battrs, blabels, bids, *, k, select,
                use_pallas=False) -> TopK:
    """Fold one data chunk into the running top-k (pipelined driver step).

    One dispatch per chunk: the host enqueues chunk transfers and fold
    dispatches back-to-back, so the device DMAs chunk i+1 while computing
    chunk i — the async replacement for the reference's scatter-then-compute
    phasing (engine.cpp:62-131, :233-257), which matters here because the
    host->device link (not the MXU) bounds the solve.
    """
    step = make_block_step(select, k, use_pallas, carry.dists.dtype)
    return step(carry, q_attrs, battrs, blabels, bids)


@jax.jit
def _boundary_cols(dists, ks):
    """(kth, last) candidate-distance columns, stacked (2, Q), taken on
    the device. The host applies the staging-eps hazard test to these
    two vectors (engine.finalize.boundary_hazard / staging_eps); the
    (Q, K) distances themselves are read back beside them (fast mode
    reports them; exact mode cuts the float64 rescore to the band the
    bound cannot order: engine.finalize.boundary_band)."""
    kcap = dists.shape[1]
    last = dists[:, kcap - 1]
    kth = jnp.take_along_axis(
        dists, jnp.clip(ks[:, None] - 1, 0, kcap - 1), axis=1)[:, 0]
    return jnp.stack([kth, last])


@functools.partial(jax.jit, static_argnames=("k",))
def _extract_finalize(od, oi, glabels, *, k):
    """Extraction-kernel epilogue: gather labels from global ids and sort
    the (unordered) running lists into the golden selection order
    (dist asc, id desc) — a tiny (Q, K) composite sort."""
    from dmlp_tpu.ops.topk import select_topk
    n = glabels.shape[0]
    labels = jnp.where(oi >= 0, glabels[jnp.clip(oi, 0, max(n - 1, 0))], -1)
    return select_topk(od, labels, oi, k)


def resolve_sweep_kernel(qpad: int, full_rows: int, na: int, kc: int, *,
                         chunk_rows: int, rung: str):
    """(kernel, impl) for a multipass plan's passes 2+, which sweep the
    whole staged array (``full_rows`` = its chunks' rows together) in
    ONE kernel call, ASSERTED to tile it. Both multipass drivers (the
    batch engine's and the resident serving engine's) call this before
    any pass is dispatched. Pass 1 was checked at ``chunk_rows``; that
    the 128 * ne divisibility and the tile caps carry from a chunk to
    its multiples is true of today's variants and of nothing else: the
    variant resolves per row count, so the carry is checked here, on
    the variant the sweep will run with (resolve_topk_kernel gates on
    supports(): whole 128 * ne sub-blocks, a tile no narrower than kc,
    VMEM room: everything the sweep's one call needs), and a tuning
    change that breaks it fails loudly instead of mis-tiling every
    pass after the first."""
    from dmlp_tpu.ops import pallas_fused
    kern, impl = pallas_fused.resolve_topk_kernel(
        qpad, full_rows, na, kc, rung=rung)
    if kern is None:
        raise AssertionError(
            f"multi-pass extract: full-array sweep shape (qb={qpad}, "
            f"rows={full_rows}, a={na}, kc={kc}) is "
            f"untileable even though the per-chunk shape "
            f"(rows={chunk_rows}) tiles — supports() invariants diverged "
            "between the chunked pass 1 and the resident passes 2+")
    return kern, impl


@functools.partial(jax.jit, static_argnames=("staging", "na", "precision"))
def _mp_floor(od, qn, dn_max, *, staging: str, na: int,
              precision: str = "f32"):
    """Next-pass floor, computed ON DEVICE so passes chain without a host
    readback (an inter-pass sync would serialize the passes). Ports
    finalize.staging_eps: floor = max(od) - eps(max(od)); exhausted rows
    (max = inf) get floor = +inf so later passes yield empty lists.
    A first pass that drops products ("bf16x3", "bf16") deepens the
    eps by its finalize.lowp_eps term (the floor must clear that error
    too, or a later pass could skip a candidate the dot pushed below
    the boundary).
    Returns (floor (Q, 1) f32, fd (Q,) f32 for post-hoc stall checks)."""
    from dmlp_tpu.engine.finalize import (EPS_CANCEL_COEF, EPS_REL_BF16,
                                          EPS_REL_F32, LOWP_COEF)
    fd = jnp.max(od, axis=1)
    rel = EPS_REL_BF16 if staging == "bfloat16" else EPS_REL_F32
    scale = qn + dn_max
    eps = (rel * jnp.sqrt(jnp.maximum(fd, 0.0) * scale)
           + (EPS_CANCEL_COEF * (na + 2) + LOWP_COEF[precision]) * scale)
    floor = jnp.where(jnp.isfinite(fd), fd - eps, jnp.inf)
    return floor[:, None].astype(jnp.float32), fd


@functools.partial(jax.jit, static_argnames=("kcap",))
def _mp_merge(dists, ids, glabels, *, kcap):
    """Merge the multi-pass extraction slabs: (Q, P*kc) concatenated
    lists -> dedup by id (eps-overlapped floors re-extract boundary
    candidates on purpose; duplicates carry identical device distances,
    so id-identity is the whole test) -> gather labels -> composite-sort
    to the final (Q, kcap) selection order (dist asc, id desc). Also returns the per-row
    valid-candidate count for the driver's shortfall check."""
    from dmlp_tpu.ops.topk import select_topk
    order = jnp.argsort(ids, axis=1)
    sid = jnp.take_along_axis(ids, order, 1)
    sd = jnp.take_along_axis(dists, order, 1)
    dup = jnp.concatenate([jnp.zeros_like(sid[:, :1], bool),
                           sid[:, 1:] == sid[:, :-1]], axis=1)
    invalid = dup | (sid < 0)
    sd = jnp.where(invalid, jnp.inf, sd)
    sid = jnp.where(invalid, -1, sid)
    n = glabels.shape[0]
    lab = jnp.where(sid >= 0, glabels[jnp.clip(sid, 0, max(n - 1, 0))], -1)
    return select_topk(sd, lab, sid, kcap), jnp.sum(sid >= 0, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("k", "data_block", "select", "use_pallas"))
def _topk_blocks(data_attrs, data_labels, data_ids, q_blocks, *, k,
                 data_block, select, use_pallas=False):
    """All query blocks in one dispatch: ``lax.map`` keeps the live distance
    tile at (query_block x data_block) while avoiding per-block Python
    dispatch + per-block device->host readbacks."""
    return jax.lax.map(
        lambda q: streaming_topk(q, data_attrs, data_labels, data_ids,
                                 k=k, data_block=data_block, select=select,
                                 use_pallas=use_pallas),
        q_blocks)


@functools.partial(jax.jit, static_argnames=("num_labels",))
def _device_epilogue(top: TopK, ks, *, num_labels):
    """Vote + report ordering on-device over (Q, K) candidate lists — the
    reference's result post-processing (engine.cpp:314-347) as a tiny
    epilogue jit shared by every device-full select path (including the
    flagship extraction kernel, whose lists _solve already sorts)."""
    rd, rids, in_k = report_order(top, ks)
    valid = in_k & (top.ids >= 0)
    predicted = majority_vote(top.labels, valid, num_labels)
    return predicted, rids, rd


@dataclasses.dataclass(eq=False)     # a record is itself, not its fields
class PendingRun:
    """One :meth:`SingleChipEngine.run` cut at its fence: what
    ``_run_begin`` enqueued, for ``_run_finish`` to read back, test and
    finalize. A batch solve makes one and finishes it at once; the
    serving engine (serve.engine.ResidentEngine) keeps two alive, so
    whatever the second half reads of "the solve in hand" lives here and
    not on the engine."""

    inp: KNNInput
    # (TopK, qpad, query_idx | None, select, boundary columns | None), all
    # still on the device
    segments: List[Tuple] = dataclasses.field(default_factory=list)
    prec: str = "f32"             # the first pass's precision
    precision: Optional[Dict[str, Any]] = None   # -> last_precision
    # the multipass driver's per-query loss flags (stall / shortfall)
    mp_hazard: Optional[np.ndarray] = None
    phase_ms: Dict[str, float] = dataclasses.field(default_factory=dict)
    repairs: int = 0              # queries the hazard test flagged
    retry_cleared: int = 0        # of them, repaired by the device retry
    # the float64 rescore: candidate slots finalized, rows gathered
    rescore_slots: int = 0
    rescore_rows: int = 0


class SingleChipEngine:
    """The one-chip engine (CPU backend in CI, TPU in production)."""

    #: the row width resolve_kcap plans the candidate window with; only
    #: the resident serving engine gives one (its ``na`` argument)
    _kcap_attrs: int | None = None

    #: the scores this engine ranks by (config.EngineConfig.score): the
    #: batch solve's streaming selects, hetk routing and multipass
    #: driver know squared L2 alone; the resident serving engine's
    #: extract path has the inner product too
    _scores: Tuple[str, ...] = ("l2",)

    def __init__(self, config: EngineConfig = EngineConfig()):
        config.require_score(
            f"{type(self).__module__}.{type(self).__name__}"
            + (" (the batch solve)" if type(self) is SingleChipEngine
               else ""), self._scores)
        self.config = config
        self._staging = config.resolve_dtype()
        self._dtype = (jnp.bfloat16 if self._staging == "bfloat16"
                       else jnp.float32)
        self.last_phase_ms: dict = {}
        self.last_hetk = None  # (bulk, outlier) counts when routing split
        self.last_mp_passes = 0  # multi-pass extraction pass count
        # Which kernel the last extract-path solve dispatched
        # ("fused" | "extract" | None) — bench/artifacts report it.
        self.last_extract_impl = None
        # The tiles that dispatch ran with
        # (ops.pallas_fused.variant_stamp); the device stamp reports it.
        self.last_variant = None
        self.last_repairs = 0
        # Degradation-ladder rung (resilience.degrade): "fused" (the
        # default) allows the fused megakernel; "heuristic" drops to the
        # two-pass extraction kernel; "streaming" forces the chunk-fold
        # driver (no extract-kernel dispatch at all);
        # last_degrade_rung reports the rung the last run() settled on.
        self._degrade_rung = "fused"
        self.last_degrade_rung = "fused"
        self._mp_hazard = None   # its per-query loss flags (run() repairs)
        # (site, device iters-sum scalar, (qb, b, a, kc)) triples the
        # extract paths queue when a cost probe is installed; flushed to
        # obs.counters after the solve fence (measured extraction term).
        self._pending_iters: list = []
        # Pruned two-stage solve accounting (ops.summaries.note_scan):
        # blocks_total/blocks_pruned/scanned_bytes/dense_bytes of the
        # last solve — the bench A/B and the CLI metrics summary read
        # it. None until a chunked driver runs.
        self.last_prune = None
        # Analytic peak-HBM model of the last solve (obs.memwatch);
        # populated only while a telemetry session is active.
        self.last_mem_model = None
        # Low-precision first-pass record of the last run(): active/
        # configured precision + the window slots the bound inflation
        # added (bench A/B and the CLI metrics summary read it).
        self.last_precision = None

    def _staging_itemsize(self) -> int:
        return 2 if self._staging == "bfloat16" else 4

    def _plan_prune(self, inp: KNNInput, nchunks: int, chunk_rows: int,
                    prec: str = "f32"):
        """Stage 0+1 of the pruned two-stage solve for a chunked
        driver: (survivor chunk schedule, plan stats | None). Active
        only on the resilience ladder's top ``lowp``/``prune`` rungs
        (run() enters at "lowp"; candidates()/run_device_full stay
        dense — fast ordering has no repair backstop), in exact mode,
        with the ``DMLP_TPU_PRUNE`` kill switch on, and when there is
        more than one block to choose between. The prune thresholds
        widen by the finalize.lowp_eps bound of the form that will
        run (active_precision) — a block must stay pruned under the
        error the first pass could add. The
        schedule preserves natural chunk order, so ChunkThrottle
        backpressure and the affine-id contract are untouched — pruned
        blocks are simply never staged."""
        n = inp.params.num_data
        dense = list(range(nchunks))
        if (nchunks <= 1 or n == 0 or inp.params.num_queries == 0
                or self._degrade_rung not in ("lowp", "prune")
                or not self.config.exact):
            return dense, None
        from dmlp_tpu.ops import summaries as osum
        if not osum.prune_enabled():
            return dense, None
        ranges = [(c * chunk_rows, min((c + 1) * chunk_rows, n))
                  for c in range(nchunks)]
        with obs_span("single.prune_score", blocks=nchunks):
            summ = osum.build_summaries(inp.data_attrs, ranges)
            keep, stats = osum.prune_mask(inp.query_attrs, inp.ks, summ,
                                          staging=self._staging,
                                          precision=prec)
        schedule = [c for c in dense if keep[c]]
        if not schedule:       # belt: prune_mask guarantees a survivor
            return dense, None
        return schedule, stats

    def _prep(self, inp: KNNInput):
        cfg = self.config
        n = inp.params.num_data
        # The scan/device-full paths fold arbitrary-id blocks, so
        # "extract" remaps here (and the granule must match what runs —
        # the extract granule has no 1024-divisor for the seg producer).
        select = cfg.resolve_streaming_select(round_up(max(n, 1), 8))
        if cfg.data_block is not None:
            data_block = min(cfg.data_block, round_up(max(n, 1), 8))
        else:
            data_block = fit_blocks(n, cfg.resolve_data_block(select),
                                    granule=cfg.resolve_granule(select))
        attrs, labels, ids = pad_dataset(inp, data_block, np.float32)
        kmax = int(inp.ks.max()) if inp.params.num_queries else 1
        k = resolve_kcap(cfg, kmax, select, attrs.shape[0],
                         staging=self._staging)
        d_attrs = stage_put(attrs, self._staging)
        self._last_select = select  # run() gates the tie-overflow repair on it
        return (d_attrs, jax.device_put(labels), jax.device_put(ids), k,
                data_block, select)

    def _solve_scan(self, inp: KNNInput) -> Tuple[TopK, int]:
        """Whole-dataset staging + one lax.map/scan dispatch ("sort" path)."""
        cfg = self.config
        d_attrs, d_labels, d_ids, k, data_block, select = self._prep(inp)
        nq = inp.params.num_queries
        qb = min(cfg.query_block, round_up(max(nq, 1), 8))
        qpad = round_up(max(nq, 1), qb)
        q_attrs = np.zeros((qpad, inp.params.num_attrs), np.float32)
        q_attrs[:nq] = inp.query_attrs
        q_blocks = stage_put(
            q_attrs.reshape(qpad // qb, qb, -1), self._staging)

        statics = dict(k=k, data_block=data_block, select=select,
                       use_pallas=cfg.use_pallas)
        obs_counters.record_dispatch(
            _topk_blocks, (d_attrs, d_labels, d_ids, q_blocks),
            statics=statics, site="single.topk_blocks")
        with obs_span("single.solve_scan", select=select,
                      qpad=qpad) as sp:
            out: TopK = _topk_blocks(d_attrs, d_labels, d_ids, q_blocks,
                                     **statics)
            sp.fence(out.dists)
        from dmlp_tpu.ops.summaries import note_scan
        dense = inp.params.num_data * inp.params.num_attrs \
            * self._staging_itemsize()
        note_scan(self, scanned_bytes=dense, dense_bytes=dense,
                  blocks_total=1, blocks_pruned=0)
        return TopK(out.dists.reshape(qpad, -1), out.labels.reshape(qpad, -1),
                    out.ids.reshape(qpad, -1)), qpad

    def _solve_pipelined(self, inp: KNNInput,
                         prec: str = "f32") -> Tuple[TopK, int]:
        """Chunked staging + one fold dispatch per chunk ("topk"/"seg").

        The dataset is staged in ~chunk_rows-row pieces, each followed by
        its fold dispatch; transfers and compute are enqueued back-to-back
        so the device DMAs chunk i+1 while folding chunk i. On a
        bandwidth-limited host link (a pod feeding over DCN) the solve
        then costs ~max(transfer, compute), not their sum.
        """
        import time as _time

        cfg = self.config
        n = inp.params.num_data
        na = inp.params.num_attrs
        nq = inp.params.num_queries
        # resolve_streaming_select: only reached when the extraction kernel
        # can't tile this shape (or select != extract in the first place)
        select = cfg.resolve_streaming_select(round_up(max(n, 1), 8))
        self._last_select = select
        granule = cfg.resolve_granule(select)

        t0 = _time.perf_counter()
        npad, nchunks, chunk_rows = plan_chunks(n, granule, cfg.data_block)

        # Query padding: multiples of 1024 keep the fused Pallas tiling
        # eligible (ops.pallas_distance.supports); 8 otherwise.
        qgran = 1024 if (cfg.use_pallas and select == "seg"
                         and nq > 1024) else 8
        qpad = round_up(max(nq, 1), qgran)
        # Bound the live (query_rows x chunk_rows) f32 tile by both the
        # configured query_block and the HBM tile budget.
        qsb = min(qpad, round_up(cfg.query_block, qgran))
        while qsb > qgran and qsb * chunk_rows * 4 > _TILE_BUDGET:
            qsb -= qgran
        nqb = -(-qpad // qsb)
        qpad = nqb * qsb

        kmax = int(inp.ks.max()) if nq else 1
        k = resolve_kcap(cfg, kmax, select, nchunks * chunk_rows,
                         staging=self._staging)

        q_attrs = np.zeros((qpad, na), np.float32)
        q_attrs[:nq] = inp.query_attrs
        q_dev = [stage_put(q_attrs[i * qsb:(i + 1) * qsb], self._staging)
                 for i in range(nqb)]

        # Stage chunks (async puts) and enqueue their folds immediately,
        # under the sliding-window backpressure (ChunkThrottle). The
        # survivor schedule (pruned two-stage solve) composes here: a
        # pruned chunk is never staged, so its bytes never cross the
        # host->device link at all.
        schedule, prune_stats = self._plan_prune(inp, nchunks, chunk_rows,
                                                 prec)
        carries = [init_topk(qsb, k) for _ in range(nqb)]
        src_attrs = np.ascontiguousarray(inp.data_attrs, np.float32)
        throttle = ChunkThrottle()
        scanned = 0
        statics = dict(k=k, select=select, use_pallas=cfg.use_pallas)
        with obs_span("single.enqueue_pipelined", select=select,
                      chunks=nchunks, scheduled=len(schedule),
                      qblocks=nqb, k=k):
            for c in schedule:
                lo, hi = c * chunk_rows, min((c + 1) * chunk_rows, n)
                a = np.zeros((chunk_rows, na), np.float32)
                lab = np.full(chunk_rows, -1, np.int32)
                ids = np.full(chunk_rows, -1, np.int32)
                if hi > lo:
                    a[:hi - lo] = src_attrs[lo:hi]
                    lab[:hi - lo] = inp.labels[lo:hi]
                    ids[:hi - lo] = np.arange(lo, hi, dtype=np.int32)
                da = stage_put(a, self._staging)
                scanned += max(hi - lo, 0) * na * self._staging_itemsize()
                dl, di = jax.device_put(lab), jax.device_put(ids)
                if c == schedule[0]:
                    obs_counters.record_dispatch(
                        _chunk_fold, (carries[0], q_dev[0], da, dl, di),
                        statics=statics, count=len(schedule) * nqb,
                        site="single.chunk_fold")
                for b in range(nqb):
                    carries[b] = _chunk_fold(carries[b], q_dev[b], da, dl,
                                             di, **statics)
                throttle.tick(carries[-1].dists)
                # Watermark tick while the chunk is still referenced —
                # chunk arrays are loop-locals, so a post-loop sample
                # would miss the staging window (no-op unless a
                # telemetry session is active).
                telemetry.sample_memory_now()
        from dmlp_tpu.ops.summaries import note_scan
        note_scan(self, scanned_bytes=scanned,
                  dense_bytes=n * na * self._staging_itemsize(),
                  blocks_total=nchunks,
                  blocks_pruned=(prune_stats or {}).get(
                      "blocks_pruned", 0))
        self.last_phase_ms["enqueue"] = (_time.perf_counter() - t0) * 1e3

        if nqb == 1:
            return carries[0], qpad
        return TopK(*(jnp.concatenate(parts) for parts in
                      zip(*carries))), qpad

    def _solve_extract(self, inp: KNNInput,
                       prec: str = "f32") -> Tuple[TopK, int] | None:
        """Chunked staging + the fused extraction kernel (select="extract").

        Each ~50k-row chunk is staged asynchronously and folded into the
        running (Q, K) lists by ops.pallas_extract.extract_topk — the
        distance tile lives only in VMEM, so HBM holds just the chunk, the
        queries, and the lists. Chunk row ranges are contiguous, giving the
        kernel its trace-time-affine ids (id_base = chunk start). Returns
        None when the kernel can't tile this shape (caller falls back).
        """
        import time as _time

        from dmlp_tpu.ops import pallas_fused
        from dmlp_tpu.ops.pallas_distance import pallas_interpret

        cfg = self.config
        n = inp.params.num_data
        na = inp.params.num_attrs
        nq = inp.params.num_queries
        if n == 0 or nq == 0:
            return None

        rs_inject.fire("single.extract_solve", rung=self._degrade_rung,
                       path="single")
        granule = cfg.resolve_granule("extract")
        t0 = _time.perf_counter()
        npad, nchunks, chunk_rows = plan_chunks(n, granule, cfg.data_block)
        # Queries pad to a whole query tile for the same reason data pads
        # to whole extraction blocks: an awkward qb (e.g. 8 * prime) would
        # force a degenerate 8-row query tile.
        from dmlp_tpu.ops.pallas_extract import QUERY_TILE, resolve_variant
        qpad = round_up(nq, QUERY_TILE)
        kmax = int(inp.ks.max())
        k = resolve_kcap(cfg, kmax, "extract", nchunks * chunk_rows,
                         staging=self._staging)
        # Fused-vs-two-pass selection, resolved HERE (outside any jitted
        # body, lint R203): kern is a concrete Python callable whose own
        # jit keys on mxu_gate + the resolved tiles, so the choice is
        # part of the jit cache key by construction.
        kern, impl = pallas_fused.resolve_topk_kernel(
            qpad, chunk_rows, na, k, rung=self._degrade_rung)
        if kern is None:
            return None
        interpret = pallas_interpret()
        self._last_select = "extract"
        self.last_extract_impl = impl
        self.last_variant = pallas_fused.variant_stamp(
            k, chunk_rows, qpad, na, prec, self._staging)

        schedule, prune_stats = self._plan_prune(inp, nchunks, chunk_rows,
                                                 prec)
        live = [c for c in schedule if c * chunk_rows < n]
        q_attrs = np.zeros((qpad, na), np.float32)
        q_attrs[:nq] = inp.query_attrs
        q_dev = stage_put(q_attrs, self._staging)
        src_attrs = np.ascontiguousarray(inp.data_attrs, np.float32)
        od = oi = None
        scanned = 0
        mi = MeasuredIters(self, "single.extract_topk",
                           (qpad, chunk_rows, na, k))
        throttle = ChunkThrottle()
        with obs_span("single.enqueue_extract", chunks=nchunks, kc=k,
                      impl=impl, scheduled=len(live),
                      variant=resolve_variant(k, chunk_rows, qpad, na)):
            for c in live:    # survivor schedule; pruned blocks are
                # never staged — the beyond-HBM payoff is exactly that
                # their bytes never leave host DRAM
                lo, hi = c * chunk_rows, min((c + 1) * chunk_rows, n)
                a = np.zeros((chunk_rows, na), np.float32)
                if hi > lo:
                    a[:hi - lo] = src_attrs[lo:hi]
                da = stage_put(a, self._staging)
                scanned += (hi - lo) * na * self._staging_itemsize()
                if c == live[0]:
                    # Resolved via the analytic kernel model
                    # (obs.kernel_cost) — pallas_call has no XLA cost.
                    obs_counters.record_dispatch(
                        kern, (q_dev, da), statics=dict(kc=k,
                                                        precision=prec),
                        count=len(live),
                        site="single.extract_topk")
                od, oi, _iters, _wide = kern(
                    q_dev, da, od, oi, n_real=hi - lo, id_base=lo, kc=k,
                    interpret=interpret, precision=prec, with_wide=True)
                mi.add(_iters, _iters * _wide)
                throttle.tick(od)
                telemetry.sample_memory_now()   # staging window live
        mi.done()
        from dmlp_tpu.ops.summaries import note_scan
        note_scan(self, scanned_bytes=scanned,
                  dense_bytes=n * na * self._staging_itemsize(),
                  blocks_total=min(nchunks, -(-n // chunk_rows)),
                  blocks_pruned=(prune_stats or {}).get(
                      "blocks_pruned", 0))
        self.last_phase_ms["enqueue"] = (_time.perf_counter() - t0) * 1e3

        top = _extract_finalize(od, oi, jax.device_put(inp.labels), k=k)
        return top, qpad

    # Multi-pass resident-dataset budget: every pass re-sweeps the staged
    # chunks, so they must stay device-resident (re-uploading P times
    # would multiply the staging cost by P). 2 GiB staged attrs
    # leaves ample HBM for lists + scratch on a 16 GiB chip; bigger
    # datasets keep the streaming fallback.
    _MP_RESIDENT_BUDGET = 2 << 30
    _MP_MAX_PASSES = 16
    _MP_KC = 512  # slots per pass — the kernel's widest tuned window

    def _solve_extract_multipass(self, inp: KNNInput, prec: str = "f32"):
        """All-wide-k solve on the extraction kernel in P floor-raised
        passes (round-4 review item 2).

        When EVERY query's k overflows the kernel's kc cap the router
        (hetk_split) has no bulk to keep and r4 dropped the whole input to
        the streaming selects — even though k is legal up to num_data
        (generate_input.py:19). Instead: stage the chunks once
        (device-resident), and sweep them P = ceil(kcap/512) times. Pass 1
        runs the plain kernel; pass p+1 masks candidates below that row's
        previous max MINUS the staging-eps margin (the kernel's new
        ``floor`` input), so each pass extracts the next ~512-wide slab of
        the top-k. The eps overlap deliberately re-extracts boundary
        candidates rather than risk losing a tie — _mp_merge dedups by id
        and composite-sorts to the final width.

        Correctness: the kernel guarantees every unextracted candidate
        sits at or above the pass's max, so the union is complete below
        the last pass's max minus eps. The two loss modes both flag for
        exact oracle repair (run() ORs _mp_hazard into the standard
        boundary test): STALL (a >512-wide tie plateau pins the floor; the
        pass adds nothing and fd stops rising) and SHORTFALL (eps-window
        duplicates ate enough slots that a row ends with fewer than
        min(k, n) distinct candidates).

        Returns a run()-compatible segment list, or None when the plan
        doesn't apply (k fits single-pass, kernel can't tile, dataset too
        big to keep resident, or P would exceed _MP_MAX_PASSES).
        """
        import time as _time

        from dmlp_tpu.ops import pallas_fused
        from dmlp_tpu.ops.pallas_distance import pallas_interpret
        from dmlp_tpu.ops.pallas_extract import QUERY_TILE

        cfg = self.config
        n = inp.params.num_data
        na = inp.params.num_attrs
        nq = inp.params.num_queries
        if n == 0 or nq == 0 or not cfg.use_pallas:
            return None
        if cfg.select not in ("auto", "extract"):
            return None
        if cfg.resolve_select(round_up(max(n, 1), 8)) != "extract":
            return None
        kc = self._MP_KC
        kmax = int(inp.ks.max())
        if resolve_kcap(cfg, kmax, "extract", 1 << 30,
                        self._staging) <= kc:
            return None  # single-pass (or the hetk router) owns this k
        granule = cfg.resolve_granule("extract")
        npad, nchunks, chunk_rows = plan_chunks(n, granule, cfg.data_block)
        kcap = resolve_kcap(cfg, kmax, "extract", npad,
                            staging=self._staging)
        npasses = -(-kcap // kc)
        if npasses > self._MP_MAX_PASSES:
            return None
        itemsize = 2 if self._staging == "bfloat16" else 4
        if npad * na * itemsize > self._MP_RESIDENT_BUDGET:
            return None
        qpad = round_up(nq, QUERY_TILE)
        kern, impl = pallas_fused.resolve_topk_kernel(
            qpad, chunk_rows, na, kc, rung=self._degrade_rung)
        if kern is None:
            return None
        # Passes 2+ sweep the FULL concatenated d_full array in one
        # call: the variant resolved for that row count must tile it
        # (resolve_sweep_kernel asserts it, before anything is staged).
        n_staged = min(nchunks, -(-n // chunk_rows))
        full_rows = n_staged * chunk_rows
        kern_full, _ = resolve_sweep_kernel(
            qpad, full_rows, na, kc, chunk_rows=chunk_rows,
            rung=self._degrade_rung)
        interpret = pallas_interpret()
        self._last_select = "extract"
        self.last_extract_impl = impl
        self.last_variant = pallas_fused.variant_stamp(
            kc, chunk_rows, qpad, na, prec, self._staging)
        rs_inject.fire("single.extract_solve", rung=self._degrade_rung,
                       path="multipass")

        t0 = _time.perf_counter()
        q_attrs = np.zeros((qpad, na), np.float32)
        q_attrs[:nq] = inp.query_attrs
        q_dev = stage_put(q_attrs, self._staging)
        src_attrs = np.ascontiguousarray(inp.data_attrs, np.float32)

        # Pass 1 overlaps with staging, like the single-pass driver; the
        # chunks stay resident for passes 2..P.
        chunks: List[Tuple] = []
        od = oi = None
        mi = MeasuredIters(self, "single.extract_mp_pass1",
                           (qpad, chunk_rows, na, kc))
        throttle = ChunkThrottle()
        for c in range(nchunks):
            lo, hi = c * chunk_rows, min((c + 1) * chunk_rows, n)
            if lo >= n:
                break
            a = np.zeros((chunk_rows, na), np.float32)
            a[:hi - lo] = src_attrs[lo:hi]
            da = stage_put(a, self._staging)
            if c == 0:
                obs_counters.record_dispatch(
                    kern, (q_dev, da), statics=dict(kc=kc, precision=prec),
                    count=n_staged, site="single.extract_mp_pass1")
            chunks.append((da, lo, hi))
            od, oi, _iters = kern(q_dev, da, od, oi, n_real=hi - lo,
                                  id_base=lo, kc=kc,
                                  interpret=interpret, precision=prec)
            mi.add(_iters)
            throttle.tick(od)
        mi.done()
        ods, ois = [od], [oi]

        # Floors chain ON DEVICE (_mp_floor): every pass enqueues without
        # a host readback, so the whole P-pass sweep pipelines like the
        # single-pass chunk driver. Stall detection moves post-hoc: the
        # per-pass fd vectors come back in ONE readback at the end
        # (plateau rows waste their later passes on duplicate lists —
        # bounded by _MP_MAX_PASSES and caught below for exact repair).
        qn_host = np.zeros(qpad, np.float64)
        qn_host[:nq] = np.einsum("qa,qa->q", inp.query_attrs,
                                 inp.query_attrs)
        dn_max = float(np.einsum("na,na->n", inp.data_attrs,
                                 inp.data_attrs).max())
        qn_dev = jax.device_put(np.asarray(qn_host, np.float32))
        # Explicit device scalar: dn_max rides _mp_floor as a traced
        # arg, and the sanitizer's transfer guard disallows the implicit
        # python-float -> device conversion at the jit boundary.
        dn_dev = jax.device_put(np.float32(dn_max))
        # Passes 2..P sweep the RESIDENT dataset: one whole-array kernel
        # dispatch per pass (the kernel grids over blocks internally)
        # instead of nchunks dispatches — chunking only existed to
        # overlap pass 1 with staging (36 -> 9 dispatches at the 204800,
        # 9-pass shape). The concat is one on-device copy (~dataset
        # bytes), well under the resident budget.
        d_full = chunks[0][0] if len(chunks) == 1 \
            else jnp.concatenate([c[0] for c in chunks], axis=0)
        telemetry.sample_memory_now()  # resident dataset ×2 peak (concat)
        del chunks  # free the duplicate once the concat is enqueued —
        # otherwise the dataset is HBM-resident TWICE for the whole sweep
        if npasses > 1:
            obs_counters.record_dispatch(
                kern_full, (q_dev, d_full),
                statics=dict(kc=kc, precision=prec),
                count=npasses - 1, site="single.extract_mp_resident")
        fds = []
        mir = MeasuredIters(self, "single.extract_mp_resident",
                            (qpad, full_rows, na, kc))
        for _p in range(1, npasses):
            floor_dev, fd = _mp_floor(ods[-1], qn_dev, dn_dev,
                                      staging=self._staging, na=na,
                                      precision=prec)
            fds.append(fd)
            od, oi, _iters = kern_full(q_dev, d_full, n_real=n, id_base=0,
                                       kc=kc, interpret=interpret,
                                       floor=floor_dev, precision=prec)
            mir.add(_iters)
            throttle.tick(od)
            ods.append(od)
            ois.append(oi)
        mir.done()
        # Final pass's fd too: a plateau pinning the LAST boundary must
        # flag as well (its ties are the one loss the outer boundary test
        # can miss when kcap >= n).
        fds.append(_mp_floor(ods[-1], qn_dev, dn_dev,
                             staging=self._staging, na=na,
                             precision=prec)[1])
        self.last_phase_ms["enqueue"] = (_time.perf_counter() - t0) * 1e3
        self.last_mp_passes = len(ods)

        obs_trace.instant("single.multipass_sweep", passes=len(ods),
                          kcap=kcap, chunks=n_staged)
        # The multipass plan keeps the dataset resident and re-sweeps
        # it; every block stays competitive against floor-raised passes,
        # so it scans densely by design (staged bytes counted once).
        from dmlp_tpu.ops.summaries import note_scan
        dense = n * na * self._staging_itemsize()
        note_scan(self, scanned_bytes=dense, dense_bytes=dense,
                  blocks_total=n_staged, blocks_pruned=0)
        top, valid = _mp_merge(jnp.concatenate(ods, axis=1),
                               jnp.concatenate(ois, axis=1),
                               jax.device_put(inp.labels), kcap=kcap)
        # One fence for everything: fd sequence (stall check), final
        # valid counts (shortfall check).
        fetched = resilient_get([valid] + fds)
        valid_h, fd_h = fetched[0], fetched[1:]
        stalled = np.zeros(qpad, bool)
        for prev, cur in zip(fd_h, fd_h[1:]):
            stalled |= np.isfinite(cur) & (cur <= prev)
        needed = np.minimum(inp.ks.astype(np.int64), n)
        shortfall = np.asarray(valid_h)[:nq] < needed
        self._mp_hazard = stalled[:nq] | shortfall
        return [(top, qpad, None, "extract")]

    def _flush_measured_iters(self) -> None:
        flush_measured_iters(self)

    def _solve(self, inp: KNNInput,
               prec: str = "f32") -> Tuple[TopK, int]:
        self.last_phase_ms = {}  # no stale phases if a path is skipped
        self._pending_iters = []
        self.last_extract_impl = self.last_variant = None
        self.last_prune = None   # no stale scan accounting either
        select = self.config.resolve_select(
            round_up(max(inp.params.num_data, 1), 8))
        if select == "sort":
            return self._solve_scan(inp)
        # The "streaming" degradation rung (resilience.degrade) forbids
        # extract-kernel dispatch: the chunk-fold driver below holds no
        # running-list kernel state and its live tile is one slab.
        if select == "extract" and self._degrade_rung != "streaming":
            out = self._solve_extract(inp, prec)
            if out is not None:
                return out
            # shape untileable for the extraction kernel — fall through to
            # the chunk-fold driver on the best remaining path
        return self._solve_pipelined(inp, prec)

    def _plan_hetk(self, inp: KNNInput):
        return hetk_split(self.config, self._staging, inp.ks,
                          inp.params.num_data,
                          round_up(max(inp.params.num_data, 1), 8))

    def _solve_extract_routed(self, inp: KNNInput, plan,
                              prec: str = "f32"):
        """Split solve: extraction kernel for the bulk queries + streaming
        fold for the huge-k outliers, sharing one staging pass.

        Each data chunk is uploaded ONCE; the extract fold (bulk) and the
        outlier fold are enqueued back-to-back on the same device array,
        so the transfer-bound end-to-end cost stays that of the unsplit
        extract path. Returns a segment list for run()/run_device_full,
        or None when the bulk shape can't tile (caller falls back).
        """
        import time as _time

        from dmlp_tpu.ops import pallas_fused
        from dmlp_tpu.ops.pallas_distance import pallas_interpret
        from dmlp_tpu.ops.pallas_extract import QUERY_TILE
        from dmlp_tpu.ops.topk import streaming_fallback

        bulk, outl = plan
        cfg = self.config
        n = inp.params.num_data
        na = inp.params.num_attrs

        granule = cfg.resolve_granule("extract")
        t0 = _time.perf_counter()
        npad, nchunks, chunk_rows = plan_chunks(n, granule, cfg.data_block)
        qpad_b = round_up(len(bulk), QUERY_TILE)
        kb = resolve_kcap(cfg, int(inp.ks[bulk].max()), "extract",
                          nchunks * chunk_rows, staging=self._staging)
        kern, impl = pallas_fused.resolve_topk_kernel(
            qpad_b, chunk_rows, na, kb, rung=self._degrade_rung)
        if kern is None:
            return None
        select_out = streaming_fallback(cfg.use_pallas)
        ko = resolve_kcap(cfg, int(inp.ks[outl].max()), select_out,
                          nchunks * chunk_rows, staging=self._staging)
        interpret = pallas_interpret()
        self._last_select = "extract"
        self.last_extract_impl = impl
        self.last_variant = pallas_fused.variant_stamp(
            kb, chunk_rows, qpad_b, na, prec, self._staging)
        self.last_hetk = (int(bulk.size), int(outl.size))
        rs_inject.fire("single.extract_solve", rung=self._degrade_rung,
                       path="routed")

        qb_host = np.zeros((qpad_b, na), np.float32)
        qb_host[:len(bulk)] = inp.query_attrs[bulk]
        qb_dev = stage_put(qb_host, self._staging)
        qo_pad = round_up(len(outl), 8)
        qo_host = np.zeros((qo_pad, na), np.float32)
        qo_host[:len(outl)] = inp.query_attrs[outl]
        qo_dev = stage_put(qo_host, self._staging)
        labels_pad = np.full(nchunks * chunk_rows, -1, np.int32)
        labels_pad[:n] = inp.labels
        labels_dev = jax.device_put(labels_pad)

        # The prune plan covers BOTH query sets (bulk and outliers ride
        # the same per-query ks), so the shared staging sweep may only
        # skip a chunk no query of either segment can need.
        schedule, prune_stats = self._plan_prune(inp, nchunks, chunk_rows,
                                                 prec)
        live_sched = [c for c in schedule if c * chunk_rows < n]
        carry_o = init_topk(qo_pad, ko)
        src_attrs = np.ascontiguousarray(inp.data_attrs, np.float32)
        od = oi = None
        scanned = 0
        mi = MeasuredIters(self, "single.extract_bulk",
                           (qpad_b, chunk_rows, na, kb))
        throttle = ChunkThrottle()
        for c in live_sched:
            lo, hi = c * chunk_rows, min((c + 1) * chunk_rows, n)
            a = np.zeros((chunk_rows, na), np.float32)
            if hi > lo:
                a[:hi - lo] = src_attrs[lo:hi]
            da = stage_put(a, self._staging)
            scanned += (hi - lo) * na * self._staging_itemsize()
            if c == live_sched[0]:
                obs_counters.record_dispatch(
                    kern, (qb_dev, da), statics=dict(kc=kb,
                                                     precision=prec),
                    count=len(live_sched),
                    site="single.extract_bulk")
            od, oi, _iters = kern(
                qb_dev, da, od, oi, n_real=hi - lo, id_base=lo, kc=kb,
                interpret=interpret, precision=prec)
            mi.add(_iters)
            carry_o = _outlier_fold(
                carry_o, qo_dev, da, labels_dev,
                jax.device_put(np.int32(lo)), jax.device_put(np.int32(n)),
                chunk_rows=chunk_rows, k=ko,
                select=select_out, use_pallas=cfg.use_pallas)
            throttle.tick(carry_o.dists)
            telemetry.sample_memory_now()   # staging window live
        mi.done()
        from dmlp_tpu.ops.summaries import note_scan
        note_scan(self, scanned_bytes=scanned,
                  dense_bytes=n * na * self._staging_itemsize(),
                  blocks_total=min(nchunks, -(-n // chunk_rows)),
                  blocks_pruned=(prune_stats or {}).get(
                      "blocks_pruned", 0))
        self.last_phase_ms["enqueue"] = (_time.perf_counter() - t0) * 1e3

        top_b = _extract_finalize(od, oi, jax.device_put(inp.labels),
                                  k=kb)
        return [(top_b, qpad_b, bulk, "extract"),
                (carry_o, qo_pad, outl, select_out)]

    def _solve_segments(self, inp: KNNInput, allow_multipass: bool = True,
                        prec: str = "f32"):
        """Solve as a list of (TopK, qpad, query_idx | None, select)
        segments — one segment for homogeneous k, two when the
        heterogeneous-k router splits huge-k outliers off the extraction
        kernel's bulk. Queries in different segments are independent
        sub-problems; run()/run_device_full merge by original index.

        ``allow_multipass`` gates the all-wide-k multi-pass extraction:
        its loss modes (tie plateau / eps-window shortfall) are only made
        exact by run()'s host repair, so run_device_full — which has no
        repair — keeps the streaming fallback instead."""
        self.last_hetk = None
        self._mp_hazard = None
        self.last_mp_passes = 0
        self._pending_iters = []
        self.last_extract_impl = self.last_variant = None
        self.last_prune = None
        # Both routed and multipass paths dispatch the extraction
        # kernel; the "streaming" rung skips straight to _solve, whose
        # own gate lands on the chunk-fold driver.
        streaming = self._degrade_rung == "streaming"
        plan = None if streaming else self._plan_hetk(inp)
        if plan is not None:
            self.last_phase_ms = {}
            segs = self._solve_extract_routed(inp, plan, prec)
            if segs is not None:
                return segs
        if allow_multipass and not streaming:
            self.last_phase_ms = {}
            segs = self._solve_extract_multipass(inp, prec)
            if segs is not None:
                return segs
        top, qpad = self._solve(inp, prec)
        return [(top, qpad, None, self._last_select)]

    def candidates(self, inp: KNNInput) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Device pass: (Q, K) selection-ordered candidate lists as NumPy."""
        kmax = int(inp.ks.max()) if inp.params.num_queries else 0
        memwatch.note_engine_model(self, inp)
        with staging_for_k(self, kmax):
            out, qpad = self._solve(inp)
        telemetry.sample_memory_now()
        nq = inp.params.num_queries
        # Explicit fenced readback (the result fetch IS the fence); the
        # sanitizer's transfer guard allows device_get, never implicit
        # conversion.
        od, ol, oi = resilient_get((out.dists, out.labels, out.ids))
        dists = np.asarray(od, np.float64)[:nq]
        labels = ol[:nq]
        ids = oi[:nq]
        self._flush_measured_iters()
        return dists, labels, ids

    def run(self, inp: KNNInput) -> List[QueryResult]:
        """Full parity pipeline: device candidates + host float64 finalize.

        On the fast "topk"/"seg" selection paths, queries whose candidate
        set may have truncated a distance-tie group (boundary_overflow) are
        recomputed exactly — parity holds on either path.

        Readback is kept small: the candidate ids, the two boundary
        columns and the (Q, K) f32 device distances cross the link
        (labels are re-derived from ids on host). Fast mode reports the
        distances; exact mode rescores in float64 and reads them only to
        leave out the rows its bound already orders
        (engine.finalize.boundary_band), so a window no hazard test
        bounds does not fetch them.
        """
        kmax = int(inp.ks.max()) if inp.params.num_queries else 0
        with staging_for_k(self, kmax):
            # Degradation ladder (resilience.degrade): on device OOM —
            # injected or real — the solve steps heuristic ->
            # streaming -> host-f64, every rung checksum-preserving.
            return rs_degrade.run_ladder(self, inp, self._run)

    def _run(self, inp: KNNInput) -> List[QueryResult]:
        pend = PendingRun(inp)
        self._run_begin(pend)
        return self._run_finish(pend)

    def _enqueue(self, pend: PendingRun) -> List[Tuple]:
        """Seam: enqueue the solve of ``pend.inp`` and return its
        segments. A batch solve keeps its own state on the engine (one
        run is alive at a time) and hands the record what the second
        half reads of it."""
        segments = self._solve_segments(pend.inp, prec=pend.prec)
        pend.mp_hazard = self._mp_hazard
        pend.phase_ms = self.last_phase_ms
        return segments

    def _run_begin(self, pend: PendingRun) -> None:
        """The first half of a run: everything that only ENQUEUES (the
        solve and the boundary columns the hazard test reads). Nothing
        here waits for the device."""
        inp = pend.inp
        n = inp.params.num_data
        memwatch.note_engine_model(self, inp)
        pend.prec = active_precision(self)
        segments = self._enqueue(pend)
        # Watermark tick at peak residency: the solve is enqueued, the
        # staged chunks/carries are live, nothing is fetched yet (no-op
        # without a telemetry session).
        telemetry.sample_memory_now()
        # Precision record for metrics/bench: what the first pass ran
        # at, the MXU passes the kernel's cross term takes a visit at
        # that form over this staging (ops.pallas_extract.mxu_passes),
        # and how many window slots the bound inflation bought the
        # rescore (kcap minus what an f32-precision plan would have
        # sized — 0 whenever precision resolves to "f32").
        from dmlp_tpu.ops.pallas_extract import mxu_passes
        kcap0 = int(segments[0][0].dists.shape[1])
        kmax0 = int(inp.ks.max()) if inp.params.num_queries else 0
        pend.precision = {
            "active": pend.prec,
            "mxu_passes": mxu_passes(pend.prec, self._staging),
            "configured": self.config.resolve_precision(self._staging),
            "kcap": kcap0,
            "kcap_inflation": kcap0 - resolve_kcap(
                self.config, kmax0, segments[0][3], kcap0,
                staging=self._staging, precision="f32",
                na=self._kcap_attrs),
        }
        for top, qpad, idx, select in segments:
            cols_dev = None
            if select in ("sort", "topk", "seg", "extract") \
                    and top.dists.shape[1] < n:
                ks = inp.ks if idx is None else inp.ks[idx]
                ks_pad = np.ones(qpad, np.int32)
                ks_pad[:len(ks)] = ks
                cols_dev = _boundary_cols(top.dists, jax.device_put(ks_pad))
            pend.segments.append((top, qpad, idx, select, cols_dev))

    def _run_finish(self, pend: PendingRun) -> List[QueryResult]:
        """The second half: the fence (the result fetch), the hazard
        test, the float64 finalize and the boundary repair."""
        import time as _time

        inp = pend.inp
        n = inp.params.num_data
        prec = pend.prec
        score = self.config.score
        self.last_comms = []   # one chip: no collectives (obs.comms)
        merged: List[QueryResult] = [None] * inp.params.num_queries
        # Max squared data-row norm (f64): scales the staging-dtype
        # perturbation bound of the hazard test. Asked of _corpus_dn_max
        # on first need only (the kcap >= n case never needs it) and
        # kept for the run's later segments.
        dn_max = None

        fetch_ms = hazard_ms = final_ms = 0.0
        targs = self._rid_args()
        for top, qpad, idx, select, cols_dev in pend.segments:
            sub = inp if idx is None else subset_queries(inp, idx)
            nq = sub.params.num_queries
            kcap = top.dists.shape[1]

            t0 = _time.perf_counter()
            self._before_fetch(pend)
            # NOTE: the "fetch" phase time includes the wait for all
            # enqueued device work (staging + solve), not just the readback
            # bytes — and past _CHUNK_WINDOW chunks the enqueue phase
            # absorbs throttled transfer wait too. Don't read this table
            # as "readback costs X ms".
            # The device distances come back wherever something reads
            # them: fast mode reports them, exact mode cuts its rescore
            # with them where the hazard test gives a bound (a window
            # that holds the whole corpus has none).
            exact = self.config.exact
            with_dists = not exact or cols_dev is not None
            fetch = ([top.dists] if with_dists else []) + [top.ids] \
                + ([cols_dev] if cols_dev is not None else [])
            # The whole call, retries and injected faults included, is
            # one device wait of this thread (site: the span's own).
            with obs_trace.device_wait("fetch", name="single.fetch",
                                       select=select, kcap=kcap, **targs):
                fetched = list(resilient_get(fetch))
            t1 = _time.perf_counter()
            fetch_ms += (t1 - t0) * 1e3
            # Everything the host does between the readback and the
            # finalize: the staging-eps hazard test and the label gather.
            # dn_max_cached says whether the test's corpus-wide scalar was
            # at hand (a resident engine's, or an earlier segment's) or
            # cost a pass over the WHOLE host corpus inside this span.
            with obs_span("single.hazard", rows=n, score=score,
                          **targs) as hz:
                dists = np.asarray(fetched.pop(0), np.float64)[:nq] \
                    if with_dists else None
                ids = fetched.pop(0)[:nq]
                flags = widths = None
                if cols_dev is not None:
                    kth, last = np.asarray(fetched.pop(0),
                                           np.float64)[:, :nq]
                    cached = dn_max is not None
                    if not cached:
                        dn_max, cached = self._corpus_dn_max(inp)
                    hz.set(dn_max_cached=cached)
                    qn = np.einsum("qa,qa->q", sub.query_attrs,
                                   sub.query_attrs)
                    eps = self._hazard_eps(last, qn, dn_max, select, prec,
                                           inp.params.num_attrs)
                    flags = boundary_hazard(kth, last, eps)
                    if exact:
                        widths = band_widths(
                            boundary_band(dists, ids, kth, eps))
                    clear = boundary_clearance(kth, last, eps)
                    if clear is not None:
                        hz.set(clear_min=clear)
                # Multi-pass extraction's own loss detectors (stall/
                # shortfall, _solve_extract_multipass) join the standard
                # boundary test.
                mp = pend.mp_hazard
                if mp is not None and idx is None:
                    flags = mp if flags is None else (flags | mp)
                labels = np.where(
                    ids >= 0,
                    inp.labels[np.clip(ids, 0, max(n - 1, 0))], -1) \
                    if n else np.full_like(ids, -1)
                hz.set(flagged=0 if flags is None
                       else int(np.count_nonzero(flags)))

            t0 = _time.perf_counter()
            hazard_ms += (t0 - t1) * 1e3
            # rows: the float64 rows the rescore gathers, one a slot of
            # a query's band (every slot where there is no band; none in
            # fast mode); gather_bytes: rows x A x 8 B.
            rows = 0 if not exact else ids.size if widths is None \
                else int(widths.sum())
            gather = rows * inp.params.num_attrs * 8
            with obs_span("single.finalize", exact=exact,
                          gather_bytes=gather, score=score, **targs) as sp:
                suspects = np.nonzero(flags)[0] if flags is not None \
                    else np.zeros(0, np.intp)
                # The flagged queries go back to the device first, where
                # the engine keeps its corpus there (_retry_begin only
                # enqueues, so the host finalizes the batch meanwhile);
                # what the wider window does not clear, and every
                # flagged query of an engine without a retry, is the
                # host oracle's.
                retry = self._retry_begin(pend, sub, suspects, select,
                                          kcap) if suspects.size else None
                if exact:
                    # The float64 gather-and-score, under a span of its
                    # own: the part of the finalize the score changes
                    # (difference form; under "ip" the product alone,
                    # under "cosine" the product over the norms).
                    # finalize_host takes the rescored distances as it
                    # takes fast mode's device ones.
                    pend.rescore_slots += ids.size
                    pend.rescore_rows += rows
                    with obs_span("single.rescore", queries=nq,
                                  slots=kcap, rows=rows, bytes=gather,
                                  band_pct=round(100.0 * rows
                                                 / max(ids.size, 1), 3),
                                  score=score, **targs):
                        dists = rescore_f64(np.asarray(ids, np.int64),
                                            sub.query_attrs,
                                            sub.data_attrs, score=score,
                                            widths=widths,
                                            data_norms=sub.data_norms)
                results = finalize_host(dists, labels, ids, sub.ks,
                                        sub.query_attrs, sub.data_attrs,
                                        exact=False, query_ids=idx,
                                        score=score)
                if suspects.size:
                    # ``repairs`` = queries FLAGGED, wherever repaired
                    pend.repairs += int(suspects.size)
                    sp.set(repairs=int(suspects.size))
                    if retry is not None:
                        suspects = self._retry_finish(pend, sub, retry,
                                                      results, dn_max)
                if suspects.size:
                    with obs_span("single.repair",
                                  queries=int(suspects.size), **targs):
                        repair_boundary_overflow(results, suspects, sub,
                                                 score=score)
            if idx is None:
                merged = results
            else:
                for local_i, orig in enumerate(idx):
                    merged[int(orig)] = results[local_i]
            final_ms += (_time.perf_counter() - t0) * 1e3
        pend.phase_ms.update(fetch=fetch_ms, hazard=hazard_ms,
                             finalize=final_ms)
        # What the engine reports of "the last run" is the last one
        # FINISHED (a serving engine has begun another by now).
        self.last_phase_ms = pend.phase_ms
        self.last_precision = pend.precision
        self.last_repairs = pend.repairs  # tie-overflow repair rate
        self._flush_measured_iters()
        return merged

    def _rid_args(self) -> dict:
        """Args every span of one solve carries: none for a batch solve;
        the serving core (serve.engine.ResidentServingCore) tags the
        micro-batch it is solving."""
        return {}

    def _hazard_eps(self, last, qn, dn_max: float, select: str,
                    prec: str, na: int) -> np.ndarray:
        """The hazard test's per-query bound for a candidate list whose
        last distance is ``last``: the staging rounding and, on the
        extract path, what the first pass's form drops ON TOP of it
        ("bf16x3", "bf16": finalize.lowp_eps; the test must clear
        both). Streaming-fallback segments never split or cast, so
        their bound stays the staging one alone."""
        score = self.config.score
        eps = staging_eps(last, qn, dn_max, self._staging, na, score)
        if select == "extract":
            eps = eps + lowp_eps(prec, qn, dn_max, score)
        return eps

    def _retry_begin(self, pend: PendingRun, sub: KNNInput,
                     suspects: np.ndarray, select: str, kcap: int):
        """Seam: enqueue a device retry of the flagged queries
        ``suspects`` (positions in ``sub``) at a wider window and
        return its record for :meth:`_retry_finish`, or None where the
        host oracle repairs them. A batch solve stages its corpus for
        one run and keeps nothing to fold again: it keeps the oracle
        (as the mesh engines do); the serving engine
        (serve.engine.ResidentEngine) folds its resident stack."""
        return None

    def _retry_finish(self, pend: PendingRun, sub: KNNInput, retry,
                      results: List[QueryResult],
                      dn_max: float) -> np.ndarray:
        """Seam: read ``retry`` back, put the answers it cleared into
        ``results`` and return the positions still flagged."""
        raise NotImplementedError

    def _corpus_dn_max(self, inp: KNNInput) -> Tuple[float, bool]:
        """Seam: the largest squared data-row norm of ``inp``'s corpus in
        float64, and whether it was at hand (True) or took a pass now
        (False). A batch solve has one input and no state: it makes the
        O(N*A) host pass, once a run. The serving core
        (serve.engine.ResidentServingCore) owns its corpus and answers
        from the value it keeps with it."""
        n = inp.params.num_data
        with obs_span("single.dn_max", rows=n, **self._rid_args()):
            return (float(np.einsum("na,na->n", inp.data_attrs,
                                    inp.data_attrs).max()) if n else 0.0,
                    False)

    def _before_fetch(self, pend: PendingRun) -> None:
        """Seam: everything of ``pend``'s solve is enqueued and its
        readback starts now. The serving engine makes a multipass
        bucket's own fence here; a batch solve has none."""

    def run_device_full(self, inp: KNNInput) -> List[QueryResult]:
        """All-device pipeline (vote + report order on TPU); f32 ordering.

        Runs the same ``_solve`` as ``run()`` — so the flagship extraction
        kernel (and the pipelined chunk overlap) serves this benchmark mode
        too — then votes and report-orders on device via the epilogue jit;
        only the final (Q, K) report lists cross the link.
        """
        num_labels = int(inp.labels.max()) + 1 if inp.params.num_data else 1
        merged: List[QueryResult] = [None] * inp.params.num_queries
        self.last_comms = []   # one chip: no collectives (obs.comms)
        memwatch.note_engine_model(self, inp)
        with no_auto_coarsen(self):
            segments = self._solve_segments(inp, allow_multipass=False)
        telemetry.sample_memory_now()
        for top, qpad, idx, _select in segments:
            sub = inp if idx is None else subset_queries(inp, idx)
            nq = sub.params.num_queries
            ks_pad = np.zeros(qpad, np.int32)
            ks_pad[:nq] = sub.ks

            p, i, d = _device_epilogue(top, jax.device_put(ks_pad),
                                       num_labels=num_labels)
            p, i, d = resilient_get((p, i, d))
            preds = p[:nq]
            rids = i[:nq]
            rd = np.asarray(d, np.float64)[:nq]
            gids = np.arange(nq) if idx is None else idx
            for qi in range(nq):
                merged[int(gids[qi])] = QueryResult(
                    int(gids[qi]), int(sub.ks[qi]), int(preds[qi]),
                    rids[qi, : int(sub.ks[qi])].astype(np.int64),
                    rd[qi, : int(sub.ks[qi])])
        self._flush_measured_iters()
        return merged
