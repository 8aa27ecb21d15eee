"""Resident serving engine: stage once, compile once per shape bucket.

:class:`ResidentEngine` is the serving daemon's solve core. It differs
from the batch :class:`~dmlp_tpu.engine.single.SingleChipEngine` in
exactly the ways a persistent server needs, and nowhere else — the
candidates -> host-float64 finalize -> boundary-hazard repair
pipeline (the byte-identity contract with the golden oracle) is
inherited unchanged:

- **Resident corpus behind a row-count mask.** The corpus is staged to
  device ONCE at construction, padded to a power-of-two capacity
  (:func:`shape_bucket`); rows beyond ``n_real`` carry the
  engines' standard ``id = -1`` sentinel, which every select path
  already masks to +inf. :meth:`ingest` appends rows by
  ``dynamic_update_slice`` into the fixed-shape buffer (row counts
  bucketed so the tiny update program compiles once per bucket) — the
  SOLVE programs see the same static shapes before and after, so
  ingestion never recompiles them.
- **Per-bucket compile-once solves.** Requests bucket to power-of-two
  (qpad, k) shape buckets. The streaming path is lowered and compiled
  AHEAD of time per bucket (``jit(...).lower(...).compile()``); the
  extract path's one program a bucket compiles on the bucket's first
  dispatch (:meth:`warmup` front-loads both before the first request).
- **One program a micro-batch folds the resident chunks.** The extract
  path's resident copy is ONE device array (nchunks, chunk_rows, A),
  filled a chunk at a time by a donated update (ingest restages a
  chunk the same way). ``_fold_stack`` folds every scheduled chunk of
  it in one dispatch: the kernel once a chunk, the first with no
  carry, the rest carried, the gated tiles summed beside them. The
  fold order, its length and the row count are device data, so a new
  schedule, a pruned chunk or an ingest reuses the executable. Python
  dispatches it once and first blocks in the readback
  (``single.fetch``); there is no per-chunk loop, no eager gate
  counter and no ``ChunkThrottle`` (which bounds STAGED chunks in
  flight; nothing here is staged). The fold reads, it does not
  derive: the kernel's DMA fetches its blocks straight out of the stack
  by a prefetched chunk index, and the rows' squared norms are resident
  beside the stack (nchunks, 1, chunk_rows), written with each chunk by
  the same donated update (``_update_chunk``), so a fold's device loop
  holds the kernel and nothing else (until PR 41 a pass beside every
  kernel call computed the chunk's norms and copied the chunk out of
  the stack, ``%multiply_reduce_fusion``: 3 ms a batch at 82 chunks).
  :attr:`compile_count` counts bucket builds — a replay whose buckets
  were all warmed must leave it unchanged, the serving layer's
  no-per-request-recompilation proof.
- **Cross-request fused-gate warm-up.** The extract path folds the
  SAME resident chunks for every request, so the MXU gate's
  effectiveness per chunk is a stable, learnable property. With
  ``gate_carry`` on, chunks fold in descending historical-winner order
  ("hot blocks first"): each query's k-th-best thresholds — the gate's
  input — tighten after the first folds, so later (cold) blocks gate
  out. The carried state is the per-chunk winner histogram, never a
  threshold itself: within a request thresholds still only tighten, so
  the fold is sound in any order, and the boundary-hazard repair makes
  ties at the candidate boundary exact either way — carry on and off
  are byte-identical by construction (and proven in the A/B).
- **A micro-batch in two halves.** ``begin_batch`` stages, prunes and
  dispatches (everything that only ENQUEUES); ``finish_batch`` makes
  the fence and does the host's share (hazard test, float64 finalize,
  repair, gate bookkeeping). The batcher begins batch N + 1 before it
  finishes batch N (when a batch's worth of queries is already queued),
  so the chip folds while the host finalizes; what a
  solve says of itself lives in its :class:`PendingBatch` until it
  finishes. ``solve_batch`` alone is both halves back to back: same
  answers, byte for byte.
- **Wide-k multipass buckets.** A k-bucket whose candidate width
  exceeds the extraction kernel's single-pass window routes through
  the batch engine's multi-pass extraction driver AGAINST THE RESIDENT
  CHUNKS (:meth:`ResidentEngine._solve_resident_multipass`): no
  staging per request, pass 1 by the program above, floor-chained
  re-sweeps of the same stack as one array (``_sweep_stack``: no
  further copy of the corpus), and the driver's stall/shortfall
  hazards feed run()'s exact repair — byte-identical to the solo
  multipass solve.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dmlp_tpu.config import EngineConfig, kernel_score
from dmlp_tpu.engine.finalize import boundary_hazard, finalize_host
from dmlp_tpu.engine.single import (_BF16_AUTO_K_CAP, PendingRun,
                                    SingleChipEngine, _boundary_cols,
                                    _extract_finalize, _topk_blocks,
                                    active_precision, fit_blocks,
                                    np_staging_dtype, plan_chunks,
                                    resilient_get, resolve_kcap, round_up,
                                    stage_put)
from dmlp_tpu.golden.reference import row_norms as row_norms_f64
from dmlp_tpu.io.grammar import KNNInput, Params, subset_queries
from dmlp_tpu.io.report import QueryResult
from dmlp_tpu.obs import telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.obs.trace import span as obs_span
from dmlp_tpu.ops.topk import TopK
from dmlp_tpu.resilience import degrade as rs_degrade


class CapacityError(RuntimeError):
    """An ingest would exceed the resident buffer's capacity (the
    daemon surfaces this as a rejected ingest, never a crash)."""


class RequestShapeError(ValueError):
    """A request shape the resident engine cannot serve (k beyond the
    serving cap) — admission rejects these before the solve."""


def shape_bucket(b: int) -> int:
    """Row count -> power-of-two bucket (the smallest power of two
    >= b). 12800 and 16000 share a bucket; 12800 and 51200 do not."""
    if b <= 1:
        return 1
    return 1 << (b - 1).bit_length()


def query_bucket(nq: int, granule: int = 8) -> int:
    """Query-count -> power-of-two jit-cache bucket (>= ``granule``;
    the extract path's granule is the kernel's QUERY_TILE)."""
    return max(shape_bucket(max(nq, 1)), granule)


def k_bucket(kmax: int) -> int:
    """Per-request max-k -> power-of-two bucket. Candidate width (and
    hence the compiled program) derives from the BUCKET, so every k in
    (bucket/2, bucket] shares one compiled solve."""
    return shape_bucket(max(kmax, 1))


@functools.partial(jax.jit, donate_argnums=(0,))
def _update_rows_2d(buf, blk, start):
    return jax.lax.dynamic_update_slice(
        buf, blk, (start, jnp.zeros((), jnp.int32)))


@functools.partial(jax.jit, donate_argnums=(0,))
def _update_rows_1d(buf, blk, start):
    return jax.lax.dynamic_update_slice(buf, blk, (start,))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _update_chunk(stack, norms, blk, c):
    """Chunk ``c`` of the resident ``stack`` written in place and, in
    the same program, its rows' squared norms into ``norms`` (nchunks,
    1, chunk_rows): computed on the device from the STAGED values by the
    kernel wrapper's own expression (``row_norms``), so staging, an
    ingest and the consistency repair's re-ingest keep rows and norms
    in step by construction. Both buffers are donated."""
    from dmlp_tpu.ops.pallas_extract import row_norms
    return (jax.lax.dynamic_update_index_in_dim(stack, blk, c, 0),
            jax.lax.dynamic_update_index_in_dim(
                norms, row_norms(blk)[None], c, 0))


#: what picks the compiled kernel: every one is part of the two
#: programs' jit cache key, resolved by their caller OUTSIDE the jit
_KERNEL_STATICS = ("kc", "interpret", "tile_q", "tile_n", "ne", "unroll",
                   "fold", "mxu_gate", "precision", "score")


def _kernel_statics(impl: str, kc: int, b: int, qb: int, a: int,
                    precision: str, interpret: bool,
                    score: str = "l2") -> Dict[str, Any]:
    """The static arguments of ``extract_topk`` for the kernel ``impl``
    ("fused" | "extract", from resolve_topk_kernel) at dispatch shape
    (qb, b, a): exactly what ``fused_topk`` / ``extract_topk`` would
    resolve for themselves, made concrete here so that it keys the
    enclosing program's jit cache; ``score`` is the engine's (a corpus
    has one), handed to the kernel as the form that orders it
    (config.kernel_score: "cosine" folds normalised operands in the
    "ip" form)."""
    from dmlp_tpu.ops.pallas_extract import _TN, resolve_variant
    v = resolve_variant(kc, b, qb, a)
    return dict(kc=kc, interpret=interpret, tile_q=v["tile_q"],
                tile_n=v.get("tile_n", _TN), ne=v["ne"],
                unroll=v["unroll"], fold=v.get("fold", 0),
                mxu_gate=impl == "fused",
                precision=precision, score=kernel_score(score))


def fold_chunks(q, stack, norms, order, nfold, span, **kern):
    """The resident fold's ONE body, traced by both resident engines
    (``_fold_stack`` here as a plain jit; the mesh engine per shard,
    under ``shard_map``): fold ``nfold`` chunks of the resident
    ``stack`` (nchunks, chunk_rows, A), in the order ``order[:nfold]``
    gives, into one running top-k: the kernel once a chunk, the first
    with no carry (the ``_fresh`` form), the rest carried in a device
    loop. The kernel reads chunk ``c``'s blocks out of ``stack`` and
    its rows' staged squared norms out of ``norms`` (nchunks, 1,
    chunk_rows) by the index itself (``extract_topk``'s stack form):
    nothing is sliced, copied or recomputed a chunk. ``span(c)`` gives
    chunk ``c``'s (id_base, n_real) as traced
    values: the one thing the two engines derive differently.
    Returns (dists, ids, gate, iters). ``gate`` counts (query tile,
    data block) pairs, an int32 pair: [0] those either gate elided (0
    recorded iterations), [1] those whose extraction ran at full width
    (the kernel's two-level selection found a bucket hiding a second
    candidate, or the shape takes no fold pass:
    ``ops.pallas_extract.fold_slabs``). ``iters`` sums the recorded
    iterations, a pair too: [0] all of them, [1] those of the
    full-width visits (``obs.kernel_cost.extract_loop_cost`` prices
    the two apart)."""
    from dmlp_tpu.ops.pallas_extract import extract_topk

    def fold(c, od, oi):
        id_base, n_real = span(c)
        od, oi, its, wide = extract_topk(
            q, stack, od, oi, n_real=n_real, id_base=id_base, chunk=c,
            d_norms=norms, with_wide=True, **kern)
        return od, oi, jnp.stack([jnp.sum(its == 0), jnp.sum(wide)]), \
            jnp.stack([jnp.sum(its), jnp.sum(its * wide)])

    def body(i, carry):
        od, oi, gate, iters = carry
        od, oi, g, its = fold(order[i], od, oi)
        return od, oi, gate + g, iters + its

    return jax.lax.fori_loop(1, nfold, body, fold(order[0], None, None))


def fold_tiles(kern: Dict[str, Any], qb: int, cr: int) -> int:
    """(query tile, data block) pairs one kernel call of ``fold_chunks``
    visits at dispatch shape (qb, cr): static shape arithmetic."""
    from dmlp_tpu.ops.pallas_distance import _tile
    return (qb // _tile(qb, kern["tile_q"], 8)) \
        * (cr // _tile(cr, kern["tile_n"], 128 * kern["ne"]))


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _fold_stack(q, stack, norms, order, nfold, n_real, **kern):
    """``fold_chunks`` over one chip's stack: chunk ``c`` holds rows
    ``c * chunk_rows`` on, real up to ``n_real``. ``order`` (padded to
    a fixed length), ``nfold`` and ``n_real`` are device data, so a new
    schedule, a pruned chunk or an ingest runs the same executable.
    Returns (dists, ids, gate): ``fold_chunks``' first three."""
    cr = stack.shape[1]

    def span(c):
        lo = c * cr
        return lo, jnp.minimum(n_real - lo, cr)

    return fold_chunks(q, stack, norms, order, nfold, span, **kern)[:3]


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _sweep_stack(q, stack, norms, n_real, floor, **kern):
    """One kernel call over the whole ``stack`` as one (nchunks *
    chunk_rows, A) array, its staged ``norms`` as one row beside it (a
    multipass re-sweep above ``floor``). The reshapes are free inside
    the program; an eager one would copy the corpus."""
    from dmlp_tpu.ops.pallas_extract import extract_topk
    return extract_topk(q, stack.reshape(-1, stack.shape[-1]),
                        n_real=n_real, id_base=0, floor=floor,
                        d_norms=norms.reshape(-1), **kern)


def _variant_args(v: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """What ``serve.solve_extract``, ``serve.warmup_bucket`` and
    ``fleet.solve_resident`` say of a solve's kernel variant ``v``
    (``last_variant``'s form): its tiles, where the engine pads it the
    width a staged row holds, the MXU passes its cross term takes a
    visit (1, 3 or 6), and where the kernel's row norms came from:
    ``norms`` "staged" (resident beside the stack, written with each
    chunk: the resident folds) or "computed" (a pass over the chunk a
    kernel call: a caller that holds nothing resident)."""
    from dmlp_tpu.ops.pallas_extract import _TN
    if not v:
        return {}
    return {"tile_n": _TN, "norms": "computed", **{k: v[k] for k in (
        "tile_q", "tile_n", "ne", "fold", "a_pad", "mxu_passes", "norms",
        "score")
        if k in v}}


@dataclasses.dataclass(eq=False)
class PendingBatch(PendingRun):
    """One micro-batch of a resident engine between ``begin_batch``
    and ``finish_batch``: its work is on the device's queue, nothing of
    it has been read back. Two are alive at once, so everything a solve
    says of itself is written here and reaches the engine's ``last_*``
    fields when the batch finishes. (The mesh engine's record adds what
    only a mesh has: ``fleet.mesh_engine.MeshPendingBatch``.)"""

    batch: Optional[int] = None     # the batcher's serial: on every span
    rids: Optional[str] = None      # comma-joined trace request ids
    overlapped: bool = False        # begun while another was in flight
    # -> _last_select, last_extract_impl, last_variant, last_kernel_calls,
    # last_mp_passes, last_prune (ops.summaries.note_scan writes it here)
    select: Optional[str] = None
    extract_impl: Optional[str] = None
    variant: Optional[Dict[str, Any]] = None
    kernel_calls: int = 0
    mp_passes: int = 0
    last_prune: Optional[Dict[str, Any]] = None
    # (fold_chunks' gate counts still on the device, tiles the fold
    # visited)
    gate: Optional[Tuple] = None
    # perf_counter at which the fold (the multipass merge) had been
    # dispatched: the start of the serve.solve_epilogue span, which
    # ends with the first half
    epilogue_pc: Optional[float] = None
    # the multipass driver's fence, not made yet: (perf_counter its
    # enqueues began at, [valid counts] + the floor chain, span args)
    mp_fence: Optional[Tuple] = None
    # an OOM-class failure of either half: the batch re-runs whole
    oom: Optional[BaseException] = None
    # (results, None) | (None, exception) once the second half has run
    outcome: Optional[Tuple] = None

    @property
    def query_attrs(self) -> np.ndarray:
        return self.inp.query_attrs

    @property
    def ks(self) -> np.ndarray:
        return self.inp.ks

    @property
    def path(self) -> Optional[str]:
        """The path the solve took (a slow cycle's record names it)."""
        return "multipass" if self.mp_passes else self.select


class _Bucket:
    """One (qpad, k-bucket) shape bucket: the resolved candidate width,
    the chosen path, and the AOT-compiled streaming program."""

    __slots__ = ("qpad", "kb", "kcap", "path", "qb", "nqb", "stream",
                 "hlo")

    def __init__(self, qpad: int, kb: int, kcap: int, path: str,
                 qb: int, nqb: int):
        self.qpad, self.kb, self.kcap = qpad, kb, kcap
        self.path = path          # "extract" | "multipass" | "stream"
        self.qb, self.nqb = qb, nqb
        self.stream = None        # AOT-compiled _topk_blocks, when built
        self.hlo = None           # {"fingerprint", "collective_bytes"}
        # of the compiled stream (obs.hlo), stamped at compile time in
        # the batcher thread — stats handlers read it lock-free

    @property
    def key(self) -> str:
        return f"q{self.qpad}k{self.kb}"


class ResidentServingCore:
    """The serving surface shared by BOTH resident engines (the
    single-chip :class:`ResidentEngine` and the mesh
    :class:`~dmlp_tpu.fleet.mesh_engine.MeshResidentEngine`):
    compile-once bucket bookkeeping, warm-up, the corpus max-sq-norm
    cache, and the memory-model hooks the admission controller and
    obs.memwatch read. Single-sourced here so a fix to any of them
    cannot silently miss the other engine.

    The batcher drives an engine through :meth:`begin_batch` and
    :meth:`finish_batch` and may have begun one micro-batch behind the
    one it finishes next, so two are alive at once. The pair, the list
    of batches in flight and ``solve_batch``'s handed-record logic are
    here, once; an engine supplies the two halves themselves
    (:meth:`_first_half`: what only enqueues; :meth:`_second_half`: the
    fence and the host's share) and keeps what a solve says of itself
    in the batch's :class:`PendingBatch`.

    What a score asks of an engine outside its programs is here too,
    once: the float64 norms kept beside the host rows and the rows and
    queries as the device holds them (x / |x| and q / |q| under
    "cosine": :meth:`_staged_rows`, :meth:`_staged_queries`), the
    micro-batch's input with those norms (:meth:`_batch_input`) and the
    serving cap under a score only the extract path has
    (:attr:`max_k`).

    Subclass contract: ``bucket_shape``/``_build_bucket``/``_kcap_for``/
    ``_MP_KC``/``_first_half``/``_second_half`` plus the resident
    state the hooks read (``_host_attrs``, ``_host_labels``, ``n_real``,
    ``capacity_rows``, ``num_attrs``, ``_staging``, ``config``); the
    subclass implements :meth:`mem_model` (its analytic per-device
    model, batch terms included iff ``nq > 0``) and
    :meth:`batch_model_bytes` (the marginal per-batch terms — the
    term names differ per model), and names its cache-invalidation
    state in :meth:`resident_state_key`.
    """

    #: serial and comma-joined rids of the micro-batch whose half is
    #: running NOW: installed by the engine for the length of a half
    #: (one thread runs one half at a time), so that every span inside
    #: tags itself with its own batch. None outside the batcher
    #: (warm-up) and whenever untraced.
    trace_batch: Optional[int] = None
    trace_rids: Optional[str] = None

    #: micro-batches whose lists can be on the device at once (the one
    #: read back and the one begun behind it): what admission multiplies
    #: one batch's price by
    batches_resident = 2

    #: the record a batch's state lives in (an engine may extend it)
    _pending_type = PendingBatch

    #: the begun micro-batch finish_batch is handing to solve_batch
    _handed = None

    #: whose spans the shared staging steps are ("serve.normalize_rows"
    #: on one chip, "fleet.normalize_rows" on a mesh)
    _span_ns = "serve"

    #: what of this engine ranks by squared L2 alone, as a request past
    #: the one-pass cap under another score is told (_k_refusal)
    _l2_only = ("serve.engine.ResidentEngine's multipass driver and "
                "streaming select")

    #: rows a block of ``_staged_rows``' float64 quotient (its one
    #: temporary: 100 MB at 1536 attributes)
    _NORMALIZE_ROWS = 8192

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # Micro-batches begun and not finished, oldest first (at most
        # two: the batcher begins one behind the one it finishes next).
        # The batcher thread's alone, like everything a solve touches.
        self._in_flight: List[PendingBatch] = []

    # -- a micro-batch in two halves (the batcher drives these) --------------

    def begin_batch(self, query_attrs, ks, batch: Optional[int] = None,
                    rids: Optional[str] = None) -> PendingBatch:
        """The first half of a micro-batch: stage its queries, choose
        and prune the fold order, dispatch the bucket's program(s).
        Everything that only ENQUEUES: the batcher calls this for batch
        N + 1 while the device still folds batch N, and only then
        finishes N. (It waits for the device in one place, the prune
        scorer's readback.) ``batch`` / ``rids`` tag the batch's spans.
        Returns the record :meth:`finish_batch` takes."""
        inp = self._batch_input(np.asarray(query_attrs, np.float64),
                                np.asarray(ks, np.int32))
        self._check_k(inp)
        pend = self._pending_type(inp, batch=batch, rids=rids,
                                  overlapped=bool(self._in_flight))
        with self._tagged(pend):
            self._first_half(pend)
        self._in_flight.append(pend)
        return pend

    def finish_batch(self, pending: PendingBatch) -> List[QueryResult]:
        """Second half: the batch's results, or what it failed with.
        It goes through :meth:`solve_batch`, the one call every served
        answer comes out of (whoever wraps that call sees every
        micro-batch), which finds the begun record in ``_handed``."""
        self._handed = pending
        try:
            with self._tagged(pending):
                return self.solve_batch(pending.query_attrs, pending.ks)
        finally:
            self._handed = None

    def solve_batch(self, query_attrs, ks) -> List[QueryResult]:
        """One coalesced micro-batch end to end: pad/bucket, solve on
        the compiled bucket program(s), float64-finalize + repair,
        update the cross-request gate state. Results carry query ids
        0..nq-1 in batch order — the batcher slices per request.

        Called alone (warm-up, the tests, every caller outside the
        batcher) it runs the two halves back to back. Called by
        ``finish_batch`` it is the second half of the batch handed to
        it, whose first half ``begin_batch`` ran earlier: the fence,
        the hazard test, the float64 finalize + repair, the gate
        bookkeeping. It raises what the batch failed with; a batch
        begun behind it is untouched. Same answers either way."""
        pend, self._handed = self._handed, None
        if pend is None:
            pend = self.begin_batch(query_attrs, ks)
        if pend in self._in_flight:
            self._in_flight.remove(pend)
        if pend.outcome is None:
            pend.outcome = self._outcome(pend)
        results, error = pend.outcome
        if error is not None:
            raise error
        return results

    def _outcome(self, pend: PendingBatch) -> Tuple:
        """(results, None) or (None, the exception) of ``pend``'s second
        half: a failure is that batch's alone."""
        try:
            with self._tagged(pend):
                return self._second_half(pend), None
        except Exception as e:  # check: no-retry — solve_batch raises it
            return None, e

    def _first_half(self, pend: PendingBatch) -> None:
        """Enqueue ``pend``'s device work; block on nothing but the
        prune scorer's mask."""
        raise NotImplementedError

    def _second_half(self, pend: PendingBatch) -> List[QueryResult]:
        """``pend``'s fence and the host's share of it; writes the
        engine's ``last_*`` report."""
        raise NotImplementedError

    def _report_finished(self, pend: PendingBatch) -> None:
        """What `stats` reports of "the last solve" is the last batch
        FINISHED, whole: each field one assignment, read lock-free."""
        self._last_select = pend.select
        self.last_extract_impl = pend.extract_impl
        self.last_variant = pend.variant
        self.last_prune = pend.last_prune
        if pend.last_prune is not None:
            self.last_prune_fraction = pend.last_prune["pruned_fraction"]

    def _batch_input(self, query_attrs: np.ndarray,
                     ks: np.ndarray) -> KNNInput:
        """A micro-batch as a KNNInput over the resident corpus (host
        views feed the float64 finalize/repair exactly as a solo solve
        over the same corpus would)."""
        nq = len(ks)
        return KNNInput(
            Params(self.n_real, nq, self.num_attrs),
            self._host_labels[:self.n_real],
            self._host_attrs[:self.n_real],
            np.asarray(ks, np.int32),
            np.asarray(query_attrs, np.float64),
            data_norms=None if self._host_norms is None
            else self._host_norms[:self.n_real])

    @property
    def max_k(self) -> int:
        """Largest per-query k this resident engine serves: the staging
        dtype's safe cap (bf16 margins blow past the resident layout
        beyond it), the corpus capacity and, under a score only the
        extract path has, the last bucket of one kernel pass."""
        cap = self.capacity_rows
        if self._staging == "bfloat16":
            cap = min(cap, _BF16_AUTO_K_CAP)
        if self.config.score != "l2":
            cap = min(cap, self._one_pass_max_k)
        return cap

    @functools.cached_property
    def _one_pass_max_k(self) -> int:
        """The largest k whose bucket still plans one kernel pass (a
        window of at most _MP_KC slots): under a score the paths past
        it lack (``_l2_only``), the serving cap."""
        return max((k for k in (1 << p for p in range(
            self._MP_KC.bit_length())) if self._kcap_for(k)
            <= self._MP_KC), default=0)

    def _check_k(self, inp: KNNInput) -> None:
        kmax = int(inp.ks.max()) if inp.params.num_queries else 0
        if kmax > self.max_k:
            raise RequestShapeError(self._k_refusal(kmax))

    def _k_refusal(self, kmax: int) -> str:
        """What a request past the serving cap is told."""
        msg = f"k={kmax} beyond the serving cap {self.max_k}"
        if self.config.score != "l2" and kmax <= self.capacity_rows:
            msg += (f" under score={self.config.score!r}: {self._l2_only} "
                    f"(a window past {self._MP_KC} slots) rank by squared "
                    "L2 alone")
        return msg

    @contextlib.contextmanager
    def _tagged(self, pending):
        """The spans of one half carry the half's own batch."""
        prev = self.trace_batch, self.trace_rids
        self.trace_batch, self.trace_rids = pending.batch, pending.rids
        try:
            yield
        finally:
            self.trace_batch, self.trace_rids = prev

    @staticmethod
    def _note_flagged(flagged: int, device: int = 0) -> None:
        """Always-on counts of the boundary repair: queries the hazard
        test flagged, and where each was repaired (``device``: cleared
        by the retry at a wider window; the rest by the host oracle)."""
        if not flagged:
            return
        reg = telemetry.registry()
        reg.counter("serve.flagged_queries").inc(flagged)
        for label, count in (("device", device),
                             ("host", flagged - device)):
            if count:
                reg.counter("serve.repairs").inc(count, label=label)

    @staticmethod
    def _repair_stats() -> Dict[str, int]:
        reg = telemetry.registry()
        repairs = reg.counter("serve.repairs")
        return {"flagged_queries": int(reg.counter(
                    "serve.flagged_queries").total()),
                "device": int(repairs.value("device")),
                "host": int(repairs.value("host"))}

    @staticmethod
    def _note_rescore(pend: "PendingBatch") -> None:
        """Always-on counts of the float64 rescore: candidate slots
        finalized and rows gathered for them (fewer where the hazard
        test's bound cut the rescore to its band:
        engine.finalize.boundary_band)."""
        reg = telemetry.registry()
        reg.counter("serve.rescore_slots").inc(pend.rescore_slots)
        reg.counter("serve.rescore_rows").inc(pend.rescore_rows)

    @staticmethod
    def _rescore_stats() -> Dict[str, int]:
        reg = telemetry.registry()
        return {"slots": int(reg.counter("serve.rescore_slots").total()),
                "rows": int(reg.counter("serve.rescore_rows").total())}

    @staticmethod
    def _overlap_stats() -> Dict[str, int]:
        """Always-on counts of the batcher's pipeline: micro-batches
        delivered, and those begun while another was in flight."""
        reg = telemetry.registry()
        return {"batches": int(reg.counter("serve.batches").total()),
                "overlapped": int(reg.counter(
                    "serve.batches_overlapped").total())}

    def _rid_args(self) -> Dict[str, Any]:
        """Span-args rider carrying the serial and the rids of the
        micro-batch whose half is running — empty (and allocation-only)
        outside the batcher."""
        out: Dict[str, Any] = {}
        if self.trace_batch is not None:
            out["batch"] = self.trace_batch
        if self.trace_rids:
            out["rids"] = self.trace_rids
        return out

    def _bucket_entry(self, nq: int, kmax: int):
        """The bucket for (nq, kmax), building (and counting) it on
        first use — warm-up pre-drives this so steady-state serving
        takes the dict hit only."""
        if kmax > self.max_k:
            raise RequestShapeError(self._k_refusal(kmax))
        key = self.bucket_shape(nq, kmax)
        entry = self._buckets.get(key)
        if entry is None:
            t0 = time.perf_counter()
            entry = self._build_bucket(*key)
            self._buckets[key] = entry
            ms = (time.perf_counter() - t0) * 1e3
            self.bucket_compile_ms[entry.key] = round(ms, 3)
            self.compile_count += 1
        return entry

    def warmup(self, buckets) -> Dict[str, float]:
        """Drive one synthetic micro-batch through every (nq, k) in
        ``buckets`` BEFORE serving: compiles the bucket programs and
        the shared epilogue jits, and records
        ``serve.cold_start_compile_ms`` — the startup SLO is a number,
        not a hope. Returns per-bucket wall ms."""
        t0 = time.perf_counter()
        per: Dict[str, float] = {}
        seen = set()
        for nq, k in buckets:
            # Clamp to the serving cap ONLY — k > n_real is a legal
            # request shape (sentinel padding, golden-identical), so a
            # requested warm bucket above the corpus row count must
            # warm THAT k-bucket, not silently a smaller one.
            k = max(1, min(int(k), self.max_k))
            nq = max(1, int(nq))
            key = self.bucket_shape(nq, k)
            if key in seen:
                continue
            seen.add(key)
            tb = time.perf_counter()
            idx = np.arange(nq) % self.n_real
            q = self._host_attrs[:self.n_real][idx]
            ks = np.full(nq, k, np.int32)
            with obs_span("serve.warmup_bucket", qpad=key[0],
                          kb=key[1]) as sp:
                self.solve_batch(q, ks)
                sp.set(**_variant_args(
                    getattr(self, "last_variant", None)))
            per[f"q{key[0]}k{key[1]}"] = round(
                (time.perf_counter() - tb) * 1e3, 3)
        per.update(self._warm_more())
        self.cold_start_compile_ms = round(
            (time.perf_counter() - t0) * 1e3, 3)
        telemetry.registry().gauge("serve.cold_start_compile_ms").set(
            self.cold_start_compile_ms)
        return per

    def _warm_more(self) -> Dict[str, float]:
        """Seam: programs beside the buckets' own that warm-up
        front-loads ({name: wall ms}); none by default."""
        return {}

    # -- corpus signature (fleet consistency checking reads this) -----------

    def _sig_init(self) -> None:
        """Seed the rolling corpus signature: per-row 64-bit hashes +
        a position-keyed fold (fleet.consistency). A pure function of
        (global row id, label, attribute bits) — every resident engine
        layout reports the same signature for the same corpus, which is
        what makes cross-replica comparison meaningful."""
        from dmlp_tpu.fleet import consistency as ccs
        n = self.n_real
        self._row_hash = np.zeros(len(self._host_labels), np.uint64)
        fold = 0
        if n:
            self._row_hash[:n] = ccs.row_hashes(
                self._host_labels[:n], self._host_attrs[:n])
            fold = ccs.fold_terms(0, self._row_hash[:n])
        # One tuple, assigned atomically: stats handlers read it while
        # the batcher thread ingests — a torn (rows, checksum) pair
        # would manufacture spurious divergences at the router.
        self._corpus_sig = (n, fold, 0)

    def _sig_update(self, start: int, end: int) -> None:
        """Fold rows ``[start, end)``'s new content in (O(m): subtract
        the old terms, add the new — idempotent overwrites are exact
        no-ops) and bump the ingest epoch."""
        from dmlp_tpu.fleet import consistency as ccs
        n0, fold, epoch = self._corpus_sig
        new_h = ccs.row_hashes(self._host_labels[start:end],
                               self._host_attrs[start:end])
        fold = ccs.fold_replace(fold, start,
                                self._row_hash[start:end], new_h)
        self._row_hash[start:end] = new_h
        self._corpus_sig = (max(n0, end), fold, epoch + 1)

    def corpus_state(self) -> Dict[str, int]:
        """The live corpus signature block the daemon exposes in
        ``stats`` (and the ``corpus`` wire op echoes): row count,
        rolling checksum, ingest epoch."""
        n, fold, epoch = self._corpus_sig
        return {"rows": n, "checksum": fold, "epoch": epoch}

    def corpus_slice(self, start: int, count: int):
        """Host rows ``[start, start+count)`` clamped to the resident
        row count — the ``corpus`` wire op's data source (executed on
        the batcher thread, so it never races an ingest)."""
        n = self._corpus_sig[0]
        start = max(0, min(int(start), n))
        end = max(start, min(start + int(count), n))
        return (self._host_labels[start:end].copy(),
                self._host_attrs[start:end].copy())

    # -- what the device holds of a row and of a query (cosine: x / |x|) -----

    def _init_host_norms(self, host_rows: int) -> None:
        """A cosine corpus' |x|, float64, beside the host rows (0 past
        the last): what normalises a staged row and what the rescore
        and the host oracle divide by (KNNInput.data_norms). None under
        the other scores."""
        self._host_norms: Optional[np.ndarray] = None
        if self.config.score == "cosine":
            self._host_norms = np.zeros(host_rows, np.float64)
            self._note_norms(0, self.n_real)

    def _note_norms(self, lo: int, hi: int) -> None:
        """``_host_norms`` of rows [lo, hi), from the host rows as
        they now stand (a cosine engine's alone)."""
        with obs_span(f"{self._span_ns}.normalize_rows", site="norms",
                      rows=hi - lo,
                      bytes=(hi - lo) * self.num_attrs * 8) as sp:
            nrm = row_norms_f64(self._host_attrs[lo:hi])
            self._host_norms[lo:hi] = nrm
            zero = int(np.count_nonzero(nrm == 0))
            sp.set(zero_rows=zero)
        if zero:
            telemetry.registry().counter("serve.zero_rows").inc(zero)

    def _staged_rows(self, lo: int, hi: int, out: np.ndarray) -> None:
        """Host rows [lo, hi) as the device holds them, cast into
        ``out[:hi - lo, :num_attrs]`` (an array of the staging dtype):
        the rows themselves, or under "cosine" x / |x|, the quotient
        taken in float64 and then cast; a zero row stays zero."""
        na = self.num_attrs
        if self.config.score != "cosine":
            out[:hi - lo, :na] = self._host_attrs[lo:hi]
            return
        step = self._NORMALIZE_ROWS
        with obs_span(f"{self._span_ns}.normalize_rows", site="stage",
                      rows=hi - lo, bytes=(hi - lo) * na * 8) as sp:
            buf = np.empty((min(step, hi - lo), na), np.float64)
            nrm = self._host_norms[lo:hi]
            for a in range(0, hi - lo, step):
                b = min(a + step, hi - lo)
                out[a:b, :na] = np.divide(
                    self._host_attrs[lo + a:lo + b],
                    np.where(nrm[a:b] > 0, nrm[a:b], 1.0)[:, None],
                    out=buf[:b - a])
            sp.set(zero_rows=int(np.count_nonzero(nrm == 0)))

    def _staged_queries(self, inp: KNNInput, qpad: int,
                        width: int) -> np.ndarray:
        """A micro-batch's query rows as the device holds them, float32,
        padded to ``qpad`` rows of ``width`` columns: the rows
        themselves, or under "cosine" q / |q|, the quotient taken in
        float64 like a staged row's (a zero query stays zero: it scores
        0 against every row)."""
        nq, na = inp.params.num_queries, self.num_attrs
        q = np.zeros((qpad, width), np.float32)
        if self.config.score != "cosine":
            q[:nq, :na] = inp.query_attrs
            return q
        with obs_span(f"{self._span_ns}.normalize_queries", queries=nq,
                      **self._rid_args()) as sp:
            nrm = row_norms_f64(inp.query_attrs)
            q[:nq, :na] = inp.query_attrs / np.where(
                nrm > 0, nrm, 1.0)[:, None]
            sp.set(zero_queries=int(np.count_nonzero(nrm == 0)))
        return q

    # -- corpus max squared norm (boundary-eps / multipass floors) ----------

    def _dn_max(self) -> float:
        if self._dn_max_cache is None and self.config.score == "cosine":
            # of the rows the DEVICE holds, x / |x|: 1, and 0 of a
            # corpus of zero rows (finalize._product_scale); no pass
            self._dn_max_cache = float(
                self._host_norms[:self.n_real].any())
        if self._dn_max_cache is None:
            a = self._host_attrs[:self.n_real]
            # The one pass over the whole float64 host corpus: spanned
            # where it runs (ResidentEngine: set-up, so no batch).
            with obs_span("single.dn_max", rows=self.n_real,
                          **self._rid_args()):
                self._dn_max_cache = float(
                    np.einsum("na,na->n", a, a).max()) if self.n_real \
                    else 0.0
        return self._dn_max_cache

    def _corpus_dn_max(self, inp: KNNInput) -> Tuple[float, bool]:
        """SingleChipEngine._run's seam: ``inp`` is a micro-batch over
        the resident corpus (_solve refuses any other), whose value is
        resident too — equal to the pass over the live rows after loads
        and appends, never smaller after an overwrite (ingest)."""
        return self._dn_max(), True

    def _note_ingested_norms(self, attrs: np.ndarray) -> None:
        """Append-only ingest keeps the cache incremental: the max
        squared norm only grows (under "cosine", of the normalised rows
        the device holds: to 1 with the first row that is not zero)."""
        if self._dn_max_cache is not None and len(attrs):
            nn = np.einsum("ma,ma->m", attrs, attrs).max()
            if self.config.score == "cosine":
                nn = float(nn > 0)
            self._dn_max_cache = max(self._dn_max_cache, float(nn))

    # -- gate effectiveness (the fused kernel's gated-tile count) ------------

    def _flush_gate(self, sp, gate: Optional[Tuple]) -> None:
        """Read back a batch's gate counts (``gate``: ``fold_chunks``'
        [gated, wide] pair, or a mesh engine's one pair a cell, summed
        here, with the tiles its fold visited: a host sync, after the
        result fetch) into ``last_gated_fraction`` and the span: of
        ``tiles`` visits, ``gated`` extracted nothing and ``wide``
        (``wide_pct`` of them) extracted at full width."""
        if gate is None:
            return
        gz, ntiles = gate
        try:
            with obs_trace.device_wait("gate", self.trace_batch):
                got = jax.device_get(gz)  # check: allow-host-sync
            gated, wide = (int(n) for n in
                           np.asarray(got).reshape(-1, 2).sum(axis=0))
            self.last_gated_fraction = gated / max(ntiles, 1)
            sp.set(gated=gated, tiles=ntiles, wide=wide,
                   wide_pct=round(100.0 * wide / max(ntiles, 1), 3))
        except Exception:  # check: no-retry — stats never fail a batch
            pass

    # -- resident block summaries on the device (pruned solve, stage 1) -----

    def _put_resident(self, value):
        """Place one small resident array where this engine's solves
        read it (a mesh engine replicates it over its mesh)."""
        return jax.device_put(value)

    def _stage_summaries(self) -> None:
        """Conservative f32 copies of ``self._summ`` on the device
        (tiny: O(blocks * a)), with the eps constants the scorer
        widens its thresholds by."""
        from dmlp_tpu.engine.finalize import (EPS_CANCEL_COEF, LOWP_COEF,
                                              EPS_REL_BF16, EPS_REL_F32)
        from dmlp_tpu.ops import summaries as osum
        dev = {k: self._put_resident(v)
               for k, v in osum.stage_summaries(self._summ).items()}
        # The scorer reads the batch's query rows as the fold does, at
        # the stack's width: a zero-wide box in every padded column.
        pad = getattr(self, "_ex_attrs", self.num_attrs) - self.num_attrs
        if pad:
            for k in ("lo", "hi"):
                dev[k] = self._put_resident(
                    np.pad(np.asarray(dev[k]), ((0, 0), (0, pad))))
        rel = EPS_REL_BF16 if self._staging == "bfloat16" else EPS_REL_F32
        # score_blocks widens thresholds by eps_rel*sqrt(thr*scale) +
        # eps_cancel*scale with scale = qn + dn_max; lowp_eps is
        # LOWP_COEF*scale, so folding the plan's coefficient into the
        # staged eps_cancel scalar composes the bf16 first-pass bound
        # additively — exactly prune_mask's precision widening. Plan-
        # level (not per-rung): on the f32 rungs the extra slack only
        # keeps a few more blocks, never drops one.
        dev["eps_rel"] = self._put_resident(np.float32(rel))
        dev["eps_cancel"] = self._put_resident(
            np.float32(EPS_CANCEL_COEF * (self.num_attrs + 2)
                       + LOWP_COEF[self._precision_plan]))
        self._summ_dev = dev

    def _score_summaries(self, inp: KNNInput, qpad: int, q_dev,
                         span: str, blocks: int) -> np.ndarray:
        """Score the RESIDENT summaries on device for one padded
        micro-batch (ops.summaries.score_blocks — compiled once per
        bucket shape) and read back the tiny (blocks,) survivor mask."""
        from dmlp_tpu.obs import counters as obs_counters
        from dmlp_tpu.ops import summaries as osum
        with obs_span(span, blocks=blocks, qpad=qpad,
                      **self._rid_args()):
            nq = inp.params.num_queries
            ks = np.ones(qpad, np.int32)
            ks[:nq] = inp.ks
            qvalid = np.zeros(qpad, bool)
            qvalid[:nq] = True
            sd = self._summ_dev
            args = (q_dev, self._put_resident(qvalid),
                    self._put_resident(ks),
                    sd["counts"], sd["nmin"], sd["nmax"], sd["lo"],
                    sd["hi"], sd["dn_max"], sd["eps_rel"],
                    sd["eps_cancel"])
            obs_counters.record_dispatch(osum.score_blocks, args,
                                         site=span)
            mask = osum.score_blocks(*args)
            # Deliberate tiny fence: the (blocks,) mask decides WHICH
            # resident chunks the folds dispatch over, so the host must
            # read it before enqueueing them — O(blocks) bytes, priced
            # by the analytic score model, nothing like a result fetch.
            with obs_trace.device_wait("prune_score", self.trace_batch):
                return np.asarray(
                    jax.device_get(mask))  # check: allow-host-sync

    # -- memory-model hooks (admission + memwatch read these) ---------------

    #: True when :meth:`mem_model` prices ONE device of several (a mesh
    #: engine): admission then holds it to the fullest device's
    #: watermark, not to the sum over the host's devices.
    mem_per_device = False

    def mem_model(self, nq: int = 0, kmax: int = 0):
        raise NotImplementedError

    def batch_model_bytes(self, nq: int, kmax: int) -> int:
        raise NotImplementedError

    def resident_state_key(self):
        """The resident-state tuple whose change invalidates a cached
        resident-floor total (admission memoizes on it)."""
        raise NotImplementedError

    def resident_model_bytes(self) -> int:
        """Per-device resident floor (corpus terms only, no batch)."""
        return int(self.mem_model(0, 0)["total_bytes"])


class ResidentEngine(ResidentServingCore, SingleChipEngine):
    """Compile-once resident engine for the serving daemon.

    ``corpus`` supplies the data side (its query section, if any, is
    ignored here — the daemon uses it to seed warm-up). ``capacity``
    is the ingest ceiling in rows (default: the corpus row count's
    power-of-two bucket, i.e. free headroom to the next boundary).

    A micro-batch is solved in two halves (:meth:`begin_batch`: what
    only enqueues; :meth:`finish_batch`: the fence and the host's
    float64 work) so that the batcher can put batch N + 1 on the
    device's queue before it reads batch N back: two batches are alive
    at once, each in its :class:`PendingBatch`, and the engine's own
    fields hold what outlives a batch (the corpus, the buckets, the
    gate histogram, the ``last_*`` report of the last batch finished).

    **Score** (``config.score``): the extract path's buckets rank by
    squared L2 or by inner product ("ip": the kernel orders -q.x, the
    hazard test and the device retry take the ip bound, the float64
    rescore the product; golden.reference has the contract). The
    streaming select, the wide-k multipass driver and the block-prune
    scorer know squared L2 alone: under "ip" an engine whose corpus
    does not take the extract path is refused at construction, a k
    whose window passes the kernel's 512 slots at admission
    (:attr:`max_k`), the ladder's ``streaming`` rung is skipped
    (resilience.degrade) and the scorer does not run, so that no path
    answers an inner-product corpus in L2. "cosine" is the "ip" form
    over unit vectors, and its work is at staging: every device copy
    holds x / |x|, computed in float64 from the host's rows and then
    cast (``_staged_rows``: first staging, a restaged chunk, an ingest),
    a batch's queries are normalised the same way
    (``_stage_batch_queries``), the kernel runs its "ip" form over them
    (config.kernel_score), and the bounds are ip's at unit operands
    (finalize.COS_NORM_COEF). The HOST keeps the rows as they were given
    with their norms beside them (``_host_norms``): the float64 rescore
    evaluates the contract on the originals, and ``corpus_slice``, the
    corpus signature and a replica seeded from this one see the corpus
    unchanged. Everything "ip" is refused, "cosine" is refused.
    """

    _scores = ("l2", "ip", "cosine")

    def __init__(self, corpus: KNNInput, config: EngineConfig = None,
                 capacity: Optional[int] = None, gate_carry: bool = True):
        super().__init__(config or EngineConfig())
        cfg = self.config
        n = corpus.params.num_data
        na = corpus.params.num_attrs
        if n < 1:
            raise ValueError("resident corpus must have at least one row")
        cap = capacity or shape_bucket(n)
        if cap < n:
            raise ValueError(f"capacity {cap} < corpus rows {n}")
        self.num_attrs = self._kcap_attrs = na
        self.gate_carry = bool(gate_carry)
        # First-pass precision PLAN, frozen at construction like every
        # other resident shape decision: bucket kcaps, the staged
        # summary-eps constants, and the active cast (engine.single
        # .active_precision clamps to this) all derive from ONE plan,
        # so an env flip mid-serve can disable the bf16 pass (windows
        # merely stay wider than needed) but can never run it against
        # windows that were planned f32.
        self._precision_plan = cfg.resolve_precision(self._staging)

        # -- plan the streaming layout once, at capacity shape ---------------
        self._stream_select = cfg.resolve_streaming_select(
            round_up(cap, 8))
        granule = cfg.resolve_granule(self._stream_select)
        self._data_block = fit_blocks(
            cap, cfg.resolve_data_block(self._stream_select),
            granule=granule)
        self.capacity_rows = round_up(cap, self._data_block)

        # -- extract-path eligibility + chunk plan (chunks stage lazily) -----
        self._extract_ok = (cfg.use_pallas and cfg.resolve_select(
            round_up(cap, 8)) == "extract")
        if self._extract_ok:
            eg = cfg.resolve_granule("extract")
            _, self._ex_nchunks, self._ex_chunk_rows = plan_chunks(
                self.capacity_rows, eg, cfg.data_block)
            self._ex_rows = self._ex_nchunks * self._ex_chunk_rows
            from dmlp_tpu.ops.pallas_distance import pallas_interpret
            self._interpret = pallas_interpret()
        else:
            # every bucket would take the streaming select, which ranks
            # by squared L2 alone
            cfg.require_score(
                "serve.engine.ResidentEngine's streaming select (a "
                "corpus that does not take the extract path: no "
                "use_pallas, or no more than "
                f"{cfg.AUTO_SELECT_THRESHOLD} rows under select='auto')")
            self._ex_nchunks = self._ex_chunk_rows = self._ex_rows = 0
            self._interpret = True
        # The extract path's resident copy: ONE device array (nchunks,
        # chunk_rows, A on whole lanes), so a whole fold is one program
        # (_fold_stack). The zero columns are staging's: the wire, the
        # host rows and num_attrs never see them.
        from dmlp_tpu.ops.pallas_extract import lane_padded
        self._ex_attrs = lane_padded(na)
        self._chunks = None
        # The stack's rows' squared norms, (nchunks, 1, chunk_rows) float32
        # beside it, and the chunks whose norms were (re)written since
        # start: every chunk once when the stack stages, one a restage.
        self._norms = None
        self.norm_restages = 0
        host_rows = max(self.capacity_rows, self._ex_rows)

        # -- host originals (float64 finalize rescore reads these) -----------
        with obs_span("serve.init.host_copy", rows=host_rows, na=na):
            self._host_attrs = np.zeros((host_rows, na), np.float64)
            self._host_attrs[:n] = corpus.data_attrs
            self._host_labels = np.full(host_rows, -1, np.int32)
            self._host_labels[:n] = corpus.labels
        self.n_real = n
        self._init_host_norms(host_rows)
        with obs_span("serve.init.row_hashes", rows=n):
            self._sig_init()
        # The corpus max-sq-norm the hazard test and the multipass floor
        # chain scale by is resident like the rows: its whole-corpus pass
        # is paid here, once a daemon, and ingest keeps it right.
        self._dn_max_cache: Optional[float] = None
        self._dn_max()

        # -- the resident staged corpus (the streaming paths' view) ----------
        # (the streaming paths' copy holds a row at its own width)
        with obs_span("serve.stage_resident", rows=self.capacity_rows,
                      na=na, a_pad=na, pad_bytes=0):
            sdt = np_staging_dtype(self._staging)
            # Allocated on the device, then filled a block at a time by
            # a donated update, as the extract stack is: the host never
            # holds a staged copy of the corpus beside its float64 one
            # (6.1 GB at 990 000 x 1536 float32, which with the harness's
            # and the engine's float64 rows passed a 40 GiB machine).
            step = self._data_block
            self._d_attrs = jnp.zeros((self.capacity_rows, na), sdt)
            for lo in range(0, n, step):
                blk = np.zeros((step, na), sdt)
                self._staged_rows(lo, min(lo + step, n), blk)
                self._d_attrs = _update_rows_2d(
                    self._d_attrs, stage_put(blk, self._staging),
                    jax.device_put(np.int32(lo)))
            ids = np.full(self.capacity_rows, -1, np.int32)
            ids[:n] = np.arange(n, dtype=np.int32)
            self._d_labels = jax.device_put(
                self._host_labels[:self.capacity_rows])
            self._d_ids = jax.device_put(ids)

        # -- bucket registry + compile bookkeeping ---------------------------
        self._buckets: Dict[Tuple[int, int], _Bucket] = {}
        self.compile_count = 0
        self.cold_start_compile_ms: Optional[float] = None
        self.bucket_compile_ms: Dict[str, float] = {}
        # the device retry's programs have compiled (_retry_begin)
        self._retry_built = False
        # Cross-request gate state: per-chunk winner histogram.
        self._block_hits = np.zeros(max(self._ex_nchunks, 1), np.int64)
        # kernel calls the last solve's programs made: the chunks an
        # extract bucket folded; a multipass bucket's folds of pass 1
        # and one whole-stack sweep a further pass
        self.last_kernel_calls = 0
        self.last_gated_fraction: Optional[float] = None
        # Pruned two-stage solve state (ops.summaries): host f64 block
        # summaries at extract-chunk granularity + their device-resident
        # conservative f32 copies, built with the chunks and rebuilt
        # per touched block on ingest (a stale summary is silent
        # unsoundness — the one failure mode the repair cannot catch).
        self._summ = None
        self._summ_dev = None
        self.summary_rebuilds = 0
        self.last_prune_fraction: Optional[float] = None
        reg = telemetry.registry()
        reg.gauge("serve.corpus_rows").set(n)
        reg.gauge("serve.capacity_rows").set(self.capacity_rows)

    # -- shape buckets --------------------------------------------------------

    @property
    def query_granule(self) -> int:
        if self._extract_ok:
            from dmlp_tpu.ops.pallas_extract import QUERY_TILE
            return QUERY_TILE
        return 8

    def bucket_shape(self, nq: int, kmax: int) -> Tuple[int, int]:
        return (query_bucket(nq, self.query_granule), k_bucket(kmax))

    def _kcap_for(self, kb: int) -> int:
        return resolve_kcap(self.config, kb, self._stream_select,
                            self.capacity_rows, staging=self._staging,
                            precision=self._precision_plan,
                            na=self._kcap_attrs)

    def bucket_plan(self, nq: int, kmax: int) -> Tuple[int, int, int]:
        """(qpad, k-bucket, kcap) for a request/batch shape — the ONE
        derivation of the candidate width the solve will allocate;
        admission pricing and the memwatch model read it from here so
        they cannot drift from what _build_bucket compiles."""
        qpad, kb = self.bucket_shape(nq, kmax)
        return qpad, kb, self._kcap_for(kb)

    def _mp_passes(self, kcap: int) -> int:
        """Kernel passes a bucket of ``kcap`` slots takes on the wide-k
        multipass driver; 0 where one pass fills it or the driver does
        not apply (no extract path, more than _MP_MAX_PASSES)."""
        passes = -(-kcap // self._MP_KC)
        return passes if self._extract_ok \
            and 1 < passes <= self._MP_MAX_PASSES else 0

    def _build_bucket(self, qpad: int, kb: int) -> _Bucket:
        cfg = self.config
        kcap = self._kcap_for(kb)
        qb = min(1 << max(min(cfg.query_block, qpad).bit_length() - 1, 3),
                 qpad)
        nqb = qpad // qb
        path = "stream"
        if self._extract_ok and kcap <= 512:
            from dmlp_tpu.ops import pallas_fused
            kern, _ = pallas_fused.resolve_topk_kernel(
                qpad, self._ex_chunk_rows, self._ex_attrs, kcap)
            if kern is not None:
                path = "extract"
                self._ensure_chunks()
        elif self._mp_passes(kcap):
            # Wide-k serving (ROADMAP item (d)): kcap past the kernel's
            # single-pass window routes through the multi-pass
            # extraction driver against the RESIDENT chunks.
            from dmlp_tpu.ops import pallas_fused
            kern, _ = pallas_fused.resolve_topk_kernel(
                qpad, self._ex_chunk_rows, self._ex_attrs, self._MP_KC)
            if kern is not None:
                path = "multipass"
                self._ensure_chunks()
        entry = _Bucket(qpad, kb, kcap, path, qb, nqb)
        if path == "stream":
            self.config.require_score(
                "serve.engine.ResidentEngine's streaming select (bucket "
                f"{entry.key}: {kcap} slots the kernel does not tile)")
            self._compile_stream(entry)
        elif path == "multipass":
            self.config.require_score(
                "serve.engine.ResidentEngine's multipass driver (bucket "
                f"{entry.key}: {kcap} slots)")
        return entry

    def _compile_stream(self, entry: _Bucket) -> None:
        """AOT lower+compile the bucket's streaming program (the
        cold-start satellite: compilation happens ahead of the first
        request, not on it)."""
        cfg = self.config
        sh = jax.ShapeDtypeStruct
        na = self.num_attrs
        entry.stream = _topk_blocks.lower(
            sh((self.capacity_rows, na), self._dtype),
            sh((self.capacity_rows,), jnp.int32),
            sh((self.capacity_rows,), jnp.int32),
            sh((entry.nqb, entry.qb, na), self._dtype),
            k=entry.kcap, data_block=self._data_block,
            select=self._stream_select,
            use_pallas=cfg.use_pallas).compile()
        try:
            # Schedule identity for the compile-once contract: the
            # smoke asserts the per-bucket fingerprint (not just
            # compile_count) is unchanged between ready and drain — a
            # recompile that lands on a DIFFERENT program can't hide
            # behind a coincidentally flat counter.
            from dmlp_tpu.obs import hlo as obs_hlo
            rep = obs_hlo.report_for(entry.stream,
                                     label=f"serve.{entry.key}")
            entry.hlo = {"fingerprint": rep.fingerprint,
                         "collective_bytes": sum(
                             t["bytes_moved"]
                             for t in rep.totals.values())}
        except Exception:  # check: no-retry — obs never fails serving
            entry.hlo = None

    # -- resident chunk staging (extract path) --------------------------------

    def _ensure_chunks(self) -> None:
        if self._chunks is not None or not self._extract_ok:
            return
        cr = self._ex_chunk_rows
        with obs_span("serve.stage_chunks", chunks=self._ex_nchunks,
                      chunk_rows=cr, na=self.num_attrs,
                      a_pad=self._ex_attrs, pad_bytes=self._pad_bytes(),
                      norm_bytes=self._ex_nchunks * cr * 4,
                      score=self.config.score):
            # Allocated on the device, then filled a chunk at a time by
            # a donated update: the host never holds a second corpus.
            self._chunks = jnp.zeros((self._ex_nchunks, cr, self._ex_attrs),
                                     np_staging_dtype(self._staging))
            self._norms = jnp.zeros((self._ex_nchunks, 1, cr), jnp.float32)
            for c in range(self._ex_nchunks):
                self._restage_chunk(c)
        self._build_summaries()

    def _pad_bytes(self) -> int:
        """Bytes of zero columns the staged stack holds beyond the
        corpus' own width (lane_padded); 0 on whole-lane rows."""
        return self._ex_nchunks * self._ex_chunk_rows \
            * (self._ex_attrs - self.num_attrs) * self._staging_itemsize()

    # -- resident block summaries (pruned two-stage solve, stage 0) -----------

    def _chunk_span(self, c: int) -> Tuple[int, int]:
        cr = self._ex_chunk_rows
        return c * cr, min(c * cr + cr, self.n_real)

    def _build_summaries(self) -> None:
        """Stage 0 at ingest granularity: one summary block per
        resident extract chunk, host f64 + device-resident f32 copies
        (tiny: O(blocks * a))."""
        from dmlp_tpu.ops import summaries as osum
        # The norm band and the box gap are lower bounds of a squared
        # L2: under another score the scorer does not run (no
        # serve.prune_score in the cycle) and every fold is dense.
        if not self._extract_ok or self._ex_nchunks <= 1 \
                or not osum.prune_enabled() or self.config.score != "l2":
            return
        with obs_span("serve.summary_build", blocks=self._ex_nchunks):
            self._summ = osum.build_summaries(
                self._host_attrs,
                [self._chunk_span(c) for c in range(self._ex_nchunks)])
            self._stage_summaries()
        telemetry.registry().gauge("prune.summary_blocks").set(
            self._ex_nchunks)

    def _rebuild_summary_blocks(self, blocks) -> None:
        """Ingest invalidation: rebuild EXACTLY the touched blocks'
        summaries from their current host rows, then restage the
        device copies — the incremental counterpart of
        _restage_chunk, counted so tests can assert the invalidation
        actually happened."""
        from dmlp_tpu.ops import summaries as osum
        if self._summ is None:
            return
        blocks = list(blocks)
        for c in blocks:
            lo, hi = self._chunk_span(c)
            osum.update_block(self._summ, c, self._host_attrs[lo:hi],
                              lo_hi=(lo, hi))
        self._stage_summaries()
        self.summary_rebuilds += len(blocks)
        telemetry.registry().counter("prune.summary_rebuilds").inc(
            len(blocks))

    def _restage_chunk(self, c: int) -> None:
        sdt = np_staging_dtype(self._staging)
        cr = self._ex_chunk_rows
        lo = c * cr
        hi = min(lo + cr, self.n_real)
        a = np.zeros((cr, self._ex_attrs), sdt)
        if hi > lo:
            self._staged_rows(lo, hi, a)
        self._chunks, self._norms = _update_chunk(
            self._chunks, self._norms, stage_put(a, self._staging),
            jax.device_put(np.int32(c)))
        self.norm_restages += 1

    # -- incremental ingestion ------------------------------------------------

    def ingest(self, labels, attrs, start: Optional[int] = None) -> int:
        """Write rows into the resident corpus behind the row-count
        mask; returns the new row count. ``start=None`` appends;
        ``start <= n_real`` writes at that global row position — an
        IDEMPOTENT row-write keyed by global row id (re-delivering the
        same rows at the same positions changes nothing, including the
        corpus signature), which is what makes the fleet's
        consistency-repair re-ingest safe to race a normal fan-out.
        The solve programs' shapes are untouched (no recompilation);
        the fixed-shape row update itself compiles once per
        power-of-two row-count bucket."""
        labels = np.asarray(labels, np.int32).reshape(-1)
        attrs = np.asarray(attrs, np.float64)
        if attrs.ndim != 2 or attrs.shape[1] != self.num_attrs:
            raise ValueError(
                f"ingest rows must be (m, {self.num_attrs}), "
                f"got {attrs.shape}")
        m = attrs.shape[0]
        if m != labels.shape[0]:
            raise ValueError("labels/attrs row-count mismatch")
        if m == 0:
            return self.n_real
        at = self.n_real if start is None else int(start)
        if at < 0 or at > self.n_real:
            raise ValueError(
                f"ingest start {at} beyond resident rows "
                f"{self.n_real} (row-writes may overwrite or append, "
                "never leave gaps)")
        end = at + m
        new_n = max(self.n_real, end)
        if end > self.capacity_rows:
            raise CapacityError(
                f"ingest of {m} rows at {at} exceeds capacity "
                f"{self.capacity_rows} (resident: {self.n_real})")
        with obs_span("serve.ingest", rows=m, corpus_rows=new_n):
            self._host_attrs[at:end] = attrs
            self._host_labels[at:end] = labels
            if self._host_norms is not None:
                self._note_norms(at, end)
            self.n_real = new_n
            # Bucketed fixed-shape device update, rebuilt from host
            # state so the pad region rewrites what is already there.
            mpad = min(shape_bucket(m), self.capacity_rows - at)
            mpad = max(mpad, m)
            sdt = np_staging_dtype(self._staging)
            blk = np.empty((mpad, self.num_attrs), sdt)
            self._staged_rows(at, at + mpad, blk)
            rng = np.arange(at, at + mpad, dtype=np.int32)
            blk_ids = np.where(rng < new_n, rng, -1).astype(np.int32)
            blk_labels = self._host_labels[at:at + mpad]
            s = jax.device_put(np.int32(at))
            self._d_attrs = _update_rows_2d(
                self._d_attrs, stage_put(blk, self._staging), s)
            self._d_labels = _update_rows_1d(
                self._d_labels, jax.device_put(blk_labels), s)
            self._d_ids = _update_rows_1d(
                self._d_ids, jax.device_put(blk_ids), s)
            if self._chunks is not None:
                cr = self._ex_chunk_rows
                touched = range(at // cr, -(-end // cr))
                for c in touched:
                    self._restage_chunk(c)
                # The summaries of exactly the touched blocks must
                # rebuild with the rows — a stale summary could keep a
                # block pruned whose NEW rows belong in a top-k.
                self._rebuild_summary_blocks(touched)
            # Overwrites can only RAISE the cached max-sq-norm (the
            # old row's norm may linger) — conservative: a too-large
            # dn_max only widens the boundary-repair eps, never
            # narrows it, so exactness is unaffected.
            self._note_ingested_norms(attrs)
            self._sig_update(at, end)
        reg = telemetry.registry()
        reg.counter("serve.ingested_rows").inc(m)
        reg.gauge("serve.corpus_rows").set(new_n)
        return new_n

    # -- resident solves ------------------------------------------------------

    def _solve_resident_stream(self, pend: PendingBatch,
                               entry: _Bucket) -> Tuple[TopK, int]:
        # no path answers an inner-product corpus in L2
        self.config.require_score(
            "serve.engine.ResidentEngine's streaming select")
        if entry.stream is None:
            # An extract-path bucket degraded to streaming: build the
            # fallback program once (counted honestly as a compile).
            t0 = time.perf_counter()
            self._compile_stream(entry)
            self.compile_count += 1
            self.bucket_compile_ms[entry.key + "_stream_fallback"] = \
                round((time.perf_counter() - t0) * 1e3, 3)
        inp = pend.inp
        nq = inp.params.num_queries
        na = self.num_attrs
        q = np.zeros((entry.qpad, na), np.float32)
        q[:nq] = inp.query_attrs
        q_blocks = stage_put(q.reshape(entry.nqb, entry.qb, na),
                             self._staging)
        pend.select = self._stream_select
        # The enqueue alone, as serve.solve_extract is: the program's
        # device time shows where the host first blocks, in single.fetch.
        with obs_span("serve.solve_stream", qpad=entry.qpad,
                      kcap=entry.kcap, **self._rid_args()):
            out: TopK = entry.stream(self._d_attrs, self._d_labels,
                                     self._d_ids, q_blocks)
        # The AOT streaming program scans the whole resident buffer by
        # construction (static shapes): a dense scan, recorded as such.
        from dmlp_tpu.ops.summaries import note_scan
        dense = self.n_real * na * self._staging_itemsize()
        note_scan(pend, scanned_bytes=dense, dense_bytes=dense,
                  blocks_total=1, blocks_pruned=0)
        return TopK(out.dists.reshape(entry.qpad, -1),
                    out.labels.reshape(entry.qpad, -1),
                    out.ids.reshape(entry.qpad, -1)), entry.qpad

    def _prune_survivors(self, inp: KNNInput, entry: _Bucket, q_dev):
        """Stage 1 per micro-batch: score the RESIDENT summaries on
        device (ops.summaries.score_blocks — compiled once per bucket
        shape) and read back the tiny (blocks,) survivor mask. Active
        on the ladder's top ``prune`` rung in exact mode only; returns
        (mask, stats) or (None, None) for a dense fold. The one place
        ``begin_batch`` waits for the device: the scorer queues behind
        the fold of the batch in flight."""
        from dmlp_tpu.ops import summaries as osum
        if (self._summ_dev is None
                or self._degrade_rung not in ("lowp", "prune")
                or not self.config.exact or not osum.prune_enabled()):
            return None, None
        keep = self._score_summaries(inp, entry.qpad, q_dev,
                                     "serve.prune_score",
                                     self._ex_nchunks)
        total = int(np.count_nonzero(
            self._summ.counts[:self._ex_nchunks] > 0))
        pruned = total - int(np.count_nonzero(keep))
        if not keep.any():
            return None, None   # belt: score_blocks keeps >= 1 block
        return keep, {"blocks_total": total, "blocks_pruned": pruned}

    def _stage_batch_queries(self, inp: KNNInput, qpad: int):
        """A micro-batch's query rows on the device, padded to the
        bucket's rows and to the resident stack's width
        (``_staged_queries``: q / |q| under "cosine")."""
        return stage_put(self._staged_queries(inp, qpad, self._ex_attrs),
                         self._staging)

    def _variant_stamp(self, kc: int, qpad: int,
                       prec: str) -> Dict[str, Any]:
        """The kernel variant a fold of the resident stack runs with
        (pallas_fused.variant_stamp at the stack's dispatch shape),
        with the width the stack holds a row at."""
        from dmlp_tpu.ops import pallas_fused
        return {**pallas_fused.variant_stamp(
            kc, self._ex_chunk_rows, qpad, self._ex_attrs, prec,
            self._staging), "a_pad": self._ex_attrs, "norms": "staged",
            "score": self.config.score}

    def _fold_resident(self, q_dev, order, impl: str, kc: int,
                       prec: str):
        """Dispatch ONE program that folds the resident chunks
        ``order`` names, in that order (_fold_stack). Returns the
        running lists, the gate counts (all three still on the
        device) and the number of (query tile, data block) pairs the
        fold visited."""
        cr = self._ex_chunk_rows
        qpad = q_dev.shape[0]
        kern = _kernel_statics(impl, kc, cr, qpad, self._ex_attrs, prec,
                               self._interpret, self.config.score)
        padded = np.zeros(self._ex_nchunks, np.int32)
        padded[:len(order)] = order
        od, oi, gated = _fold_stack(
            q_dev, self._chunks, self._norms,
            *jax.device_put((padded, np.int32(len(order)),
                             np.int32(self.n_real))), **kern)
        return od, oi, gated, len(order) * fold_tiles(kern, qpad, cr)

    def _solve_resident_extract(self, pend: PendingBatch, entry: _Bucket
                                ) -> Optional[Tuple[TopK, int]]:
        from dmlp_tpu.ops import pallas_fused
        from dmlp_tpu.ops.summaries import note_scan
        inp = pend.inp
        na = self.num_attrs
        cr = self._ex_chunk_rows
        with obs_span("serve.solve_stage", qpad=entry.qpad,
                      **self._rid_args()):
            kern, impl = pallas_fused.resolve_topk_kernel(
                entry.qpad, cr, self._ex_attrs, entry.kcap,
                rung=self._degrade_rung)
            if kern is None:
                return None
            prec = active_precision(self)  # plan-clamped; outside the jits
            q_dev = self._stage_batch_queries(inp, entry.qpad)
            order = self._chunk_order()
            survivors, prune_stats = self._prune_survivors(inp, entry,
                                                           q_dev)
            # Survivor ∩ hot-first order: the winner-histogram sort
            # stays the fold order, pruned chunks simply drop out, and
            # so do chunks past the last real row.
            order = [c for c in order if c * cr < self.n_real
                     and (survivors is None or survivors[c])]
            if not order:
                # Cannot happen with a sound mask (score_blocks keeps
                # >= 1 block): fall back to a dense fold.
                return None
            pend.select = "extract"
            pend.extract_impl = impl
            pend.variant = self._variant_stamp(
                entry.kcap, entry.qpad, prec)
        clock = time.perf_counter
        with obs_span("serve.solve_extract", qpad=entry.qpad,
                      kcap=entry.kcap, impl=impl,
                      carry=self.gate_carry, scheduled=len(order),
                      **_variant_args(pend.variant),
                      **self._rid_args()) as sp:
            t0 = clock()
            od, oi, gated, ntiles = self._fold_resident(
                q_dev, order, impl, entry.kcap, prec)
            ms = (clock() - t0) * 1e3
            pend.phase_ms["dispatch"] = ms
            # One program folds every scheduled chunk: the host only
            # enqueues it (nothing here waits for the device; the fold's
            # device time shows where the host first blocks, in
            # single.fetch).
            sp.set(dispatches=1, chunks=len(order),
                   kernel_dispatch_ms=round(ms, 3), throttle_wait_ms=0.0)
        # serve.solve_epilogue: closed where the first half ends
        # (_run_begin), after the epilogue's enqueues.
        pend.epilogue_pc = clock()
        pend.gate = (gated, ntiles)
        pend.kernel_calls = len(order)
        item = self._staging_itemsize()
        scanned = sum(min(self.n_real - c * cr, cr) for c in order)
        note_scan(pend, scanned_bytes=scanned * na * item,
                  dense_bytes=self.n_real * na * item,
                  blocks_total=(prune_stats or {}).get(
                      "blocks_total", -(-self.n_real // cr)),
                  blocks_pruned=(prune_stats or {}).get(
                      "blocks_pruned", 0))
        top = _extract_finalize(od, oi, self._d_labels, k=entry.kcap)
        return top, entry.qpad

    def _run_begin(self, pend: PendingBatch) -> None:
        """The first half, and the end of ``serve.solve_epilogue``:
        from the fold's (the multipass merge's) dispatch to here the
        host only enqueues (the label gather and sort, the boundary
        columns). The span belongs to the first half alone: it never
        reaches into a second half, its own or another batch's."""
        super()._run_begin(pend)
        e0, pend.epilogue_pc = pend.epilogue_pc, None
        if e0 is not None:
            obs_trace.complete_at("serve.solve_epilogue", e0,
                                  time.perf_counter(), **self._rid_args())

    def _before_fetch(self, pend: PendingBatch) -> None:
        if pend.mp_fence is not None:
            self._mp_fetch(pend)

    # -- wide-k multipass serving (ROADMAP item (d)) --------------------------

    def _solve_resident_multipass(self, pend: PendingBatch, entry: _Bucket
                                  ) -> Optional[Tuple[TopK, int]]:
        """k past the kernel's single-pass window, served on the
        existing multi-pass extraction driver (engine.single
        ._solve_extract_multipass) against the RESIDENT chunks: pass 1
        folds them in natural order with the single-pass path's one
        program (``_fold_stack``; no staging), passes 2+ re-sweep the
        same stack as one array (``_sweep_stack``) with the on-device
        floor chain (``_mp_floor``), and ``_mp_merge`` dedups and
        composite-sorts to the bucket width. Everything here ENQUEUES;
        the driver's one fence, and its two loss modes (tie-plateau
        stall / eps-window shortfall) read from it, are the batch's
        second half's (``_mp_fetch``): they set ``pend.mp_hazard``
        exactly like the batch engine, and the boundary repair makes
        them exact — byte-identical to the solo multipass solve and the
        golden oracle."""
        from dmlp_tpu.engine.single import (_mp_floor, _mp_merge,
                                            resolve_sweep_kernel)
        from dmlp_tpu.ops import pallas_fused
        from dmlp_tpu.ops.summaries import note_scan
        inp = pend.inp
        kc = self._MP_KC
        kcap = entry.kcap
        if self._chunks is None or -(-kcap // kc) > self._MP_MAX_PASSES:
            return None
        kern, impl = pallas_fused.resolve_topk_kernel(
            entry.qpad, self._ex_chunk_rows, self._ex_attrs, kc,
            rung=self._degrade_rung)
        if kern is None:    # before any span: the fallback opens its own
            return None
        targs = self._rid_args()
        # What the extract path's serve.solve_stage holds: the sweep's
        # variant resolved and the batch's queries on the device,
        # nothing dispatched yet.
        with obs_span("serve.solve_stage", qpad=entry.qpad, **targs):
            prec = active_precision(self)  # plan-clamped; outside the jits
            cr = self._ex_chunk_rows
            # Passes 2+ sweep the whole stack in ONE kernel call: the
            # variant resolved for that row count must tile it, or the
            # solve stops here, before anything is dispatched.
            full_rows = self._ex_nchunks * cr
            _kern_full, impl_full = resolve_sweep_kernel(
                entry.qpad, full_rows, self._ex_attrs, kc, chunk_rows=cr,
                rung=self._degrade_rung)
            npasses = -(-kcap // kc)
            nq = inp.params.num_queries
            na = self.num_attrs
            n = self.n_real
            nchunks = -(-n // cr)
            q_dev = self._stage_batch_queries(inp, entry.qpad)
            pend.select = "extract"
            pend.extract_impl = impl
            pend.variant = self._variant_stamp(kc, entry.qpad, prec)
            sweep = _kernel_statics(impl_full, kc, full_rows, entry.qpad,
                                    self._ex_attrs, prec, self._interpret,
                                    self.config.score)
            floor_args = dict(staging=self._staging, na=na,
                              precision=prec)
        t_begin = time.perf_counter()
        # Every pass is enqueued without a readback (the floors chain
        # on the device): serve.mp_pass and serve.mp_merge time the
        # enqueue alone, and the device's time shows where the host
        # first blocks, in serve.mp_fetch.
        with obs_span("serve.mp_pass", kc=kc, rows=n, **{"pass": 1},
                      **targs):
            od, oi, _gated, _tiles = self._fold_resident(
                q_dev, range(nchunks), impl, kc, prec)
        ods, ois = [od], [oi]
        # The floor chain's scalars (query norms, the corpus's largest
        # norm, n), put while pass 1 runs: the device has its work first.
        with obs_span("serve.mp_norms", **targs):
            qn_host = np.zeros(entry.qpad, np.float64)
            qn_host[:nq] = np.einsum("qa,qa->q", inp.query_attrs,
                                     inp.query_attrs)
            qn_dev = jax.device_put(np.asarray(qn_host, np.float32))
            dn_dev, n_dev = jax.device_put((np.float32(self._dn_max()),
                                            np.int32(n)))
        fds = []
        for p in range(2, npasses + 1):
            with obs_span("serve.mp_pass", kc=kc, rows=n,
                          **{"pass": p}, **targs):
                floor_dev, fd = _mp_floor(ods[-1], qn_dev, dn_dev,
                                          **floor_args)
                fds.append(fd)
                od, oi, _its = _sweep_stack(q_dev, self._chunks,
                                            self._norms, n_dev, floor_dev,
                                            **sweep)
            ods.append(od)
            ois.append(oi)
        with obs_span("serve.mp_merge", kcap=kcap,
                      slots=npasses * kc, **targs):
            fds.append(_mp_floor(ods[-1], qn_dev, dn_dev,
                                 **floor_args)[1])
            top, valid = _mp_merge(jnp.concatenate(ods, axis=1),
                                   jnp.concatenate(ois, axis=1),
                                   self._d_labels, kcap=kcap)
        # serve.solve_multipass runs from t_begin to the end of the
        # fence (_mp_fetch), so that it holds the batch's kernel events:
        # it CROSSES batches (with another batch in flight it holds that
        # batch's second half too); serve.solve_epilogue, from here to
        # the end of the first half, does not.
        pend.epilogue_pc = time.perf_counter()
        pend.mp_fence = (t_begin, [valid] + fds,
                         dict(qpad=entry.qpad, kcap=kcap, passes=npasses,
                              impl=impl, queries=nq, chunks=nchunks))
        pend.mp_passes = npasses
        pend.kernel_calls = nchunks + npasses - 1
        # The multipass plan re-sweeps the whole resident corpus: a
        # dense scan by design, staged bytes counted once.
        dense = n * na * self._staging_itemsize()
        note_scan(pend, scanned_bytes=dense, dense_bytes=dense,
                  blocks_total=self._ex_nchunks, blocks_pruned=0)
        return top, entry.qpad

    def _mp_fetch(self, pend: PendingBatch) -> None:
        """The multipass driver's ONE fence: the fd chain (stall check)
        and the final valid counts (shortfall check); the boundary
        repair makes both exact. Closes ``serve.solve_multipass``,
        which began with the batch's first enqueue in the FIRST half:
        a span that crosses batches when another is in flight (so it
        carries no ``cpu_ms`` / ``offcpu_ms``; ``serve.mp_fetch`` and
        the spans of each half do)."""
        (t_begin, fence, args), pend.mp_fence = pend.mp_fence, None
        inp = pend.inp
        nq = inp.params.num_queries
        targs = self._rid_args()
        with obs_trace.device_wait("mp_fetch", name="serve.mp_fetch",
                                   **targs):
            fetched = resilient_get(fence)
        valid_h, fd_h = fetched[0], fetched[1:]
        stalled = np.zeros(args["qpad"], bool)
        for prev, cur in zip(fd_h, fd_h[1:]):
            stalled |= np.isfinite(cur) & (cur <= prev)
        stalled = stalled[:nq]
        needed = np.minimum(inp.ks.astype(np.int64), inp.params.num_data)
        shortfall = np.asarray(valid_h)[:nq] < needed
        pend.mp_hazard = stalled | shortfall
        counts = {"stalled": int(np.count_nonzero(stalled)),
                  "shortfall": int(np.count_nonzero(shortfall))}
        obs_trace.complete_at(
            "serve.solve_multipass", t_begin, time.perf_counter(), **args,
            flagged=int(np.count_nonzero(pend.mp_hazard)), **counts,
            **targs)
        reg = telemetry.registry()
        reg.counter("serve.multipass_batches").inc()
        reg.counter("serve.multipass_passes").inc(args["passes"])
        for label, count in counts.items():
            if count:
                reg.counter("serve.multipass_flagged").inc(count,
                                                           label=label)

    # -- the device retry of flagged queries (boundary repair, stage 1) -------

    #: query rows of one retry fold: ONE short query tile (a bfloat16
    #: block's 16 sublanes). A flagged batch holds one or two flagged
    #: queries (about 1 in 10^4 under bfloat16 staging); measured over
    #: 10^7 x 128 bf16 rows at 512 slots, fold + epilogue + readback
    #: (PR 38, TPU v5 lite; PERF.md section 5): 8 rows 19.0 ms, 16 rows
    #: 19.6, 32 rows 20.9, 64 rows 24.3, the buckets' own granule of
    #: 128 rows (two tiles of 64) 38.4. More flagged queries than this
    #: go in groups: the corpus is read once a group.
    _RETRY_QUERIES = 16

    def _retry_kernel(self, kcap: int, select: Optional[str] = "extract"):
        """(impl, qpad, kc) of the device retry of a bucket whose first
        window held ``kcap`` slots, or None where the host oracle
        repairs instead: the flagged queries, _RETRY_QUERIES of them at
        a time, folded again over the resident stack at the kernel's
        widest single-pass window. A bucket that already planned that
        window or more (a multipass bucket's loss flags too), a solve
        off the extract path and an engine off its kernel rungs keep the
        oracle, as does an engine told to (``boundary_retry`` False).
        The fold is the buckets' own program (``_fold_stack``) at one
        more shape, whatever the bucket: one compile an engine."""
        kc = self._MP_KC
        if (not self.config.boundary_retry or select != "extract"
                or self._chunks is None or kcap >= kc
                or self._degrade_rung == "streaming"):
            return None
        from dmlp_tpu.ops import pallas_fused
        qpad = self._RETRY_QUERIES
        kern, impl = pallas_fused.resolve_topk_kernel(
            qpad, self._ex_chunk_rows, self._ex_attrs, kc,
            rung=self._degrade_rung)
        return None if kern is None else (impl, qpad, kc)

    def _retry_begin(self, pend: PendingBatch, sub: KNNInput,
                     suspects: np.ndarray, select: str, kcap: int):
        """ENQUEUE the retry of the flagged queries: each group of
        _RETRY_QUERIES padded like a micro-batch, every chunk that holds
        rows folded in natural order (no pruning, no gate order: the
        answer does not depend on either), the lists sorted and their
        boundary columns taken on the device. Nothing waits here: the
        host finalizes the batch while the device works through the
        batch begun behind this one and then through this."""
        plan = None if pend.mp_passes else self._retry_kernel(kcap, select)
        if plan is None:
            return None
        impl, qpad, kc = plan
        t0 = time.perf_counter()
        first = not self._retry_built
        order = range(-(-self.n_real // self._ex_chunk_rows))
        groups = []
        with obs_span("single.retry_begin", queries=int(suspects.size),
                      kcap=kc, score=self.config.score,
                      **self._rid_args()):
            for g0 in range(0, suspects.size, qpad):
                idx = suspects[g0:g0 + qpad]
                gin = subset_queries(sub, idx)
                od, oi, _gated, _tiles = self._fold_resident(
                    self._stage_batch_queries(gin, qpad), order, impl,
                    kc, pend.prec)
                top = _extract_finalize(od, oi, self._d_labels, k=kc)
                ks_pad = np.ones(qpad, np.int32)
                ks_pad[:idx.size] = gin.ks
                cols = _boundary_cols(top.dists, jax.device_put(ks_pad))
                groups.append((idx, gin, ([] if self.config.exact
                                          else [top.dists])
                               + [top.ids, cols]))
        if first:
            # the retry's programs compile on their first dispatch:
            # warm-up's, where the daemon warmed a bucket (_warm_more)
            self._retry_built = True
            self.compile_count += 1
            self.bucket_compile_ms["retry"] = round(
                (time.perf_counter() - t0) * 1e3, 3)
        return t0, groups, kc

    def _retry_finish(self, pend: PendingBatch, sub: KNNInput, retry,
                      results: List[QueryResult],
                      dn_max: float) -> np.ndarray:
        """The retry's fence and the host's share: the SAME hazard test
        (the first pass's bounds, at the wider list's last distance) on
        each retried query, the float64 rescore of the wider lists
        (device distances in fast mode), and the answers of the queries
        that cleared put where the first window's stood. Returns the
        positions still flagged: the host oracle's."""
        t0, groups, kc = retry
        n = sub.params.num_data
        exact = self.config.exact
        nq = sum(int(idx.size) for idx, _gin, _dev in groups)
        left = []
        clock = time.perf_counter
        t1 = clock()
        with obs_span("single.retry", queries=nq, kcap=kc, passes=1,
                      enqueue_ms=round((t1 - t0) * 1e3, 3),
                      **self._rid_args()) as sp:
            # The fold this waits for queued behind the fold of the
            # batch begun meanwhile: up to a whole fold of sleep, which
            # is that batch's device time and not the retry's
            # (``wait_ms``; ``host_ms`` is the rest of the span).
            tw = clock()
            with obs_trace.device_wait("retry", self.trace_batch):
                fetched = resilient_get([dev for _i, _g, dev in groups])
            t2 = clock()
            for (idx, gin, _dev), got in zip(groups, fetched):
                m = int(idx.size)
                got = list(got)
                dists = None if exact \
                    else np.asarray(got.pop(0), np.float64)[:m]
                ids = got.pop(0)[:m]
                kth, last = np.asarray(got.pop(0), np.float64)[:, :m]
                qn = np.einsum("qa,qa->q", gin.query_attrs,
                               gin.query_attrs)
                still = boundary_hazard(kth, last, self._hazard_eps(
                    last, qn, dn_max, "extract", pend.prec,
                    sub.params.num_attrs))
                labels = np.where(
                    ids >= 0, sub.labels[np.clip(ids, 0, n - 1)], -1)
                fixed = finalize_host(
                    dists, labels, ids, gin.ks, gin.query_attrs,
                    sub.data_attrs, exact=exact,
                    query_ids=np.asarray(
                        [results[int(qi)].query_id for qi in idx]),
                    score=self.config.score, data_norms=sub.data_norms)
                for j, qi in enumerate(idx):
                    if not still[j]:
                        results[int(qi)] = fixed[j]
                left.append(idx[still])
            left = np.concatenate(left)
            pend.retry_cleared += nq - int(left.size)
            sp.set(cleared=nq - int(left.size),
                   fell_through=int(left.size),
                   wait_ms=round((t2 - tw) * 1e3, 3),
                   host_ms=round((clock() - t2) * 1e3, 3))
        return left

    def _warm_more(self) -> Dict[str, float]:
        """The retry's programs, front-loaded with the buckets' own
        wherever a warmed bucket has a retry (_retry_kernel): under
        bfloat16 staging about one query in 10^4 is flagged on uniform
        rows (PERF.md), so a daemon meets its first within seconds; under
        float32 staging no measured cell has flagged one, and the first
        that does must not compile on the batcher thread either."""
        kcaps = [e.kcap for e in self._buckets.values()
                 if e.path == "extract"]
        plan = self._retry_kernel(min(kcaps)) if kcaps else None
        if plan is None or self._retry_built:
            return {}
        t0 = time.perf_counter()
        with obs_span("serve.warmup_retry", qpad=plan[1], kcap=plan[2]):
            inp = self._batch_input(self._host_attrs[:1],
                                    np.ones(1, np.int32))
            pend = PendingBatch(inp, prec=active_precision(self))
            retry = self._retry_begin(pend, inp, np.zeros(1, np.intp),
                                      "extract", 0)
            self._retry_finish(pend, inp, retry, [QueryResult(
                0, 1, -1, np.full(1, -1, np.int64), np.full(1, np.inf))],
                self._dn_max())
        return {"retry": round((time.perf_counter() - t0) * 1e3, 3)}

    def _chunk_order(self) -> List[int]:
        """Fold order over the resident chunks: hottest (most past
        winners) first when gate carry-over is on, natural otherwise.
        Stable sort: cold chunks keep their natural relative order.
        The histogram is one batch staler with a batch in flight (its
        winners are credited when it finishes): the order decides what
        is gated, never what is answered."""
        with obs_span("serve.fold_schedule", chunks=self._ex_nchunks,
                      carry=self.gate_carry, **self._rid_args()):
            idx = range(self._ex_nchunks)
            if not self.gate_carry:
                return list(idx)
            return list(np.argsort(-self._block_hits[:self._ex_nchunks],
                                   kind="stable"))

    # -- SingleChipEngine seam overrides --------------------------------------

    def _enqueue(self, pend: PendingBatch) -> List[Tuple]:
        """One segment a micro-batch (no hetk routing on the resident
        paths: the per-request slicing stays trivial); a wide-k bucket
        takes the multipass driver. Whatever the solve says of itself
        goes to ``pend``, not to the engine: two batches are alive."""
        inp = pend.inp
        if inp.params.num_data != self.n_real:
            raise ValueError(
                f"resident solve got a foreign corpus "
                f"({inp.params.num_data} rows, resident {self.n_real}) — "
                "build micro-batches with _batch_input/solve_batch")
        nq = inp.params.num_queries
        kmax = int(inp.ks.max()) if nq else 1
        entry = self._bucket_entry(nq, kmax)
        out = None
        if self._degrade_rung != "streaming":
            if entry.path == "extract":
                out = self._solve_resident_extract(pend, entry)
            elif entry.path == "multipass":
                out = self._solve_resident_multipass(pend, entry)
        top, qpad = out or self._solve_resident_stream(pend, entry)
        return [(top, qpad, None, pend.select)]

    def _run(self, inp: KNNInput) -> List[QueryResult]:
        pend = PendingBatch(inp)
        self._run_begin(pend)
        return self._run_finish(pend)

    def _run_finish(self, pend: PendingBatch) -> List[QueryResult]:
        results = super()._run_finish(pend)
        self._note_flagged(pend.repairs, pend.retry_cleared)
        self._note_rescore(pend)
        self._after_batch(pend, results)
        self._report_finished(pend)
        self.last_kernel_calls = pend.kernel_calls
        self.last_mp_passes = pend.mp_passes
        return results

    def run(self, inp: KNNInput, first: int = 0) -> List[QueryResult]:
        """One solve whole, on the degrade ladder from rung ``first``."""
        # No staging_for_k swap (the parent flips bf16->f32 staging for
        # wide k, which would mismatch the resident buffers): max_k
        # already refuses the shapes that swap existed for.
        self._check_k(inp)
        return rs_degrade.run_ladder(self, inp, self._run, first=first)

    # -- the serving entry ----------------------------------------------------

    def _first_half(self, pend: PendingBatch) -> None:
        """An OOM-class failure here is kept in the record: the second
        half re-runs the batch whole, a rung down. Any other raises."""
        try:
            with rs_degrade.top_rung(self):
                self._run_begin(pend)
        except Exception as e:
            if not rs_degrade.steps_down(e):
                raise
            pend.oom = e

    def _second_half(self, pend: PendingBatch) -> List[QueryResult]:
        """An OOM-class failure in either half walks the ladder."""
        if pend.oom is None:
            try:
                with rs_degrade.top_rung(self):
                    return self._run_finish(pend)
            except Exception as e:
                if not rs_degrade.steps_down(e):
                    raise
                pend.oom = e
        return self._rerun_alone(pend)

    def _rerun_alone(self, pend: PendingBatch) -> List[QueryResult]:
        """The ladder's meaning with two batches alive: the batch that
        ran out of memory runs again WHOLE from the next rung, with the
        device to itself. Its own lists go first; the batch begun
        behind it is finished (its outcome kept for its own second
        half to return) before anything is dispatched again."""
        pend.segments, pend.gate, pend.mp_fence = [], None, None
        while self._in_flight:
            other = self._in_flight.pop(0)
            other.outcome = self._outcome(other)
        rungs = rs_degrade.RUNGS
        rs_degrade.note_step(rungs[0], rungs[1], pend.oom)
        return self.run(pend.inp, first=1)

    def _after_batch(self, pend: PendingBatch,
                     results: List[QueryResult]) -> None:
        with obs_span("serve.after_batch", **self._rid_args()) as sp:
            self._flush_gate(sp, pend.gate)
            pend.gate = None
            if self.gate_carry and self._ex_nchunks and results:
                ids = np.concatenate(
                    [np.asarray(r.neighbor_ids, np.int64)
                     for r in results])
                ids = ids[ids >= 0]
                if ids.size:
                    hits = np.bincount(ids // self._ex_chunk_rows,
                                       minlength=self._ex_nchunks)
                    self._block_hits[:len(hits)] += hits

    # -- memory-model hooks (ResidentServingCore contract) --------------------

    def mem_model(self, nq: int = 0, kmax: int = 0):
        """The analytic per-device model at this engine's OWN
        bucket_plan (the one kcap derivation — no drift between model
        and solve); batch terms included iff ``nq > 0``."""
        from dmlp_tpu.obs import memwatch
        qpad = kcap = 0
        if nq > 0:
            qpad, _kb, kcap = self.bucket_plan(nq, max(kmax, 1))
        return memwatch.serve_engine_model(
            self.capacity_rows, self.num_attrs, staging=self._staging,
            qpad=qpad, kcap=kcap,
            extract_chunks=(self._ex_nchunks
                            if self._chunks is not None else 0),
            chunk_rows=self._ex_chunk_rows, chunk_attrs=self._ex_attrs,
            summary_blocks=(self._ex_nchunks
                            if self._summ_dev is not None else 0),
            mp_slots=self._mp_passes(kcap) * self._MP_KC,
            # the retry's lists, wherever _retry_kernel can give one
            retry=(self._RETRY_QUERIES, self._MP_KC)
            if self.config.boundary_retry and self._chunks is not None
            and 0 < kcap < self._MP_KC else (0, 0))

    def batch_model_bytes(self, nq: int, kmax: int) -> int:
        terms = self.mem_model(nq, kmax)["terms"]
        return int(terms["query_blocks"] + terms["topk_carries"]
                   + terms.get("multipass_lists", 0)
                   + terms.get("retry_lists", 0))

    def resident_state_key(self):
        # The floor moves when the extract chunks stage (wide-k sweeps
        # read the same stack: no further copy).
        return (self._chunks is not None,)

    # -- introspection --------------------------------------------------------

    @staticmethod
    def _multipass_stats() -> Dict[str, int]:
        """The always-on counts of the wide-k driver: micro-batches it
        solved, kernel passes over the corpus they took, and queries it
        flagged for the host repair, by cause."""
        reg = telemetry.registry()
        flagged = reg.counter("serve.multipass_flagged")
        return {"batches": int(reg.counter(
                    "serve.multipass_batches").total()),
                "passes": int(reg.counter(
                    "serve.multipass_passes").total()),
                "flagged_stalled": int(flagged.value("stalled")),
                "flagged_shortfall": int(flagged.value("shortfall"))}

    def bucket_stats(self) -> Dict[str, object]:
        # Snapshot the bucket table FIRST: handler threads call this
        # through daemon.stats() while the batcher thread may be
        # inserting a new bucket — iterating the live dict twice could
        # raise "dict changed size" or return paths/buckets from two
        # different states. list() of a dict is a single atomic read
        # under the GIL; the engine stays single-writer.
        entries = list(self._buckets.values())
        # Same single-read discipline for last_prune: the batcher
        # thread replaces it whenever a batch finishes, so one read
        # serves both the isinstance check and the copy.
        lp = self.last_prune
        return {
            "buckets": sorted(e.key for e in entries),
            "paths": {e.key: e.path for e in entries},
            # bucket key -> compiled-stream HLO fingerprint (obs.hlo;
            # only stream-path buckets have one): the schedule-identity
            # map the compile-once assertions compare.
            "hlo_schedule": {e.key: e.hlo["fingerprint"]
                             for e in entries if e.hlo},
            "compile_count": self.compile_count,
            "bucket_compile_ms": dict(self.bucket_compile_ms),
            "cold_start_compile_ms": self.cold_start_compile_ms,
            "corpus_rows": self.n_real,
            "capacity_rows": self.capacity_rows,
            # a row's own width, and the width the extract path's
            # stack holds it at on the device (lane_padded)
            "num_attrs": self.num_attrs,
            "staged_attrs": self._ex_attrs,
            "gate_carry": self.gate_carry,
            "last_gated_fraction": self.last_gated_fraction,
            # the resident chunks that hold rows: what a dense fold
            # dispatches (capacity past the last row is staged too)
            "extract_chunks": -(-self.n_real // self._ex_chunk_rows)
            if self._chunks is not None else 0,
            # kernel calls the LAST solve made: the chunks it folded
            # (fewer than extract_chunks when some were pruned) and, on
            # a multipass bucket, one whole-stack sweep a further pass
            # (chunks + passes - 1)
            "last_kernel_calls": self.last_kernel_calls,
            "last_mp_passes": self.last_mp_passes,
            "multipass": self._multipass_stats(),
            "overlap": self._overlap_stats(),
            # queries the hazard test flagged, and where each was
            # repaired (the device retry / the host oracle)
            "repairs": self._repair_stats(),
            # candidate slots finalized in float64 and rows gathered
            # for them since start (the band: finalize.boundary_band)
            "rescore": self._rescore_stats(),
            "summary_blocks": self._ex_nchunks if self._summ else 0,
            "summary_rebuilds": self.summary_rebuilds,
            # chunks whose staged row norms were written since start:
            # every chunk once when the stack stages, one a restage
            "norm_restages": self.norm_restages,
            "last_prune_fraction": self.last_prune_fraction,
            "last_prune": dict(lp) if isinstance(lp, dict) else None,
            "precision_plan": self._precision_plan,
            "last_precision": dict(self.last_precision)
            if isinstance(self.last_precision, dict) else None,
        }

