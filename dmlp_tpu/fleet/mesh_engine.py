"""Mesh-resident serving engine: the sharded corpus held resident.

:class:`MeshResidentEngine` is the fleet's corpus-scale pillar — the
serving counterpart of the batch mesh engines the way
:class:`~dmlp_tpu.serve.engine.ResidentEngine` is the serving
counterpart of the single-chip engine. It differs from a per-request
:class:`~dmlp_tpu.engine.sharded.ShardedEngine` solve in exactly the
ways a persistent multi-chip server needs:

- **Per-shard resident chunk buffers.** The corpus is staged ONCE at
  construction into ONE device array ``(T, R * chunk_rows, A)`` sharded
  ``P(None, "data", None)``: a shard holds ``(T, chunk_rows, A)``, its
  piece of every chunk, padded to a power-of-two capacity; filled a
  chunk at a time by a donated update (ingest restages a chunk the same
  way). Global row ids stay the affine ``rr * shard_rows + t *
  chunk_rows + j`` the fold derives on device (``_chunk_span``'s rule)
  — the row count is DATA, so the corpus can grow without recompiling
  any solve program.
- **One program a micro-batch folds a shard's resident chunks.**
  ``_resident_fold_fn`` wraps the single-chip resident engine's fold
  body (``serve.engine.fold_chunks``: the ``_fresh`` kernel call, then
  a device loop over the carried ones, the gated tiles summed beside
  them) in ``shard_map``. The fold order, its length, the row count and
  the per-(shard, chunk) live mask are device data, so a new hot-first
  order, a pruned piece, a restaged chunk or appended rows reuse the
  executable. Python dispatches it once and first blocks in
  ``fleet.merge_drain``; there is no per-chunk loop, no eager gate
  counter and no ``ChunkThrottle`` (nothing here is staged).
- **The merge collective as the micro-batch epilogue.** After the fold,
  the engines' existing allgather/ring candidate merge
  (``_chunk_merge_fn``, its own program) — followed by the unchanged
  host float64 finalize + boundary-hazard repair, so every served
  response is byte-identical to the solo solve over the same corpus AND
  the golden oracle.
- **A micro-batch in two halves** (``ResidentServingCore``'s pair, the
  one-chip engine's too). ``_first_half`` stages, scores and dispatches
  the fold AND the merge (everything that only enqueues);
  ``_second_half`` starts at the first host read: the fence
  (``fleet.merge_drain``, ``fleet.merge``: what is left of each), the
  fetch, the hazard test, the float64 finalize + repair, the gate
  bookkeeping. The batcher begins batch N + 1 before it finishes batch
  N, so the chips fold while the host finalizes; what a solve says of
  itself lives in its :class:`MeshPendingBatch` until it finishes.
- **Resident per-(shard, chunk) summaries.** The pruned two-stage
  solve's block summaries (PR 13) are built once over the shard-local
  chunk ranges and kept resident, replicated over the mesh; each
  micro-batch scores them on the devices (the single-chip resident
  engine's scorer) into the (R, T) live mask, and chunks every shard
  pruned are left out of the fold order. Ingest rebuilds exactly the
  touched blocks' summaries.
- **Shard-routed ingest.** Appended rows land at their global row
  positions — i.e. in the owning shard's span of the touched chunk
  buffers — via a full restage of exactly those chunks of the stack
  (data inputs, never shapes: zero solve recompilation,
  asserted by the compile counter like the single-chip resident
  engine).

- **Three scores on the extract path.** ``config.score`` "l2", "ip" or
  "cosine" reaches the shards as the kernel's ``score`` static, the
  staged rows and queries (x / |x| and q / |q| under "cosine", taken in
  float64 on the host) and the ``score`` argument of every bound and of
  the float64 rescore: the one-chip resident engine's forms, shared
  through ``ResidentServingCore``; nothing here is a second
  implementation of them. The merge orders what the kernel emits.

Configs whose plan does not select the extraction kernel fall back to
a resident MONOLITHIC layout: the full capacity-padded
``(R * shard_rows, A)`` dataset + label/id arrays staged once, solved
by the engines' merged ``_fn`` program (the allgather/ring merge runs
inside it). Both layouts share the one global-row-id contract. That
program ranks by squared L2 alone: under "ip" or "cosine" a corpus or a
bucket that would take it is refused by name (``_ensure_monolithic``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.finalize import (band_widths, boundary_band,
                                      boundary_clearance,
                                      boundary_hazard, finalize_host,
                                      kth_column, lowp_eps,
                                      repair_boundary_overflow,
                                      rescore_f64, staging_eps)
from dmlp_tpu.engine.sharded import (ShardedEngine, _chunk_span,
                                     _np_staging_dtype)
from dmlp_tpu.engine.single import (MeasuredIters, SingleChipEngine,
                                    flush_measured_iters, plan_chunks,
                                    resilient_get, resolve_kcap, round_up)
from dmlp_tpu.io.grammar import KNNInput
from dmlp_tpu.io.report import QueryResult
from dmlp_tpu.obs import counters as obs_counters
from dmlp_tpu.obs import memwatch, telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.obs.comms import engine_comms
from dmlp_tpu.obs.trace import span as obs_span
from dmlp_tpu.ops.pallas_extract import mxu_passes
from dmlp_tpu.ops.topk import TopK
from dmlp_tpu.parallel.mesh import DATA_AXIS, QUERY_AXIS, make_mesh
from dmlp_tpu.serve.engine import (_KERNEL_STATICS, CapacityError,
                                   PendingBatch, ResidentServingCore,
                                   _kernel_statics, _update_chunk,
                                   _variant_args, fold_chunks, fold_tiles,
                                   k_bucket, query_bucket, shape_bucket)
from dmlp_tpu.utils.compat import shard_map


class _MeshBucket:
    """One (qpad, k-bucket) shape bucket of the mesh-resident engine:
    the resolved candidate width and the chosen path ("extract" folds
    the resident chunks, "stream" runs the monolithic merged program)."""

    __slots__ = ("qpad", "kb", "kcap", "path", "qloc")

    def __init__(self, qpad: int, kb: int, kcap: int, path: str,
                 qloc: int):
        self.qpad, self.kb, self.kcap = qpad, kb, kcap
        self.path = path
        self.qloc = qloc

    @property
    def key(self) -> str:
        return f"q{self.qpad}k{self.kb}"


@dataclasses.dataclass(eq=False)
class MeshPendingBatch(PendingBatch):
    """What a mesh micro-batch's record holds beside the one-chip
    engine's: the handles its second half fences and fetches, and the
    parts of the engine's ``last_*`` report only a mesh solve has."""

    # the fold's per-shard lists (cd, ci), still on the devices: what
    # fleet.merge_drain waits for (None on the stream path)
    lists: Optional[Tuple] = None
    top: Optional[TopK] = None      # the merged lists, on the devices
    # -> last_comms (obs.comms traffic of the merge)
    comms: list = dataclasses.field(default_factory=list)
    # MeasuredIters queues the fold's iteration sums here (flushed
    # after the batch's fence, in fleet.after_batch)
    _pending_iters: list = dataclasses.field(default_factory=list)


class MeshResidentEngine(ResidentServingCore, ShardedEngine):
    """Compile-once mesh-resident engine for the serving daemon.

    Drop-in for :class:`~dmlp_tpu.serve.engine.ResidentEngine` behind
    the daemon's batcher/admission surface (``solve_batch``/``ingest``/
    ``warmup``/``bucket_plan``/``bucket_stats``); ``mesh_shape`` (or an
    explicit ``mesh``) picks the 2D grid, ``merge`` the candidate-merge
    collective ("allgather" | "ring" | "auto" — "auto" hands the
    cross-shard merge to the GSPMD partitioner via the engines' "gspmd"
    chunk-merge program; no analytic comms model, see obs.comms).

    **Score** (``config.score``): the extract path's buckets rank by
    squared L2, by inner product or by cosine, through the forms the
    one-chip engine has and no other: the fold takes the kernel's
    ``score`` static as it takes ``precision`` (``_kernel_statics``),
    staging and ingest put x / |x| on the shards under "cosine" with
    the float64 norms kept beside the host rows (``_staged_rows``,
    ``_host_norms``: ``ResidentServingCore``'s), a batch's queries are
    normalised the same way (``_staged_queries``), and the hazard test,
    the band, the float64 rescore and the host oracle are called with
    the score. The merge needs no form of its own: it re-selects by
    (value ascending, id descending) whatever the kernel emits, and
    under a product score that is -q.x, so every list still ascends.
    The block-prune scorer's bounds are squared L2's and it does not
    run under another score (every fold dense, as on one chip); the
    monolithic ``stream`` layout ranks by squared L2 alone, so a corpus
    or a bucket that would fall to it is refused by name, and a k past
    one kernel pass at admission (``max_k``).
    """

    _scores = ("l2", "ip", "cosine")
    _span_ns = "fleet"
    _l2_only = ("fleet.mesh_engine.MeshResidentEngine's monolithic "
                "stream path")
    #: slots of the kernel's widest single pass (the one-chip engines')
    _MP_KC = SingleChipEngine._MP_KC

    def __init__(self, corpus: KNNInput, config: EngineConfig = None,
                 mesh=None, mesh_shape: Optional[Tuple[int, int]] = None,
                 capacity: Optional[int] = None,
                 merge: str = "allgather", gate_carry: bool = True):
        if merge == "auto":
            merge = "gspmd"     # the engine-internal strategy name
        if merge not in ("allgather", "ring", "gspmd"):
            raise ValueError(f"unknown merge strategy {merge!r}")
        cfg = config or EngineConfig(mode="sharded")
        if mesh is None:
            # An explicit shape takes the first shape-many devices — a
            # replica pinned to 2 of a host's 8 virtual devices is the
            # normal fleet deployment, not an error.
            mesh = make_mesh(mesh_shape or cfg.mesh_shape)
        super().__init__(cfg, mesh)
        self._merge_strategy = merge
        r, c = self.mesh.devices.shape
        n = corpus.params.num_data
        na = corpus.params.num_attrs
        if n < 1:
            raise ValueError("resident corpus must have at least one row")
        cap = capacity or shape_bucket(n)
        if cap < n:
            raise ValueError(f"capacity {cap} < corpus rows {n}")
        self.num_attrs = na
        # First-pass precision PLAN, frozen at construction like the
        # single-chip resident engine's: bucket kcaps and the active
        # cast both derive from it (_active_prec clamps the per-batch
        # resolve to the plan), so an env flip mid-serve can disable
        # the bf16 pass but never run it against f32-planned windows.
        self._precision_plan = cfg.resolve_precision(self._staging)
        self.last_precision = None
        # (with last_phase_ms, last_comms, last_prune, last_variant and
        # last_extract_impl: the report of the last batch FINISHED)
        self._last_select = None
        self.last_repairs = 0
        # Cross-request fused-gate warm-up, mesh edition (ROADMAP
        # follow-on (e)): the single-chip hot-block histogram doesn't
        # port 1:1 — here heat is tracked PER (shard, chunk), and the
        # fold schedule (every shard folds its piece of chunk t at the
        # same step) orders chunks by their across-shard aggregate heat.
        # The carried state is a winner histogram, never a threshold:
        # within a request thresholds only tighten, so the fold is
        # sound in any order and carry on/off stay byte-identical
        # (boundary repair makes candidate-edge ties exact).
        self.gate_carry = bool(gate_carry)
        self.last_gated_fraction = None

        # -- per-shard chunk plan at CAPACITY shape (fixed for life) ---------
        self._extract_ok = (cfg.use_pallas and cfg.resolve_select(
            round_up(max(-(-cap // r), 1), 8)) == "extract")
        granule = cfg.resolve_granule("extract") if self._extract_ok else 8
        shard_rows, nchunks, chunk_rows = plan_chunks(
            max(-(-cap // r), 1), granule, cfg.data_block)
        self._shard_rows = shard_rows
        self._nchunks = nchunks
        self._chunk_rows = chunk_rows       # per-shard rows per chunk
        self.capacity_rows = r * shard_rows
        if self._extract_ok:
            from dmlp_tpu.ops.pallas_distance import pallas_interpret
            self._interpret = pallas_interpret()
        else:
            self._interpret = True

        # -- host originals (the float64 finalize rescore reads these) -------
        with obs_span("fleet.init.host_copy", rows=self.capacity_rows,
                      na=na):
            self._host_attrs = np.zeros((self.capacity_rows, na),
                                        np.float64)
            self._host_attrs[:n] = corpus.data_attrs
            self._host_labels = np.full(self.capacity_rows, -1, np.int32)
            self._host_labels[:n] = corpus.labels
        self.n_real = n
        self._init_host_norms(self.capacity_rows)
        with obs_span("fleet.init.row_hashes", rows=n):
            self._sig_init()
        # Corpus max squared norm for the boundary-repair eps — cached
        # (an O(n*a) host pass per micro-batch would sit in every
        # request's tail latency at corpus scale), updated
        # incrementally on the append-only ingest.
        self._dn_max_cache: Optional[float] = None

        # -- resident device state -------------------------------------------
        self._csh = NamedSharding(self.mesh, P(DATA_AXIS, None))
        self._ssh = NamedSharding(self.mesh, P(None, DATA_AXIS, None))
        self._nsh = NamedSharding(self.mesh, P(None, None, DATA_AXIS))
        self._lsh = NamedSharding(self.mesh, P(DATA_AXIS))
        self._qsh = NamedSharding(self.mesh, P(QUERY_AXIS, None))
        self._rsh = NamedSharding(self.mesh, P())
        self._lab_dev = jax.device_put(
            np.ascontiguousarray(self._host_labels), self._rsh)
        self._chunks: Optional[jax.Array] = None   # the (T, R*cr, A) stack
        # its rows' squared norms (T, 1, R*cr), sharded like it, and the
        # chunks whose norms were (re)written since start
        self._norms: Optional[jax.Array] = None
        self.norm_restages = 0
        self._live_dense = None        # (R, T) all-ones live mask
        self._mono = None              # (attrs, labels, ids) when staged
        if self._extract_ok:
            self._stage_chunks()
        else:
            self._ensure_monolithic(
                "a corpus that does not take the extract path: no "
                f"use_pallas, or no more than {cfg.AUTO_SELECT_THRESHOLD} "
                "rows a shard under select='auto'")
        self._check_placement()

        # -- resident per-(shard, chunk) summaries ---------------------------
        self._summ = None
        self._summ_dev = None
        self.summary_rebuilds = 0
        self.last_prune_fraction = None
        if self._chunks is not None:
            self._build_summaries()
        # Gate-carry state: per-(shard, chunk) winner histogram.
        self._block_hits = np.zeros((r, max(self._nchunks, 1)), np.int64)

        # -- bucket registry + compile bookkeeping ---------------------------
        self._buckets: Dict[Tuple[int, int], _MeshBucket] = {}
        self.compile_count = 0
        self.cold_start_compile_ms: Optional[float] = None
        self.bucket_compile_ms: Dict[str, float] = {}
        reg = telemetry.registry()
        reg.gauge("serve.corpus_rows").set(n)
        reg.gauge("serve.capacity_rows").set(self.capacity_rows)
        reg.gauge("serve.mesh_shards").set(r)

    def corpus_rows_per_device(self) -> Dict[str, int]:
        """Resident corpus rows each device holds (the device stamp)."""
        from dmlp_tpu.obs.run import rows_per_device
        return rows_per_device([self._chunks if self._chunks is not None
                                else self._mono[0]])

    def _check_placement(self) -> None:
        """Refuse a placement that leaves a mesh device without its
        share: every device of the mesh holds the same number of
        resident rows (its shard's chunk buffers, or its slice of the
        monolithic layout) and no device outside the mesh holds any. A
        mesh daemon whose corpus sits on one device would still answer
        exactly, so nothing downstream would notice."""
        r, _ = self.mesh.devices.shape
        want = (self._nchunks * self._chunk_rows
                if self._chunks is not None else self._shard_rows)
        rows = self.corpus_rows_per_device()
        mesh_ids = {str(d.id) for d in self.mesh.devices.flat}
        if set(rows) != mesh_ids or any(v != want for v in rows.values()):
            raise RuntimeError(
                f"mesh {list(self.mesh.devices.shape)} placement refused: "
                f"each of devices {sorted(mesh_ids, key=int)} must hold "
                f"{want} resident rows (capacity {self.capacity_rows} "
                f"over {r} data shards), rows per device are {rows}")

    # -- resident staging -----------------------------------------------------

    def _block_span(self, rr: int, t: int) -> Tuple[int, int]:
        """Global row range of shard ``rr``'s piece of chunk ``t`` —
        the ONE derivation shared by staging, summaries, and ingest
        routing (mirrors the fold programs' on-device ``_chunk_span``)."""
        lo = rr * self._shard_rows + t * self._chunk_rows
        hi = min(lo + self._chunk_rows, (rr + 1) * self._shard_rows,
                 self.n_real)
        return lo, max(hi, lo)

    def _chunk_host(self, t: int) -> np.ndarray:
        """Chunk ``t``'s (R * chunk_rows, A) staging buffer from the
        current host rows as the device holds them (``_staged_rows``:
        x / |x| under "cosine"), each shard's span in its slot, pad
        zeroed."""
        r, _ = self.mesh.devices.shape
        cr = self._chunk_rows
        sdt = _np_staging_dtype(self._staging)
        a = np.zeros((r * cr, self.num_attrs), sdt)
        for rr in range(r):
            lo, hi = self._block_span(rr, t)
            if hi > lo:
                self._staged_rows(lo, hi, a[rr * cr:(rr + 1) * cr])
        return a

    def _stage_chunks(self) -> None:
        r, _ = self.mesh.devices.shape
        with obs_span("fleet.stage_resident", chunks=self._nchunks,
                      mesh=list(self.mesh.devices.shape),
                      norm_bytes=self._nchunks * r * self._chunk_rows * 4,
                      score=self.config.score):
            # Allocated on the devices, then filled a chunk at a time by
            # a donated update (ResidentEngine._ensure_chunks' way): a
            # stack of separately staged chunks would hold the corpus
            # twice while it is built.
            self._chunks = jnp.zeros(
                (self._nchunks, r * self._chunk_rows, self.num_attrs),
                _np_staging_dtype(self._staging), device=self._ssh)
            self._norms = jnp.zeros(
                (self._nchunks, 1, r * self._chunk_rows), jnp.float32,
                device=self._nsh)
            for t in range(self._nchunks):
                self._restage_chunk(t)
        self._live_dense = jax.device_put(
            np.ones((r, self._nchunks), np.int32), self._csh)

    def _restage_chunk(self, t: int) -> None:
        """Write chunk ``t``'s current host rows into the stack, and
        their norms beside it, in place: same shapes before and after,
        so no solve recompiles."""
        self._chunks, self._norms = _update_chunk(
            self._chunks, self._norms,
            jax.device_put(self._chunk_host(t), self._csh),
            jax.device_put(np.int32(t), self._rsh))
        self.norm_restages += 1

    def _ensure_monolithic(self, why: str = "a bucket off the extract "
                           "path") -> None:
        """The streaming paths' resident layout: full capacity-padded
        (attrs, labels, ids) staged once, sharded over "data". The
        engines' merged program that reads it ranks by squared L2
        alone: under another score whatever asks for it (``why``) is
        refused by name."""
        self.config.require_score(
            f"{self._l2_only} ({why})")
        if self._mono is not None:
            return
        sdt = _np_staging_dtype(self._staging)
        attrs = np.zeros((self.capacity_rows, self.num_attrs), sdt)
        attrs[:self.n_real] = self._host_attrs[:self.n_real]
        ids = np.full(self.capacity_rows, -1, np.int32)
        ids[:self.n_real] = np.arange(self.n_real, dtype=np.int32)
        with obs_span("fleet.stage_monolithic", rows=self.capacity_rows):
            self._mono = (
                jax.device_put(attrs, self._csh),
                jax.device_put(self._host_labels, self._lsh),
                jax.device_put(ids, self._lsh))

    # -- resident summaries (pruned two-stage solve, stage 0) -----------------

    def _block_ranges(self) -> List[Tuple[int, int]]:
        r, _ = self.mesh.devices.shape
        return [self._block_span(rr, t)
                for rr in range(r) for t in range(self._nchunks)]

    def _build_summaries(self) -> None:
        from dmlp_tpu.ops import summaries as osum
        r, _ = self.mesh.devices.shape
        # The norm band and the box gap are lower bounds of a squared
        # L2: under another score the scorer does not run (no
        # fleet.prune_score in the cycle) and every fold is dense, as on
        # one chip.
        if r * self._nchunks <= 1 or not osum.prune_enabled() \
                or self.config.score != "l2":
            return
        with obs_span("fleet.summary_build", blocks=r * self._nchunks):
            self._summ = osum.build_summaries(self._host_attrs,
                                              self._block_ranges())
            self._stage_summaries()
        telemetry.registry().gauge("prune.summary_blocks").set(
            r * self._nchunks)

    def _rebuild_summary_blocks(self, blocks) -> None:
        """Ingest invalidation: rebuild exactly the touched (shard,
        chunk) blocks' summaries from their current host rows — a stale
        summary could keep a block pruned whose NEW rows belong in a
        top-k (the one failure mode the repair cannot catch)."""
        from dmlp_tpu.ops import summaries as osum
        if self._summ is None:
            return
        blocks = list(blocks)
        for rr, t in blocks:
            lo, hi = self._block_span(rr, t)
            b = rr * self._nchunks + t
            osum.update_block(self._summ, b, self._host_attrs[lo:hi],
                              lo_hi=(lo, hi))
        self._stage_summaries()
        self.summary_rebuilds += len(blocks)
        telemetry.registry().counter("prune.summary_rebuilds").inc(
            len(blocks))

    def _put_resident(self, value):
        return jax.device_put(value, self._rsh)

    def _prune_live(self, inp: KNNInput, entry: _MeshBucket, q_dev):
        """Per-micro-batch stage 1: score the RESIDENT summaries on the
        devices (the single-chip resident engine's scorer over the
        mesh-replicated copies; on the host in float64 the pass cost
        seconds a batch at corpus scale) into an (R, T) live mask +
        stats, or (None, None) for a dense fold. Sound per
        ops.summaries: a pruned block provably contributes nothing
        below the staging-eps margin, and the exact stage is
        unchanged. The one place ``begin_batch`` waits for the chips:
        the scorer queues behind the fold of the batch in flight."""
        from dmlp_tpu.ops import summaries as osum
        if (self._summ_dev is None or not self.config.exact
                or not osum.prune_enabled()
                or inp.params.num_queries == 0):
            return None, None
        r, _ = self.mesh.devices.shape
        keep = self._score_summaries(inp, entry.qpad, q_dev,
                                     "fleet.prune_score",
                                     r * self._nchunks)
        if not keep.any():
            return None, None   # belt: score_blocks keeps >= 1 block
        total = int(np.count_nonzero(self._summ.counts > 0))
        pruned = total - int(np.count_nonzero(keep))
        return keep.reshape(r, self._nchunks), {
            "blocks_total": total, "blocks_pruned": pruned}

    # -- shape buckets --------------------------------------------------------

    @property
    def query_granule(self) -> int:
        if self._extract_ok:
            from dmlp_tpu.ops.pallas_extract import QUERY_TILE
            return QUERY_TILE
        return 8

    def bucket_shape(self, nq: int, kmax: int) -> Tuple[int, int]:
        _r, c = self.mesh.devices.shape
        qloc = query_bucket(max(-(-max(nq, 1) // c), 1),
                            self.query_granule)
        return (c * qloc, k_bucket(kmax))

    def _kcap_for(self, kb: int) -> int:
        return resolve_kcap(self.config, kb, "extract",
                            self.capacity_rows, staging=self._staging,
                            precision=self._precision_plan,
                            na=self.num_attrs)

    def bucket_plan(self, nq: int, kmax: int) -> Tuple[int, int, int]:
        """(qpad, k-bucket, kcap) — the ONE candidate-width derivation
        admission pricing and the memwatch model share with the solve."""
        qpad, kb = self.bucket_shape(nq, kmax)
        return qpad, kb, self._kcap_for(kb)

    def _active_prec(self) -> str:
        """Per-batch active first-pass precision: the config resolve
        (env kill switch included, read per call) clamped to the
        construction-time plan. No resilience ladder here — the mesh
        engines solve without run_ladder — so there is no rung gate."""
        return self.config.resolve_precision(
            self._staging, allow_bf16=self._precision_plan == "bf16")

    def _build_bucket(self, qpad: int, kb: int) -> _MeshBucket:
        _r, c = self.mesh.devices.shape
        qloc = qpad // c
        kcap = self._kcap_for(kb)
        path = "stream"
        if self._extract_ok and kcap <= self._MP_KC:
            from dmlp_tpu.ops import pallas_fused
            kern, _ = pallas_fused.resolve_topk_kernel(
                qloc, self._chunk_rows, self.num_attrs, kcap)
            if kern is not None:
                path = "extract"
        entry = _MeshBucket(qpad, kb, kcap, path, qloc)
        if path == "stream":
            self._ensure_monolithic(
                f"bucket {entry.key}: {kcap} slots the kernel does not "
                "tile")
        return entry

    # -- resident solves ------------------------------------------------------

    def _stage_queries(self, inp: KNNInput, qpad: int):
        """A micro-batch's query rows over the query axis
        (``_staged_queries``: q / |q| under "cosine")."""
        q = self._staged_queries(inp, qpad, self.num_attrs)
        return jax.device_put(q.astype(self._np_dtype(), copy=False),
                              self._qsh)

    def _block_rows(self) -> np.ndarray:
        """(R, T) resident rows of every (shard, chunk) block."""
        def rows(rr, t):
            lo, hi = self._block_span(rr, t)
            return hi - lo

        r, _ = self.mesh.devices.shape
        return np.asarray([[rows(rr, t) for t in range(self._nchunks)]
                           for rr in range(r)], np.int64)

    def _resident_fold_fn(self, kern: Dict[str, Any]):
        """The one program a bucket: every (row, col) cell folds its
        shard's pieces of the scheduled resident chunks into its running
        (qloc, K) lists — ``serve.engine.fold_chunks``, the single-chip
        resident engine's body, under ``shard_map``. What the mesh adds
        is how a chunk index becomes (id_base, n_real): ``_chunk_span``'s
        rule and this shard's bit of the (R, T) live mask (a pruned
        piece folds with n_real = 0: every id masks to the sentinel, a
        provable no-op). ``kern`` (``_kernel_statics`` at the cell's
        dispatch shape, resolved by the caller OUTSIDE the jit) is the
        cache key; order, its length, the row count and the mask are
        device data."""
        key = ("residentfold",) + tuple(kern[s] for s in _KERNEL_STATICS)
        if key not in self._fns:
            cr, sr = self._chunk_rows, self._shard_rows

            def local(q_attrs, stack, norms, order, nfold, n, live):
                def span(t):
                    id_base, n_real = _chunk_span((n, t * cr, sr), cr)
                    return id_base, jnp.where(live[0, t] > 0, n_real, 0)

                od, oi, gate, iters = fold_chunks(
                    q_attrs, stack, norms, order, nfold, span, **kern)
                # Per cell: the gate counts and the summed kernel
                # iterations, a pair each, (R, C, 2) after shard_map,
                # read back once a batch.
                return (od[None], oi[None], gate[None, None],
                        iters[None, None])

            sharded = shard_map(
                local, mesh=self.mesh,
                in_specs=(P(QUERY_AXIS, None), P(None, DATA_AXIS, None),
                          P(None, None, DATA_AXIS), P(), P(), P(),
                          P(DATA_AXIS, None)),
                out_specs=(P(DATA_AXIS, QUERY_AXIS, None),
                           P(DATA_AXIS, QUERY_AXIS, None),
                           P(DATA_AXIS, QUERY_AXIS, None),
                           P(DATA_AXIS, QUERY_AXIS, None)),
                check_vma=False)

            # Named for the device trace, like the merge.
            def dmlp_mesh_fold(q_attrs, stack, norms, order, nfold, n,
                               live):
                return sharded(q_attrs, stack, norms, order, nfold, n,
                               live)

            self._fns[key] = jax.jit(dmlp_mesh_fold)
        return self._fns[key]

    def _solve_resident_chunks(self, pend: MeshPendingBatch,
                               entry: _MeshBucket) -> None:
        """The mesh-resident hot path, its first half: ONE program folds
        every scheduled resident chunk on every shard (pruned pieces
        masked per the live mask, chunks no shard needs left out of the
        order), and the merge across the data axis is dispatched behind
        it. Python only enqueues both; the batch's second half first
        blocks in ``fleet.merge_drain``."""
        from dmlp_tpu.ops import pallas_fused
        from dmlp_tpu.ops.summaries import note_scan
        inp = pend.inp
        r, c = self.mesh.devices.shape
        k, cr, na = entry.kcap, self._chunk_rows, self.num_attrs
        prec = pend.prec                # resolved outside the jits (R2)
        with obs_span("fleet.stage_queries", qpad=entry.qpad,
                      **self._rid_args()):
            # (the bucket took this path because a kernel tiles it)
            impl = pallas_fused.resolve_topk_kernel(
                entry.qloc, cr, na, k)[1] or "extract"
            pend.select = "extract"
            pend.extract_impl = impl
            pend.variant = {**pallas_fused.variant_stamp(
                k, cr, entry.qloc, na, prec, self._staging),
                "norms": "staged", "score": self.config.score}
            kern = _kernel_statics(impl, k, cr, entry.qloc, na, prec,
                                   self._interpret, self.config.score)
            q_dev = self._stage_queries(inp, entry.qpad)
            fold = self._resident_fold_fn(kern)
        keep_m, prune_stats = self._prune_live(inp, entry, q_dev)
        # The fold order follows _chunk_order(): hottest chunks first
        # when gate carry-over is on, so every query's k-th-best
        # thresholds tighten before the cold chunks' tiles reach the MXU
        # gate. Left out of it: the capacity tail (no resident rows on
        # any shard yet) and chunks every shard pruned.
        with obs_span("fleet.fold_schedule", chunks=self._nchunks,
                      carry=self.gate_carry, **self._rid_args()) as sp:
            rows = self._block_rows()
            live = rows > 0
            if keep_m is not None:
                live &= keep_m
            order = [t for t in self._chunk_order() if live[:, t].any()]
            sp.set(scheduled=len(order))
        item = np.dtype(self._np_dtype()).itemsize
        clock = time.perf_counter
        with obs_span("fleet.solve_resident", qpad=entry.qpad, kcap=k,
                      scheduled=len(order), impl=impl, mesh=[r, c],
                      carry=self.gate_carry,
                      **_variant_args(pend.variant),
                      **self._rid_args()) as sp:
            t0 = clock()
            padded = np.zeros(self._nchunks, np.int32)
            padded[:len(order)] = order
            # (the resident all-ones mask unless a block with rows in
            # it was pruned: the scorer usually prunes none)
            mask = self._live_dense if live.sum() == np.count_nonzero(rows) \
                else jax.device_put(live.astype(np.int32), self._csh)
            args = (q_dev, self._chunks, self._norms,
                    *jax.device_put((padded, np.int32(len(order)),
                                     np.int32(self.n_real)), self._rsh),
                    mask)
            obs_counters.record_dispatch(fold, args, site="fleet.chunk_fold")
            cd, ci, gate, iters = fold(*args)
            t1 = clock()
            # The merge program goes onto the devices' queues behind
            # the fold (its dispatch, the collective, the re-select):
            # nothing here waits for either.
            merge_fn = self._chunk_merge_fn(k)
            obs_counters.record_dispatch(merge_fn,
                                         (cd, ci, self._lab_dev),
                                         site="fleet.chunk_merge")
            pend.lists = (cd, ci)
            pend.top = merge_fn(cd, ci, self._lab_dev)
            t2 = clock()
            # One program folds every scheduled chunk: the host only
            # enqueues it (serve.solve_extract's convention); the fold's
            # device time shows where the host first blocks, in the
            # second half's fleet.merge_drain.
            sp.set(dispatches=1, chunks=len(order),
                   kernel_dispatch_ms=round((t1 - t0) * 1e3, 3),
                   merge_dispatch_ms=round((t2 - t1) * 1e3, 3),
                   throttle_wait_ms=0.0)
        pend.phase_ms["dispatch"] = (t2 - t0) * 1e3
        mi = MeasuredIters(pend, "fleet.chunk_fold",
                           (entry.qloc, cr, na, k))
        mi.add(iters[..., 0], iters[..., 1])
        mi.done()
        # Gate effectiveness: a 0-iteration tile was gated (or
        # skip-gated) outright, a full-width one found a bucket hiding
        # a second candidate — counted a cell inside the program, read
        # back once per micro-batch in _after_batch. The tile COUNT is
        # static shape arithmetic, no transfer.
        pend.gate = (gate,
                     len(order) * r * c * fold_tiles(kern, entry.qloc, cr))
        note_scan(pend,
                  scanned_bytes=int(rows[live].sum()) * na * item,
                  dense_bytes=self.n_real * na * item,
                  blocks_total=(prune_stats or {}).get(
                      "blocks_total", int(np.count_nonzero(rows))),
                  blocks_pruned=(prune_stats or {}).get(
                      "blocks_pruned", 0))
        pend.comms = engine_comms(self._merge_strategy, (r, c),
                                  entry.qpad // c, k)

    def _chunk_order(self) -> List[int]:
        """Fold order over the resident chunks: step ``i`` of the
        program folds ALL shards' ``order[i]``-th pieces, so the schedule
        is one permutation of ``t`` — ordered by the chunks' across-shard
        aggregate winner count (hottest first) when gate carry-over is
        on, natural otherwise. Stable sort: cold chunks keep their
        natural relative order."""
        if not self.gate_carry:
            return list(range(self._nchunks))
        heat = self._block_hits.sum(axis=0)
        return list(np.argsort(-heat[:self._nchunks], kind="stable"))

    def _after_batch(self, pend: MeshPendingBatch,
                     results: List[QueryResult]) -> None:
        """Cross-request gate bookkeeping (the single-chip resident
        engine's discipline, per-shard): flush the batch's gated-tile
        readback, then credit each winner row's owning (shard, chunk)
        block in the carried histogram."""
        with obs_span("fleet.after_batch", **self._rid_args()) as sp:
            flush_measured_iters(pend)
            self._flush_gate(sp, pend.gate)
            pend.gate = None
            if self.gate_carry and self._nchunks and results:
                ids = np.concatenate(
                    [np.asarray(r.neighbor_ids, np.int64)
                     for r in results])
                ids = ids[ids >= 0]
                if ids.size:
                    r, _ = self.mesh.devices.shape
                    rr = ids // self._shard_rows
                    t = (ids - rr * self._shard_rows) // self._chunk_rows
                    hits = np.bincount(rr * self._nchunks + t,
                                       minlength=r * self._nchunks)
                    self._block_hits += hits.reshape(r, self._nchunks)

    def _solve_resident_stream(self, pend: MeshPendingBatch,
                               entry: _MeshBucket) -> None:
        """Streaming fallback on the resident MONOLITHIC arrays, its
        first half: the engines' merged program (collective epilogue
        inside the jit), enqueued."""
        from dmlp_tpu.ops.summaries import note_scan
        inp = pend.inp
        self._ensure_monolithic()
        d_attrs, d_labels, d_ids = self._mono
        with obs_span("fleet.stage_queries", qpad=entry.qpad,
                      **self._rid_args()):
            q_dev = self._stage_queries(inp, entry.qpad)
        # solve_global reports on the engine (the batch engines' way):
        # what it says is this batch's, and the engine's own report
        # stays the last batch FINISHED.
        report = (self._last_select, self.last_extract_impl,
                  self.last_variant, self._pending_iters)
        self._pending_iters = []
        t0 = time.perf_counter()
        try:
            with obs_span("fleet.solve_stream", qpad=entry.qpad,
                          kcap=entry.kcap, **self._rid_args()):
                pend.top = self.solve_global(d_attrs, d_labels, d_ids,
                                             q_dev, kmax=entry.kb)
            pend.phase_ms["dispatch"] = (time.perf_counter() - t0) * 1e3
            pend.select, pend.extract_impl, pend.variant = (
                self._last_select, self.last_extract_impl,
                self.last_variant)
            pend._pending_iters = self._pending_iters
        finally:
            (self._last_select, self.last_extract_impl, self.last_variant,
             self._pending_iters) = report
        dense = self.n_real * self.num_attrs \
            * np.dtype(self._np_dtype()).itemsize
        note_scan(pend, scanned_bytes=dense, dense_bytes=dense,
                  blocks_total=self.mesh.devices.shape[0],
                  blocks_pruned=0)

    # -- a micro-batch's two halves (ResidentServingCore drives them) ---------

    _pending_type = MeshPendingBatch

    def _first_half(self, pend: MeshPendingBatch) -> None:
        """Bucket, stage the queries, score and schedule, dispatch the
        fold and the merge across "data" (or the merged stream
        program). No resilience ladder here: a failure raises and the
        batch fails alone."""
        inp = pend.inp
        nq = inp.params.num_queries
        pend.prec = self._active_prec()
        pend.precision = {
            "active": pend.prec, "configured": self._precision_plan,
            "mxu_passes": mxu_passes(pend.prec, self._staging)}
        memwatch.note_engine_model(self, inp)
        entry = self._bucket_entry(nq, int(inp.ks.max()) if nq else 1)
        if entry.path == "extract":
            self._solve_resident_chunks(pend, entry)
        else:
            self._solve_resident_stream(pend, entry)

    def _second_half(self, pend: MeshPendingBatch) -> List[QueryResult]:
        """From the first host read on: the fence (what is left of the
        fold, then of the merge), fetch, float64 finalize + boundary
        repair, gate bookkeeping; then the engine's ``last_*`` report
        becomes this batch's."""
        inp, prec, top = pend.inp, pend.prec, pend.top
        n = inp.params.num_data
        nq = inp.params.num_queries
        clock = time.perf_counter
        if pend.lists is not None:
            # The fold finishes here, so that fleet.merge times what is
            # left of the merge program alone (the collective, the
            # re-select) and not the fold. With a batch begun behind
            # this one the host arrives after the chips and both wait
            # for nothing.
            # (blocked on whether or not a tracer is installed: the
            # phase timings behind `stats` are the same numbers the
            # spans carry.)
            t_drain = clock()
            with obs_trace.device_wait("merge_drain",
                                       name="fleet.merge_drain",
                                       dispatches=1, **self._rid_args()):
                jax.block_until_ready(pend.lists)  # check: allow-host-sync
            merge_bytes = sum(t.bytes_total for t in pend.comms)
            telemetry.registry().counter("fleet.merge_bytes").inc(
                merge_bytes)
            t_merge = clock()
            with obs_span("fleet.merge",
                          mesh=list(self.mesh.devices.shape),
                          kc=int(top.dists.shape[1]),
                          strategy=self._merge_strategy, bytes=merge_bytes,
                          **self._rid_args()):
                with obs_trace.device_wait("merge", self.trace_batch):
                    jax.block_until_ready(top.dists)  # check: allow-host-sync
            pend.phase_ms["dispatch"] += (t_merge - t_drain) * 1e3
            pend.phase_ms["merge"] = (clock() - t_merge) * 1e3
        t0 = clock()
        telemetry.sample_memory_now()
        with obs_trace.device_wait("fetch", name="fleet.fetch",
                                   **self._rid_args()):
            od, ol, oi = resilient_get(
                (top.dists, top.labels, top.ids), site="sharded.fetch")
        dists = np.asarray(od, np.float64)[:nq]
        labels = ol[:nq]
        ids = oi[:nq]
        t1 = clock()
        # What the host does between the readback and the finalize: the
        # eps-widened boundary test. dn_max_cached says whether the
        # corpus-wide scalar was at hand or cost a pass over the WHOLE
        # float64 host corpus inside this span (a daemon's first batch).
        suspects = np.zeros(0, np.int64)
        exact, widths = self.config.exact, None
        score = self.config.score
        with obs_span("fleet.hazard", rows=n, score=score,
                      **self._rid_args()) as hz:
            if pend.select in ("sort", "topk", "seg", "extract") \
                    and dists.shape[1] < n:
                # Same per-shard-truncation hazard test as the batch
                # mesh engines (engine.sharded._run): the merged kcap-th
                # bounds every shard's horizon, so the eps-widened
                # boundary test covers per-shard truncation too.
                hz.set(dn_max_cached=self._dn_max_cache is not None)
                dn_max = self._dn_max()
                qn = np.einsum("qa,qa->q", inp.query_attrs,
                               inp.query_attrs)
                last = dists[:, -1]
                eps = staging_eps(last, qn, dn_max, self._staging,
                                  self.num_attrs, score)
                if pend.select == "extract":
                    # A first pass that drops products ("bf16x3",
                    # "bf16") perturbs device distances beyond the
                    # staging model — widen the hazard test by the
                    # bound of the form that ran (finalize.lowp_eps;
                    # zero for the one HIGHEST dot).
                    eps = eps + lowp_eps(prec, qn, dn_max, score)
                kth = kth_column(dists, inp.ks)
                suspects = np.nonzero(
                    boundary_hazard(kth, last, eps))[0]
                hz.set(flagged=int(suspects.size))
                clear = boundary_clearance(kth, last, eps)
                if clear is not None:
                    hz.set(clear_min=clear)
                if exact:
                    # the rescore's band, by the same bound
                    widths = band_widths(
                        boundary_band(dists, ids, kth, eps))
        t2 = clock()
        # rows: the float64 rows the rescore gathers, one a slot of a
        # query's band (every slot where there is no band; none in fast
        # mode), as single.finalize / single.rescore say it
        rows = 0 if not exact else ids.size if widths is None \
            else int(widths.sum())
        gather = rows * self.num_attrs * 8
        band_pct = round(100.0 * rows / max(ids.size, 1), 3)
        with obs_span("fleet.finalize", exact=exact, queries=nq,
                      slots=dists.shape[1], rows=rows,
                      gather_bytes=gather, band_pct=band_pct, score=score,
                      **self._rid_args()) as sp:
            if exact:
                # The float64 gather-and-score, under a span of its own
                # (single.rescore's twin): the part of the finalize the
                # score changes (difference form; under "ip" the product
                # alone, under "cosine" the product over the norms).
                pend.rescore_slots += ids.size
                pend.rescore_rows += rows
                with obs_span("fleet.rescore", queries=nq,
                              slots=dists.shape[1], rows=rows,
                              bytes=gather, band_pct=band_pct,
                              score=score, **self._rid_args()):
                    dists = rescore_f64(np.asarray(ids, np.int64),
                                        inp.query_attrs, inp.data_attrs,
                                        score=score, widths=widths,
                                        data_norms=inp.data_norms)
            results = finalize_host(dists, labels, ids, inp.ks,
                                    inp.query_attrs, inp.data_attrs,
                                    exact=False, score=score)
            if suspects.size:
                with obs_span("fleet.repair", queries=int(suspects.size),
                              **self._rid_args()):
                    repair_boundary_overflow(results, suspects, inp,
                                             score=score)
                pend.repairs += int(suspects.size)
                # every flagged query is the host oracle's here: the
                # device retry is the one-chip engine's
                self._note_flagged(int(suspects.size))
            sp.set(repairs=int(suspects.size))
        t3 = clock()
        pend.phase_ms.update(fetch=(t1 - t0) * 1e3, hazard=(t2 - t1) * 1e3,
                             finalize=(t3 - t2) * 1e3)
        self._note_rescore(pend)
        self._after_batch(pend, results)
        self._report_finished(pend)
        self.last_phase_ms = pend.phase_ms
        self.last_comms = pend.comms
        self.last_precision = pend.precision
        self.last_repairs = pend.repairs
        return results

    # -- incremental shard-routed ingestion -----------------------------------

    def ingest(self, labels, attrs, start: Optional[int] = None) -> int:
        """Write rows behind the row-count mask. Rows land at their
        global positions — the owning shard's span of the touched chunk
        buffers — by restaging exactly those fixed-shape device arrays
        (and the touched blocks' summaries). ``start=None`` appends;
        ``start <= n_real`` is the idempotent row-write keyed by global
        row id the fleet's consistency repair replays. No solve program
        sees a new shape: zero recompilation, counter-asserted."""
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
        r, _ = self.mesh.devices.shape
        sr, cr = self._shard_rows, self._chunk_rows
        with obs_span("fleet.ingest", rows=m, corpus_rows=new_n):
            self._host_attrs[at:end] = attrs
            self._host_labels[at:end] = labels
            if self._host_norms is not None:
                self._note_norms(at, end)
            self.n_real = new_n
            self._note_ingested_norms(attrs)
            self._sig_update(at, end)
            # Touched (shard, chunk) blocks from the [at, end)
            # span by block arithmetic — never a per-row Python loop
            # (a corpus-scale append would stall the solve loop).
            touched = []
            for rr in range(r):
                lo = max(at, rr * sr)
                hi = min(end, (rr + 1) * sr)
                if hi <= lo:
                    continue
                t_lo = (lo - rr * sr) // cr
                t_hi = (hi - 1 - rr * sr) // cr
                touched.extend(
                    (rr, min(t, self._nchunks - 1))
                    for t in range(t_lo, t_hi + 1))
            touched = sorted(set(touched))
            if self._chunks is not None:
                for t in sorted({t for _rr, t in touched}):
                    self._restage_chunk(t)
                self._rebuild_summary_blocks(touched)
            self._lab_dev = jax.device_put(
                np.ascontiguousarray(self._host_labels), self._rsh)
            if self._mono is not None:
                self._mono = None
                self._ensure_monolithic()
        reg = telemetry.registry()
        reg.counter("serve.ingested_rows").inc(m)
        reg.gauge("serve.corpus_rows").set(new_n)
        return new_n

    # -- memory-model hooks (ResidentServingCore contract) --------------------

    mem_per_device = True

    def mem_model(self, nq: int = 0, kmax: int = 0) -> Dict[str, object]:
        """Per-device fleet model at this engine's own bucket_plan;
        batch terms included iff ``nq > 0``."""
        r, c = self.mesh.devices.shape
        qloc = kcap = 0
        if nq > 0:
            qpad, _kb, kcap = self.bucket_plan(nq, max(kmax, 1))
            qloc = qpad // c
        return memwatch.fleet_engine_model(
            mesh_shape=(r, c), shard_rows=self._shard_rows,
            na=self.num_attrs, staging=self._staging,
            chunks=(self._nchunks if self._chunks is not None else 0),
            chunk_rows=self._chunk_rows,
            monolithic=self._mono is not None,
            capacity_rows=self.capacity_rows,
            summary_blocks=(r * self._nchunks if self._summ is not None
                            else 0),
            qloc=qloc, kcap=kcap, merge=self._merge_strategy)

    def batch_model_bytes(self, nq: int, kmax: int) -> int:
        """Marginal per-device bytes of one micro-batch bucket on top
        of the resident floor (query shard + local lists + the merge
        buffer — the allgather merge materializes all R shards' lists)."""
        terms = self.mem_model(nq, kmax)["terms"]
        return int(terms.get("query_shard", 0)
                   + terms.get("local_topk", 0)
                   + terms.get("merge_buffer", 0))

    def resident_state_key(self):
        # The per-device floor moves when the monolithic layout stages
        # lazily (a stream-path bucket on an extract-capable config
        # adds a second full corpus copy per device).
        return (self._chunks is not None, self._mono is not None)

    # -- introspection --------------------------------------------------------

    def bucket_stats(self) -> Dict[str, object]:
        entries = list(self._buckets.values())
        lp = self.last_prune
        r, c = self.mesh.devices.shape
        return {
            "buckets": sorted(e.key for e in entries),
            "paths": {e.key: e.path for e in entries},
            # Mesh buckets dispatch shard_map jits through the jit
            # cache — there is no per-bucket jax.stages.Compiled handle
            # to fingerprint (the single-chip ResidentEngine's
            # stream-path map is the populated case); explicit marker,
            # not silence.
            "hlo_schedule": {},
            "hlo_unavailable": "mesh buckets have no AOT-compiled "
                               "stream handle",
            "compile_count": self.compile_count,
            "bucket_compile_ms": dict(self.bucket_compile_ms),
            "cold_start_compile_ms": self.cold_start_compile_ms,
            "corpus_rows": self.n_real,
            "capacity_rows": self.capacity_rows,
            "gate_carry": self.gate_carry,
            "last_gated_fraction": self.last_gated_fraction,
            "overlap": self._overlap_stats(),
            "repairs": self._repair_stats(),
            "rescore": self._rescore_stats(),
            "extract_chunks": (self._nchunks if self._chunks is not None
                               else 0),
            "summary_blocks": (r * self._nchunks if self._summ else 0),
            "summary_rebuilds": self.summary_rebuilds,
            "norm_restages": self.norm_restages,
            "last_prune_fraction": self.last_prune_fraction,
            "last_prune": dict(lp) if isinstance(lp, dict) else None,
            "precision_plan": self._precision_plan,
            "last_precision": dict(self.last_precision)
            if isinstance(self.last_precision, dict) else None,
            "mesh": [r, c],
            "merge": self._merge_strategy,
            "shard_rows": self._shard_rows,
            "score": self.config.score,
        }
