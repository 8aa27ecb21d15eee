"""Compiler-sharded KNN engine: GSPMD chooses the collective schedule.

The sharded/ring engines hand-roll every collective — ``shard_map``
cells plus an explicit all-gather merge or ring ppermute reduction
(parallel.collectives). This engine expresses the SAME chunked
distance -> top-k solve as one pure ``jit`` program whose inputs carry
``NamedSharding(mesh, P("data"))`` / ``P("query")`` placements and whose
merge point is a ``jax.lax.with_sharding_constraint`` resharding
(data-partitioned per-shard candidate lists -> query-partitioned merged
lists): XLA's GSPMD partitioner picks the collective schedule the
hand-written engines spell out by hand (PAPERS.md arXiv 2204.06514 is
the method paper). No benchmark cell runs either yet (ROADMAP D5):
where GSPMD matches the hand-rolled layouts on the chip that justifies
deleting code, where it loses it justifies keeping shard_map.

Correctness is inherited, not re-proven: the program returns merged
(dist, label, id) candidate lists in the engines' selection order, and
the UNCHANGED ShardedEngine ``_run`` pipeline (fetch -> float64
``finalize_host`` rescore -> eps-widened ``boundary_overflow`` repair)
takes it from there, so responses are byte-identical to the golden
oracle on every path the hand-rolled engines cover.

Composition with the config axes happens where they resolve — OUTSIDE
the jit (R2 discipline):

- prune (``$DMLP_TPU_PRUNE``): the host-side summary scoring of
  ``_plan_prune_mesh`` masks whole (shard, chunk) blocks before
  staging — pruned rows stage as sentinel (id = -1) zeros, which the
  streaming fold provably ignores. Like the mesh engines' monolithic
  path, the saving is host-DRAM scan bytes (ops.summaries.note_scan
  documents the link-bytes caveat: the padded device_put still ships
  the zero-filled rows).
- precision (``$DMLP_TPU_PRECISION``): a "bf16" first pass runs as
  bfloat16 STAGING (the streamed operands of the distance dot are
  bf16; accumulation stays f32 per ops.distance) — resolved before the
  solve, so the existing staging machinery supplies the widened
  resolve_kcap window and the staging_eps hazard test that keep the
  f64 rescore byte-exact. Fast mode never applies it (no repair
  backstop), same contract as everywhere else.
- fused (``$DMLP_TPU_FUSED``): the GSPMD program streams with the
  XLA selects (no Pallas dispatch inside the partitioned jit — a
  manually-tiled kernel would need its own partitioning rules, exactly
  the hand-rolling this engine exists to avoid), so the toggle cannot
  change its results.

No ANALYTIC comms model: the schedule is the compiler's, so
obs.comms.engine_comms returns the honest empty for the "gspmd" merge
strategy rather than asserting traffic this module never dispatched.
Since PR 20 the record is no longer empty, though — it is *derived*:
:meth:`AutoShardedEngine.comms_from_hlo` reads the compiled program's
collective schedule (obs.hlo) and populates ``last_comms`` with
``gspmd_*`` traffic records naming which collectives the partitioner
actually chose, on which mesh axis, and how many bytes they move. The
derivation lowers outside the timed region and only when introspection
is requested (CLI ``--hlo-report``), so the solve
path itself stays claim-free.
"""

from __future__ import annotations

import contextlib
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.sharded import ShardedEngine
from dmlp_tpu.engine.single import (fit_blocks, pad_dataset, resilient_get,
                                    resolve_kcap, round_up)
from dmlp_tpu.io.grammar import KNNInput
from dmlp_tpu.io.report import QueryResult
from dmlp_tpu.obs import counters as obs_counters
from dmlp_tpu.obs import memwatch, telemetry
from dmlp_tpu.obs.trace import span as obs_span
from dmlp_tpu.ops.topk import TopK, select_topk, streaming_topk
from dmlp_tpu.parallel.mesh import DATA_AXIS, QUERY_AXIS
from dmlp_tpu.resilience import inject as rs_inject
from dmlp_tpu.resilience import retry as rs_retry


class AutoShardedEngine(ShardedEngine):
    """GSPMD-partitioned engine over the same 2D ("data", "query") mesh.

    Subclasses :class:`~dmlp_tpu.engine.sharded.ShardedEngine` for the
    whole host-side contract (``run``/``_run`` fetch -> finalize ->
    boundary repair, ``candidates``, staging-dtype bookkeeping) and
    replaces only the device solve: no ``shard_map``, no explicit
    collective — one jit with pinned in/out shardings and a
    ``with_sharding_constraint`` merge point.
    """

    # Not a hand-rolled merge: obs.comms has no analytic model for a
    # compiler-chosen schedule; comms_from_hlo() derives the real one
    # from the compiled program on request.
    _merge_strategy = "gspmd"

    def __init__(self, config: EngineConfig = EngineConfig(mode="auto"),
                 mesh: Optional[Mesh] = None):
        super().__init__(config, mesh)
        axes = set(self.mesh.axis_names)
        missing = sorted({DATA_AXIS, QUERY_AXIS} - axes)
        if missing:
            # The sharding constraints below name these axes; GSPMD
            # would fail at trace time with an opaque error — fail at
            # construction with the actual contract instead.
            raise ValueError(
                f"auto engine mesh must declare axes "
                f"({DATA_AXIS!r}, {QUERY_AXIS!r}); got "
                f"{tuple(self.mesh.axis_names)} (missing {missing})")

    # -- precision composition (resolved OUTSIDE the jit) --------------------
    @contextlib.contextmanager
    def _precision_staging(self):
        """The auto engine's bf16 first pass IS bf16 staging: swap the
        wire/operand dtype for the solve so every existing margin
        (resolve_kcap's 96 + k/2 window, _run's staging_eps hazard
        test) applies unchanged. Only in exact mode (resolve_precision
        already returns "f32" in fast mode) and only when staging is
        not already bf16."""
        if self.config.resolve_precision() != "bf16" \
                or self._staging != "float32":
            yield
            return
        self._staging, self._dtype = "bfloat16", jnp.bfloat16
        try:
            yield
        finally:
            self._staging, self._dtype = "float32", jnp.float32

    def run(self, inp: KNNInput) -> List[QueryResult]:
        with self._precision_staging():
            return super().run(inp)

    # -- the compiled GSPMD program ------------------------------------------
    def _fn_auto(self, k: int, data_block: int, select: str):
        """One pure-jit solve: vmap the per-shard streaming fold over
        the data-sharded leading axis, then reshard the concatenated
        candidates to query-partitioned and re-select with the
        composite (dist asc, id desc) order. in/out shardings are
        pinned (check R902) so the partitioner sees the full placement
        contract instead of inferring it from the first dispatch."""
        key = ("auto", k, data_block, select)
        if key not in self._fns:
            mesh = self.mesh
            dsh3 = NamedSharding(mesh, P(DATA_AXIS, None, None))
            dsh2 = NamedSharding(mesh, P(DATA_AXIS, None))
            qsh = NamedSharding(mesh, P(QUERY_AXIS, None))
            use_pallas = self.config.use_pallas

            def solve(d_attrs, d_labels, d_ids, q_attrs):
                def cell(a, lab, ids):
                    return streaming_topk(q_attrs, a, lab, ids, k=k,
                                          data_block=data_block,
                                          select=select,
                                          use_pallas=use_pallas)

                # (R, shard_rows, A): the leading axis IS the mesh data
                # axis, so the per-shard folds stay local to their tile.
                tops = jax.vmap(cell)(d_attrs, d_labels, d_ids)
                # The merge point. Collapsing the shard axis into the
                # candidate axis and constraining the result onto the
                # query axis is the data->query reshard the hand-rolled
                # engines spell as allgather_merge_topk /
                # ring_allreduce_topk — here GSPMD schedules it.
                qpad = q_attrs.shape[0]
                md = jnp.moveaxis(tops.dists, 0, 1).reshape(qpad, -1)
                ml = jnp.moveaxis(tops.labels, 0, 1).reshape(qpad, -1)
                mi = jnp.moveaxis(tops.ids, 0, 1).reshape(qpad, -1)
                md = jax.lax.with_sharding_constraint(md, qsh)
                ml = jax.lax.with_sharding_constraint(ml, qsh)
                mi = jax.lax.with_sharding_constraint(mi, qsh)
                return select_topk(md, ml, mi, k)

            self._fns[key] = jax.jit(
                solve,
                in_shardings=(dsh3, dsh2, dsh2, qsh),
                out_shardings=TopK(qsh, qsh, qsh))
        return self._fns[key]

    # -- staging + solve ------------------------------------------------------
    def _solve_auto(self, inp: KNNInput, allow_prune: bool):
        """Stage (data-sharded 3D view + query-sharded queries), run the
        GSPMD program, return the single segment the inherited ``_run``
        finalizes. Pruning masks whole (shard, chunk) blocks on host
        before staging — sentinel rows fold as provable no-ops."""
        import time as _time

        cfg = self.config
        n = inp.params.num_data
        nq = inp.params.num_queries
        na = inp.params.num_attrs
        r, c = self.mesh.devices.shape

        kmax = int(inp.ks.max()) if nq else 1
        shard_rows_est = round_up(max(-(-n // r), 1), 8)
        select = cfg.resolve_streaming_select(shard_rows_est)
        data_block = min(cfg.data_block, shard_rows_est) \
            if cfg.data_block is not None else \
            fit_blocks(max(-(-n // r), 1), cfg.resolve_data_block(select),
                       granule=cfg.resolve_granule(select))
        self._last_select = select

        attrs, labels, ids = pad_dataset(inp, r * data_block, np.float32)
        shard_rows = attrs.shape[0] // r
        qpad = c * round_up(max(-(-nq // c), 1), 8)
        k = resolve_kcap(cfg, kmax, select, r * shard_rows,
                         staging=self._staging)

        # Prune stage 0+1 (host, outside the jit): the mesh block plan
        # at data_block granularity. A pruned block's rows stage as
        # sentinel zeros — never read from host DRAM, though the
        # monolithic device_put still ships them (see module docstring).
        nchunks = shard_rows // data_block
        keep_m, prune_stats = self._plan_prune_mesh(
            inp, r, shard_rows, nchunks, data_block, allow_prune,
            precision="f32")
        np_dtype = self._np_dtype()
        item = np.dtype(np_dtype).itemsize
        scanned = n * na * item
        if keep_m is not None:
            for rr in range(r):
                for t in range(nchunks):
                    if keep_m[rr, t]:
                        continue
                    lo = rr * shard_rows + t * data_block
                    hi = min(lo + data_block, (rr + 1) * shard_rows, n)
                    if hi > lo:
                        attrs[lo:hi] = 0
                        labels[lo:hi] = -1
                        ids[lo:hi] = -1
                        scanned -= (hi - lo) * na * item
        from dmlp_tpu.ops.summaries import note_scan
        note_scan(self, scanned_bytes=scanned,
                  dense_bytes=n * na * item,
                  blocks_total=(prune_stats or {}).get(
                      "blocks_total",
                      sum(1 for rr in range(r) for t in range(nchunks)
                          if min(rr * shard_rows + (t + 1) * data_block,
                                 (rr + 1) * shard_rows, n)
                          > rr * shard_rows + t * data_block)),
                  blocks_pruned=(prune_stats or {}).get(
                      "blocks_pruned", 0))

        t0 = _time.perf_counter()
        dsh3 = NamedSharding(self.mesh, P(DATA_AXIS, None, None))
        dsh2 = NamedSharding(self.mesh, P(DATA_AXIS, None))
        qsh = NamedSharding(self.mesh, P(QUERY_AXIS, None))
        q_attrs = np.zeros((qpad, na), np.float32)
        q_attrs[:nq] = inp.query_attrs
        with obs_span("auto.stage_enqueue",
                      mesh=list(self.mesh.devices.shape)):
            # One-hop staging straight into the jit's pinned shardings
            # (same rationale as ShardedEngine._shard_inputs_inner).
            args = (
                jax.device_put(
                    attrs.astype(np_dtype, copy=False).reshape(
                        r, shard_rows, na), dsh3),
                jax.device_put(labels.reshape(r, shard_rows), dsh2),
                jax.device_put(ids.reshape(r, shard_rows), dsh2),
                jax.device_put(q_attrs.astype(np_dtype, copy=False), qsh))
        self.last_phase_ms["stage_enqueue"] = \
            (_time.perf_counter() - t0) * 1e3

        fn = self._fn_auto(k, data_block, select)
        obs_counters.record_dispatch(fn, args, site="auto.solve")
        # Shape specs only (no buffers kept alive): comms_from_hlo()
        # re-lowers this signature post-solve to read the schedule.
        self._last_dispatch = (fn, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))

        def _op():
            rs_inject.fire("auto.solve", which="gspmd")
            return fn(*args)

        with obs_span("auto.solve", select=select,
                      mesh=[r, c], kcap=k) as sp:
            # Re-dispatching the jitted program on the same placed
            # arrays is idempotent — the retry wrapper's requirement.
            top = rs_retry.call_with_retry(_op, "auto.solve")
            sp.fence(top.dists)
        telemetry.sample_memory_now()
        return [(top, qpad, None, select)]

    # -- engine entry points --------------------------------------------------
    def _reset_solve_state(self) -> None:
        self.last_hetk = None        # no heterogeneous-k split: the
        # streaming selects take any k natively, so nothing routes
        self.last_phase_ms = {}
        self.last_comms = []         # compiler-chosen schedule: no
        # analytic traffic claim until comms_from_hlo() derives the
        # real one from the compiled program (module docstring)
        self._last_dispatch = None
        self._pending_iters = []
        self.last_extract_impl = self.last_variant = None
        self.last_prune = None

    def comms_from_hlo(self):
        """Derive the REAL comms record from the compiled program.

        Lowers the last solve's dispatch signature (shape specs stored
        by ``_solve_auto``), reads its collective schedule via obs.hlo,
        and populates ``last_comms`` with ``gspmd_*`` CollectiveTraffic
        records (which collectives GSPMD chose, on which mesh axis, how
        many bytes). Returns the :class:`~dmlp_tpu.obs.hlo.HloReport`,
        or None when no solve ran or the signature cannot lower —
        introspection never raises into the solve path. Call it OUTSIDE
        the timed region: the AOT lower+compile is not free (the
        fingerprint cache dedupes repeat calls)."""
        from dmlp_tpu.obs import hlo as obs_hlo
        disp = getattr(self, "_last_dispatch", None)
        if disp is None:
            return None
        fn, specs = disp
        rep = obs_hlo.report_for_fn(fn, specs, label="auto.solve")
        if rep is None:
            return None
        mesh_axes = dict(zip(self.mesh.axis_names,
                             self.mesh.devices.shape))
        self.last_comms = obs_hlo.traffic_from_report(
            rep, mesh_axes=mesh_axes)
        return rep

    def _solve_segments(self, inp: KNNInput):
        self._reset_solve_state()
        # Precision resolves outside the jit; run() already swapped the
        # staging dtype when the bf16 first pass applies, so the ACTIVE
        # record is whatever the solve actually stages with.
        # (No Pallas kernel here, so no split form: a float32 pass is
        # ops.distance's one HIGHEST dot, "f32", in either mode.)
        prec = "bf16" if self.config.resolve_precision() == "bf16" \
            else "f32"
        self.last_precision = {
            "active": "bf16" if (prec == "bf16"
                                 and self._staging == "bfloat16")
            else "f32",
            "configured": prec}
        return self._solve_auto(inp, allow_prune=self.config.exact)

    def _candidates(self, inp: KNNInput):
        nq = inp.params.num_queries
        self._reset_solve_state()
        memwatch.note_engine_model(self, inp)
        # Same dense-scan rationale as ShardedEngine._candidates: the
        # per-shard candidate-horizon consumers preclude global-k
        # pruning.
        [(top, _qpad, _idx, _select)] = self._solve_auto(
            inp, allow_prune=False)
        od, ol, oi = resilient_get((top.dists, top.labels, top.ids),
                                   site="auto.fetch")
        return (np.asarray(od, np.float64)[:nq], ol[:nq], oi[:nq])

    def solve_global(self, d_attrs, d_labels, d_ids, q_attrs, kmax: int):
        # engine.sharded._fn now carries a "gspmd" merged program (the
        # fleet's merge="auto" stream path uses it single-controller),
        # but the multi-host contract feed (parallel.distributed) has
        # never been qualified against it. Multi-host GSPMD is the
        # TPU-round follow-on (ROADMAP); fail loudly until then.
        raise NotImplementedError(
            "AutoShardedEngine has no multi-host contract path yet; "
            "use mode='sharded'/'ring' for parallel.distributed feeds")

    def solve_local_shards(self, d_attrs, d_labels, d_ids, q_attrs,
                           kmax: int):
        raise NotImplementedError(
            "AutoShardedEngine has no multi-host contract path yet; "
            "use mode='sharded'/'ring' for parallel.distributed feeds")

    def _run_device_full(self, inp: KNNInput) -> List[QueryResult]:
        from dmlp_tpu.engine.single import (_device_epilogue,
                                            flush_measured_iters)

        nq = inp.params.num_queries
        num_labels = int(inp.labels.max()) + 1 if inp.params.num_data else 1
        ksh = NamedSharding(self.mesh, P(QUERY_AXIS))
        self._reset_solve_state()
        memwatch.note_engine_model(self, inp)
        # Device-full output IS the device ordering — no repair
        # backstop, so no pruning (same contract as the mesh engines).
        [(top, qpad, _idx, _select)] = self._solve_auto(
            inp, allow_prune=False)
        ks_pad = np.zeros(qpad, np.int32)
        ks_pad[:nq] = inp.ks
        p, i, d = _device_epilogue(top, jax.device_put(ks_pad, ksh),
                                   num_labels=num_labels)
        p, i, d = resilient_get((p, i, d), site="auto.fetch")
        preds = p[:nq]
        rids = i[:nq]
        rd = np.asarray(d, np.float64)[:nq]
        results = [QueryResult(qi, int(inp.ks[qi]), int(preds[qi]),
                               rids[qi, : int(inp.ks[qi])].astype(np.int64),
                               rd[qi, : int(inp.ks[qi])])
                   for qi in range(nq)]
        flush_measured_iters(self)
        return results
