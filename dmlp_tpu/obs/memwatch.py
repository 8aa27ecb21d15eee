"""Device-memory watermarks: the analytic peak-HBM model and its
reconciliation against measured watermarks.

Memory was the one resource with zero observability anywhere in the
package: FLOPs and collective bytes both have analytic models reconciled
against measurement (obs.kernel_cost vs measured iters, obs.comms vs
traces), while the resilience ladder reacted to OOM blindly. This module
is the missing sibling of :mod:`dmlp_tpu.obs.comms`, for bytes resident
in device memory:

- :func:`resident_bytes_model` — the analytic peak-HBM model per
  engine/config, computed from the SAME plan functions the dispatch
  paths use (``plan_chunks`` / ``resolve_kcap`` / ``fit_blocks``), so
  tests can validate the terms against hand-computed byte counts for a
  concrete shape. Terms cover the staged corpus (whole-dataset for the
  scan path, the :data:`~dmlp_tpu.engine.single._CHUNK_WINDOW` staging
  window for the chunked drivers, the resident dataset ×2 during the
  multipass concat), query blocks, double-buffered top-k carries, the
  extract/fused kernels' HBM-visible outputs, and the train step's
  params/grads/moments/batch/activations.
- **measured watermarks** — :func:`device_memory_stats` polls per-device
  ``memory_stats()`` (None on backends that report nothing — this
  container's CPU backend); :func:`live_array_bytes` sums live jax
  array bytes as the fallback basis. Neither ever *initializes* a
  backend: they no-op unless the process already imported jax.
- :func:`reconcile` — model vs measured with per-basis documented
  tolerance ratio bounds (:data:`RATIO_BOUNDS`), and the explicit
  ``mem_stats_unavailable`` marker where the backend cannot report
  memory — never a silent pass.

The model is a *resident-set* model: it counts the arrays the engine
deliberately keeps in device memory, not XLA's transient scratch or
allocator slack — hence ratio bounds rather than a percent band. The
``memory_stats`` basis is the real allocator (slack above the model);
the ``live_arrays`` basis counts every live buffer in the process
(warmup leftovers and observability scalars ride along), so its bounds
are looser and both are named in the reconcile record.

Import-light: jax strictly lazy; engine modules imported only inside
the model functions.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional

#: documented model-vs-measured tolerance, per basis, as ratio bounds on
#: measured/model: the model must sit within [lo, hi]× of the watermark
RATIO_BOUNDS: Dict[str, tuple] = {
    # allocator stats: slack + XLA temporaries above the resident set,
    # fragmentation below a just-freed peak
    "memory_stats": (0.5, 3.0),
    # every live buffer in the process rides along (and the allocator
    # may cache freed chunk buffers the model already rotated out)
    "live_arrays": (0.3, 4.0),
}

#: byte widths shared with the engines (TopK triple = f32 + i32 + i32)
_TOPK_ITEMSIZE = 12
_EXTRACT_CARRY_ITEMSIZE = 8   # od f32 + oi i32


def _staging_itemsize(staging: str) -> int:
    return 2 if staging == "bfloat16" else 4


# -- measured bases -----------------------------------------------------------

def device_memory_stats() -> Optional[List[Optional[Dict[str, Any]]]]:
    """Per-device ``memory_stats()`` dicts (None entries where a device
    reports nothing), or None when jax was never imported — polling
    must not initialize a backend as a side effect."""
    if "jax" not in sys.modules:
        return None
    try:
        import jax
        out = []
        for d in jax.devices():
            try:
                out.append(d.memory_stats())
            except Exception:  # a device without the API is a
                out.append(None)   # None entry, not a failure
        return out
    except Exception:  # observability never raises
        return None


def live_array_bytes() -> Optional[int]:
    """Total bytes of live jax arrays in this process — the fallback
    watermark basis on backends whose ``memory_stats()`` is None."""
    if "jax" not in sys.modules:
        return None
    try:
        import jax
        return int(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:  # observability never raises
        return None


def measured_watermark(per_device: bool = False) -> Dict[str, Any]:
    """One-shot watermark: allocator peak when available, live-array
    bytes otherwise, explicit marker when neither basis reports. For a
    watermark tracked ACROSS a run, use the telemetry sampler's
    ``measured_peak()`` (it maxes over ticks).

    The allocator basis is the SUM over the host's devices — the
    process's footprint, what a single-device engine's model and budget
    are compared with. ``per_device`` gives the FULLEST device's peak
    instead: what a per-device model (``fleet_engine_model``) and a
    per-device budget (one chip's ``bytes_limit``) must be held to, or
    a mesh whose every chip is a quarter full reads as one chip
    overfull."""
    stats = device_memory_stats()
    if stats is not None:
        peaks = [st.get("peak_bytes_in_use", st.get("bytes_in_use", 0))
                 for st in stats if st]
        if peaks:
            return {"bytes": int(max(peaks) if per_device else sum(peaks)),
                    "basis": "memory_stats"}
    live = live_array_bytes()
    if live:
        return {"bytes": live, "basis": "live_arrays"}
    return {"unavailable": "backend reports no memory_stats and no "
                           "live jax arrays exist"}


# -- analytic models ----------------------------------------------------------

def single_engine_model(n: int, nq: int, na: int, kmax: int,
                        config=None, staging: Optional[str] = None
                        ) -> Dict[str, Any]:
    """Peak resident device bytes for one SingleChipEngine solve at
    (num_data n, num_queries nq, num_attrs na, max-k kmax), mirroring
    the dispatch planning in :mod:`dmlp_tpu.engine.single`:

    - the **scan path** ("sort") stages the whole padded dataset plus
      labels/ids and all query blocks;
    - the **chunked drivers** ("topk"/"seg"/"extract") hold at most the
      ``_CHUNK_WINDOW + 1`` in-flight staged chunks (the backpressure
      window plus the chunk being staged) — except the **multipass**
      wide-k plan, which keeps the dataset resident and briefly ×2
      during its concat;
    - top-k carries are double-buffered (the fold consumes the old
      carry while producing the new one), ``P`` slabs for multipass;
    - the extract/fused kernels' HBM-visible outputs (od/oi + the
      per-tile iters diagnostics) are the carry term — the distance
      tile itself lives only in VMEM (the whole point of the fused
      kernel), so no (Q, N) term appears on any path.

    Every term is reported; ``total_bytes`` is their sum. Hand-computed
    for a concrete shape in tests/test_telemetry.py.
    """
    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.engine.single import (_CHUNK_WINDOW, fit_blocks,
                                        plan_chunks, resolve_kcap,
                                        round_up)
    from dmlp_tpu.ops.pallas_extract import QUERY_TILE

    cfg = config or EngineConfig()
    staging = staging or cfg.resolve_dtype()
    item = _staging_itemsize(staging)
    n, nq = max(n, 1), max(nq, 1)
    select = cfg.resolve_select(round_up(n, 8))
    terms: Dict[str, int] = {}

    if select == "sort":
        # _solve_scan: whole dataset + labels/ids + all query blocks
        data_block = (min(cfg.data_block, round_up(n, 8))
                      if cfg.data_block is not None
                      else fit_blocks(n, cfg.resolve_data_block(select),
                                      granule=cfg.resolve_granule(select)))
        npad = round_up(n, data_block)
        kc = resolve_kcap(cfg, kmax, select, npad, staging=staging)
        qb = min(cfg.query_block, round_up(nq, 8))
        qpad = round_up(nq, qb)
        terms["staged_corpus"] = npad * na * item
        terms["labels_ids"] = npad * 8
        terms["query_blocks"] = qpad * na * item
        terms["topk_out"] = qpad * kc * _TOPK_ITEMSIZE
        return _finish(terms, select=select, kcap=kc, npad=npad,
                       qpad=qpad, staging=staging)

    if select == "extract":
        granule = cfg.resolve_granule("extract")
        npad, nchunks, chunk_rows = plan_chunks(n, granule, cfg.data_block)
        qpad = round_up(nq, QUERY_TILE)
        kc = resolve_kcap(cfg, kmax, "extract", nchunks * chunk_rows,
                          staging=staging)
        multipass = kc > 512
        window = min(nchunks, _CHUNK_WINDOW + 1)
        if multipass:
            # resident dataset + transient ×2 during the concat, and
            # P = ceil(kcap/512) carry slabs of the per-pass kc=512
            npasses = -(-kc // 512)
            terms["staged_corpus"] = 2 * npad * na * item
            terms["topk_carries"] = (npasses + 1) * qpad * 512 \
                * _EXTRACT_CARRY_ITEMSIZE
        else:
            terms["staged_corpus"] = window * chunk_rows * na * item
            # double-buffered od/oi during the fold chain
            terms["topk_carries"] = 2 * qpad * kc * _EXTRACT_CARRY_ITEMSIZE
        terms["query_blocks"] = qpad * na * item
        terms["labels_ids"] = n * 4          # labels staged once (finalize)
        # fused/extract scratch visible in HBM: the per-(tile) iters
        # diagnostics output, one i32 per grid cell per in-flight chunk
        terms["kernel_scratch"] = window * 4 * max(
            (qpad // 128) * max(chunk_rows // 1024, 1), 1)
        return _finish(terms, select=select, kcap=kc, npad=npad,
                       qpad=qpad, staging=staging,
                       multipass=multipass)

    # chunked streaming fold ("topk" / "seg")
    granule = cfg.resolve_granule(select)
    npad, nchunks, chunk_rows = plan_chunks(n, granule, cfg.data_block)
    qpad = round_up(nq, 8)
    kc = resolve_kcap(cfg, kmax, select, nchunks * chunk_rows,
                      staging=staging)
    window = min(nchunks, _CHUNK_WINDOW + 1)
    terms["staged_corpus"] = window * chunk_rows * na * item
    terms["labels_ids"] = window * chunk_rows * 8
    terms["query_blocks"] = qpad * na * item
    terms["topk_carries"] = 2 * qpad * kc * _TOPK_ITEMSIZE
    return _finish(terms, select=select, kcap=kc, npad=npad, qpad=qpad,
                   staging=staging)


def mesh_engine_model(n: int, nq: int, na: int, kmax: int,
                      mesh_shape, mode: str = "sharded",
                      config=None, staging: Optional[str] = None
                      ) -> Dict[str, Any]:
    """Peak resident bytes PER DEVICE for the mesh engines: each (data
    r × query c) cell holds its corpus shard + replicated query shard +
    its top-k lists, and the merge buffer differs by strategy — the
    all-gather merge materializes all r cells' (q_local, k) triples,
    the ring merge only the O(k) accumulator (that asymmetry IS the
    ring engine's reason to exist, now a modeled number)."""
    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.engine.single import resolve_kcap, round_up

    cfg = config or EngineConfig(mode=mode)
    staging = staging or cfg.resolve_dtype()
    item = _staging_itemsize(staging)
    r, c = mesh_shape
    n, nq = max(n, 1), max(nq, 1)
    shard_rows = round_up(-(-n // r), 8)
    q_local = round_up(-(-nq // c), 8)
    kc = resolve_kcap(cfg, kmax, cfg.resolve_select(shard_rows),
                      shard_rows, staging=staging)
    terms = {
        "corpus_shard": shard_rows * na * item,
        "labels_ids_shard": shard_rows * 8,
        "query_shard": q_local * na * item,
        "local_topk": q_local * kc * _TOPK_ITEMSIZE,
        # mode="ring" keeps the O(k) accumulator; "sharded" (allgather)
        # materializes all r lists, and "auto" (GSPMD) prices that
        # worst case — the compiler may pick it.
        "merge_buffer": (2 if mode == "ring" else r)
        * q_local * kc * _TOPK_ITEMSIZE,
    }
    return _finish(terms, mode=mode, mesh=[r, c], kcap=kc,
                   shard_rows=shard_rows, q_local=q_local,
                   staging=staging, per_device=True, n_devices=r * c)


def train_step_model(dims, batch: int, optimizer: str = "sgd",
                     mesh_shape=None, compute_dtype: Optional[str] = None
                     ) -> Dict[str, Any]:
    """Peak resident bytes per device for one dp×tp train step: params
    + grads + optimizer moments (adam: 2× params) + the local batch +
    the forward activations kept for backward (one (batch_local,
    width) f32 per layer boundary). tp shards the hidden dims across
    ``tp``; dp shards the batch across ``dp``."""
    dims = list(dims)
    dp, tp = (mesh_shape or (1, 1))[:2]
    param_count = sum(dims[i] * dims[i + 1] + dims[i + 1]
                      for i in range(len(dims) - 1))
    pbytes = param_count * 4 // max(tp, 1)
    moments = {"sgd": 0, "adam": 2}.get(optimizer, 0)
    b_local = max(batch // max(dp, 1), 1)
    act_item = 2 if compute_dtype == "bfloat16" else 4
    acts = b_local * sum(dims[1:]) * act_item // max(tp, 1)
    terms = {
        "params": pbytes,
        "grads": pbytes,
        "opt_moments": moments * pbytes,
        "batch": b_local * (dims[0] + 1) * 4,
        "activations": acts,
    }
    return _finish(terms, kind="train", dims=dims, batch=batch,
                   optimizer=optimizer, per_device=True,
                   n_devices=max(dp, 1) * max(tp, 1))


def serve_engine_model(capacity_rows: int, na: int,
                       staging: str = "float32", qpad: int = 0,
                       kcap: int = 0, extract_chunks: int = 0,
                       chunk_rows: int = 0,
                       summary_blocks: int = 0,
                       chunk_attrs: int = 0,
                       mp_slots: int = 0,
                       retry: tuple = (0, 0)) -> Dict[str, Any]:
    """Peak resident device bytes for the serving layer's
    :class:`~dmlp_tpu.serve.engine.ResidentEngine`: the capacity-padded
    resident corpus (+ labels/ids mask arrays), the extract path's
    resident chunk copies when staged, and — when a micro-batch bucket
    (qpad, kcap) is given — that batch's transient terms (padded query
    block + double-buffered candidate lists). The admission controller
    reads the corpus terms as the floor and prices each bucket's
    marginal bytes on top. ``chunk_attrs`` is the width a row of the
    chunk stack (and of a staged query) holds on the device when the
    engine pads it to whole lanes (0: ``na``). ``mp_slots`` is what a
    wide-k bucket's passes extract together (passes x 512 slots a
    query; 0 for a bucket one pass fills): the multipass driver holds
    every pass's (qpad, 512) list pair until the merge, their
    concatenation, and the merge's id-sorted copy and sort workspace
    (compiled for a v5e at 1024 x 1536: 26.8 MB of temporaries for
    12.6 MB of lists): four times the lists, beside the merged
    (qpad, kcap) result that ``topk_carries`` prices. The sweeps read
    the resident stack itself: no term of the corpus's size. ``retry``
    is the (query rows, slots) of the device retry of the bucket's
    flagged queries ((0, 0): the bucket has none): one more padded
    query block and one more pair of candidate lists, of ONE group (a
    later group's reuse the first's once it is read back), beside the
    batch's own; it folds the resident stack too."""
    item = _staging_itemsize(staging)
    ca = chunk_attrs or na
    terms: Dict[str, int] = {
        "resident_corpus": capacity_rows * na * item,
        "labels_ids": capacity_rows * 8,
    }
    if extract_chunks:
        terms["extract_chunks"] = extract_chunks * chunk_rows * ca * item
        # the stack's rows' squared norms, staged beside it: one dense
        # float32 row a chunk (serve.engine._update_chunk)
        terms["chunk_norms"] = extract_chunks * chunk_rows * 4
    if summary_blocks:
        # Device-resident block summaries of the pruned two-stage
        # solve (ops.summaries.stage_summaries): two (B, A) f32 boxes
        # at the stack's width, two (B,) f32 norm bands, one (B,) i32
        # count vector.
        terms["resident_summaries"] = summary_blocks * (8 * ca + 12)
    if qpad:
        terms["query_blocks"] = qpad * ca * item
        terms["topk_carries"] = 2 * qpad * kcap * _TOPK_ITEMSIZE
        if mp_slots:
            # a list slot is a float32 distance and an int32 id
            terms["multipass_lists"] = 4 * qpad * mp_slots * 8
        if retry[0]:
            terms["retry_lists"] = retry[0] * ca * item \
                + 2 * retry[0] * retry[1] * _TOPK_ITEMSIZE
    return _finish(terms, kind="serve", capacity_rows=capacity_rows,
                   staging=staging)


def fleet_engine_model(mesh_shape, shard_rows: int, na: int,
                       staging: str = "float32", chunks: int = 0,
                       chunk_rows: int = 0, monolithic: bool = False,
                       capacity_rows: int = 0, summary_blocks: int = 0,
                       qloc: int = 0, kcap: int = 0,
                       merge: str = "allgather") -> Dict[str, Any]:
    """Peak resident bytes PER DEVICE for the mesh-resident serving
    engine (:class:`~dmlp_tpu.fleet.mesh_engine.MeshResidentEngine`):
    each device holds its shard's resident chunk buffers (or the
    monolithic shard slice), the replicated label vector, its share of
    the resident summaries, and — when a micro-batch bucket (qloc,
    kcap) is given — that batch's transient terms: the per-column query
    shard, the local candidate lists, and the merge buffer (all R
    shards' lists for the all-gather merge, the O(k) accumulator for
    the ring). The admission controller reads the corpus terms as the
    per-device floor and prices each bucket's marginal bytes on top."""
    item = _staging_itemsize(staging)
    r, c = mesh_shape
    terms: Dict[str, int] = {
        # Replicated labels ride every device (tiny — int32 * capacity).
        "labels_replicated": max(capacity_rows, r * shard_rows) * 4,
    }
    if chunks:
        terms["resident_chunks"] = chunks * chunk_rows * na * item
        terms["chunk_norms"] = chunks * chunk_rows * 4
    if monolithic:
        terms["monolithic_shard"] = shard_rows * na * item
        terms["labels_ids_shard"] = shard_rows * 8
    if summary_blocks:
        terms["resident_summaries"] = summary_blocks * (8 * na + 12)
    if qloc:
        terms["query_shard"] = qloc * na * item
        terms["local_topk"] = qloc * kcap * _TOPK_ITEMSIZE
        # Ring keeps the O(k) accumulator; allgather materializes all R
        # lists. "gspmd" (merge="auto") prices the allgather worst case:
        # the compiler may choose it, and the admission controller must
        # not under-budget on a schedule it cannot see.
        terms["merge_buffer"] = (2 if merge == "ring" else r) \
            * qloc * kcap * _TOPK_ITEMSIZE
    return _finish(terms, kind="fleet", mesh=[r, c],
                   shard_rows=shard_rows, staging=staging,
                   per_device=True, n_devices=r * c)


def _finish(terms: Dict[str, int], **meta) -> Dict[str, Any]:
    out: Dict[str, Any] = {"model_schema": 1,
                           "terms": {k: int(v) for k, v in terms.items()},
                           "total_bytes": int(sum(terms.values()))}
    out.update(meta)
    return out


def resident_bytes_model(kind: str, **params) -> Dict[str, Any]:
    """Dispatch on workload kind: "single" | "sharded" | "ring" |
    "train" — the one public entry the CLI/engines/smoke call."""
    if kind == "single":
        return single_engine_model(**params)
    if kind in ("sharded", "ring"):
        return mesh_engine_model(mode=kind, **params)
    if kind == "train":
        return train_step_model(**params)
    if kind == "serve":
        return serve_engine_model(**params)
    if kind == "fleet":
        return fleet_engine_model(**params)
    raise ValueError(f"unknown workload kind {kind!r}")


def model_for_engine(engine, inp) -> Dict[str, Any]:
    """The analytic model for a live engine + parsed input — reads the
    engine's real config/staging so the model sees exactly the plan the
    solve will resolve."""
    p = inp.params
    kmax = int(inp.ks.max()) if p.num_queries else 1
    if hasattr(engine, "mem_model"):
        # The resident serving engines (serve.ResidentEngine,
        # fleet.MeshResidentEngine) own their model parameterization —
        # bucket_plan is the one kcap derivation, so the model cannot
        # drift from what the solve allocates.
        return engine.mem_model(p.num_queries, kmax)
    if type(engine).__name__ == "SingleChipEngine":
        return single_engine_model(p.num_data, p.num_queries, p.num_attrs,
                                   kmax, config=engine.config,
                                   staging=engine._staging)
    mode = {"RingEngine": "ring",
            "AutoShardedEngine": "auto"}.get(
        type(engine).__name__, "sharded")
    return mesh_engine_model(p.num_data, p.num_queries, p.num_attrs,
                             kmax, tuple(engine.mesh.devices.shape),
                             mode=mode, config=engine.config,
                             staging=engine._staging)


def note_engine_model(engine, inp) -> Optional[Dict[str, Any]]:
    """Engine hook: compute the model and publish it (gauge +
    ``engine.last_mem_model``) when a telemetry session is active;
    no-op otherwise so the hot path pays one module-global read."""
    from dmlp_tpu.obs import telemetry
    if not telemetry.enabled():
        engine.last_mem_model = None
        return None
    try:
        model = model_for_engine(engine, inp)
        engine.last_mem_model = model
        telemetry.registry().gauge("mem.model.resident_bytes").set(
            model["total_bytes"])
        return model
    except Exception:  # observability never fails a solve
        engine.last_mem_model = None
        return None


# -- reconciliation -----------------------------------------------------------

def reconcile(model: Dict[str, Any],
              measured: Dict[str, Any]) -> Dict[str, Any]:
    """Model vs measured watermark. ``measured`` is a
    :func:`measured_watermark` / sampler ``measured_peak()`` dict;
    an unavailable basis yields the explicit ``mem_stats_unavailable``
    marker (markers never gate — PR 5 convention). Otherwise the
    verdict is ``within_tolerance`` against the basis's documented
    :data:`RATIO_BOUNDS`."""
    # Measured bases are PROCESS-WIDE (sums over devices); a per-device
    # model must scale by its device count before the two compare —
    # otherwise an 8-device mesh run reports a healthy solve as ~8x
    # over model.
    scale = int(model.get("n_devices", 1)) if model.get("per_device") \
        else 1
    out: Dict[str, Any] = {
        "model_bytes": int(model["total_bytes"]) * scale}
    if scale != 1:
        out["model_bytes_per_device"] = int(model["total_bytes"])
        out["n_devices"] = scale
    if "unavailable" in measured or not measured.get("bytes"):
        out["mem_stats_unavailable"] = measured.get(
            "unavailable", "measured watermark is zero")
        return out
    basis = measured.get("basis", "memory_stats")
    lo, hi = RATIO_BOUNDS.get(basis, RATIO_BOUNDS["memory_stats"])
    mbytes = int(measured["bytes"])
    ratio = mbytes / max(out["model_bytes"], 1)
    out.update(measured_bytes=mbytes, basis=basis,
               ratio=round(ratio, 3), ratio_bounds=[lo, hi],
               delta_pct=round((mbytes - out["model_bytes"])
                               / out["model_bytes"] * 100.0, 2)
               if out["model_bytes"] else None,
               within_tolerance=bool(lo <= ratio <= hi))
    return out


__all__ = [
    "RATIO_BOUNDS", "device_memory_stats", "live_array_bytes",
    "measured_watermark", "single_engine_model", "mesh_engine_model",
    "train_step_model", "serve_engine_model", "resident_bytes_model",
    "model_for_engine",
    "note_engine_model", "reconcile",
]
