"""Multi-host runtime: process bring-up and per-process sharded input feed.

The reference's multi-node story is mpirun spawning ranks across 2 SLURM
nodes (run_bench.sh:78-84), rank 0 reading the entire input and Scatterv-ing
it (common.cpp:93-117, engine.cpp:62-209) — a single-host ingest bottleneck
the survey (§7 "host input pipeline") flags. The TPU-native story:

- :func:`initialize` wraps ``jax.distributed.initialize`` (the
  MPI_Init/Finalize analog, survey §5.8) — call once per process before
  device use; no-op for single-process runs.
- :func:`shard_bounds` + :func:`read_data_shard` let every process parse
  only its own slice of the same input file (offset-indexed: one cheap
  newline scan, then the native/Python parser on the local byte range),
  preserving global ids by line order.
- :func:`make_global_dataset` assembles the per-process arrays into global
  jax.Arrays laid out on the ("data", "query") mesh via
  ``jax.make_array_from_process_local_data`` — the declarative Scatterv.

The sharded engines consume the resulting global arrays unchanged: on one
host this path is exercised end-to-end by tests; on a pod each process
feeds only its shard and XLA never moves the full dataset through one host.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

from dmlp_tpu.engine.finalize import boundary_hazard, staging_eps
from dmlp_tpu.parallel.mesh import DATA_AXIS, QUERY_AXIS
from dmlp_tpu.resilience import inject as rs_inject
from dmlp_tpu.resilience import retry as rs_retry


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               auto: bool = False) -> None:
    """Bring up the multi-process runtime (the MPI_Init analog).

    Explicit form: pass coordinator/num_processes/process_id (like mpirun
    passing rank/size). Auto form: ``auto=True`` calls bare
    ``jax.distributed.initialize()`` so managed environments (Cloud TPU
    pods, SLURM) self-detect topology. With neither, this is a no-op —
    suitable only for genuinely single-process runs; a pod launcher that
    skips both forms would silently see local chips only, so multi-host
    entry points should pass ``auto=True``.
    """
    if auto:
        jax.distributed.initialize()
        return
    if num_processes is None or num_processes <= 1:
        return
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def shard_bounds(n: int, num_shards: int, shard: int) -> Tuple[int, int]:
    """[start, stop) of shard's block in a length-n axis (remainder spread
    over the leading shards — balanced, unlike the reference's
    all-remainder-to-rank-0 choice at engine.cpp:62-63)."""
    base, rem = divmod(n, num_shards)
    start = shard * base + min(shard, rem)
    return start, start + base + (1 if shard < rem else 0)


def line_offsets(data: bytes) -> np.ndarray:
    """Byte offset of every line start (one vectorized newline scan)."""
    nl = np.flatnonzero(np.frombuffer(data, np.uint8) == ord("\n"))
    return np.concatenate([[0], nl + 1]).astype(np.int64)


def read_row_range(path: str, start: int, stop: int):
    """Parse data rows [start, stop) (plus all query lines) from the
    canonical input file — one vectorized newline scan over an mmap, then
    the native/Python parser on just the local byte range.

    The mmap keeps per-process HELD memory proportional to the local
    shard: the newline scan touches every page once (an index must see
    every byte), but pages stay in the evictable OS cache rather than a
    process-private heap buffer, and only the local rows + queries are
    ever copied out.

    Returns (params, local_labels, local_attrs, ks, query_attrs); queries
    are replicated (they are small and every process needs them to build
    the query-axis feed and to finalize).
    """
    import mmap

    from dmlp_tpu.io.grammar import parse_params

    with open(path, "rb") as f:
        raw = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
    try:
        offs = line_offsets(raw)
        header = raw[offs[0]:offs[1]].decode("ascii")
        params = parse_params(header)
        nd = params.num_data
        stop = min(stop, nd)
        start = min(start, stop)

        # Reassemble a small instance: header + local data lines + queries
        # (slicing an mmap copies just those byte ranges).
        local_bytes = (
            f"{stop - start} {params.num_queries} {params.num_attrs}\n"
            .encode("ascii")
            + raw[offs[1 + start]:offs[1 + stop]]
            + raw[offs[1 + nd]:])
    finally:
        raw.close()
    # io.BytesIO -> parse_input routes large shards through the native C++
    # tokenizer (bytes pass straight through, no decode round-trip).
    import io as _io
    from dmlp_tpu.io.grammar import parse_input
    sub = parse_input(_io.BytesIO(local_bytes))
    return params, sub.labels, sub.data_attrs, sub.ks, sub.query_attrs


def read_data_shard(path: str, num_shards: int, shard: int):
    """Parse only this shard's (balanced, shard_bounds) data lines plus all
    query lines. Returns (params, labels, attrs, start, ks, query_attrs)."""
    with open(path, "rb") as f:
        header = f.readline().decode("ascii")
    from dmlp_tpu.io.grammar import parse_params
    nd = parse_params(header).num_data
    start, stop = shard_bounds(nd, num_shards, shard)
    params, labels, attrs, ks, q_attrs = read_row_range(path, start, stop)
    return params, labels, attrs, start, ks, q_attrs


def process_slice(sharding, global_shape) -> Tuple[int, int]:
    """This process's contiguous [start, stop) block along axis 0, derived
    from the sharding itself.

    ``shard_bounds(process_id)``-style arithmetic silently assumes process
    boundaries align with mesh-axis positions; on a mesh where one
    process's devices span several positions of an axis (2 processes x 4
    devices on a (4, 2) mesh) that assumption feeds the wrong rows. The
    sharding's own ``addressable_devices_indices_map`` is the ground truth
    for what this process must supply. Raises if the addressable block is
    not contiguous (a mesh/process layout this feed does not support).
    """
    imap = sharding.addressable_devices_indices_map(tuple(global_shape))
    spans = sorted({(idx[0].start or 0,
                     global_shape[0] if idx[0].stop is None else idx[0].stop)
                    for idx in imap.values()})
    lo, hi = spans[0][0], max(e for _, e in spans)
    cur = lo
    for s, e in spans:
        if s > cur:
            raise ValueError(
                f"process-addressable block not contiguous: gap [{cur},{s}) "
                f"(spans {spans}); choose a mesh whose data/query axes align "
                "with process boundaries")
        cur = max(cur, e)
    return lo, hi


def build_global(sharding, global_shape, local_np: np.ndarray, lo: int):
    """Assemble a global array from this process's local block.

    ``local_np`` holds rows [lo, lo + len) of axis 0 (the process's
    process_slice block); every other axis is full-size. The callback form
    serves exactly the shards this process's devices need — the declarative
    Scatterv, correct for any process-to-mesh layout.
    """
    def cb(index):
        sl = index[0]
        start = sl.start or 0
        stop = global_shape[0] if sl.stop is None else sl.stop
        return local_np[start - lo:stop - lo]

    return jax.make_array_from_callback(tuple(global_shape), sharding, cb)


def padded_shard(labels: np.ndarray, attrs: np.ndarray, start: int,
                 uniform_rows: int):
    """Pad one process's data rows to ``uniform_rows`` with sentinel rows
    (label = id = -1, masked to +inf by the distance kernel) — every
    process must contribute identical local shapes to
    jax.make_array_from_process_local_data. Global ids come from ``start``
    (the shard's first global line index)."""
    n, na = attrs.shape
    assert n <= uniform_rows
    out_attrs = np.zeros((uniform_rows, na), np.float32)
    out_attrs[:n] = attrs
    out_labels = np.full(uniform_rows, -1, np.int32)
    out_labels[:n] = labels
    out_ids = np.full(uniform_rows, -1, np.int32)
    out_ids[:n] = np.arange(start, start + n, dtype=np.int32)
    return out_attrs, out_labels, out_ids


def plan_shapes(engine, n: int, nq: int):
    """Global padded shapes for the sharded feed — identical on every
    process (pure function of the header + engine config/mesh)."""
    from dmlp_tpu.engine.single import round_up

    cfg = engine.config
    r, c = engine.mesh.devices.shape
    rows_est = round_up(max(-(-n // r), 1), 8)
    qgran = 8
    if cfg.data_block is None and cfg.resolve_select(rows_est) == "extract":
        # The per-shard solver (_plan_shard) will pick the extraction
        # kernel when these shapes support it, so pad shards to whole
        # extraction blocks and query shards to whole query tiles — a
        # merely-lane-divisible shard would tile degenerately (see
        # config.resolve_granule). If _plan_shard later falls back (e.g.
        # kcap past the kernel's cap), the streaming selects still accept
        # these shapes, just at non-ideal blocking — slower, never wrong.
        from dmlp_tpu.ops.pallas_extract import QUERY_TILE
        granule = cfg.resolve_granule("extract")
        qgran = QUERY_TILE
    else:
        granule = cfg.resolve_granule(cfg.resolve_streaming_select(rows_est))
    shard_rows = round_up(max(-(-n // r), 1), granule)
    qpad = c * round_up(max(-(-nq // c), 1), qgran)
    return r * shard_rows, shard_rows, qpad


def read_local_inputs(path: str, engine) -> dict:
    """Per-process sharded file read (host parse only, no device work).

    Each process derives its data/query blocks from the shardings
    themselves (process_slice), parses only those file rows, and returns
    everything place_global_inputs needs — no host ever ingests the full
    dataset (the survey's rank-0 bottleneck, common.cpp:93-117). Split
    from placement so the contract timer can start after parsing, like the
    reference's (common.cpp: parse, barrier, then start_time)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = engine.mesh
    with open(path, "rb") as f:
        header = f.readline().decode("ascii")
    from dmlp_tpu.io.grammar import parse_params
    hdr = parse_params(header)
    n, nq, na = hdr.num_data, hdr.num_queries, hdr.num_attrs
    npad, shard_rows, qpad = plan_shapes(engine, n, nq)

    dsh2 = NamedSharding(mesh, P(DATA_AXIS, None))
    qsh = NamedSharding(mesh, P(QUERY_AXIS, None))

    dlo, dhi = process_slice(dsh2, (npad, na))
    params, labels, attrs, ks, q_attrs = read_row_range(path, dlo, dhi)
    p_attrs, p_labels, p_ids = padded_shard(labels, attrs, dlo, dhi - dlo)

    qlo, qhi = process_slice(qsh, (qpad, na))
    q_local = np.zeros((qhi - qlo, na), np.float32)
    src = q_attrs[qlo:min(qhi, nq)]
    q_local[:src.shape[0]] = src

    local = {"data_attrs": attrs, "data_labels": labels, "offset": dlo,
             "shard_rows": shard_rows, "query_attrs": q_attrs}
    return {"params": params, "ks": ks, "local": local,
            "npad": npad, "qpad": qpad, "na": na,
            "p_attrs": p_attrs, "p_labels": p_labels, "p_ids": p_ids,
            "dlo": dlo, "q_local": q_local, "qlo": qlo}


def place_global_inputs(engine, parsed: dict):
    """Parsed per-process blocks -> global mesh arrays (the Scatterv
    analog, engine.cpp:62-209 — device placement only, belongs inside the
    contract's timed region). Returns (ga, gl, gi, gq)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ga, gl, gi = place_global_data(engine, parsed)
    qsh = NamedSharding(engine.mesh, P(QUERY_AXIS, None))
    gq = build_global(qsh, (parsed["qpad"], parsed["na"]),
                      parsed["q_local"].astype(
                          engine._np_dtype(), copy=False),
                      parsed["qlo"])
    return ga, gl, gi, gq


def place_global_data(engine, parsed: dict):
    """Data-side placement only (attrs/labels/ids) — the heterogeneous-k
    router shares this across query segments instead of paying an unused
    full-query placement inside the timed region."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = engine.mesh
    npad, na = parsed["npad"], parsed["na"]
    dsh2 = NamedSharding(mesh, P(DATA_AXIS, None))
    dsh1 = NamedSharding(mesh, P(DATA_AXIS))
    # Stage attrs in the engine's resolved dtype: each process converts
    # its own shard on host, so bf16 halves the per-host feed bytes.
    np_dtype = engine._np_dtype()
    ga = build_global(dsh2, (npad, na),
                      parsed["p_attrs"].astype(np_dtype, copy=False),
                      parsed["dlo"])
    gl = build_global(dsh1, (npad,), parsed["p_labels"], parsed["dlo"])
    gi = build_global(dsh1, (npad,), parsed["p_ids"], parsed["dlo"])
    return ga, gl, gi


def stage_global_inputs(path: str, engine):
    """Sharded file read + global mesh placement in one call.

    Returns (ga, gl, gi, gq, params, ks, local), where ``local`` carries
    what finalization needs later: this process's f64 data block + offset
    and the full f64 query attrs.
    """
    parsed = read_local_inputs(path, engine)
    ga, gl, gi, gq = place_global_inputs(engine, parsed)
    return ga, gl, gi, gq, parsed["params"], parsed["ks"], parsed["local"]


def place_query_subset(engine, q64: np.ndarray, idx: np.ndarray,
                       qgran: int):
    """Global query-axis placement of the query rows in ``idx``.

    Queries are replicated on every process (read_row_range), so each
    process can serve any slice of the padded subset directly — used by
    the heterogeneous-k router to feed each segment its own query array
    while the (large) data placement is shared. Returns (global_array,
    qpad)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dmlp_tpu.engine.single import round_up

    mesh = engine.mesh
    c = mesh.devices.shape[1]
    na = q64.shape[1]
    nqs = len(idx)
    qpad = c * round_up(max(-(-nqs // c), 1), qgran)
    # Stage through f32 like every other site (f64 -> f32 -> bf16): a
    # direct f64 -> bf16 round can differ in the last ulp near a bf16
    # midpoint, and staged bytes stay bit-identical across paths.
    qh = np.zeros((qpad, na), np.float32)
    qh[:nqs] = q64[idx]
    qh = qh.astype(engine._np_dtype(), copy=False)
    qsh = NamedSharding(mesh, P(QUERY_AXIS, None))
    return jax.make_array_from_callback(
        (qpad, na), qsh, lambda ix: qh[ix]), qpad


def sharded_solve_from_file(path: str, engine):
    """Whole multi-host feed: sharded read -> global arrays -> the engine's
    compiled sharded (merged) program. Returns (TopK, params, ks) — the
    caller finalizes. For the full contract run (distributed f64 rescore +
    rank-0 report) use distributed_contract_run instead.
    """
    from dmlp_tpu.engine.single import staging_for_k

    parsed = read_local_inputs(path, engine)
    params, ks = parsed["params"], parsed["ks"]
    kmax = int(ks.max()) if params.num_queries else 1
    # Wide-k solves stage f32 under dtype="auto" (engine.single
    # .staging_for_k): the context spans placement AND solve so the
    # staged wire dtype, the kcap margin, and the hazard eps agree.
    with staging_for_k(engine, kmax):
        ga, gl, gi, gq = place_global_inputs(engine, parsed)
        top = engine.solve_global(ga, gl, gi, gq, kmax)
    from dmlp_tpu.engine.single import flush_measured_iters
    flush_measured_iters(engine)
    return top, params, ks


def _exact_shard_topk(q64: np.ndarray, d64: np.ndarray, labels: np.ndarray,
                      id_base: np.ndarray, k: int):
    """Exact f64 top-k of one query over one data shard, by the selection
    total order (dist asc, id desc — the measured label-free
    oracle-binary comparator, golden.reference). The per-query repair for
    f32 tie-boundary hazards — all inputs are local to the owning process.
    """
    diff = d64 - q64[None, :]
    dist = np.einsum("na,na->n", diff, diff)
    order = np.lexsort((-id_base, dist))[:k]
    out_d = np.full(k, np.inf)
    out_l = np.full(k, -1, np.int32)
    out_i = np.full(k, -1, np.int32)
    m = len(order)
    out_d[:m] = dist[order]
    out_l[:m] = labels[order]
    out_i[:m] = id_base[order]
    return out_d, out_l, out_i


def rescore_local_shards(top, local, ks: np.ndarray, nq: int,
                         staging: str = "float32"):
    """Distributed float64 rescore: each process rescores the candidates of
    the data shards it owns, using only its own f64 rows.

    ``top`` is the (R, Qpad, K) per-shard TopK from solve_local_shards.
    Returns (R, Qpad, K) numpy arrays (f64 dists / labels / ids) holding
    this process's cells, +inf/-1 elsewhere — elementwise min/max across
    processes then reconstructs the full tensors (each cell has exactly one
    owner). Per-shard f32 tie-boundary hazards (candidate truncation, see
    engine.finalize.boundary_overflow) are repaired here from local f64
    data, so no golden-model pass over the full dataset is ever needed.
    """
    r_axis, qpad, kcap = top.dists.shape
    my_d = np.full((r_axis, qpad, kcap), np.inf)
    my_l = np.full((r_axis, qpad, kcap), -1, np.int32)
    my_i = np.full((r_axis, qpad, kcap), -1, np.int32)

    attrs64 = np.asarray(local["data_attrs"], np.float64)
    labels_loc = np.asarray(local["data_labels"])
    offset, shard_rows = local["offset"], local["shard_rows"]
    q64 = np.asarray(local["query_attrs"], np.float64)
    nreal = attrs64.shape[0]
    ks = np.asarray(ks)

    d_shards = {(s.index[0].start or 0, s.index[1].start or 0): s
                for s in top.dists.addressable_shards}
    l_shards = {(s.index[0].start or 0, s.index[1].start or 0): s
                for s in top.labels.addressable_shards}
    for s in top.ids.addressable_shards:
        r0 = s.index[0].start or 0
        q0 = s.index[1].start or 0
        qs = s.index[1]
        q1 = qpad if qs.stop is None else qs.stop
        ids_blk = np.array(s.data)[0]                      # (qloc, K), owned
        f32_blk = np.asarray(d_shards[(r0, q0)].data)[0]
        lab_blk = np.array(l_shards[(r0, q0)].data)[0]
        qrows = np.arange(q0, q1)

        if nreal == 0 or nq == 0:
            # All-padding shard (small n on a wide mesh) or no queries:
            # every candidate is a sentinel; nothing to rescore.
            my_d[r0, q0:q1] = np.inf
            my_l[r0, q0:q1] = -1
            my_i[r0, q0:q1] = -1
            continue

        # f64 rescore of this shard's candidates (ids are global rows in
        # [offset, offset + nreal) or -1); padded query rows (>= nq) score
        # against query 0 and are discarded at finalize.
        safe = np.clip(ids_blk - offset, 0, nreal - 1)
        gathered = attrs64[safe]                           # (qloc, K, A)
        qv = q64[np.minimum(qrows, nq - 1)]                # (qloc, A)
        diff = gathered - qv[:, None, :]
        d64 = np.einsum("qka,qka->qk", diff, diff)
        d64[ids_blk < 0] = np.inf

        # Per-shard tie-boundary repair, from local f64 data only. The
        # truncation gate uses THIS shard's real row count (sh_hi - sh_lo):
        # a candidate list that already holds every real row of the shard
        # cannot have truncated anything, even when the process's full
        # block (nreal rows across several shards) is wider than kcap.
        sh_lo = r0 * shard_rows - offset
        sh_hi = min(sh_lo + shard_rows, nreal)
        ks_blk = np.minimum(ks[np.minimum(qrows, max(nq - 1, 0))], kcap)
        kth = f32_blk[np.arange(q1 - q0), np.clip(ks_blk - 1, 0, kcap - 1)]
        # eps-widened truncation test (engine.finalize.staging_eps): a
        # staging dtype with non-monotone rounding (bf16) can displace a
        # true neighbor past the shard horizon without an exact tie. The
        # shard's own f64 rows bound the missed point's norm — it lives
        # in this shard by construction.
        qn_blk = np.einsum("qa,qa->q", qv, qv)
        dn_max_sh = (float(np.einsum("na,na->n", attrs64[sh_lo:sh_hi],
                                     attrs64[sh_lo:sh_hi]).max())
                     if sh_hi > sh_lo else 0.0)
        last_blk = np.asarray(f32_blk[:, -1], np.float64)
        eps = staging_eps(last_blk, qn_blk, dn_max_sh, staging,
                          attrs64.shape[1])
        hazard = boundary_hazard(kth, last_blk, eps) \
            & (qrows < nq) & (kcap < sh_hi - sh_lo)
        if hazard.any():
            base_ids = np.arange(offset + sh_lo, offset + sh_hi,
                                 dtype=np.int32)
            for j in np.nonzero(hazard)[0]:
                d64[j], lab_blk[j], ids_blk[j] = _exact_shard_topk(
                    q64[qrows[j]], attrs64[sh_lo:sh_hi],
                    labels_loc[sh_lo:sh_hi], base_ids, kcap)

        my_d[r0, q0:q1] = d64
        my_l[r0, q0:q1] = lab_blk
        my_i[r0, q0:q1] = ids_blk
    return my_d, my_l, my_i


def distributed_contract_run(path: str, engine, out=None, err=None,
                             warmup: bool = False):
    """The end-to-end multi-host contract run — the TPU-native form of
    ``mpirun ./engine < input`` (common.cpp:81-135 + run_bench.sh:82-84).

    Per process: sharded file read (no rank-0 ingest) -> per-shard device
    top-k (no f32 cross-shard merge) -> distributed f64 rescore + tie
    repair on the owning process -> host all-gather of the tiny candidate
    tensors -> every process merges/finalizes, process 0 prints the
    canonical stdout in query order + the ``Time taken`` stderr line.
    No host ever touches the full f64 dataset.
    """
    import sys
    import time

    from dmlp_tpu.engine.finalize import finalize_host
    from dmlp_tpu.io.report import format_results
    from dmlp_tpu.obs import dist_trace
    from dmlp_tpu.obs.trace import span as obs_span

    out = out if out is not None else sys.stdout
    err = err if err is not None else sys.stderr
    # whatever engine a caller hands in: the per-shard exact rescore
    # and the host merge here are squared L2's
    engine.config.require_score(
        "parallel.distributed.distributed_contract_run (the multi-host "
        "feed)")

    # Parse outside the timed region (the reference starts its timer after
    # rank-0 stdin ingest, common.cpp:119-124); device placement — the
    # Scatterv analog — happens inside solve(), which IS timed there.
    with obs_span("dist.read_local_inputs"):
        parsed = read_local_inputs(path, engine)
    params, ks, local = parsed["params"], parsed["ks"], parsed["local"]

    def solve_segment(ga, gl, gi, gq, ks_seg, q64_seg, idx):
        """Per-shard solve + distributed f64 rescore + host all-gather +
        finalize for one query segment (the whole query set when idx is
        None)."""
        nqs = len(ks_seg)
        kmax = int(ks_seg.max()) if nqs else 1

        def _solve_op():
            rs_inject.fire("dist.rank_solve", rank=jax.process_index())
            return engine.solve_local_shards(ga, gl, gi, gq, kmax)

        with obs_span("dist.solve_local_shards", nq=nqs, kmax=kmax) as sp:
            # Re-dispatch on the same placed global arrays is idempotent;
            # a transient per-rank dispatch failure retries locally
            # (collective-free per-shard solve) instead of failing the
            # whole cluster.
            top = rs_retry.call_with_retry(_solve_op, "dist.rank_solve")
            sp.fence(top.dists)
        # The fence above synchronized the per-shard solve: drain the
        # measured extract-iters queue now (scalar readback) so the
        # multi-host path's counters also report extraction_term=
        # measured when a probe is installed.
        from dmlp_tpu.engine.single import flush_measured_iters
        flush_measured_iters(engine)
        local_s = dict(local, query_attrs=q64_seg)
        with obs_span("dist.rescore_local_shards", nq=nqs):
            my_d, my_l, my_i = rescore_local_shards(
                top, local_s, ks_seg, nqs,
                staging=engine._staging)

        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            # nbytes is the REAL payload; the shape args let
            # tools/merge_traces.py recompute the analytic expectation
            # (obs.comms.host_allgather_candidates_traffic) and
            # reconcile the two per rank.
            def _gather_op():
                rs_inject.fire("dist.allgather", rank=jax.process_index())
                return (multihost_utils.process_allgather(my_d),
                        multihost_utils.process_allgather(my_l),
                        multihost_utils.process_allgather(my_i))

            with obs_span("dist.allgather_candidates",
                          nbytes=int(my_d.nbytes + my_l.nbytes
                                     + my_i.nbytes),
                          ranks=int(jax.process_count()),
                          r_shards=int(my_d.shape[0]),
                          qpad=int(my_d.shape[1]),
                          kcap=int(my_d.shape[2]),
                          itemsizes=[int(my_d.dtype.itemsize),
                                     int(my_l.dtype.itemsize),
                                     int(my_i.dtype.itemsize)]):
                all_d, all_l, all_i = rs_retry.call_with_retry(
                    _gather_op, "dist.allgather")
            my_d = all_d.min(axis=0)
            my_l = all_l.max(axis=0)
            my_i = all_i.max(axis=0)

        # (R, Qpad, K) -> (Q, R*K): per query, all shards' candidates.
        r_axis, qpad, kcap = my_d.shape
        flat = lambda x: x.transpose(1, 0, 2).reshape(qpad, r_axis * kcap)  # noqa: E731
        with obs_span("dist.finalize", nq=nqs):
            return finalize_host(flat(my_d)[:nqs], flat(my_l)[:nqs],
                                 flat(my_i)[:nqs], ks_seg, q64_seg, None,
                                 exact=False, query_ids=idx)

    def solve():
        from dmlp_tpu.engine.single import hetk_split, round_up

        nq = params.num_queries
        n = params.num_data
        r = engine.mesh.devices.shape[0]
        split = hetk_split(engine.config, engine._staging,
                           ks, n, round_up(max(-(-n // r), 1), 8))
        if split is None:
            with obs_span("dist.place_global_inputs"):
                ga, gl, gi, gq = place_global_inputs(engine, parsed)
            return solve_segment(ga, gl, gi, gq, ks,
                                 local["query_attrs"], None)

        # Heterogeneous-k routing, multi-host form: the (large) data
        # placement is shared; each segment gets its own query-axis feed
        # (queries are replicated per process) — bulk on the per-shard
        # extraction kernel, wide-k outliers on the streaming select.
        from dmlp_tpu.ops.pallas_extract import QUERY_TILE
        bulk_idx, out_idx = split
        ga, gl, gi = place_global_data(engine, parsed)
        merged = [None] * nq
        q64 = local["query_attrs"]
        for idx, qgran in ((bulk_idx, QUERY_TILE), (out_idx, 8)):
            gq_s, _ = place_query_subset(engine, q64, idx, qgran)
            for res in solve_segment(ga, gl, gi, gq_s, ks[idx],
                                     q64[idx], idx):
                merged[res.query_id] = res
        return merged

    from dmlp_tpu.engine.single import staging_for_k
    kmax_all = int(ks.max()) if params.num_queries else 0
    with staging_for_k(engine, kmax_all):
        if warmup:
            with obs_span("dist.warmup"):
                solve()
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("dmlp_tpu.contract.start")
        # The barrier releases every rank within network latency of one
        # wall instant — the clock-sync stamp merge_traces aligns rank
        # timelines on. Single-process runs stamp here too (zero-offset
        # reference point, so the merge tool needs no special case).
        dist_trace.clock_sync()
        t0 = time.perf_counter()
        with obs_span("dist.solve", rank=jax.process_index(),
                      nq=params.num_queries, n=params.num_data):
            results = solve()
        elapsed_ms = (time.perf_counter() - t0) * 1e3
    if jax.process_index() == 0:
        out.write(format_results(results, debug=engine.config.debug))
        err.write(f"Time taken: {int(round(elapsed_ms))} ms\n")
    return results


def make_global_dataset(mesh: jax.sharding.Mesh, local_attrs: np.ndarray,
                        local_labels: np.ndarray, local_ids: np.ndarray):
    """Per-process data shards -> global arrays sharded on the "data" axis.

    Each process passes the rows it read (padded so every process
    contributes the same row count — jax.make_array_from_process_local_data
    requires uniform shards). Returns (attrs, labels, ids) global arrays
    placed P("data", None) / P("data") on the mesh.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh2 = NamedSharding(mesh, P(DATA_AXIS, None))
    sh1 = NamedSharding(mesh, P(DATA_AXIS))
    return (jax.make_array_from_process_local_data(sh2, local_attrs),
            jax.make_array_from_process_local_data(sh1, local_labels),
            jax.make_array_from_process_local_data(sh1, local_ids))


def make_global_queries(mesh: jax.sharding.Mesh, local_q_attrs: np.ndarray):
    """Per-process query shards -> a global array sharded on "query"."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    qsh = NamedSharding(mesh, P(QUERY_AXIS, None))
    return jax.make_array_from_process_local_data(qsh, local_q_attrs)
