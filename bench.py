"""Driver benchmark: times the TPU-native KNN solve, prints ONE JSON line.

Workload: the reference's headline benchmark shape — brute-force KNN
classification (survey §6). The timed region matches the reference's
(common.cpp:122-131 brackets Engine::KNN after stdin ingest): everything
the reference's timed call does — distribution (host staging + transfer,
the scatter analog), device solve, and result finalization — via the same
``engine.run()`` pipeline for every mode. Parsing/generation is outside;
compile is excluded via a warmup call (XLA compiles once per shape; the
reference pays no JIT either).

Baseline: a blocked NumPy (BLAS f32) implementation of the same solve on the
host CPU — the portable stand-in for the reference's CPU/MPI engine, whose
published numbers do not exist and whose binaries cannot run here (survey §6).
``vs_baseline`` is the speedup ratio baseline_ms / engine_ms (>1 = faster).

Env overrides: BENCH_NUM_DATA, BENCH_NUM_QUERIES, BENCH_NUM_ATTRS, BENCH_K,
BENCH_REPEATS, BENCH_MODE (single|sharded|ring).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def make_workload(num_data: int, num_queries: int, num_attrs: int, k: int,
                  seed: int = 42):
    """Synthetic workload with the generator's distribution
    (generate_input.py:13-21: uniform attrs, uniform labels, fixed seed) —
    built as arrays directly; text parsing is outside the timed region anyway.
    """
    rng = np.random.default_rng(seed)
    data_attrs = rng.uniform(0.0, 100.0, (num_data, num_attrs))
    query_attrs = rng.uniform(0.0, 100.0, (num_queries, num_attrs))
    labels = rng.integers(0, 10, num_data, dtype=np.int32)
    ks = np.full(num_queries, k, dtype=np.int32)
    from dmlp_tpu.io.grammar import KNNInput, Params
    return KNNInput(Params(num_data, num_queries, num_attrs), labels,
                    data_attrs, ks, query_attrs)


def time_baseline_ms(inp, k: int, sample_queries: int = 1024,
                     block: int = 256) -> float:
    """Blocked NumPy KNN solve time, measured on a query subsample and
    scaled linearly to the full query count (matmul cost is linear in Q) —
    reported as ``baseline_ms_est`` because of that extrapolation. The vote
    is a vectorized batched bincount, so the baseline is a fair BLAS
    implementation, not a Python-loop strawman."""
    d = inp.data_attrs.astype(np.float32)
    dn = (d * d).sum(axis=1)
    qs = min(sample_queries, inp.params.num_queries)
    q = inp.query_attrs[:qs].astype(np.float32)
    num_labels = int(inp.labels.max()) + 1 if inp.params.num_data else 1

    t0 = time.perf_counter()
    for q0 in range(0, qs, block):
        qb = q[q0:q0 + block]
        # In-place epilogue: the broadcast form's (b, N) temporaries cost
        # ~10x the sgemm at this shape (see golden.fast) — the baseline
        # should be the best honest CPU implementation, not a strawman.
        dist = qb @ d.T
        dist *= -2.0
        dist += (qb * qb).sum(axis=1)[:, None]
        dist += dn[None, :]
        idx = np.argpartition(dist, kth=min(k, dist.shape[1] - 1), axis=1)[:, :k]
        lab = inp.labels[idx]
        counts = np.zeros((lab.shape[0], num_labels), np.int64)
        rows = np.broadcast_to(np.arange(lab.shape[0])[:, None], lab.shape)
        np.add.at(counts, (rows, lab), 1)
        counts.argmax(axis=1)
    elapsed = (time.perf_counter() - t0) * 1e3
    return elapsed * (inp.params.num_queries / qs)


def stage_extract_inputs(inp):
    """Stage (queries, data, labels) padded to whole extract tiles on the
    device, fenced. Shared by bench.py and the tools/ sweep/scale harnesses
    so padding and staging scope can't silently diverge between artifacts."""
    import jax.numpy as jnp

    from dmlp_tpu.engine.single import round_up
    from dmlp_tpu.ops.pallas_extract import BLOCK_ROWS, QUERY_TILE

    n, a = inp.data_attrs.shape
    nq = inp.params.num_queries
    npad = round_up(n, BLOCK_ROWS)
    qpad = round_up(nq, QUERY_TILE)
    d = jnp.zeros((npad, a), jnp.float32).at[:n].set(
        jnp.asarray(inp.data_attrs, jnp.float32))
    q = jnp.zeros((qpad, a), jnp.float32).at[:nq].set(
        jnp.asarray(inp.query_attrs, jnp.float32))
    lab = jnp.asarray(inp.labels, jnp.int32)
    float(jnp.sum(d))  # fence staging
    return q, d, lab, npad, qpad


def time_fenced_solve_ms(fn, q, d, repeats: int) -> float:
    """Fenced repeat-timing of a jitted solve ``fn(q, d) -> (Q, K) dists``:
    compile + fence, warm the eager perturbation chain (its tiny kernels
    compile on first use), then time ``repeats`` chained dispatches
    bounded by a dependent scalar readback. Shared by bench.py and
    tools/."""
    r = fn(q, d)
    _ = float(r[0, 0])           # compile + fence
    r = fn(q + 0.0 * r[0, 0], d)
    _ = float(r[0, 0])           # warm the perturbation chain
    t0 = time.perf_counter()
    for _i in range(repeats):
        r = fn(q + 0.0 * r[0, 0], d)  # chain dependency
    _ = float(r[0, 0])
    return (time.perf_counter() - t0) / repeats * 1e3


def _time_extract_solve_ms(inp, repeats: int, use_pallas: bool):
    """Fenced on-chip time of the fused extraction solve (select="extract",
    ops.pallas_extract): one call over the whole padded dataset — the
    distance tile never reaches HBM. The timed region includes the
    label-gather + composite-sort epilogue (engine.single._extract_finalize)
    so the number is scope-comparable with the seg/topk streaming folds,
    which carry labels and merge inside the fold. None only when Pallas
    was not asked for; a shape the kernel cannot tile raises."""
    from dmlp_tpu.engine.single import _extract_finalize, round_up
    from dmlp_tpu.ops.pallas_extract import BLOCK_ROWS, QUERY_TILE, extract_topk
    from dmlp_tpu.ops.pallas_extract import supports as extract_supports

    n, a = inp.data_attrs.shape
    nq = inp.params.num_queries
    k = round_up(int(inp.ks.max()) + 8, 8)
    if not use_pallas:
        return None
    # Padding matches stage_extract_inputs (whole extraction blocks /
    # query tiles — awkward sizes otherwise tile degenerately,
    # config.resolve_granule).
    qpad, npad = round_up(nq, QUERY_TILE), round_up(n, BLOCK_ROWS)
    if not extract_supports(qpad, npad, a, k):
        raise ValueError(f"extract kernel cannot tile (qb={qpad}, "
                         f"b={npad}, a={a}, kc={k})")
    q, d, lab, npad, qpad = stage_extract_inputs(inp)

    def fn(q_, d_):
        od, oi, _ = extract_topk(q_, d_, n_real=n, kc=k)
        return _extract_finalize(od, oi, lab, k=k).dists

    return round(time_fenced_solve_ms(fn, q, d, repeats), 1)


def time_device_solve_ms(inp, repeats: int, use_pallas: bool) -> dict:
    """On-chip solve time alone: arrays pre-staged, chained dispatches,
    fenced by a dependent scalar readback. Reported alongside the
    end-to-end number: the decomposition is what shows where engineering
    effort lands.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from dmlp_tpu.engine.single import round_up
    from dmlp_tpu.ops.pallas_distance import _tile
    from dmlp_tpu.ops.topk import streaming_topk

    n, a = inp.data_attrs.shape
    nq = inp.params.num_queries
    k = round_up(int(inp.ks.max()) + 8, 8)
    out = {}
    selects = tuple(
        s for s in (t.strip() for t in os.environ.get(
            "BENCH_DEVICE_SOLVE_SELECTS", "extract,seg").split(","))
        if s in ("extract", "seg", "topk", "sort"))
    for select in selects:
        if select == "extract":
            ms = _time_extract_solve_ms(inp, repeats, use_pallas)
            if ms is not None:
                out["device_solve_ms_extract"] = ms
                # Which variant actually ran (tuner cache entry when one
                # exists for this device/shape/kc, else the heuristic) —
                # artifacts must say what they measured.
                from dmlp_tpu.ops.pallas_extract import (BLOCK_ROWS,
                                                         QUERY_TILE,
                                                         resolve_variant)
                out["extract_variant"] = resolve_variant(
                    k, round_up(n, BLOCK_ROWS), round_up(nq, QUERY_TILE),
                    a)
            continue
        pallas = use_pallas and select == "seg"
        granule = 1024 if pallas else 128
        npad = round_up(n, granule)
        qpad = round_up(nq, 1024)
        if select == "seg":
            # One chunk if the live (Q, B) f32 tile fits the HBM budget:
            # seg's selection + merge cost is ~independent of chunk size,
            # so fewer chunks amortize it (measured 395 -> ~245 ms at r3).
            dmax = max((9 << 30) // (qpad * 4), granule)
            dblock = _tile(npad, min(npad, dmax), granule)
        else:
            # topk/sort concat the whole (Q, B) tile into the merge, so
            # their live footprint is ~3x the tile — keep chunks small.
            dblock = _tile(npad, 51200, granule)
        d = jnp.zeros((npad, a), jnp.float32).at[:n].set(
            jnp.asarray(inp.data_attrs, jnp.float32))
        lab = jnp.full(npad, -1, jnp.int32).at[:n].set(jnp.asarray(inp.labels))
        ids = jnp.where(jnp.arange(npad) < n,
                        jnp.arange(npad, dtype=jnp.int32), -1)
        q = jnp.zeros((qpad, a), jnp.float32).at[:nq].set(
            jnp.asarray(inp.query_attrs, jnp.float32))
        fn = jax.jit(functools.partial(streaming_topk, k=k,
                                       data_block=dblock, select=select,
                                       use_pallas=pallas))
        float(jnp.sum(d))  # fence staging
        r = fn(q, d, lab, ids)
        _ = float(r.dists[0, 0])  # compile + fence
        # Warm the perturbation chain too: `q + 0.0 * r.dists[0, 0]` is
        # eager op-by-op dispatch whose tiny kernels compile on first use,
        # which inflated the round-2 number (first call 1692 ms, repeats
        # ~400).
        r = fn(q + 0.0 * r.dists[0, 0], d, lab, ids)
        _ = float(r.dists[0, 0])  # fence warmup
        t0 = time.perf_counter()
        for _i in range(repeats):
            r = fn(q + 0.0 * r.dists[0, 0], d, lab, ids)  # chain dependency
        _ = float(r.dists[0, 0])  # fence
        out[f"device_solve_ms_{select}"] = round(
            (time.perf_counter() - t0) / repeats * 1e3, 1)
    return out


def time_engine_ms(inp, mode: str, repeats: int):
    """Median engine.run() wall time, plus a record of which code path
    actually ran (select strategy, pallas on/off, phase breakdown) — the
    round-1 bench silently fell back off the fused path and the JSON gave
    no way to see it."""
    from dmlp_tpu.cli import make_engine
    from dmlp_tpu.config import EngineConfig

    from dmlp_tpu.ops.pallas_distance import pallas_interpret
    use_pallas = os.environ.get("BENCH_PALLAS", "1") == "1"
    exact = os.environ.get("BENCH_EXACT", "0") == "1"
    # BENCH_DTYPE=bfloat16 stages attrs in bf16 — halves the upload
    # bytes; pair with BENCH_EXACT=1
    # for checksum parity (f64 host rescore; tie-overflow repairs are
    # reported in path.repairs).
    dtype = os.environ.get("BENCH_DTYPE", "float32")
    # query_block 16384 lets the pipelined driver fold every query block in
    # one dispatch per chunk (the HBM tile budget still caps the live tile).
    cfg = EngineConfig(mode=mode, exact=exact, dtype=dtype,
                       query_block=16384, use_pallas=use_pallas)
    engine = make_engine(cfg)

    run = engine.run  # same pipeline for every mode -> comparable numbers
    run(inp)  # warmup: compile + first dispatch
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        run(inp)
        times.append((time.perf_counter() - t0) * 1e3)
    path = {
        "select": getattr(engine, "_last_select", cfg.select),
        "use_pallas": use_pallas,
        "pallas_interpret": pallas_interpret(),
        "exact": exact,
        "dtype": cfg.resolve_dtype(),
        "repairs": getattr(engine, "last_repairs", None),
        "phases_ms": {name: round(ms, 1) for name, ms in
                      getattr(engine, "last_phase_ms", {}).items()},
    }
    return float(np.median(times)), path


def main() -> int:
    num_data = _env_int("BENCH_NUM_DATA", 200_000)
    num_queries = _env_int("BENCH_NUM_QUERIES", 10_000)
    num_attrs = _env_int("BENCH_NUM_ATTRS", 64)
    k = _env_int("BENCH_K", 32)
    repeats = _env_int("BENCH_REPEATS", 3)
    mode = os.environ.get("BENCH_MODE", "single")

    from dmlp_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()  # before any compile

    if mode == "train":
        from dmlp_tpu.train.bench import train_bench
        print(json.dumps(train_bench()))
        return 0

    inp = make_workload(num_data, num_queries, num_attrs, k)
    engine_ms, path = time_engine_ms(inp, mode, repeats)
    if os.environ.get("BENCH_DEVICE_SOLVE", "1") == "1":
        path["phases_ms"].update(
            time_device_solve_ms(inp, repeats, path["use_pallas"]))
    baseline_ms = time_baseline_ms(inp, k)

    pairs_per_s = num_data * num_queries / (engine_ms / 1e3)
    out = {
        "metric": "knn_solve_ms",
        "value": round(engine_ms, 3),
        "unit": "ms",
        "vs_baseline": round(baseline_ms / engine_ms, 3),
        "baseline_ms_est": round(baseline_ms, 1),
        # What the baseline IS (round-4 review weak #4: the bare ratio invited
        # over-reading): a measured same-host BLAS argpartition KNN solve,
        # query-subsampled and linearly extrapolated — NOT the reference's
        # MPI binaries (for those see vs_reference_binary below).
        "baseline_kind": "host_cpu_blas_knn_extrapolated",
        "qd_pairs_per_sec": round(pairs_per_s),
        "shape": {"num_data": num_data, "num_queries": num_queries,
                  "num_attrs": num_attrs, "k": k, "mode": mode},
        "path": path,
    }
    # MEASURED reference-binary comparison, when a capture exists for this
    # shape (tools/capture_oracle.sh; bench_4's 200k x 10k x 64 config).
    # NOT an exact workload match: input3's per-query k is uniform in
    # [1, 32] while this bench fixes k=32 for EVERY query, so the engine
    # side solves the strictly harder workload and the multiple below is
    # conservative (ADVICE r5). The harness config 1-4 path compares
    # sha256-pinned identical inputs; this one trades that exactness for
    # a same-shape annotation. Still the real thing the estimated ratio
    # above is not: the reference's own stripped engine, run in THIS
    # container via isolated-singleton Open MPI, checksum-parity-verified
    # against this framework (oracle_capture/ORACLE_GOLDEN.json,
    # tools/oracle_diff.py).
    if (num_data, num_queries, num_attrs, k) == (200_000, 10_000, 64, 32):
        from dmlp_tpu.bench.harness import reference_binary_fields
        out.update(reference_binary_fields(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "oracle_capture", "ORACLE_GOLDEN.json"),
            4, engine_ms))
    # Promote the fenced on-chip number: `value` includes host<->device
    # transfers; the device solve is the architecture-bound metric.
    dev = {k_: v for k_, v in path["phases_ms"].items()
           if k_.startswith("device_solve_ms_")}
    if dev:
        out["device_solve_ms"] = min(dev.values())
        out["device_qd_pairs_per_sec"] = round(
            num_data * num_queries / (out["device_solve_ms"] / 1e3))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
