"""Beyond-HBM capacity proof: solve a dataset LARGER than device memory.

The dense distance tile never materializes (O(N*A + Q*K) working
set). This tool proves the stronger streaming
claim — the long-context analog (survey §5.7) — by running the chunked
extract driver on a dataset whose f32 form EXCEEDS the chip's HBM: only
the in-flight chunks (bounded by engine.single.ChunkThrottle), the
queries, and the running (Q, K) lists are ever device-resident, so the
solve completes where any monolithic staging would OOM by construction.

Since ISSUE 13 the tool runs TWO arms and records ``scanned_bytes``
both ways: the dense arm (``DMLP_TPU_PRUNE=0``) streams every chunk;
the pruned arm lets the two-stage solve (ops.summaries) prove blocks
out of every top-k from resident summaries BEFORE their bytes move —
on a beyond-HBM corpus that is the difference between O(corpus) and
O(survivors) host->device traffic per solve. Both arms byte-identical
to each other and validated against the f64 oracle.

Shape (default): 72M x 64 f32 = 18.4 GB, ~1.09x HBM. Queries kept small
(2048) so the run is staging-bound, like a real larger-than-memory scan.
Data is generated directly as arrays (the text grammar at 64M rows is a
multi-GB file serving no purpose here); distribution matches the seeded
generator (uniform [0, 100], labels uniform 0..9).

``--cpu-smoke`` runs a small NORM-BANDED shape instead (blocks of
progressively offset coordinate bands, queries near band 0) so the
pruned-vs-dense scanned-bytes ratio is provable in CI on this CPU
container: the smoke FAILS unless the pruned arm scans < 0.5x the
dense bytes and both arms match the oracle checksum-for-checksum. The
full shape keeps the honest ``needs the native TPU backend`` bail-out.

Correctness: exact mode (f64 rescore + eps-hazard repair) end-to-end;
VALIDATE_QUERIES queries are solved by the vectorized f64 oracle and
diffed checksum-for-checksum, per arm.

Writes a schema RunRecord (obs.run) to $BENCH_OUT (default
outputs/CAPACITY_BEYOND_HBM.json). Env: CAP_NUM_DATA, CAP_NUM_QUERIES, CAP_VALIDATE
(default 8), BENCH_OUT.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _solve_arm(inp, cfg, prune: bool):
    """One arm: (results, engine, solve_s) under DMLP_TPU_PRUNE=1/0."""
    from dmlp_tpu.engine.single import SingleChipEngine
    prev = os.environ.get("DMLP_TPU_PRUNE")
    os.environ["DMLP_TPU_PRUNE"] = "1" if prune else "0"
    try:
        eng = SingleChipEngine(cfg)
        t0 = time.perf_counter()
        results = eng.run(inp)
        return results, eng, time.perf_counter() - t0
    finally:
        if prev is None:
            os.environ.pop("DMLP_TPU_PRUNE", None)
        else:
            os.environ["DMLP_TPU_PRUNE"] = prev


def main(argv=None) -> int:
    import numpy as np

    import jax

    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.golden.fast import knn_golden_fast
    from dmlp_tpu.io.grammar import KNNInput, Params, subset_queries
    from dmlp_tpu.ops.pallas_distance import pallas_interpret

    argv = list(sys.argv[1:] if argv is None else argv)
    cpu_smoke = "--cpu-smoke" in argv

    if not cpu_smoke and pallas_interpret():
        print("needs the native TPU backend", file=sys.stderr)
        return 1

    rng = np.random.default_rng(42)
    if cpu_smoke:
        # Small norm-banded shape: 8 blocks of 8192 rows, each band
        # offset by +60 per block so later blocks provably cannot hold
        # any near-band-0 query's top-k — the pruned arm must skip them.
        n = int(os.environ.get("CAP_NUM_DATA", 65_536))
        nq = int(os.environ.get("CAP_NUM_QUERIES", 256))
        nv = int(os.environ.get("CAP_VALIDATE", 8))
        na, k = 16, 32
        block = 8192
        out_path = os.environ.get("BENCH_OUT",
                                  "outputs/CAPACITY_PRUNE_SMOKE.json")
        data = rng.random((n, na), dtype=np.float32) * np.float32(10)
        for b in range(-(-n // block)):
            data[b * block:(b + 1) * block] += np.float32(60.0 * b)
        queries = rng.uniform(0, 10, (nq, na)).astype(np.float32)
        cfg = EngineConfig(dtype="float32", select="topk",
                           data_block=block)
    else:
        n = int(os.environ.get("CAP_NUM_DATA", 72_000_000))
        nq = int(os.environ.get("CAP_NUM_QUERIES", 2048))
        nv = int(os.environ.get("CAP_VALIDATE", 8))
        na, k = 64, 32
        out_path = os.environ.get(
            "BENCH_OUT", os.path.join("outputs",
                                      "CAPACITY_BEYOND_HBM.json"))
        # f32 directly (rng.random supports dtype; rng.uniform does not
        # and would materialize a 2x-size f64 intermediate): this IS the
        # staged form; f64 originals at this scale would double host
        # memory for no benefit (the rescore casts gathered rows only).
        data = rng.random((n, na), dtype=np.float32) * np.float32(100)
        queries = rng.uniform(0, 100, (nq, na)).astype(np.float32)
        # margin 64 (kcap 96): at 72M-row density the rank-32 distance
        # gaps approach the f32 quantum, and a deeper window keeps the
        # (exact) eps-hazard test clear of mass repairs.
        cfg = EngineConfig(dtype="float32", use_pallas=True, margin=64)

    dev = jax.devices()[0]
    hbm_bytes = 0
    try:
        stats = dev.memory_stats() or {}
        hbm_bytes = int(stats.get("bytes_limit", 0))
    except Exception:
        pass
    if not hbm_bytes:
        hbm_bytes = int(15.75 * 2**30)  # v5e, when memory_stats is absent

    t0 = time.perf_counter()
    labels = rng.integers(0, 10, n).astype(np.int32)
    ks = rng.integers(1, k + 1, nq).astype(np.int32)
    gen_s = time.perf_counter() - t0
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)

    if cpu_smoke:
        # Untimed warm solve so BOTH timed arms see warm jit caches —
        # the repo's A/B discipline: a dense-first cold run would give
        # the pruned arm every compile for free and overstate its wall
        # win. (The full beyond-HBM shape stays single-shot: one extra
        # dense sweep there costs tens of minutes of staging; its
        # gated claim is the scanned-bytes ratio, and the record says
        # so via wall_time_basis.)
        _solve_arm(inp, cfg, prune=False)
    arms = {}
    for name, prune in (("dense", False), ("pruned", True)):
        results, eng, solve_s = _solve_arm(inp, cfg, prune)
        arms[name] = {
            "results": results, "solve_s": solve_s,
            "repairs": eng.last_repairs,
            "prune": dict(eng.last_prune or {}),
            "select": eng._last_select,
            "phases_ms": {m: round(v, 1)
                          for m, v in eng.last_phase_ms.items()},
        }

    # Arms must agree checksum-for-checksum, and both must match the
    # oracle on the validated subset.
    t0 = time.perf_counter()
    cross = sum(a.checksum() != b.checksum()
                for a, b in zip(arms["dense"]["results"],
                                arms["pruned"]["results"]))
    vidx = np.linspace(0, nq - 1, nv).astype(np.int64)
    golden = knn_golden_fast(subset_queries(inp, vidx))
    mismatches = cross
    for arm in arms.values():
        mismatches += sum(arm["results"][int(q)].checksum() != g.checksum()
                          for q, g in zip(vidx, golden))
    validate_s = time.perf_counter() - t0

    sb_d = arms["dense"]["prune"].get("scanned_bytes", 0)
    sb_p = arms["pruned"]["prune"].get("scanned_bytes", 0)
    ratio = round(sb_p / sb_d, 4) if sb_d else None

    from dmlp_tpu.obs.run import RunRecord, round_from_name

    dataset_bytes = n * na * 4
    solve_s = arms["dense"]["solve_s"]
    rec = RunRecord(
        kind="capacity", tool="tools.capacity_beyond_hbm",
        config={"note": "Chunked solve with a pruned-vs-dense scan A/B: "
                        "the pruned arm proves blocks out of every "
                        "top-k from resident summaries before their "
                        "bytes move (ops.summaries); arms checksum-"
                        "identical and oracle-validated. cpu_smoke "
                        "uses a norm-banded corpus so the ratio is "
                        "provable in CI; the full beyond-HBM shape "
                        "needs the native TPU backend.",
                "cpu_smoke": cpu_smoke,
                "num_data": n, "num_queries": nq, "num_attrs": na,
                "kmax": k, "select": arms["dense"]["select"],
                "dataset_bytes_f32": dataset_bytes,
                "hbm_bytes": hbm_bytes},
        metrics={
            "dataset_vs_hbm": round(dataset_bytes / hbm_bytes, 3),
            # Honest timing basis: warmed sequential arms in cpu-smoke,
            # cold single-shot on the full shape — wall times are
            # context, the gated claim is the scanned-bytes ratio.
            "wall_time_basis": ("warmed_sequential" if cpu_smoke
                                else "cold_single_shot"),
            "repairs": arms["dense"]["repairs"],
            "gen_s": round(gen_s, 1),
            "solve_wall_s": round(solve_s, 1),
            "solve_wall_s_pruned": round(arms["pruned"]["solve_s"], 1),
            "qd_pairs_per_sec_wall": int(n * nq / max(solve_s, 1e-9)),
            "scanned_bytes_dense": int(sb_d),
            "scanned_bytes_pruned": int(sb_p),
            "scanned_bytes_ratio": ratio,
            "blocks_pruned": arms["pruned"]["prune"].get(
                "blocks_pruned", 0),
            "blocks_total": arms["pruned"]["prune"].get(
                "blocks_total", 0),
            "phases_ms": arms["dense"]["phases_ms"],
            "validated_queries": nv,
            "validate_mismatches": int(mismatches),
            "validate_s": round(validate_s, 1),
        },
        device=str(getattr(dev, "device_kind", dev.platform)),
        round=round_from_name(out_path))
    d = os.path.dirname(out_path)
    if d:
        os.makedirs(d, exist_ok=True)
    rec.write(out_path)
    print(rec.to_json())
    if mismatches:
        return 1
    if cpu_smoke and (ratio is None or ratio >= 0.5):
        print(f"cpu-smoke: pruned arm scanned {ratio}x the dense bytes "
              "(must be < 0.5 on the banded corpus)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
