"""Wide-k extraction-kernel tuning sweep (round-3 review item 4).

SCALE_r03 showed the extraction solve degrading 1.64x from kcap 40 to 136
(98 -> 161 ms at 204800 x 10240 x 64) with the k=40-tuned defaults
(tq=128, tn=12800, ne=2, unroll=1). This sweep times the fenced kernel
(label-gather/sort epilogue included, like bench.py) across kcap in
{64, 136, 256, 512} x a variant grid over (tile_q, ne, unroll), so the
engine can pick per-kc tuning instead of one-size-fits-all.

Writes SWEEP_WIDEK_r{N}.jsonl: one schema-1 RunRecord (obs.run) per
line — config carries (kc, variant), metrics the fenced timing — plus a
final ``kind: "sweep_widek_summary"`` record with best_per_kc. Env:
BENCH_REPEATS (default 3), BENCH_OUT.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (_env_int, make_workload, stage_extract_inputs,  # noqa: E402
                   time_fenced_solve_ms)


def main() -> int:
    from dmlp_tpu.engine.single import _extract_finalize
    from dmlp_tpu.ops.pallas_distance import pallas_interpret
    from dmlp_tpu.ops.pallas_extract import BLOCK_ROWS, extract_topk

    if pallas_interpret():
        print("needs the native TPU backend", file=sys.stderr)
        return 1

    repeats = _env_int("BENCH_REPEATS", 3)
    out_path = os.environ.get("BENCH_OUT", "SWEEP_WIDEK_r06.jsonl")
    n, nq, na = 204800, 10240, 64
    inp = make_workload(n, nq, na, 32)
    q, d, lab, npad, qpad = stage_extract_inputs(inp)

    kcs = [int(x) for x in os.environ.get(
        "BENCH_KCS", "64,136,256,512").split(",")]
    # kc is padded to 8 by the engines; 136 is SCALE_r03's literal rung.
    variants = [
        {"tile_q": 128, "ne": 2, "unroll": 1},   # r3 default
        {"tile_q": 64, "ne": 2, "unroll": 1},
        {"tile_q": 256, "ne": 2, "unroll": 1},
        {"tile_q": 128, "ne": 4, "unroll": 1},
        {"tile_q": 64, "ne": 4, "unroll": 1},
        {"tile_q": 128, "ne": 2, "unroll": 2},
    ]
    if os.environ.get("BENCH_VARIANTS"):
        variants = json.loads(os.environ["BENCH_VARIANTS"])

    from dmlp_tpu.engine.single import round_up
    from dmlp_tpu.obs.run import RunRecord

    shape = {"num_data": n, "num_queries": nq, "num_attrs": na}
    if os.path.exists(out_path):
        os.remove(out_path)  # fresh sweep; append_jsonl accumulates below
    results = []
    for kc in kcs:
        kcp = round_up(kc, 8)
        for v in variants:
            def fn(q_, d_):
                od, oi, _ = extract_topk(q_, d_, n_real=n, kc=kcp,
                                         tile_n=BLOCK_ROWS, **v)
                return _extract_finalize(od, oi, lab, k=kcp).dists

            try:
                t0 = time.perf_counter()
                _ = float(fn(q, d)[0, 0])  # compile + fence
                compile_s = time.perf_counter() - t0
                metrics = {"ms": round(time_fenced_solve_ms(fn, q, d,
                                                            repeats), 1),
                           "compile_s": round(compile_s, 1)}
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                metrics = {"error": repr(e)[:200]}
            rec = {"kc": kcp, **v, **metrics}
            RunRecord(kind="sweep_widek", tool="tools/sweep_widek",
                      config={"kc": kcp, "variant": v, "shape": shape,
                              "repeats": repeats},
                      metrics=metrics).append_jsonl(out_path)
            print(json.dumps(rec), flush=True)
            results.append(rec)

    best = {}
    for rec in results:
        if "ms" in rec and rec["ms"] < best.get(rec["kc"], {}).get("ms", 1e18):
            best[rec["kc"]] = rec
    RunRecord(kind="sweep_widek_summary", tool="tools/sweep_widek",
              config={"shape": shape, "kcs": kcs},
              metrics={"best_per_kc": {str(k): v for k, v in best.items()}},
              ).append_jsonl(out_path)
    print(json.dumps({"best_per_kc": best}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
