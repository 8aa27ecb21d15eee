"""Scale + capacity proof for the extraction solve (round-3 review item 8).

Runs the fenced extract solve (sort epilogue included, bench.py scope) at
dataset rungs up to >= 4M x 10k x 64 — a shape whose dense (Q, N) f32
distance tile would be ~164 GB, an order of magnitude beyond HBM — and
records the device's peak_bytes_in_use alongside, proving the
O(N*A + Q*K) memory claim at a scale where the tile could never fit.
Also re-times the kcap=136 rung with the r4 tuned wide-k variant
(SCALE_r03: 160.8 ms with the one-size default).

Writes SCALE_r04.json. Env: BENCH_REPEATS (default 3), BENCH_OUT.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (_env_int, make_workload, stage_extract_inputs,  # noqa: E402
                   time_fenced_solve_ms)


def main() -> int:
    import jax

    from dmlp_tpu.engine.single import _extract_finalize
    from dmlp_tpu.ops.pallas_distance import pallas_interpret
    from dmlp_tpu.ops.pallas_extract import extract_topk, supports

    if pallas_interpret():
        print("needs the native TPU backend", file=sys.stderr)
        return 1

    repeats = _env_int("BENCH_REPEATS", 3)
    out_path = os.environ.get("BENCH_OUT", "SCALE_r04.json")
    nq, na = 10240, 64
    # Ordered by resident-set size: peak_bytes_in_use (where available) is
    # a process-lifetime high-water mark, so a later SMALLER rung would
    # otherwise report an earlier rung's peak as its own.
    rungs = [(204800, 40), (204800, 136), (1024000, 40), (4006400, 40)]

    runs = []
    for n, kc in rungs:
        inp = make_workload(n, nq, na, 32)
        q, d, lab, npad, qpad = stage_extract_inputs(inp)
        assert supports(qpad, npad, na, kc), (n, kc)

        def fn(q_, d_):
            od, oi, _ = extract_topk(q_, d_, n_real=n, kc=kc)
            return _extract_finalize(od, oi, lab, k=kc).dists

        ms = time_fenced_solve_ms(fn, q, d, repeats)

        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        dense_tile = qpad * npad * 4
        rec = {
            "num_data": n, "num_queries": nq, "num_attrs": na, "kcap": kc,
            "device_solve_ms": round(ms, 1),
            "qd_pairs_per_sec": round(n * nq / (ms / 1e3)),
            "peak_hbm_bytes": peak,
            "dense_tile_bytes": dense_tile,
            "peak_vs_dense_tile": (round(peak / dense_tile, 4)
                                   if peak else None),
        }
        runs.append(rec)
        print(json.dumps(rec), flush=True)
        del d, q, inp

    doc = {
        "note": "Fenced extract solve (sort epilogue included) vs dataset "
                "size; peak_bytes_in_use recorded per rung. The 4M rung's "
                "dense (Q, N) f32 tile would be ~164 GB — peak HBM stays "
                "at the O(N*A + Q*K) resident set, proving the capacity "
                "claim (survey §5.7) at a scale the tile could never fit. "
                "kcap=136 rung uses the r4 tuned wide-k variant "
                "(SCALE_r03 default-tuned: 160.8 ms).",
        "device": str(jax.devices()[0]),
        "runs": runs,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
