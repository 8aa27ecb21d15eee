"""bf16-staged end-to-end benchmark (round-3 review item 5), interleaved A/B.

Staging attrs in bfloat16 halves the upload bytes. This tool measures
exact-mode (f64 host rescore -> checksum parity) f32-staged vs
bf16-staged runs INTERLEAVED (alternating order, so machine conditions
hit both equally), verifies both produce
IDENTICAL results query-for-query, and reports the bf16 tie-overflow
repair rate (bf16's coarser distances make boundary ties more frequent).

Writes a schema RunRecord (obs.run) to BENCH_BF16_r06.json — the
ledger-ingestible artifact form (python -m dmlp_tpu.report); the r04
ad-hoc shape is grandfathered. Env: BENCH_REPS (default 5), shape knobs
as in bench.py, BENCH_OUT.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _env_int, make_workload  # noqa: E402


def main() -> int:
    import jax

    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.engine.single import SingleChipEngine
    from dmlp_tpu.io.report import format_results

    num_data = _env_int("BENCH_NUM_DATA", 200_000)
    num_queries = _env_int("BENCH_NUM_QUERIES", 10_000)
    num_attrs = _env_int("BENCH_NUM_ATTRS", 64)
    k = _env_int("BENCH_K", 32)
    reps = _env_int("BENCH_REPS", 5)
    out_path = os.environ.get("BENCH_OUT", "BENCH_BF16_r06.json")

    inp = make_workload(num_data, num_queries, num_attrs, k)
    engines = {
        "f32": SingleChipEngine(EngineConfig(exact=True, dtype="float32",
                                             query_block=16384,
                                             use_pallas=True)),
        "bf16": SingleChipEngine(EngineConfig(exact=True, dtype="bfloat16",
                                              query_block=16384,
                                              use_pallas=True)),
    }
    names = list(engines)

    # Warmup (compile) + capture results for the parity check.
    results = {}
    for name in names:
        results[name] = engines[name].run(inp)
    parity = (format_results(results["f32"])
              == format_results(results["bf16"]))

    times: dict = {name: [] for name in names}
    repairs: dict = {name: [] for name in names}
    for rep in range(reps):
        order = names if rep % 2 == 0 else names[::-1]
        for name in order:
            t0 = time.perf_counter()
            engines[name].run(inp)
            times[name].append(round((time.perf_counter() - t0) * 1e3, 1))
            repairs[name].append(getattr(engines[name], "last_repairs", None))

    from dmlp_tpu.obs.run import RunRecord, current_device, round_from_name

    metrics = {"results_identical": bool(parity)}
    for name in names:
        metrics[f"{name}_median_ms"] = float(np.median(times[name]))
        metrics[f"{name}_min_ms"] = float(np.min(times[name]))
        metrics[f"{name}_times_ms"] = times[name]
        metrics[f"{name}_repairs"] = repairs[name]
        metrics[f"{name}_staged_attr_mb"] = round(
            (num_data + num_queries) * num_attrs
            * (2 if name == "bf16" else 4) / 1e6, 1)
    RunRecord(
        kind="bench", tool="tools.bench_bf16_staging",
        config={"note": "Exact-mode (f64 host rescore) end-to-end "
                        "engine.run(), f32-staged vs bf16-staged, "
                        "interleaved A/B reps (alternating order); "
                        "results_identical verifies "
                        "query-for-query parity, repairs counts "
                        "oracle-repair fallbacks.",
                "num_data": num_data, "num_queries": num_queries,
                "num_attrs": num_attrs, "k": k, "reps": reps,
                "use_pallas": True,
                "platform": jax.devices()[0].platform,
                "select": {n: getattr(engines[n], "_last_select", None)
                           for n in names}},
        metrics=metrics, device=current_device(),
        round=round_from_name(out_path)).write(out_path)
    print(json.dumps({n: {"median_ms": float(np.median(times[n])),
                          "min_ms": float(np.min(times[n]))}
                      for n in names} | {"identical": bool(parity)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
