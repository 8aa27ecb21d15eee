"""Interleaved A/B mode comparison: single vs sharded vs ring (one chip).

Round-3 review item 2: measuring each mode in its own block lets drift
in machine conditions masquerade as a mode difference. This tool
measures the modes INTERLEAVED (A/B/C/A/B/C..., rotating the starting
mode each rep) and reports per-mode median + spread, so slow intervals
hit every mode equally.

Writes one schema-1 RunRecord (obs.run) to BENCH_MODES_r{N}.json — the
versioned envelope every migrated emitter shares; the interleaved-rep
methodology and per-mode payload live in ``config``/``metrics``. Env:
BENCH_REPS (default 5), BENCH_NUM_DATA / BENCH_NUM_QUERIES /
BENCH_NUM_ATTRS / BENCH_K as in bench.py, BENCH_OUT.
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

    from dmlp_tpu.cli import make_engine
    from dmlp_tpu.config import EngineConfig

    num_data = _env_int("BENCH_NUM_DATA", 200_000)
    num_queries = _env_int("BENCH_NUM_QUERIES", 10_000)
    num_attrs = _env_int("BENCH_NUM_ATTRS", 64)
    k = _env_int("BENCH_K", 32)
    reps = _env_int("BENCH_REPS", 5)
    # r06+: RunRecord schema (the r04 artifact keeps its grandfathered
    # ad-hoc shape; this tool stopped emitting it)
    out_path = os.environ.get("BENCH_OUT", "BENCH_MODES_r06.json")

    inp = make_workload(num_data, num_queries, num_attrs, k)
    modes = ["single", "sharded", "ring"]
    engines = {}
    for m in modes:
        cfg = EngineConfig(mode=m, exact=False, query_block=16384,
                           use_pallas=True)
        engines[m] = make_engine(cfg)

    # Warmup (compile) every mode before ANY timed rep, so compilation
    # never lands inside a measurement.
    compile_ms = {}
    for m in modes:
        t0 = time.perf_counter()
        engines[m].run(inp)
        compile_ms[m] = round((time.perf_counter() - t0) * 1e3, 1)

    times: dict = {m: [] for m in modes}
    for rep in range(reps):
        order = modes[rep % len(modes):] + modes[:rep % len(modes)]
        for m in order:
            t0 = time.perf_counter()
            engines[m].run(inp)
            times[m].append(round((time.perf_counter() - t0) * 1e3, 1))

    runs = []
    for m in modes:
        ts = np.asarray(times[m])
        runs.append({
            "mode": m,
            "median_ms": float(np.median(ts)),
            "min_ms": float(ts.min()),
            "max_ms": float(ts.max()),
            "times_ms": times[m],
            "select": getattr(engines[m], "_last_select", None),
            "phases_ms": {kk: round(v, 1) for kk, v in
                          getattr(engines[m], "last_phase_ms", {}).items()},
            "compile_plus_first_run_ms": compile_ms[m],
        })
    from dmlp_tpu.obs.run import RunRecord
    RunRecord(
        kind="bench_modes", tool="tools/bench_modes_ab",
        config={
            "shape": {"num_data": num_data, "num_queries": num_queries,
                      "num_attrs": num_attrs, "k": k},
            "platform": jax.devices()[0].platform,
            "device": str(jax.devices()[0]),
            "n_devices": len(jax.devices()),
            "interleaved_reps": reps,
            "use_pallas": True,
        },
        metrics={
            "note": "Interleaved A/B/C reps (rotating start), per-mode "
                    "median + spread — machine conditions hit every "
                    "mode equally. 1-device mesh for sharded/ring "
                    "unless more chips exist; end-to-end engine.run() "
                    "wall time (fast mode).",
            "runs": runs,
        },
    ).write(out_path)
    print(json.dumps({m: {"median_ms": r["median_ms"],
                          "spread": [r["min_ms"], r["max_ms"]]}
                      for m, r in zip(modes, runs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
