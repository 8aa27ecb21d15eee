"""Run the full bench harness (configs 1-5) and record HARNESS_r{N}.json.

Every config's checksum PASS/FAIL + oracle/engine times, with the
engine path flags the config pinned (use_pallas/select), run on
whatever platform the environment provides (the single-chip configs on
the caller's platform; a virtual CPU mesh for the mesh/multi-process
configs, which declare it in configs.py).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlp_tpu.bench.configs import BENCH_CONFIGS  # noqa: E402
from dmlp_tpu.bench.harness import run_config  # noqa: E402


def main() -> int:
    args = [a for a in sys.argv[1:] if a != "--force-oracle"]
    force = "--force-oracle" in sys.argv[1:]  # re-time the oracle (e.g.
    # after oracle-speed changes) instead of reusing the cached .err
    round_tag = args[0] if args else "r03"
    results = []
    for cid, cfg in sorted(BENCH_CONFIGS.items()):
        # reps=3 + median (the recorded artifact keeps all rep times).
        res = run_config(cid, base_dir=".", timeout_s=580.0,
                         force_oracle=force, reps=3)
        res.update({"mode": cfg.mode, "use_pallas": cfg.use_pallas,
                    "select": cfg.select, "procs": cfg.procs,
                    "virtual_devices": cfg.virtual_devices,
                    "shape": [cfg.num_data, cfg.num_queries, cfg.num_attrs]})
        results.append(res)
        print(json.dumps(res), flush=True)
    ok = all(r["checksums_match"] for r in results)
    with open(f"HARNESS_{round_tag}.json", "w") as f:
        json.dump({"all_pass": ok, "configs": results}, f, indent=1)
    print(f"HARNESS_{round_tag}.json written, all_pass={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
