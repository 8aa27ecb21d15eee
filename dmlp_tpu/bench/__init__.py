"""Differential correctness harness — the TPU-native run_bench.sh (reference L4).

Replaces SLURM + mpirun + stripped oracle binaries (run_bench.sh:77-162)
with: a config registry (configs 1-5, like the reference's hardcoded
hardware/input combos), seeded input regeneration (the canonical inputs are
missing upstream — survey §6), the portable golden oracle with output
caching (the analog of outputs/test_N.{out,err} caching at
run_bench.sh:79-84), checksum diffing, and the same compare_times report.

``python -m dmlp_tpu.bench 1|..|5|all`` answers "are the checksums right";
its ``Time taken`` comparison is the reference's report format, not a
performance record. Speed is measured on the chip by ``python3 -m
benchmark.run`` (PERF.md).
"""

from dmlp_tpu.bench.configs import BENCH_CONFIGS, BenchConfig  # noqa: F401
from dmlp_tpu.bench.harness import run_config  # noqa: F401
