#!/usr/bin/env python
"""Distributed-observability smoke: 2-process CPU cluster, traced end to end.

The `make obs-dist-smoke` driver. Spawns a real 2-process jax.distributed
(Gloo) CPU cluster running the contract entry point with ``--trace``
(tests/test_distributed_contract.py's spawn pattern), then:

- asserts process 0's stdout is byte-identical to the golden oracle's and
  carries the ``Time taken`` stderr line — tracing must not perturb the
  contract channels;
- merges the per-rank ``trace-rank<NN>.json`` files with
  tools/merge_traces.py (clock-sync alignment + per-rank span
  cross-check);
- validates the merged trace's structural contract with
  tools/check_trace.py --dist (distinct rank pids, metadata + clock-sync
  events, monotonic per-rank timestamps, dist.solve spans).

Some jax builds (including this container's) cannot run multi-process
computations on the CPU backend at all — the same root cause failing the
seed suite's 2-process contract tests. When the cluster dies with that
exact signature, the smoke falls back to EMULATED ranks: N independent
single-process contract runs, each writing its rank file via the
DMLP_TPU_TRACE_RANK override — the per-rank artifact/merge/validate
chain is then still exercised end to end (clearly labeled in the
output); the collective path itself is covered by the real-cluster form
wherever the backend supports it.

Usage: JAX_PLATFORMS=cpu python tools/obs_dist_smoke.py [--dir outputs/dist_obs]
Exit 0 on success.
"""

from __future__ import annotations

import argparse
import os
import shutil
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


#: the error signature of a jax build whose CPU backend cannot run
#: multi-process computations (the seed suite's 2-process contract
#: failures share this root cause) — detected here AND by
#: tests/test_obs_dist.py, which imports these helpers
MULTIPROC_UNSUPPORTED = "Multiprocess computations aren't implemented"


def cluster_env(devices_per_proc: int = 2) -> dict:
    """Subprocess environment for a virtual-CPU cluster rank (the
    test-suite recipe: pin the CPU platform and its device count)."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_proc}")
    return env


def spawn_traced_cluster(input_path: str, trace_dir: str, procs: int = 2,
                         timeout: float = 240.0,
                         devices_per_proc: int = 2):
    """Spawn a real ``procs``-rank jax.distributed (Gloo) CPU cluster
    running the traced contract entry point; returns the Popen list and
    their (stdout, stderr) pairs."""
    env = cluster_env(devices_per_proc)
    port = _free_port()
    ps = [subprocess.Popen(
        [sys.executable, "-m", "dmlp_tpu.distributed",
         "--input", str(input_path),
         "--coordinator", f"localhost:{port}",
         "--processes", str(procs), "--process-id", str(pid),
         "--trace", str(trace_dir)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, cwd=REPO)
        for pid in range(procs)]
    return ps, [p.communicate(timeout=timeout) for p in ps]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dir", default=os.path.join("outputs", "dist_obs"))
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=240.0)
    args = ap.parse_args(argv)

    from dmlp_tpu.golden.reference import knn_golden
    from dmlp_tpu.io.datagen import generate_input_text
    from dmlp_tpu.io.grammar import parse_input_text
    from dmlp_tpu.io.report import format_results

    trace_dir = os.path.abspath(args.dir)
    if os.path.isdir(trace_dir):
        shutil.rmtree(trace_dir)
    os.makedirs(trace_dir)

    text = generate_input_text(211, 23, 5, -4, 4, 1, 12, 4, seed=9)
    input_path = os.path.join(trace_dir, "smoke.in")
    with open(input_path, "w") as f:
        f.write(text)
    want = format_results(knn_golden(parse_input_text(text)))

    procs, outs = spawn_traced_cluster(input_path, trace_dir,
                                       procs=args.procs,
                                       timeout=args.timeout)
    errs = "\n".join(o[1].decode()[-2000:] for o in outs)
    if any(p.returncode != 0 for p in procs):
        if MULTIPROC_UNSUPPORTED not in errs:
            print(f"obs_dist_smoke: FAIL: a rank exited nonzero:\n{errs}",
                  file=sys.stderr)
            return 1
        # This jax build cannot run ANY multi-process computation on CPU
        # (the seed suite's 2-process contract tests fail the same way).
        # Emulate the ranks so the tracing/merge/validate chain is still
        # smoke-tested; the real-cluster form above runs wherever the
        # backend supports it.
        print("obs_dist_smoke: CPU backend lacks multi-process "
              "computations (known jax drift); falling back to "
              f"{args.procs} EMULATED single-process ranks")
        for pid in range(args.procs):
            e = dict(cluster_env(2), DMLP_TPU_TRACE_RANK=str(pid),
                     DMLP_TPU_TRACE_RANKS=str(args.procs))
            proc = subprocess.run(
                [sys.executable, "-m", "dmlp_tpu.distributed",
                 "--input", input_path, "--trace", trace_dir],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=e,
                cwd=REPO, timeout=args.timeout)
            if proc.returncode != 0:
                print(f"obs_dist_smoke: FAIL: emulated rank {pid} exited "
                      f"{proc.returncode}:\n"
                      f"{proc.stderr.decode()[-2000:]}", file=sys.stderr)
                return 1
            if proc.stdout.decode() != want:
                print(f"obs_dist_smoke: FAIL: emulated rank {pid} stdout "
                      "diverged from the golden oracle", file=sys.stderr)
                return 1
    else:
        if outs[0][0].decode() != want:
            print("obs_dist_smoke: FAIL: traced cluster stdout diverged "
                  "from the golden oracle", file=sys.stderr)
            return 1
        if "Time taken:" not in outs[0][1].decode():
            print("obs_dist_smoke: FAIL: contract stderr line missing",
                  file=sys.stderr)
            return 1
    print("obs_dist_smoke: contract channels ok (stdout golden-identical)")

    merged = os.path.join(trace_dir, "trace-merged.json")
    for tool_argv in (
            [sys.executable, os.path.join(REPO, "tools", "merge_traces.py"),
             trace_dir, "-o", merged],
            [sys.executable, os.path.join(REPO, "tools", "check_trace.py"),
             "--dist", merged, "--ranks", str(args.procs)]):
        proc = subprocess.run(tool_argv, cwd=REPO)
        if proc.returncode != 0:
            return proc.returncode

    # Straggler path: the checker's --json verdict must carry the
    # per-rank skew table the merge embedded (every rank present, skew
    # vs the across-rank median computed) — the smoke-level proof that
    # the straggler detector runs on every merged trace.
    import json
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_trace.py"),
         "--dist", merged, "--ranks", str(args.procs), "--json"],
        cwd=REPO, stdout=subprocess.PIPE)
    if proc.returncode != 0:
        return proc.returncode
    verdict = json.loads(proc.stdout.decode())
    straggler = verdict.get("straggler") or {}
    per_rank = straggler.get("per_rank") or {}
    if sorted(int(r) for r in per_rank) != list(range(args.procs)):
        print(f"obs_dist_smoke: FAIL: straggler skew table missing or "
              f"incomplete in the --json verdict: {straggler}",
              file=sys.stderr)
        return 1
    print(f"obs_dist_smoke: straggler skew table ok — "
          f"{ {r: row.get('skew_vs_median') for r, row in sorted(per_rank.items())} }")
    print(f"obs_dist_smoke: ok — {args.procs}-rank traced run merged and "
          f"validated under {trace_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
