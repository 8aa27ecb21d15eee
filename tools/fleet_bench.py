#!/usr/bin/env python
"""Fleet load sweep: the p99-under-offered-load curve, one record a level.

Spawns N daemon replicas + the fleet router over a paced trace, then
replays the trace OPEN-LOOP (serve.client.replay_open_loop — requests
fire on the t_ms schedule regardless of completions, so daemon-side
queueing lands in the latency quantiles) at a sweep of offered-load
multipliers, ``--reps`` times per level. One RunRecord per level lands
in ``--metrics`` (kind "fleet", the level tag in ``config``); the
router's closed-loop snapshot record
rides along under level "router".

Not part of ``make test`` (``make fleet-smoke`` is the CI gate), and
not the performance record (``python3 -m benchmark.run``, PERF.md;
ROADMAP D1). On a TPU host drop JAX_PLATFORMS and pass
``--replica-flags "--pallas --select extract"``.

Usage::

    JAX_PLATFORMS=cpu python tools/fleet_bench.py \
        --metrics outputs/fleet_bench/FLEET_BENCH.jsonl \
        [--replicas 2] [--reps 3] \
        [--speeds 1,2,4,8] [--trace inputs/serve_trace2.jsonl] \
        [--mesh-replica] [--replica-flags "..."]
"""

from __future__ import annotations

import argparse
import os
import shlex
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dmlp_tpu.fleet import harness as fh     # noqa: E402
from dmlp_tpu.fleet import loadgen           # noqa: E402
from dmlp_tpu.io.grammar import parse_input_text  # noqa: E402
from dmlp_tpu.serve import client as sc      # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "inputs", "serve_trace2.jsonl"))
    ap.add_argument("--metrics", required=True,
                    help="append fleet RunRecords (JSONL) here")
    ap.add_argument("--out", default="outputs/fleet_bench",
                    help="scratch dir for corpus/ready/logs")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--mesh-replica", action="store_true",
                    help="make the last replica mesh-resident (2x1)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--speeds", default="1,2,4,8",
                    help="offered-load multipliers of the trace pace")
    ap.add_argument("--batch-cap", type=int, default=32)
    ap.add_argument("--replica-flags", default="",
                    help="extra daemon flags (quoted)")
    args = ap.parse_args(argv)

    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    metrics_path = os.path.abspath(args.metrics)
    speeds = [float(s) for s in args.speeds.split(",") if s.strip()]

    header, reqs = sc.load_trace(args.trace)
    corpus_txt = sc.corpus_text(header)
    corpus_path = os.path.join(out, "corpus.in")
    with open(corpus_path, "w") as f:
        f.write(corpus_txt)
    corpus = parse_input_text(corpus_txt)
    golden = sc.golden_reference(corpus, header, reqs)
    warm = ",".join(f"{q}x{k}" for q, k in
                    sc.warm_buckets_for_trace(reqs, args.batch_cap))
    flags = shlex.split(args.replica_flags)

    replicas = []
    router = None
    router_record = os.path.join(out, "ROUTER_RECORD.jsonl")
    if os.path.exists(router_record):
        os.remove(router_record)
    try:
        for i in range(args.replicas):
            rflags = list(flags)
            env = None
            if args.mesh_replica and i == args.replicas - 1:
                rflags += ["--mesh", "2x1"]
                env = {"XLA_FLAGS":
                       "--xla_force_host_platform_device_count=2"}
            replicas.append(fh.spawn_replica(
                corpus_path, out, f"replica_{i}", warm,
                batch_cap=args.batch_cap, flags=rflags,
                env_extra=env))
        for fp in replicas:
            fh.await_replica(fp)
        router = fh.spawn_router(out, replicas, record=router_record)
        print(f"fleet_bench: router port={router.ready['port']} over "
              f"{args.replicas} replicas; warming done")

        # Correctness gate before any timing claim: one closed-loop
        # replay must be byte-identical to the golden oracle.
        res = sc.replay(router.ready["port"], header, reqs,
                        connections=3)
        if sc.contract_text([r.get("checksums", []) for r in res]) != \
                sc.contract_text(golden):
            print("fleet_bench: FAIL: routed replay differs from the "
                  "golden oracle", file=sys.stderr)
            return 1

        recs = loadgen.run_levels(
            router.ready["port"], header, reqs, speeds=speeds,
            reps=args.reps, replicas=args.replicas,
            trace=os.path.basename(args.trace))
        for rec in recs:
            rec.append_jsonl(metrics_path)
            print(f"fleet_bench: {rec.config['level']}: offered "
                  f"{rec.metrics.get('offered_qps')} qps -> p99 "
                  f"{rec.metrics.get('p99_ms')} ms "
                  f"(p50 {rec.metrics.get('p50_ms')}, errors "
                  f"{rec.metrics.get('errors')})")
        fh.drain_fleet(router, replicas)
        # The router's own closed-loop record joins the same JSONL.
        if os.path.exists(router_record):
            with open(router_record) as f, \
                    open(metrics_path, "a") as g:
                g.write(f.read())
    finally:
        fh.kill_all(replicas + ([router] if router else []))
    print(f"fleet_bench: wrote {metrics_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
