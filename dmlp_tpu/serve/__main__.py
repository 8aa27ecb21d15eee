"""``python -m dmlp_tpu.serve`` — the resident serving daemon CLI.

Usage::

    python -m dmlp_tpu.serve --corpus FILE [--port 0]
        [--capacity ROWS] [--max-k K] [--max-batch-queries N]
        [--max-queue-queries N] [--tick-ms MS] [--gate-carry on|off]
        [--hbm-budget BYTES|auto] [--pallas] [--select auto|...]
        [--dtype auto|float32|bfloat16] [--score l2|ip|cosine] [--data-block N]
        [--warm-buckets NQxK,NQxK,...] [--compile-cache DIR]
        [--telemetry FILE] [--telemetry-port PORT] [--record FILE]
        [--snapshot-every-s S] [--ready-file PATH] [--faults FILE]

The corpus file is the standard input grammar; its data section
becomes the resident corpus, its query section seeds the warm-up
buckets. The daemon prints ``dmlp_tpu.serve: ready port=P`` on stderr
(and writes ``--ready-file``) once every warm bucket is compiled, then
serves until SIGTERM / an in-band ``drain`` op — which finishes
in-flight micro-batches, flushes the final telemetry snapshot and
serve RunRecord, and exits 0 with no flight dump.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional, Sequence, Tuple


def _parse_warm_buckets(spec: str) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            nq, k = part.lower().split("x")
            out.append((int(nq), int(k)))
        except ValueError:
            raise SystemExit(
                f"--warm-buckets entries are NQxK, got {part!r}")
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    from dmlp_tpu.config import SCORES     # (imports no jax)
    p = argparse.ArgumentParser(prog="dmlp_tpu.serve",
                                description=__doc__)
    p.add_argument("--corpus", required=True,
                   help="input-grammar file; data section = resident "
                        "corpus, query section = warm-up shapes")
    p.add_argument("--port", type=int, default=0,
                   help="TCP port (0 = ephemeral; announced on stderr "
                        "and in --ready-file)")
    p.add_argument("--capacity", type=int, default=None,
                   help="ingest ceiling in rows (default: corpus rows "
                        "rounded to the next power of two)")
    p.add_argument("--max-k", type=int, default=None,
                   help="largest per-query k admitted (default: the "
                        "engine's serving cap)")
    p.add_argument("--max-batch-queries", type=int, default=1024)
    p.add_argument("--max-queue-queries", type=int, default=4096)
    p.add_argument("--tick-ms", type=float, default=2.0,
                   help="micro-batch coalescing tick")
    p.add_argument("--gate-carry", choices=["on", "off"], default="on",
                   help="cross-request fused-gate warm-up (hot-block "
                        "fold ordering); results are byte-identical "
                        "either way")
    p.add_argument("--hbm-budget", default="auto",
                   help="admission memory budget in bytes ('auto' = "
                        "backend bytes_limit when reported, else "
                        "memory shedding off)")
    p.add_argument("--pallas", action="store_true",
                   help="extract-kernel resident path where supported")
    p.add_argument("--select", default="auto",
                   choices=["auto", "sort", "topk", "seg", "extract"])
    p.add_argument("--dtype", default="auto",
                   choices=["auto", "float32", "bfloat16"])
    p.add_argument("--precision", default="auto",
                   choices=["auto", "f32", "bf16"],
                   help="first-pass dot precision for the extract-path "
                        "kernels; bf16 widens candidate windows by the "
                        "analytic lowp_eps bound and keeps responses "
                        "byte-identical via the f64 rescore + repair "
                        "(plan frozen at startup; $DMLP_TPU_PRECISION "
                        "=f32 is the live kill switch). Fleet replicas "
                        "inherit this through --spawn-flags.")
    p.add_argument("--boundary-retry", choices=["on", "off"], default="on",
                   help="repair a query the boundary-hazard test flags "
                        "on the device first (one more fold of the "
                        "resident stack at a 512-slot window) and on "
                        "the host only what that does not clear; off = "
                        "the host oracle alone; answers are identical "
                        "either way")
    p.add_argument("--score", choices=list(SCORES), default="l2",
                   help="what the corpus is ranked by: l2 = smallest "
                        "squared Euclidean distance; ip = LARGEST inner "
                        "product (s descending, larger id first on "
                        "ties; 'dists' then carries s itself); cosine = "
                        "LARGEST q.x / (|q||x|), 0 against a zero vector "
                        "(same order; 'dists' carries the angular "
                        "distance 1 - s, ascending). The extract path "
                        "(--pallas, more than 8192 rows a chip or --select "
                        "extract), on one chip or with --mesh, has the ip "
                        "and cosine forms; a k past one kernel pass, the "
                        "streaming select and the mesh's monolithic stream "
                        "path refuse them by name")
    p.add_argument("--data-block", type=int, default=None)
    p.add_argument("--warm-buckets", default=None, metavar="NQxK,...",
                   help="extra shape buckets to compile before ready")
    p.add_argument("--mesh", default=None, metavar="RxC",
                   help="serve MESH-RESIDENT: hold the corpus sharded "
                        "across an RxC device mesh "
                        "(dmlp_tpu.fleet.mesh_engine; per-shard "
                        "resident buffers, allgather/ring merge as the "
                        "micro-batch epilogue)")
    p.add_argument("--mesh-merge",
                   choices=["allgather", "ring", "auto"],
                   default="allgather",
                   help="candidate-merge collective for --mesh "
                        "('auto' hands the cross-shard merge to the "
                        "GSPMD partitioner — engine.auto's merge "
                        "point as the micro-batch epilogue)")
    p.add_argument("--compile-cache", metavar="DIR", default=None,
                   help="persistent XLA compilation cache dir; "
                        "default <checkout>/.jax_cache, and "
                        "$JAX_COMPILATION_CACHE_DIR, when set, "
                        "wins over both (utils.compile_cache)")
    p.add_argument("--telemetry", metavar="FILE", default=None)
    p.add_argument("--telemetry-port", type=int, default=None,
                   metavar="PORT")
    p.add_argument("--record", metavar="FILE", default=None,
                   help="append serve RunRecords (ledger serve/ "
                        "series) here — final on drain, periodic with "
                        "--snapshot-every-s")
    p.add_argument("--snapshot-every-s", type=float, default=0.0)
    p.add_argument("--trace", metavar="FILE", default=None,
                   help="write a Chrome-trace JSON of request-phase "
                        "spans here on drain (rid-tagged; merge the "
                        "fleet's files with tools/merge_traces.py "
                        "--fleet)")
    p.add_argument("--slo", action="append", default=None,
                   metavar="SPEC",
                   help="declare an SLO objective (repeatable), e.g. "
                        "'serve.request_latency_ms p99 < 50 over 1m' "
                        "or 'serve.requests_completed/serve.admitted "
                        "availability > 0.999 over 5m'; evaluated "
                        "continuously (dmlp_tpu.obs.slo) — transitions "
                        "emit slo.alert trace/flight events and the "
                        "slo_* OpenMetrics family")
    p.add_argument("--ready-file", metavar="PATH", default=None)
    p.add_argument("--faults", metavar="FILE", default=None,
                   help="fault-injection schedule "
                        "(dmlp_tpu.resilience.inject; the serve.admit "
                        "oom fault is the injected memory squeeze)")
    args = p.parse_args(argv)

    # The actual DMLP_TPU_RACECHECK=1 install happens in
    # dmlp_tpu/serve/__init__.py — which `python -m dmlp_tpu.serve`
    # executes BEFORE this module, i.e. before the serving imports
    # create any locks. This call is an idempotent backstop for
    # embedders who import __main__.main directly;
    # DMLP_TPU_RACECHECK_OUT collects the verdict at drain (the
    # `make race-smoke` harness reads it).
    from dmlp_tpu.check import racecheck
    racecheck.install_from_env()

    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.io.grammar import parse_input
    from dmlp_tpu.resilience import inject as rs_inject
    from dmlp_tpu.serve.daemon import ServeDaemon
    from dmlp_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(args.compile_cache)
    budget = None
    if args.hbm_budget != "auto":
        budget = int(args.hbm_budget)
    with open(args.corpus) as f:
        corpus = parse_input(f)
    warm = None
    if args.warm_buckets:
        warm = _parse_warm_buckets(args.warm_buckets)
        from dmlp_tpu.serve.daemon import default_warm_buckets
        warm = default_warm_buckets(corpus) + warm
    config = EngineConfig(dtype=args.dtype, select=args.select,
                          use_pallas=args.pallas,
                          data_block=args.data_block,
                          precision=args.precision,
                          boundary_retry=args.boundary_retry == "on",
                          score=args.score)
    mesh_shape = None
    if args.mesh:
        try:
            r, c = args.mesh.lower().split("x")
            mesh_shape = (int(r), int(c))
        except ValueError:
            raise SystemExit(f"--mesh is RxC, got {args.mesh!r}")
    schedule = rs_inject.install_from_env(args.faults)
    daemon = ServeDaemon(
        corpus, config, port=args.port, capacity=args.capacity,
        gate_carry=args.gate_carry == "on", budget_bytes=budget,
        max_batch_queries=args.max_batch_queries,
        max_queue_queries=args.max_queue_queries, max_k=args.max_k,
        tick_s=args.tick_ms / 1e3, telemetry_path=args.telemetry,
        telemetry_port=args.telemetry_port, record_path=args.record,
        snapshot_every_s=args.snapshot_every_s, warm_buckets=warm,
        mesh_shape=mesh_shape, mesh_merge=args.mesh_merge,
        trace_path=args.trace, objectives=args.slo)
    try:
        daemon.start()
        sys.stderr.write(f"dmlp_tpu.serve: ready port={daemon.port} "
                         f"cold_start_compile_ms="
                         f"{daemon.engine.cold_start_compile_ms}\n")
        sys.stderr.flush()
        if args.ready_file:
            daemon.write_ready_file(args.ready_file)
        daemon.run_until_drained()
        racecheck.write_report_if_requested()
        sys.stderr.write("dmlp_tpu.serve: drained clean\n")
        return 0
    except Exception:
        if daemon.session is not None:
            from dmlp_tpu.obs import telemetry
            telemetry.dump_on_crash("serve_crash")
        raise
    finally:
        if schedule is not None:
            rs_inject.write_log_if_requested()
            rs_inject.uninstall()


if __name__ == "__main__":
    sys.exit(main())
