"""Drop-in engine CLI: reads the input grammar on stdin, writes results.

The TPU-native equivalent of ``mpirun ./engine < input`` (reference
common.cpp:81-135 + run_bench.sh:82-84): stdout carries per-query results
(checksums, or the -DDEBUG listing with ``--debug``), stderr carries the
``Time taken: <ms> ms`` contract line. No mpirun: one process drives the
device mesh.

Observability (dmlp_tpu.obs) is opt-in and leaves both contract channels
byte-identical: ``--trace FILE`` writes a Perfetto-loadable span trace,
``--metrics FILE`` appends JSONL records whose final summary carries XLA
cost-analysis counters (or an explicit ``counters_unavailable`` marker)
and collective-traffic accounting; ``--counters`` prints a roofline
summary to stderr after the contract line.

Usage::

    python -m dmlp_tpu [--mode single|sharded|ring|auto] [--debug] [--fast]
                       [--engine jax|golden|auto] [--score l2|ip|cosine]
                       [--phase-times]
                       [--compile-cache DIR] [--hlo-report FILE]
                       [--trace FILE] [--metrics FILE] [--counters] < input.in
"""

from __future__ import annotations

import argparse
import sys
from typing import IO, Optional, Sequence

from dmlp_tpu.config import SCORES, EngineConfig
from dmlp_tpu.io.grammar import parse_input
from dmlp_tpu.io.report import format_results
from dmlp_tpu.obs.trace import span as obs_span
from dmlp_tpu.utils.timing import EngineTimer


def parse_mesh_arg(parser, value):
    """Validate an R,C mesh flag (argparse usage error, not a traceback)."""
    if not value:
        return None
    parts = value.split(",")
    if len(parts) != 2 or not all(p.strip().lstrip("-").isdigit()
                                  for p in parts):
        parser.error(f"--mesh expects R,C (two integers), got {value!r}")
    r, c = int(parts[0]), int(parts[1])
    if r <= 0 or c <= 0:
        parser.error(f"--mesh axes must be positive, got {value!r}")
    return (r, c)


def make_engine(config: EngineConfig):
    """Engine registry (lazy imports keep CLI start light). An explicit
    mesh_shape that needs more devices than this host has is an error
    (parallel.mesh.make_mesh raises): a run on another mesh than the one
    asked for is not the run that was asked for."""
    if config.mode == "single":
        from dmlp_tpu.engine.single import SingleChipEngine
        return SingleChipEngine(config)
    if config.mode in ("sharded", "ring", "auto"):
        if config.mode == "sharded":
            from dmlp_tpu.engine.sharded import ShardedEngine as cls
        elif config.mode == "auto":
            from dmlp_tpu.engine.auto import AutoShardedEngine as cls
        else:
            from dmlp_tpu.engine.ring import RingEngine as cls
        return cls(config)
    raise ValueError(f"unknown mode {config.mode!r}")


def _emit_metrics(path: str, args, inp, timer: EngineTimer, phase_ms: dict,
                  counters: Optional[dict], comms: Optional[dict],
                  extract_impl: Optional[str] = None,
                  mem_model: Optional[dict] = None,
                  prune: Optional[dict] = None,
                  precision: Optional[dict] = None,
                  engine=None) -> None:
    """Append per-phase records + one run summary to the metrics JSONL.

    The summary is the contract record: it always carries a ``counters``
    block — either cost-analysis flops/bytes or the explicit
    ``counters_unavailable`` marker — never silence. A device solve also
    says where it ran (``device``, obs.run.device_stamp) and what the
    persistent compile cache did (``compile_cache``)."""
    from dmlp_tpu.obs.run import SCHEMA_VERSION, device_stamp
    from dmlp_tpu.utils import compile_cache
    from dmlp_tpu.utils.metrics_log import MetricsLogger

    with MetricsLogger(path=path) as mlog:
        for name, ms in phase_ms.items():
            mlog.log(event="phase", name=name, ms=round(ms, 3))
        summary = {
            "event": "summary", "schema": SCHEMA_VERSION,
            "mode": args.mode, "engine": args.engine,
            "exact": not args.fast,
            "elapsed_ms": round(timer.elapsed_ms, 3),
            "num_data": inp.params.num_data,
            "num_queries": inp.params.num_queries,
            "num_attrs": inp.params.num_attrs,
            "parser": inp.parser,
            "counters": counters if counters is not None
            else {"counters_unavailable": True},
        }
        if engine is not None:
            summary["device"] = device_stamp(engine)
            summary["compile_cache"] = compile_cache.stats()
        if comms is not None:
            summary["comms"] = comms
        if extract_impl is not None:
            # Which top-k kernel the solve actually dispatched ("fused"
            # | "extract").
            summary["extract_impl"] = extract_impl
        if mem_model is not None:
            # The analytic peak-HBM model + measured watermark
            # reconcile (obs.memwatch) — the mem block carries the
            # explicit mem_stats_unavailable marker where the backend
            # reports no memory, never silence.
            summary["mem"] = mem_model
        if prune is not None:
            # Scanned-bytes + prune accounting of the pruned two-stage
            # solve (ops.summaries.note_scan) — `make prune-smoke`
            # reads these per arm.
            summary["prune"] = prune
        if precision is not None:
            # First-pass precision record (engine.last_precision:
            # active/configured precision, kcap, window inflation) —
            # `make precision-smoke` refuses a vacuous (never-cast-bf16)
            # arm by it and asserts the inflation is visible.
            summary["precision"] = precision
        # Recovery is never silent: when the resilience layer did
        # anything (or a fault schedule was installed, even if nothing
        # fired), the summary carries the counters the chaos harness
        # asserts against.
        from dmlp_tpu.resilience import inject as rs_inject
        from dmlp_tpu.resilience import stats as rs_stats
        if rs_stats.any_activity() or rs_inject.active() is not None:
            summary["resilience"] = rs_stats.snapshot()
        mlog.log(**summary)


def _emit_counters_stderr(counters: Optional[dict], elapsed_ms: float,
                          stderr: IO) -> None:
    """The --counters human summary (after the contract line)."""
    if not counters or counters.get("counters_unavailable"):
        stderr.write("counters: unavailable (no analyzable dispatches "
                     "on this backend)\n")
        return
    from dmlp_tpu.obs.counters import roofline
    stderr.write(f"counters: flops={counters['flops']:.4e} "
                 f"hbm_bytes={counters['bytes_accessed']:.4e} "
                 f"dispatches={counters['dispatches_recorded']}\n")
    rl = roofline(counters["flops"], counters["bytes_accessed"],
                  elapsed_ms / 1e3)
    if "achieved_flops_per_s" in rl:
        line = f"roofline: {rl['achieved_flops_per_s']:.4e} FLOP/s achieved"
        if "utilization_vs_peak" in rl:
            line += (f", {rl['utilization_vs_peak'] * 100:.3f}% of "
                     f"{rl['peak_flops_per_chip']:.3g} peak")
        if "arithmetic_intensity" in rl:
            line += f", {rl['arithmetic_intensity']:.2f} FLOP/B"
        stderr.write(line + "\n")


def main(argv: Optional[Sequence[str]] = None,
         stdin: Optional[IO] = None,
         stdout: Optional[IO] = None,
         stderr: Optional[IO] = None) -> int:
    parser = argparse.ArgumentParser(prog="dmlp_tpu", description=__doc__)
    parser.add_argument("--mode", default="single",
                        choices=["single", "sharded", "ring", "auto"],
                        help="engine: 'auto' is the compiler-sharded "
                             "(GSPMD) engine — pure jit + NamedSharding "
                             "constraints instead of hand-rolled "
                             "collectives (engine.auto)")
    parser.add_argument("--mesh", default=None, metavar="R,C",
                        help="mesh shape (data x query axes) for the "
                             "sharded/ring/auto engines; default "
                             "auto-factorizes all devices "
                             "(MPI_Dims_create analog)")
    parser.add_argument("--engine", default="jax",
                        choices=["jax", "golden", "auto"],
                        help="'golden' runs the NumPy oracle (differential "
                             "testing reference); 'auto' is shorthand for "
                             "the jax engine with --mode auto")
    parser.add_argument("--debug", action="store_true",
                        help="human-readable output (the -DDEBUG build)")
    parser.add_argument("--fast", action="store_true",
                        help="skip the float64 host rescore (f32 ordering)")
    parser.add_argument("--device-full", action="store_true",
                        help="vote + report ordering on device too")
    parser.add_argument("--data-block", type=int, default=None,
                        help="data rows per inner step (default: per-select)")
    parser.add_argument("--query-block", type=int, default=1024)
    parser.add_argument("--dtype", default="auto",
                        choices=["auto", "float32", "bfloat16"],
                        help="staging/distance dtype; auto = bfloat16 on "
                             "TPU in exact mode (results unchanged: f64 "
                             "rescore), float32 elsewhere")
    parser.add_argument("--select", default="auto",
                        choices=["auto", "sort", "topk", "seg", "extract"],
                        help="device k-selection strategy")
    parser.add_argument("--score", default="l2", choices=list(SCORES),
                        help="what the corpus is ranked by: l2 = smallest "
                             "squared distance; ip = LARGEST inner product "
                             "(larger id first on ties; --debug prints the "
                             "products); cosine = LARGEST q.x / (|q||x|), 0 "
                             "against a zero vector (--debug prints the "
                             "angular distance 1 - s). The golden model "
                             "(--engine golden) and the serving daemon's "
                             "extract path (one chip or --mesh) have the ip "
                             "and cosine forms; the batch engines refuse "
                             "them by name")
    parser.add_argument("--phase-times", action="store_true",
                        help="per-phase ms breakdown on stderr (extension)")
    parser.add_argument("--pallas", action="store_true",
                        help="fused Pallas kernels (implies extract "
                             "selection on large inputs)")
    parser.add_argument("--profile", metavar="DIR", default=None,
                        help="write a jax.profiler trace of the solve to "
                             "DIR (survey §5.1 observability gap)")
    parser.add_argument("--trace", metavar="FILE", default=None,
                        help="write a Perfetto/Chrome-trace JSON of the "
                             "run's phase spans to FILE (obs.trace; load "
                             "at ui.perfetto.dev)")
    parser.add_argument("--metrics", metavar="FILE", default=None,
                        help="append JSONL metrics to FILE; the final "
                             "summary record carries cost-analysis "
                             "counters and collective-traffic accounting")
    parser.add_argument("--hlo-report", metavar="FILE", default=None,
                        help="append one kind='hlo' RunRecord to FILE: "
                             "the compiled program's collective "
                             "schedule, memory_analysis and cost "
                             "(obs.hlo HloReport per executable) plus "
                             "the three-way reconcile vs the analytic "
                             "comms/memwatch models and any trace; "
                             "implies dispatch recording. Contract "
                             "channels stay byte-identical")
    parser.add_argument("--counters", action="store_true",
                        help="print an XLA cost-analysis + roofline "
                             "summary to stderr (extension; implies "
                             "counter collection)")
    parser.add_argument("--warmup", action="store_true",
                        help="run the solve once untimed first, so the "
                             "timed region excludes XLA compilation (the "
                             "reference engine pays no JIT)")
    parser.add_argument("--compile-cache", metavar="DIR", default=None,
                        help="persistent XLA compilation cache dir; "
                             "default <checkout>/.jax_cache, and "
                             "$JAX_COMPILATION_CACHE_DIR, when set, "
                             "wins over both (utils.compile_cache)")
    parser.add_argument("--sanitize", action="store_true",
                        help="wrap the solve in "
                             "jax.transfer_guard('disallow') + "
                             "jax.checking_leaks (dmlp_tpu.check."
                             "sanitize): implicit host syncs and tracer "
                             "leaks raise instead of silently "
                             "serializing; $DMLP_TPU_SANITIZE=1 "
                             "enables it too. Output is byte-identical "
                             "on a clean program.")
    parser.add_argument("--faults", metavar="FILE", default=None,
                        help="deterministic fault-injection schedule "
                             "(JSON; dmlp_tpu.resilience.inject) — the "
                             "chaos harness's knob; $DMLP_TPU_FAULTS "
                             "sets it too. Recovery must keep stdout "
                             "byte-identical (make chaos-smoke)")
    parser.add_argument("--telemetry", metavar="FILE", default=None,
                        help="live telemetry (obs.telemetry): "
                             "periodically rewrite FILE as an "
                             "OpenMetrics snapshot (metrics registry + "
                             "device-memory watermarks + span "
                             "latencies), and arm the crash flight "
                             "recorder (FLIGHT_*.json next to FILE on "
                             "crash/fatal fault/SIGTERM). Contract "
                             "channels stay byte-identical")
    parser.add_argument("--telemetry-port", type=int, default=None,
                        metavar="PORT",
                        help="opt-in localhost HTTP endpoint serving "
                             "the OpenMetrics text on GET /metrics "
                             "(0 = ephemeral port; implies the "
                             "telemetry session)")
    args = parser.parse_args(argv)

    stdin = stdin or sys.stdin
    stdout = stdout or sys.stdout
    stderr = stderr or sys.stderr

    tracer = probe = telemetry_session = None
    from dmlp_tpu.resilience import inject as rs_inject
    from dmlp_tpu.resilience import stats as rs_stats
    rs_stats.reset()   # resets the registry's resilience.* counters too
    if args.telemetry or args.telemetry_port is not None:
        from dmlp_tpu.obs import telemetry
        telemetry_session = telemetry.start(path=args.telemetry,
                                            port=args.telemetry_port)
    if args.trace:
        from dmlp_tpu.obs import trace as obs_trace
        tracer = obs_trace.install(
            obs_trace.Tracer(annotate=bool(args.profile)))
    if args.metrics or args.counters or args.hlo_report:
        from dmlp_tpu.obs import counters as obs_counters
        probe = obs_counters.install()
    schedule = rs_inject.install_from_env(args.faults)
    try:
        return _run_cli(parser, args, stdin, stdout, stderr, tracer, probe)
    except Exception:
        # The whole reason the flight recorder exists: the last N
        # spans/events/metric deltas survive the crash as a
        # FLIGHT_*.json post-mortem artifact. Exception, NOT
        # BaseException: a usage error's SystemExit (parser.error) is
        # not a crash and must not leave a misleading FLIGHT artifact;
        # external kills are the SIGTERM handler's job.
        if telemetry_session is not None:
            from dmlp_tpu.obs import telemetry
            telemetry.dump_on_crash("crash")
        raise
    finally:
        if schedule is not None:
            rs_inject.write_log_if_requested()
            rs_inject.uninstall()
        if tracer is not None:
            from dmlp_tpu.obs import trace as obs_trace
            obs_trace.uninstall()
        if probe is not None:
            from dmlp_tpu.obs import counters as obs_counters
            obs_counters.uninstall()
        if telemetry_session is not None:
            telemetry_session.close()


def _run_cli(parser, args, stdin, stdout, stderr, tracer, probe) -> int:
    mesh_shape = parse_mesh_arg(parser, args.mesh)
    if args.engine == "auto":
        # --engine auto == the jax engine with the compiler-sharded
        # mode; keep the summary-record fields consistent.
        args.engine, args.mode = "jax", "auto"
    config = EngineConfig(mode=args.mode, debug=args.debug,
                          exact=not args.fast, data_block=args.data_block,
                          query_block=args.query_block, dtype=args.dtype,
                          select=args.select, use_pallas=args.pallas,
                          mesh_shape=mesh_shape, score=args.score)

    timer = EngineTimer()
    with timer.phase("parse"), obs_span("cli.parse"):
        inp = parse_input(stdin)

    # Only the solve is timed, matching the reference's timed region
    # (common.cpp:122-131 brackets Engine::KNN after ingest).
    from dmlp_tpu.check.sanitize import maybe_sanitized
    engine = None
    if args.engine == "golden":
        timer.start()
        from dmlp_tpu.golden.reference import knn_golden
        with obs_span("cli.solve", engine="golden"):
            results = knn_golden(inp, score=config.score)
    else:
        from dmlp_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache(args.compile_cache)  # before any compile
        engine = make_engine(config)
        solve = engine.run_device_full if args.device_full else engine.run
        if args.warmup:
            with timer.phase("warmup_compile"), \
                    obs_span("cli.warmup_compile"), \
                    maybe_sanitized(force=args.sanitize):
                solve(inp)
            if probe is not None:
                # The warmup solve recorded the same dispatches the timed
                # solve is about to; without a reset every counter would
                # double and the roofline (counters / timed elapsed)
                # would overstate achieved FLOP/s ~2x.
                probe.reset()
        import contextlib
        profile_cm = contextlib.nullcontext()
        if args.profile:
            import jax
            profile_cm = jax.profiler.trace(args.profile)
        timer.start()
        with profile_cm, obs_span("cli.solve", mode=args.mode,
                                  engine="jax"), \
                maybe_sanitized(force=args.sanitize):
            results = solve(inp)
    with obs_span("cli.format_results"):
        text = format_results(results, debug=config.debug)
    timer.stop()

    stdout.write(text)
    stderr.write(timer.stderr_line())
    if args.phase_times:
        for name, ms in timer.phase_ms.items():
            stderr.write(f"phase {name}: {ms:.1f} ms\n")

    # -- observability epilogue (outside the timed region; contract
    # channels above are already written and stay byte-identical) --------
    if probe is not None or tracer is not None:
        phase_ms = dict(timer.phase_ms)
        if engine is not None:
            phase_ms.update(getattr(engine, "last_phase_ms", {}))
        counters = None
        if probe is not None:
            with obs_span("cli.collect_counters"):
                counters = probe.collect()
        hlo_rep_auto = None
        if args.hlo_report and engine is not None \
                and hasattr(engine, "comms_from_hlo"):
            # Derive the GSPMD engine's real comms record from the
            # compiled program BEFORE summarizing, so the metrics
            # comms block and the hlo reconcile see the same traffic.
            with obs_span("cli.hlo_derive_comms"):
                hlo_rep_auto = engine.comms_from_hlo()
        comms = None
        if engine is not None and getattr(engine, "last_comms", None):
            from dmlp_tpu.obs.comms import summarize
            comms = summarize(engine.last_comms)
        mem_model = None
        if (args.metrics or args.hlo_report) and engine is not None:
            # Only _emit_metrics consumes the reconcile; a
            # --counters/--trace-only run must not pay the
            # live-array enumeration for a discarded result.
            # Analytic peak-HBM model + watermark reconcile
            # (obs.memwatch): against the telemetry sampler's tracked
            # peak when a session ran, else a one-shot basis — with
            # the explicit marker where the backend reports nothing.
            from dmlp_tpu.obs import memwatch, telemetry
            try:
                model = memwatch.model_for_engine(engine, inp)
                sess = telemetry.session()
                measured = (sess.sampler.measured_peak() if sess
                            else memwatch.measured_watermark())
                mem_model = memwatch.reconcile(model, measured)
            except Exception:  # check: no-retry — obs never fails a run
                mem_model = None
        if args.metrics:
            _emit_metrics(args.metrics, args, inp, timer, phase_ms,
                          counters, comms,
                          extract_impl=getattr(engine, "last_extract_impl",
                                               None)
                          if engine is not None else None,
                          mem_model=mem_model,
                          prune=getattr(engine, "last_prune", None)
                          if engine is not None else None,
                          precision=getattr(engine, "last_precision",
                                            None)
                          if engine is not None else None,
                          engine=engine)
        if args.counters:
            _emit_counters_stderr(counters, timer.elapsed_ms, stderr)
        if args.hlo_report and probe is not None:
            with obs_span("cli.hlo_report"):
                _emit_hlo_report(args, engine, probe, tracer, mem_model,
                                 hlo_rep_auto)
        if tracer is not None:
            tracer.write(args.trace)
    return 0


def _emit_hlo_report(args, engine, probe, tracer, mem_model,
                     hlo_rep_auto) -> None:
    """Append the kind='hlo' RunRecord: per-executable HloReports for
    every recorded dispatch signature + the three-way reconcile (HLO vs
    analytic comms models vs traced spans vs the memwatch mem block).
    Entirely outside the timed region; never raises into the run."""
    try:
        from dmlp_tpu.obs import hlo as obs_hlo
        from dmlp_tpu.obs.run import (RunRecord, current_device,
                                      round_from_name)
        reports, skipped = obs_hlo.probe_reports(probe)
        if hlo_rep_auto is not None and not any(
                rep.fingerprint == hlo_rep_auto.fingerprint
                for rep, _c, _s in reports):
            reports.append((hlo_rep_auto, 1, "auto.solve"))
        mesh_axes = None
        if engine is not None and getattr(engine, "mesh", None) \
                is not None:
            mesh_axes = dict(zip(engine.mesh.axis_names,
                                 engine.mesh.devices.shape))
        doc = obs_hlo.build_report_doc(
            reports, skipped=skipped,
            traffics=getattr(engine, "last_comms", None)
            if engine is not None else None,
            events=tracer.events() if tracer is not None else None,
            mem_block=mem_model, mesh_axes=mesh_axes)
        rec = RunRecord(
            kind="hlo", tool="dmlp_tpu.cli",
            config={"mode": args.mode, "engine": args.engine,
                    "exact": not args.fast,
                    **({"mesh": list(mesh_axes.values())}
                       if mesh_axes else {})},
            metrics=obs_hlo.flat_metrics(doc),
            comms=doc,
            device=current_device(),
            round=round_from_name(args.hlo_report))
        rec.append_jsonl(args.hlo_report)
    except Exception as e:  # check: no-retry — obs never fails a run
        import sys as _sys
        _sys.stderr.write(f"warning: --hlo-report failed: "
                          f"{type(e).__name__}: {e}\n")


if __name__ == "__main__":
    sys.exit(main())
