"""One cell, once: ``python -m benchmark.run --workload W --seed N
--seconds S --trace 0|1``.

One process that holds the chip. It builds the cell's data from the seed
as arrays, builds the daemon in-process through the constructors the
program's entry point uses, warms the cell's own shapes
(compiled programs come from the persistent cache, ``<checkout>/.jax_cache``
by ``dmlp_tpu.utils.compile_cache``'s rule), measures for ``--seconds``,
checks a seeded sample of the window's own answers against the reference
its configuration names (``benchmark/reference.py`` where it names none)
outside the window, and prints one JSON object as its last line, each
number compared beside its limit under ``checks``, its last key (and as
the last lines on standard error). ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics and a breakdown.

A run that finds no TPU, fewer chips than the cell asks for, a device
kind missing from ``peaks.json``, a Pallas kernel in interpret mode or a
degrade rung other than the first exits non-zero and prints no result.

``--rehearse`` is for the tests and for CPU rehearsals only: the toy
sizes each of the cell's files states under its own ``rehearse`` key, any
platform, and every number named ``rehearsal.<metric>``. ``--control`` runs the configuration's stated
control (the program's own lower-precision path) in the cell's place; it
must come out ``correct: false``.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import check, data, spec, trace_reduce
from benchmark.readers import percentile


_IMPORTED_PC = time.perf_counter()


def _process_age_s() -> float:
    """Seconds since this process was started (exec), from /proc."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return time.perf_counter() - _IMPORTED_PC


class Refused(Exception):
    """The run may not print a result (wrong device, degraded path...)."""


def say(**obj) -> None:
    print(json.dumps(obj), flush=True)


class Context:
    """What the readers read."""

    def __init__(self):
        self.spans: List[Dict[str, Any]] = []     # name, t0, t1, args
        self.window_pc = (0.0, 0.0)
        self.requests: List[Dict[str, Any]] = []
        self.registry_before: Dict[str, Dict[str, float]] = {}
        self.registry_after: Dict[str, Dict[str, float]] = {}
        self.trace: Optional[Dict[str, Any]] = None
        self.scan_shape: Optional[Dict[str, int]] = None
        self.peaks: Dict[str, Any] = {}
        self.notes: Dict[str, Any] = {}

    def window_spans(self, name: str) -> List[Dict[str, Any]]:
        lo, hi = self.window_pc
        return [s for s in self.spans
                if s["name"] == name and s["t0"] >= lo and s["t1"] <= hi]


def _registry_snapshot() -> Dict[str, Dict[str, float]]:
    from dmlp_tpu.obs import telemetry
    reg = telemetry.registry()
    out = {}
    for name in reg.names():
        h = reg.get(name)
        if getattr(h, "kind", "") == "histogram":
            out[name] = {"count": h.count, "sum": h.sum}
    return out


class DeviceTrace:
    """A few seconds of the profiler's trace, bracketed by the two
    annotations the reduction aligns the host spans on."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.sync_pc = 0.0

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME):
            self.sync_pc = time.perf_counter()

    def stop(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(trace_reduce.END_NAME):
            pass
        jax.profiler.stop_trace()

    def load(self) -> Dict[str, Any]:
        """Reduce the stopped trace to the neutral form — after the
        window: the parse holds the interpreter lock."""
        try:
            path = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
            return trace_reduce.from_xplane(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _engine_config(cell: spec.Cell):
    from dmlp_tpu.config import EngineConfig
    opts = dict(cell.config["engine"])
    if opts.get("mesh_shape") is not None:
        opts["mesh_shape"] = tuple(opts["mesh_shape"])
    return EngineConfig(**opts)


def _check_stamp(stamp: Dict[str, Any], cell: spec.Cell) -> None:
    """No CPU, interpret-mode, degraded-rung or host-oracle run may
    print a metrics line."""
    if cell.rehearse:
        return
    from dmlp_tpu.resilience.degrade import RUNGS
    bad = []
    if stamp.get("platform") != "tpu":
        bad.append(f"platform is {stamp.get('platform')!r}")
    if cell.config["engine"].get("use_pallas") \
            and stamp.get("pallas_interpret") is not False:
        bad.append("Pallas kernels ran in interpret mode")
    if stamp.get("degrade_rung") not in (None, RUNGS[0]):
        bad.append(f"degrade rung {stamp.get('degrade_rung')!r}, not "
                   f"{RUNGS[0]!r}")
    if stamp.get("degradations"):
        bad.append(f"{stamp['degradations']} degradations")
    want = cell.config.get("expect_select")
    if want and stamp.get("select") != want:
        bad.append(f"select is {stamp.get('select')!r}, not {want!r}")
    if bad:
        raise Refused("; ".join(bad))


# -- the run -----------------------------------------------------------------

class LoadGen:
    """The generator's process: started early, told the port when the
    daemon is ready, always reaped."""

    COMMAND = [sys.executable, "-m", "benchmark.loadgen"]

    def __init__(self, cell: spec.Cell, params: Dict[str, Any], seed: int,
                 seconds: float):
        env = dict(os.environ)
        env["PYTHONPATH"] = spec.ROOT + os.pathsep + env.get("PYTHONPATH",
                                                            "")
        self.proc = subprocess.Popen(
            self.COMMAND, cwd=spec.ROOT,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True)
        job = {"config": {k: cell.config[k]
                          for k in ("num_attrs", "values", "modules")
                          if k in cell.config},
               "kind": cell.kind_name, "params": params,
               "seed": seed, "seconds": seconds}
        self.proc.stdin.write(json.dumps(job) + "\n")
        self.proc.stdin.flush()

    def _read(self, event: str) -> Dict[str, Any]:
        line = self.proc.stdout.readline()
        if not line:
            raise Refused(f"load generator died before {event!r} "
                          f"(exit {self.proc.poll()})")
        obj = json.loads(line)
        if obj.get("event") != event:
            raise Refused(f"load generator said {obj!r}, not {event!r}")
        return obj

    def wait_ready(self) -> Dict[str, Any]:
        return self._read("ready")

    def go(self, port: int) -> None:
        self.proc.stdin.write(json.dumps({"port": port}) + "\n")
        self.proc.stdin.flush()

    def result(self) -> Dict[str, Any]:
        return self._read("result")

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for f in (self.proc.stdin, self.proc.stdout):
            try:
                f.close()
            except OSError:
                pass


def run_served(cell: spec.Cell, args, ctx: Context,
               t_setup: Dict[str, Any]) -> Dict[str, Any]:
    gen = LoadGen(cell, cell.params, args.seed, args.seconds)
    try:
        return _run_served(cell, args, ctx, gen, t_setup)
    finally:
        gen.close()


def build_daemon(cell: spec.Cell, labels: np.ndarray, rows: np.ndarray):
    """The daemon as ``python -m dmlp_tpu.serve`` builds it
    (serve/__main__.py), every option from the configuration's file."""
    from dmlp_tpu.io.grammar import KNNInput, Params
    from dmlp_tpu.serve.daemon import ServeDaemon
    from dmlp_tpu.utils import compile_cache

    serve = cell.config["serve"]
    compile_cache.enable_compile_cache(None)
    na = rows.shape[1]
    corpus = KNNInput(Params(len(rows), 0, na), labels, rows,
                      np.zeros(0, np.int32), np.zeros((0, na), np.float64))
    budget = None if serve["hbm_budget"] == "auto" \
        else int(serve["hbm_budget"])
    mesh = tuple(serve["mesh_shape"]) if serve.get("mesh_shape") else None
    return ServeDaemon(
        corpus, _engine_config(cell), port=0, capacity=serve["capacity"],
        gate_carry=bool(serve["gate_carry"]), budget_bytes=budget,
        max_batch_queries=int(serve["max_batch_queries"]),
        max_queue_queries=int(serve["max_queue_queries"]),
        max_k=serve["max_k"], tick_s=float(serve["tick_ms"]) / 1e3,
        warm_buckets=[tuple(b) for b in cell.workload["warm_buckets"]],
        mesh_shape=mesh, mesh_merge=serve["mesh_merge"])


def _run_served(cell, args, ctx, gen, t_setup) -> Dict[str, Any]:
    from dmlp_tpu.utils import compile_cache

    cfg = cell.config
    t = time.perf_counter()
    labels, rows = data.corpus(cfg, args.seed)
    t_setup["generate_s"] = time.perf_counter() - t
    na = rows.shape[1]
    t = time.perf_counter()
    daemon = build_daemon(cell, labels, rows)
    t_setup["construct_s"] = time.perf_counter() - t
    drained = False
    try:
        t = time.perf_counter()
        daemon.start()
        t_setup["warmup_s"] = time.perf_counter() - t
        t_setup["warmup_ms_by_bucket"] = dict(daemon.warmup_ms)
        stats0 = daemon.stats()
        _check_stamp(stats0["device"], cell)
        t = time.perf_counter()
        ready = gen.wait_ready()
        t_setup["wait_generator_s"] = time.perf_counter() - t
        t_setup["generator_encode_s"] = ready["encode_s"]
        compiles0 = compile_cache.stats()["requests"]
        ctx.registry_before = _registry_snapshot()

        dtrace: Optional[DeviceTrace] = None
        trace_cfg = cell.workload.get("trace", {})
        setup_s = _process_age_s()
        t_go = time.perf_counter()
        gen.go(daemon.port)
        if args.trace:
            # late in the window and short: stopping a trace stalls the
            # process, and a stall early in an open loop at 0.8 x the
            # knee is a backlog for the rest of the window
            time.sleep(min(float(trace_cfg.get("start_s", 3.0)),
                           0.8 * args.seconds))
            dtrace = DeviceTrace()
            dtrace.start()
            time.sleep(max(0.1, min(float(trace_cfg.get("seconds", 3.0)),
                                    0.15 * args.seconds)))
            dtrace.stop()
        res = gen.result()
        ctx.window_pc = (t_go, time.perf_counter())
        if dtrace is not None:
            ctx.trace, ctx.notes["sync_pc"] = dtrace.load(), dtrace.sync_pc
        ctx.registry_after = _registry_snapshot()
        stats1 = daemon.stats()
        _check_stamp(stats1["device"], cell)
        eng = stats1["engine"]
        chunks = int(eng.get("extract_chunks") or 0)   # that hold rows
        k = int(cell.params["k"])
        lp = eng.get("last_prune") or {}
        qpad, _kb, kcap = daemon.engine.bucket_plan(
            max(r["nq"] for r in res["requests"]) if res["requests"]
            else 1, k)
        if lp.get("dense_bytes") and chunks:
            n = int(eng["corpus_rows"])
            ctx.scan_shape = {
                "nq": int(qpad), "n": n, "na": na, "kc": int(kcap),
                "itemsize": int(round(lp["dense_bytes"] / (n * na))),
                "dispatches": chunks}
        peak = _memory_peak()
        say(event="served", requests=len(res["requests"]),
            batches=stats1["batches"] - stats0["batches"],
            admission=stats1["admission"],
            compiles_in_window=compile_cache.stats()["requests"]
            - compiles0, buckets=eng["buckets"], paths=eng["paths"],
            compile_count=eng["compile_count"],
            compile_cache=compile_cache.stats(),
            last_prune=lp, scan_shape=ctx.scan_shape,
            **_cycle_account(stats0, stats1))
        daemon.drain()
        drained = True
    finally:
        if not drained:
            daemon.close()
    del daemon

    recs = res["requests"]
    ctx.requests = recs
    ok = [r for r in recs if r["ok"]]
    failed = len(recs) - len(ok)
    if failed:
        say(event="failed_requests", count=failed,
            first=[r.get("error") for r in recs if not r["ok"]][:3])
    metrics: Dict[str, float] = {"setup_s": setup_s}
    names = {m["name"] for m in cell.end_to_end()}
    lags = sorted(r["lag_ms"] for r in recs)
    # the median latency of each third of the window, by due time: a run
    # that is slow throughout and one that stalled once read differently
    by_due = sorted(recs, key=lambda r: r["due_s"])
    third = max(1, -(-len(by_due) // 3))
    thirds = [percentile(sorted(r["latency_ms"] for r in by_due[i:i + third]),
                         50) for i in range(0, len(by_due), third)]
    say(event="generator", mode=cell.kind_name, requests=len(recs),
        lag_p50_ms=percentile(lags, 50) if lags else None,
        lag_p95_ms=percentile(lags, 95) if lags else None,
        lag_max_ms=lags[-1] if lags else None,
        offered_s=res["offered_s"], p50_by_third_ms=thirds)
    if "qps" in names:
        # all the work and all the time: every query of every request
        # that came back ok, over the time to the last completion
        span = max((r["done_s"] for r in recs), default=0.0)
        metrics["qps"] = sum(r["nq"] for r in ok) / span if span else 0.0
    if names & {"p50_ms", "p95_ms"}:
        # a request that failed or was refused misses every latency
        lat = sorted(r["latency_ms"] if r["ok"] else res["timeout_ms"]
                     for r in recs)
        metrics["p50_ms"] = percentile(lat, 50)
        metrics["p95_ms"] = percentile(lat, 95)
    t = time.perf_counter()
    wl = cell.workload["check"]
    verdict = check.check_served(
        cell.reference, cfg, rows, labels, recs, res["answers"],
        cell.params["k"],
        int(wl["requests"]), int(wl["per_request"]),
        int(wl["plain_queries"]), args.seed, wl["limits"])
    say(event="reference", seconds=time.perf_counter() - t)
    return {"metrics": metrics, "attempted": len(recs), "failed": failed,
            "verdict": verdict, "stamp": stats1["device"],
            "memory_peak_bytes": peak}


def _cycle_account(stats0: Dict[str, Any], stats1: Dict[str, Any]
                   ) -> Dict[str, Any]:
    """What names an untraced run's stall and its flagged queries, from
    the program's always-on account, read once the window has closed:
    ``stats.batcher`` (cycles closed, of them before the window, and the
    ring of slow ones), ``stats.phases_ms.cycle`` with the longest cycle
    since the daemon started, and ``stats.engine.repairs``. A program
    that lacks one of them prints null there."""
    from dmlp_tpu.obs import telemetry
    batcher = stats1.get("batcher")
    if batcher is not None:
        batcher = dict(batcher, cycles_before_window=(
            stats0.get("batcher") or {}).get("cycles"))
    cycle = (stats1.get("phases_ms") or {}).get("cycle")
    h = telemetry.registry().get("serve.cycle_ms")
    if cycle is not None and h is not None and h.count:
        cycle = dict(cycle, max_ms=h.snapshot().get("max"))
    return {"batcher": batcher, "cycle_ms": cycle,
            "repairs": stats1["engine"].get("repairs")}


def _memory_peak() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        try:
            peak = max(peak, int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        except Exception:   # backend without memory stats (cpu)
            pass
    return peak


# -- the line -----------------------------------------------------------------

def _layer_metrics(cell: spec.Cell, ctx: Context) -> Dict[str, Any]:
    out = {}
    for doc in cell.per_layer():
        value = spec.reader(doc["reader"]).read(ctx, **doc.get("args", {}))
        if value is not None:
            out[doc["name"]] = {"value": float(value), "unit": doc["unit"]}
    return out


def _breakdown(ctx: Context) -> Optional[Dict[str, Any]]:
    if ctx.trace is None:
        return None
    spans = trace_reduce.spans_on_trace_clock(
        ctx.spans, ctx.notes["sync_pc"], ctx.trace["sync_ns"])
    gaps = trace_reduce.busy(ctx.trace)["gaps_ns"]
    return {"device_ops": trace_reduce.top_ops(ctx.trace),
            "idle_gaps": trace_reduce.attribute_gaps(gaps, spans)}


def checks_of(lines: List[Dict[str, Any]]) -> Dict[str, Any]:
    """``Verdict.lines()`` for the result line: each number compared,
    its limit and how many were compared (a number that is not finite
    goes as a string: the line is JSON)."""
    return {c["number"]: {"value": c["value"] if math.isfinite(c["value"])
                          else str(c["value"]),
                          "limit": c["limit"], "compared": c["compared"]}
            for c in lines}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run", description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    try:
        return _main(args)
    except (Refused, spec.SpecError) as e:
        print(f"benchmark.run: refused: {e}", file=sys.stderr)
        return 2


def _main(args) -> int:
    cell = spec.Cell(args.workload, rehearse=args.rehearse,
                     control=args.control)
    try:
        import dmlp_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}")
    import jax
    devs = jax.devices()
    t_setup: Dict[str, Any] = {"start_to_devices_s": _process_age_s()}
    platform, kind = devs[0].platform, str(devs[0].device_kind)
    if not args.rehearse:
        if platform != "tpu":
            raise Refused(f"no TPU: jax reports platform {platform!r}")
        if len(devs) < cell.chips:
            raise Refused(f"cell needs {cell.chips} chips, jax has "
                          f"{len(devs)}")
    ctx = Context()
    ctx.peaks = spec.peaks(kind) if not args.rehearse else \
        {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    tracer = None
    if args.trace:
        from dmlp_tpu.obs import trace as obs_trace
        # the tracer's events count from its construction on this clock
        ctx.notes["tracer_epoch_pc"] = time.perf_counter()
        tracer = obs_trace.install(obs_trace.Tracer())
    try:
        out = run_served(cell, args, ctx, t_setup)
    finally:
        if tracer is not None:
            from dmlp_tpu.obs import trace as obs_trace
            obs_trace.uninstall()
    say(event="setup_parts", **t_setup)
    verdict = out["verdict"]
    checks = verdict.lines()
    for line in checks:
        say(**line)

    prefix = "rehearsal." if args.rehearse else ""
    stamp = out["stamp"]           # obs.run.device_stamp, as the program
    device = {"platform": stamp["platform"], "kind": stamp["device_kind"],
              "count": stamp["device_count"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line: Dict[str, Any] = {
        "correct": verdict.correct, "attempted": out["attempted"],
        "failed": out["failed"]}
    if args.trace:
        epoch = ctx.notes.pop("tracer_epoch_pc")
        ctx.spans = [{"name": e["name"], "args": e.get("args", {}),
                      "t0": epoch + e["ts"] / 1e6,
                      "t1": epoch + (e["ts"] + e["dur"]) / 1e6}
                     for e in tracer.events() if e.get("ph") == "X"]
        metrics = _layer_metrics(cell, ctx)
        if ctx.trace is not None:
            b = trace_reduce.busy(ctx.trace)
            device["busy_s"], device["window_s"] = b["busy_s"], \
                b["window_s"]
            if b["busy_s"] <= 0 and not args.rehearse:
                raise Refused("the traced window saw no operation on the "
                              "device")
        bd = _breakdown(ctx)
        if bd is not None:
            line["breakdown"] = bd
        notes = {k: v for k, v in ctx.notes.items() if k != "sync_pc"}
        if notes:
            say(event="notes", **notes)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        metrics = {name: {"value": float(out["metrics"][name]),
                          "unit": units[name]} for name in units}
    line["metrics"] = {prefix + k: v for k, v in metrics.items()}
    line["device"] = device
    if args.rehearse:
        line["rehearsal"] = True
    if args.control:
        line["control"] = True
    # each number compared beside its limit: the line's last key, and
    # the last lines on standard error
    line["checks"] = checks_of(checks)
    print(json.dumps(line), flush=True)
    for c in checks:
        print(f"check {c['number']}: {c['value']!r} (limit {c['limit']!r},"
              f" {c['compared']} compared)", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
