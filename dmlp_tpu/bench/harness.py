"""Differential check: generate -> oracle (cached) -> engine -> compare.

The TPU-native run_bench.sh. Per config (configs.py):

1. regenerate the canonical input if missing (seeded, byte-stable —
   inputs/inputN.in, the reference's missing inputs protocol, survey §6);
2. run the oracle once and cache outputs/test_N.{out,err}
   (run_bench.sh:79-84's cache), using the fast-exact golden model in
   place of the unrunnable x86 oracle binaries;
3. run the engine via the real CLI entry (same stdin/stdout/stderr
   contract as `mpirun ./engine < input`), writing outputs/tmp.{out,err};
4. diff the checksum channel (correctness) and compare the `Time taken`
   lines (performance) in run_bench.sh:29-72's report format.
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys
import time
from typing import Optional, TextIO

from dmlp_tpu.bench.configs import BENCH_CONFIGS, BenchConfig


def _extract_ms(err_text: str) -> Optional[int]:
    m = re.search(r"Time taken:\s*(\d+)", err_text)
    return int(m.group(1)) if m else None


def compare_times(bench_err: str, engine_err: str, out: TextIO) -> Optional[float]:
    """The compare_times report (run_bench.sh:29-72); returns percent diff
    (positive = engine slower), or None if a timing line is missing."""
    bench_ms = _extract_ms(bench_err)
    engine_ms = _extract_ms(engine_err)
    if bench_ms is None or engine_ms is None:
        out.write("Error: Could not extract timing information from .err files.\n")
        return None
    out.write("\n=== Performance Comparison ===\n")
    out.write(f"Benchmark time: {bench_ms} ms\n")
    out.write(f"Engine time:    {engine_ms} ms\n")
    diff = engine_ms - bench_ms
    if bench_ms == 0:
        # Oracle rounded to 0 ms — a percentage would be meaningless (and
        # claiming 0% would falsely declare parity).
        out.write(f"Difference:     +{diff} ms (oracle < 1 ms; no %)\n")
        out.write("==============================\n\n")
        return None
    percent = (engine_ms - bench_ms) / bench_ms * 100.0
    if percent > 0:
        out.write(f"Difference:     +{abs(diff)} ms ({percent:.2f}% slower)\n")
    elif percent < 0:
        out.write(f"Difference:     -{abs(diff)} ms ({abs(percent):.2f}% "
                  "faster) \U0001f389\U0001f389\U0001f389\n")
    else:
        out.write("Difference:     0 ms (No difference)\n")
    out.write("==============================\n\n")
    return percent


def ensure_input(cfg: BenchConfig, inputs_dir: str) -> str:
    """Generate the config's seeded input if not cached; returns the path."""
    from dmlp_tpu.io.datagen import generate_input_text

    os.makedirs(inputs_dir, exist_ok=True)
    path = os.path.join(inputs_dir, cfg.input_name)
    if not os.path.exists(path):
        text = generate_input_text(cfg.num_data, cfg.num_queries,
                                   cfg.num_attrs, cfg.min_attr, cfg.max_attr,
                                   cfg.min_k, cfg.max_k, cfg.num_labels,
                                   seed=cfg.seed)
        with open(path, "w") as f:
            f.write(text)
    return path


def ensure_oracle(cfg: BenchConfig, input_path: str, outputs_dir: str,
                  out: TextIO, force: bool = False) -> tuple[str, str]:
    """Run the golden oracle (cached) for a config; returns (.out, .err) paths."""
    from dmlp_tpu.golden.fast import knn_golden_fast
    from dmlp_tpu.io.grammar import parse_input
    from dmlp_tpu.io.report import format_results
    from dmlp_tpu.utils.timing import format_time_taken

    os.makedirs(outputs_dir, exist_ok=True)
    out_path = os.path.join(outputs_dir, f"test_{cfg.config_id}.out")
    err_path = os.path.join(outputs_dir, f"test_{cfg.config_id}.err")
    if os.path.exists(err_path) and os.path.exists(out_path) and not force:
        out.write("Output found in cache. Skipping...\n")
        return out_path, err_path
    with open(input_path, "rb") as f:  # binary -> native parser dispatch
        inp = parse_input(f)
    t0 = time.perf_counter()
    stats: dict = {}
    results = knn_golden_fast(inp, stats=stats)
    elapsed_ms = (time.perf_counter() - t0) * 1e3
    if stats.get("fallbacks"):
        out.write(f"oracle: {stats['fallbacks']} queries took the strict "
                  "fallback path\n")
    with open(out_path, "w") as f:
        f.write(format_results(results))
    with open(err_path, "w") as f:
        f.write(format_time_taken(elapsed_ms))
    return out_path, err_path


class EngineTimeout(RuntimeError):
    """The engine subprocess exceeded the harness timeout and was killed."""


def _engine_flags(cfg: BenchConfig, effective_mode: str) -> list:
    """Engine path flags shared by the single- and multi-process runners —
    one place to wire new BenchConfig knobs (the r2 harness silently
    benched the default path because these never reached the argv)."""
    argv = []
    if cfg.mesh_shape is not None and effective_mode != "single":
        argv += ["--mesh", f"{cfg.mesh_shape[0]},{cfg.mesh_shape[1]}"]
    if cfg.use_pallas:
        argv.append("--pallas")
    if cfg.select != "auto":
        argv += ["--select", cfg.select]
    return argv


def _config_env(cfg: BenchConfig, env: Optional[dict]) -> Optional[dict]:
    """Subprocess environment for a config: ``virtual_devices`` is the
    config's own declaration that it runs on the CPU platform with that
    many virtual devices (configs.py); every other config inherits the
    caller's platform."""
    if not cfg.virtual_devices:
        return env
    e = dict(env if env is not None else os.environ)
    e["JAX_PLATFORMS"] = "cpu"
    # Append to (not replace) any caller/CI XLA_FLAGS; ours goes last so a
    # stale device-count flag from the environment cannot override it.
    prior = e.get("XLA_FLAGS", "")
    mine = f"--xla_force_host_platform_device_count={cfg.virtual_devices}"
    e["XLA_FLAGS"] = f"{prior} {mine}".strip()
    return e


def run_engine(cfg: BenchConfig, input_path: str, outputs_dir: str,
               mode: Optional[str] = None, fast: bool = False,
               warmup: bool = True, timeout_s: float = 300.0,
               env: Optional[dict] = None,
               obs_flags: Optional[list] = None) -> tuple[str, str]:
    """Run the engine CLI as a subprocess over a real pipe, under a kill
    timeout; returns (tmp.out, tmp.err) paths.

    A subprocess + timeout mirrors the reference's hang protection
    (``mpirun --timeout 300``, run_bench.sh:82) — one wedged jit must fail
    its config, not block the whole suite. Defaults to exact (f64-parity)
    mode — the harness exists to prove checksum parity, like the
    reference's oracle diff; ``fast=True`` drops the host rescore for
    pure-device timing at the cost of f32 ordering. ``cfg.mesh_shape``
    (run_bench.sh's task-count analog) is passed through as ``--mesh``.
    ``obs_flags`` (e.g. ``["--trace", path]``) ride through to the engine
    CLI — the per-config observability capture.
    """
    import subprocess

    argv = [sys.executable, "-m", "dmlp_tpu", "--mode", mode or cfg.mode]
    argv += _engine_flags(cfg, mode or cfg.mode)
    if fast:
        argv.append("--fast")
    if warmup:
        argv.append("--warmup")
    if obs_flags:
        argv += list(obs_flags)
    env = _config_env(cfg, env)
    with open(input_path, "rb") as stdin:
        proc = subprocess.Popen(argv, stdin=stdin, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, env=env)
        try:
            out_b, err_b = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            raise EngineTimeout(
                f"engine exceeded {timeout_s:.0f}s timeout (killed), "
                f"cf. mpirun --timeout at run_bench.sh:82")
    if proc.returncode != 0:
        raise RuntimeError(f"engine CLI exited {proc.returncode}: "
                           f"{err_b.decode()[-2000:]}")
    tmp_out = os.path.join(outputs_dir, "tmp.out")
    tmp_err = os.path.join(outputs_dir, "tmp.err")
    with open(tmp_out, "wb") as f:
        f.write(out_b)
    with open(tmp_err, "wb") as f:
        f.write(err_b)
    return tmp_out, tmp_err


def run_engine_multiproc(cfg: BenchConfig, input_path: str, outputs_dir: str,
                         timeout_s: float = 300.0,
                         env: Optional[dict] = None) -> tuple[str, str]:
    """Run the engine as a real ``cfg.procs``-process jax.distributed
    (Gloo) cluster under the kill timeout — the harness-owned form of the
    reference's 2-node mpirun (run_bench.sh:82-84). Process 0's stdout is
    the canonical results channel (grader-diffed); all processes must exit
    0 within the timeout or the config fails."""
    import concurrent.futures as cf
    import socket
    import subprocess

    def launch_once():
        # NOTE: probe-then-rebind has an inherent TOCTOU window (another
        # process can grab the ephemeral port before the coordinator binds
        # it); kept because jax.distributed offers no bind-then-hand-off
        # API. The caller retries once on a bind failure.
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]

        argv0 = [sys.executable, "-m", "dmlp_tpu.distributed",
                 "--input", input_path,
                 "--coordinator", f"localhost:{port}",
                 "--processes", str(cfg.procs), "--warmup"]
        argv0 += _engine_flags(cfg, cfg.mode)
        procs = [subprocess.Popen(argv0 + ["--process-id", str(pid)],
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, env=env)
                 for pid in range(cfg.procs)]
        # Drain every process concurrently under ONE cluster deadline:
        # sequential communicate(timeout) would leave later processes' pipes
        # undrained (a stalled collective once ~64 KiB of Gloo/JAX stderr
        # backs up) and would multiply the worst-case wall clock by N.
        with cf.ThreadPoolExecutor(len(procs)) as ex:
            futs = [ex.submit(p.communicate) for p in procs]
            done, pending = cf.wait(futs, timeout=timeout_s)
            if pending:
                for proc in procs:
                    proc.kill()
                outs = [f.result() for f in futs]  # drains after the kills
                raise EngineTimeout(
                    f"{cfg.procs}-process cluster exceeded {timeout_s:.0f}s "
                    f"timeout (killed), cf. mpirun --timeout at "
                    f"run_bench.sh:82")
            outs = [f.result() for f in futs]
        for pid, proc in enumerate(procs):
            if proc.returncode != 0:
                raise RuntimeError(
                    f"process {pid} exited {proc.returncode}: "
                    f"{outs[pid][1].decode()[-2000:]}")
        return outs

    env = _config_env(cfg, env)
    try:
        outs = launch_once()
    except RuntimeError as e:
        # One retry with a fresh port when the TOCTOU race above landed
        # (the coordinator loses its probed port to another process).
        if not isinstance(e, EngineTimeout) \
                and "ddress already in use" in str(e):
            outs = launch_once()
        else:
            raise
    tmp_out = os.path.join(outputs_dir, "tmp.out")
    tmp_err = os.path.join(outputs_dir, "tmp.err")
    with open(tmp_out, "wb") as f:
        f.write(outs[0][0])                      # proc-0 canonical stdout
    with open(tmp_err, "wb") as f:
        f.write(outs[0][1])
    return tmp_out, tmp_err


def run_config(config_id: int, base_dir: str = ".",
               mode: Optional[str] = None, fast: bool = False,
               force_oracle: bool = False, out: Optional[TextIO] = None,
               timeout_s: float = 300.0, env: Optional[dict] = None,
               reps: int = 1, trace_dir: Optional[str] = None,
               counters: bool = False,
               record_path: Optional[str] = None,
               profile_dir: Optional[str] = None,
               telemetry_dir: Optional[str] = None) -> dict:
    """Full benchmark flow for one config; returns a result summary dict.

    ``reps`` > 1 runs the engine subprocess that many times and reports
    the MEDIAN engine time (all runs' times recorded as engine_ms_reps;
    checksums verified on every run). The reference protocol is
    single-shot (one mpirun each, run_bench.sh:82-84); a median over
    reps is the deviation, documented here and visible in the
    artifact.

    Observability (dmlp_tpu.obs): ``trace_dir`` captures a per-config
    Perfetto trace + metrics JSONL from the engine subprocess
    (trace_configN.json / metrics_configN.jsonl; the LAST rep's trace
    wins, every rep's metrics append); ``counters`` adds the engine's
    stderr roofline summary; ``record_path`` appends one versioned
    RunRecord per config — the schema replacing ad-hoc BENCH_*.json.
    Single-process configs only (a multi-process cluster would collide
    on the artifact files).

    ``profile_dir`` requests a per-config on-device ``jax.profiler`` XLA
    capture (the engine CLI's ``--profile``) into
    ``profile_dir/profile_configN/``, linked from that config's
    RunRecord artifacts. Real-TPU runs only: a config forced onto the
    virtual-CPU platform (``cfg.virtual_devices``) or an environment
    pinned to CPU records the explicit ``profile_unavailable`` marker
    instead of a capture — never a silently absent artifact.
    """
    out = out or sys.stdout
    cfg = BENCH_CONFIGS[config_id]
    if cfg.timeout_s is not None:
        timeout_s = cfg.timeout_s   # per-config override (configs.py)
    inputs_dir = os.path.join(base_dir, "inputs")
    outputs_dir = os.path.join(base_dir, "outputs")

    # One cpu-pinned verdict for both the profile marker and the
    # RunRecord device field: virtual-device configs and JAX_PLATFORMS=
    # cpu environments run the engine on CPU; anything else is the real
    # backend, which the parent must NOT probe (jax.devices() here would
    # claim the chip the engine subprocesses need). The RunRecord takes
    # its device from the engine's own device stamp where the run wrote
    # a metrics file (--trace), and stays unset otherwise rather than
    # guessed.
    cpu_pinned = bool(cfg.virtual_devices) or (
        (env if env is not None else os.environ)
        .get("JAX_PLATFORMS", "") == "cpu")

    obs_flags: list = []
    if counters:
        obs_flags.append("--counters")
    if telemetry_dir:
        # Per-config live-telemetry capture (obs.telemetry): the engine
        # subprocess rewrites an OpenMetrics snapshot while it runs;
        # the final file is linked from the config's RunRecord
        # artifacts like the trace/metrics pair.
        os.makedirs(telemetry_dir, exist_ok=True)
        obs_flags += ["--telemetry",
                      os.path.join(telemetry_dir,
                                   f"telemetry_config{config_id}.prom")]
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        obs_flags += ["--trace",
                      os.path.join(trace_dir, f"trace_config{config_id}.json"),
                      "--metrics",
                      os.path.join(trace_dir,
                                   f"metrics_config{config_id}.jsonl")]
    profile: Optional[tuple] = None   # ("path", p) | ("unavailable", why)
    if profile_dir:
        if cpu_pinned:
            profile = ("unavailable", "cpu platform (virtual devices or "
                       "JAX_PLATFORMS=cpu) — on-device XLA capture needs "
                       "the real TPU")
            out.write(f"Config {config_id}: note — --profile is a no-op "
                      "on CPU; recording profile_unavailable\n")
        else:
            pdir = os.path.join(profile_dir, f"profile_config{config_id}")
            os.makedirs(pdir, exist_ok=True)
            obs_flags += ["--profile", pdir]
            profile = ("path", pdir)
    if obs_flags and cfg.procs > 1:
        out.write(f"Config {config_id}: note — observability capture "
                  "applies to single-process configs only; skipping\n")
        obs_flags = []
        if profile is not None and profile[0] == "path":
            profile = ("unavailable", "multi-process config")

    input_path = ensure_input(cfg, inputs_dir)
    oracle_out, oracle_err = ensure_oracle(cfg, input_path, outputs_dir, out,
                                           force=force_oracle)
    with open(oracle_out) as f:
        want = f.read()
    if cfg.procs > 1 and (mode or fast):
        out.write(f"Config {config_id}: note — --mode/--fast do not apply "
                  "to multi-process configs (the cluster runs the full "
                  "exact contract pipeline)\n")
    n_reps = max(reps, 1)
    # Checksums are verified on every rep except in single-process --fast
    # mode, where f64-oracle diffs are documented as expected output.
    check_reps = not (fast and cfg.procs == 1)
    rep_ms: list = []
    got = ee = None
    for _rep in range(n_reps):
        try:
            if cfg.procs > 1:
                engine_out, engine_err = run_engine_multiproc(
                    cfg, input_path, outputs_dir, timeout_s=timeout_s,
                    env=env)
            else:
                engine_out, engine_err = run_engine(
                    cfg, input_path, outputs_dir, mode=mode, fast=fast,
                    timeout_s=timeout_s, env=env, obs_flags=obs_flags)
        except (EngineTimeout, RuntimeError) as e:
            if got is not None:
                # Later-rep flake on the swinging link: keep the earlier
                # good reps instead of failing a config that already
                # produced a verified result (the reason reps exist).
                out.write(f"Config {config_id}: rep {_rep + 1}/{n_reps} "
                          f"failed ({e}); keeping {len(rep_ms)} good "
                          "rep(s)\n")
                break
            kind = "TIMEOUT" if isinstance(e, EngineTimeout) else "ERROR"
            out.write(f"Config {config_id}: {kind} ({e})\n")
            res = {"config": config_id, "checksums_match": False,
                   "oracle_ms": None, "engine_ms": None,
                   "percent_vs_oracle": None}
            if kind == "TIMEOUT":
                # Explicit marker, PR 5 convention: markers record an
                # honest non-result and never gate — a hung config must
                # not fail the whole bench run, only document itself.
                res["timeout"] = True          # legacy spelling
                res["timed_out"] = True        # the marker consumers key on
            else:
                res["error"] = str(e)
            if profile is not None and profile[0] == "path":
                # A killed/errored engine wrote no capture: record the
                # explicit marker (never a silently absent artifact) and
                # drop the pre-created empty capture dir.
                try:
                    os.rmdir(profile[1])
                except OSError:
                    pass
                profile = ("unavailable",
                           f"engine run failed ({kind.lower()})")
            if record_path:
                _append_run_record(record_path, cfg, res, trace_dir,
                                   profile=profile, cpu_pinned=cpu_pinned)
            return res
        with open(engine_out) as f:
            got_r = f.read()
        with open(engine_err) as f:
            ee_r = f.read()
        if check_reps and got_r != want:
            # A mismatching run's timing must not enter the median — the
            # artifact would otherwise carry a number derived from wrong
            # output with only checksums_match hinting at it.
            got, ee = got_r, ee_r
            rep_ms = []
            break
        got, ee = got_r, ee_r
        ms = _extract_ms(ee_r)
        if ms is not None:
            rep_ms.append(ms)

    checksums_match = want == got
    status = "PASS" if checksums_match else "FAIL"
    out.write(f"Config {config_id}: checksums {status} "
              f"({cfg.num_queries} queries)\n")

    with open(oracle_err) as f:
        oe = f.read()
    percent = compare_times(oe, ee, out)  # human report: last run
    oracle_ms = _extract_ms(oe)
    res = {"config": config_id, "checksums_match": checksums_match,
           "oracle_ms": oracle_ms, "engine_ms": _extract_ms(ee),
           "percent_vs_oracle": percent}
    if len(rep_ms) > 1:
        res["engine_ms"] = round(statistics.median(rep_ms))
        res["engine_ms_reps"] = rep_ms
        if oracle_ms:
            res["percent_vs_oracle"] = (
                (res["engine_ms"] - oracle_ms) / oracle_ms * 100.0)
    # MEASURED reference-binary baseline, when a capture exists for this
    # config (tools/capture_oracle.sh ran bench_1..4 in-container via
    # isolated-singleton Open MPI; configs 1-4 map 1:1 onto the captured
    # workloads; config 5's input has no captured binary counterpart).
    # (checksums_match gate: a wrong-output run's timing must not carry a
    # reference-binary multiple either.)
    if config_id in (1, 2, 3, 4) and res["engine_ms"] \
            and res["checksums_match"]:
        res.update(reference_binary_fields(
            os.path.join(base_dir, "oracle_capture", "ORACLE_GOLDEN.json"),
            config_id, res["engine_ms"]))
    if profile is not None and profile[0] == "path" \
            and not os.listdir(profile[1]):
        # The engine accepted --profile but wrote nothing (e.g. the
        # backend rejected the capture): an explicit marker, not a
        # RunRecord pointing at an empty directory.
        profile = ("unavailable", "engine wrote no capture")
    if record_path:
        _append_run_record(record_path, cfg, res, trace_dir,
                           profile=profile, cpu_pinned=cpu_pinned,
                           telemetry_dir=telemetry_dir)
    return res


def _append_run_record(record_path: str, cfg: BenchConfig, res: dict,
                       trace_dir: Optional[str],
                       profile: Optional[tuple] = None,
                       cpu_pinned: bool = False,
                       telemetry_dir: Optional[str] = None) -> None:
    """One versioned RunRecord per config run (obs.run) — the uniform
    artifact new bench emitters share instead of private BENCH_* shapes.
    ``profile`` is ("path", dir) to link an on-device capture from the
    artifacts block, or ("unavailable", why) for the explicit marker."""
    import dataclasses

    from dmlp_tpu.obs.run import RunRecord

    artifacts = {}
    metrics = dict(res)
    failed = bool(res.get("timeout") or res.get("error"))
    if trace_dir and cfg.procs == 1 and not failed:
        # Only paths that actually exist, and only for completed runs: a
        # timed-out/killed engine never wrote its trace, and a RunRecord
        # pointing at a missing (or stale earlier-rep) file would
        # mislead every consumer.
        candidates = {
            "trace": os.path.join(
                trace_dir, f"trace_config{cfg.config_id}.json"),
            "metrics": os.path.join(
                trace_dir, f"metrics_config{cfg.config_id}.jsonl"),
        }
        artifacts = {k: p for k, p in candidates.items()
                     if os.path.exists(p)}
    if telemetry_dir and cfg.procs == 1 and not failed:
        tpath = os.path.join(telemetry_dir,
                             f"telemetry_config{cfg.config_id}.prom")
        if os.path.exists(tpath):
            artifacts["telemetry"] = tpath
    if profile is not None:
        if profile[0] == "path" and not failed:
            artifacts["profile"] = profile[1]
        else:
            metrics["profile_unavailable"] = profile[1]
    from dmlp_tpu.obs.run import round_from_name
    # Schema-2 envelope fields. Device: what the
    # engine subprocess itself stamped into its metrics summary, else
    # "cpu" when the harness pinned it there (the cpu_pinned verdict
    # from run_config), else unset — never jax.devices() in this parent.
    device = _child_device(artifacts.get("metrics")) \
        or ("cpu" if cpu_pinned else None)
    RunRecord(kind="bench", tool="dmlp_tpu.bench",
              config=dataclasses.asdict(cfg), metrics=metrics,
              artifacts=artifacts, device=device,
              round=round_from_name(record_path)).append_jsonl(record_path)


def _child_device(metrics_path: Optional[str]) -> Optional[str]:
    """``device_kind`` from the device stamp in the LAST summary record
    of an engine subprocess's ``--metrics`` file (None: no file, or a
    run that wrote no stamp)."""
    from dmlp_tpu.obs.run import stamp_device_kind
    if not metrics_path:
        return None
    stamp = None
    with open(metrics_path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("event") == "summary":
                stamp = rec.get("device")
    return stamp_device_kind(stamp)


def reference_binary_fields(cap_path: str, config_id: int,
                            engine_ms: float) -> dict:
    """Annotation fields comparing an engine time against the captured
    reference-binary run for ``config_id``. Best-effort by
    contract: returns {} (never raises, never partial fields) when the
    capture is absent, unreadable, or malformed — the annotation must not
    be able to discard a completed benchmark result."""
    try:
        with open(cap_path) as f:
            ref = json.load(f)["configs"][str(config_id)]
        ref_ms = float(ref["time_taken_ms"])  # validate; store raw below
        ref_np = int(ref["np"])
    except (OSError, KeyError, TypeError, ValueError,
            json.JSONDecodeError):
        return {}
    # `not (ref_ms > 0)` also rejects NaN (NaN <= 0 is False) — a NaN
    # multiple would serialize as invalid strict JSON downstream.
    if not engine_ms or not (ref_ms > 0):
        return {}
    return {"reference_binary_ms": ref["time_taken_ms"],
            "reference_binary_np": ref_np,
            "vs_reference_binary": round(ref_ms / engine_ms, 1)}


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="dmlp_tpu.bench", description=__doc__)
    p.add_argument("config", help="1|2|3|4|5|all")
    p.add_argument("--mode", default=None,
                   choices=[None, "single", "sharded", "ring", "auto"])
    p.add_argument("--fast", action="store_true",
                   help="drop the f64 host rescore (f32 ordering; checksum "
                        "diffs vs the f64 oracle are then expected)")
    p.add_argument("--force-oracle", action="store_true")
    p.add_argument("--base-dir", default=".")
    p.add_argument("--timeout", type=float, default=300.0,
                   help="per-config engine kill timeout in seconds "
                        "(mpirun --timeout 300 analog)")
    p.add_argument("--reps", type=int, default=1,
                   help="engine runs per config; >1 reports the median "
                        "(the reference protocol is single-shot)")
    p.add_argument("--trace", metavar="DIR", default=None, dest="trace_dir",
                   help="per-config observability capture: the engine "
                        "subprocess writes DIR/trace_configN.json "
                        "(Perfetto) + DIR/metrics_configN.jsonl")
    p.add_argument("--metrics", metavar="FILE", default=None,
                   help="append one versioned RunRecord (obs.run) per "
                        "config to FILE — the uniform bench artifact")
    p.add_argument("--counters", action="store_true",
                   help="engine subprocesses print XLA cost-analysis + "
                        "roofline summaries on stderr")
    p.add_argument("--telemetry", metavar="DIR", default=None,
                   dest="telemetry_dir",
                   help="per-config live-telemetry capture: the engine "
                        "subprocess rewrites DIR/telemetry_configN.prom "
                        "as an OpenMetrics snapshot (obs.telemetry), "
                        "linked from the config's RunRecord artifacts")
    p.add_argument("--profile", metavar="DIR", default=None,
                   dest="profile_dir",
                   help="per-config on-device jax.profiler capture into "
                        "DIR/profile_configN (real-TPU runs; CPU configs "
                        "record the profile_unavailable marker), linked "
                        "from the config's RunRecord artifacts")
    args = p.parse_args(argv)

    ids = list(BENCH_CONFIGS) if args.config == "all" else [int(args.config)]
    ok = True
    for cid in ids:
        res = run_config(cid, base_dir=args.base_dir, mode=args.mode,
                         fast=args.fast, force_oracle=args.force_oracle,
                         timeout_s=args.timeout, reps=args.reps,
                         trace_dir=args.trace_dir, counters=args.counters,
                         record_path=args.metrics,
                         profile_dir=args.profile_dir,
                         telemetry_dir=args.telemetry_dir)
        # `timed_out` is a marker, not a verdict (markers never gate):
        # the config's RunRecord documents the hang; a wrong checksum
        # still fails the run.
        ok = ok and (res["checksums_match"] or res.get("timed_out", False))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
