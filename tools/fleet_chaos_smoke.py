#!/usr/bin/env python
"""Fleet chaos smoke: prove the self-healing fleet under seeded chaos.

The ``make fleet-chaos-smoke`` checker (wired into ``make test``).
Three seeded failure campaigns over REAL fleets on CPU — every served
response byte-identical to the float64 golden oracle throughout, every
process alive at drain time exiting 0, no flight dumps:

1. **Seeded replica kill mid-traffic** (supervised fleet, mesh 2x1
   replicas): one managed replica is SIGKILLed while a replay wave is
   in flight. Every response of the wave still matches the golden
   oracle (the router's bounded retry hides the crash), the supervisor
   detects the death, relaunches within its budget
   (``fleet.scale.{crashes,relaunches}`` non-vacuous), and the revived
   fleet serves golden again.
2. **Forced shard re-split under open-loop load**: far-row ingest
   through the router pushes both replicas past the capacity-padded
   buffer threshold while paced open-loop traffic keeps firing. The
   supervisor stages one split at a time — grown-layout replacement,
   checksum-verified corpus replay, atomic routing-table swap, drain
   of the old replica — until every replica runs the grown capacity
   (``fleet.reshard.splits`` >= 2). No request is lost: every wave
   response is either golden (far rows provably cannot enter any
   top-k, so the base oracle stays exact under every interleaving) or
   an explicit rejection; the post-split replay matches the
   grown-corpus oracle on the doubled layout.
3. **Injected ingest divergence** (static fleet + PR 7 fault site):
   one replica runs under a seeded ``serve.ingest`` transient fault —
   its first ingest is dropped, forking the corpus. The router reports
   the divergence to the client, the health prober's checksum
   comparison detects it, and the targeted delta re-ingest repairs it
   (``fleet.consistency.{divergences,repairs}`` non-vacuous); the
   repaired fleet answers the grown-corpus oracle byte-for-byte and
   drains rc 0.

4. **Warm compile-cache relaunch**: a replica is cold-started against
   an empty persistent compile cache (``--compile-cache``), drained,
   and relaunched against the now-populated cache. The relaunch must
   be served every program from the cache (hits = the cold start's
   misses, no miss; the ``chaos_warm_cache`` record's
   ``cold_start_compile_ms`` is the warm number), with a flat bucket
   ``compile_count`` and byte-identical golden replies from the
   warmed executables.

Each campaign lands one kind="fleet" RunRecord (level ``chaos_*``);
the file is read back at the end (``RunRecord.load_all``).

Usage::

    python tools/fleet_chaos_smoke.py --out outputs/fleet_chaos \
        [--record outputs/fleet_chaos/FLEET_CHAOS_SMOKE.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np                                         # noqa: E402

from dmlp_tpu.fleet import harness as fh                   # noqa: E402
from dmlp_tpu.io.grammar import KNNInput, Params, parse_input_text  # noqa: E402
from dmlp_tpu.fleet.loadgen import served_device          # noqa: E402
from dmlp_tpu.obs.run import RunRecord                     # noqa: E402
from dmlp_tpu.serve import client as sc                    # noqa: E402

BATCH_CAP = 16
BASE_CORPUS = dict(num_data=200, num_queries=4, num_attrs=4,
                   min_attr=0.0, max_attr=50.0, min_k=1, max_k=8,
                   num_labels=5, seed=42)
HEADER = {"serve_trace_schema": 1, "corpus": BASE_CORPUS}
REQS = [{"t_ms": t, "nq": nq, "k": k, "seed": 9000 + i}
        for i, (t, nq, k) in enumerate(
            [(0, 1, 3), (0, 2, 8), (30, 4, 5), (60, 1, 8), (90, 3, 3),
             (120, 2, 5), (150, 1, 5), (180, 4, 8), (210, 2, 3),
             (240, 1, 8), (270, 3, 5), (300, 2, 8)])]
FAR_OFFSET = 1e6      # ingested rows no top-k can reach: the base
#                       oracle stays exact under every interleaving


def fail(msg: str):
    print(f"fleet_chaos_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"fleet_chaos_smoke: {msg}")
    sys.stdout.flush()


def router_stats(port: int) -> dict:
    cli = sc.ServeClient(port)
    try:
        return cli.stats()["stats"]
    finally:
        cli.close()


def await_stats(port: int, pred, what: str, timeout_s: float = 300.0,
                proc=None, errlog: str = "") -> dict:
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            fail(f"router died waiting for {what}; see {errlog}")
        try:
            last = router_stats(port)
            if pred(last):
                return last
        except (OSError, ValueError, KeyError):
            pass
        time.sleep(0.25)
    fail(f"timed out waiting for {what}; last stats: "
         f"{json.dumps(last)[:800] if last else None}")


def spawn_supervised_router(out: str, corpus_path: str, warm: str,
                            record: str):
    ready = os.path.join(out, "router_ready.json")
    errlog = os.path.join(out, "router.err")
    if os.path.exists(ready):
        os.remove(ready)
    cmd = [sys.executable, "-m", "dmlp_tpu.fleet",
           "--spawn-corpus", corpus_path,
           "--spawn-replicas", "2", "--max-replicas", "4",
           "--out-dir", out, "--spawn-warm", warm,
           "--spawn-batch-cap", str(BATCH_CAP),
           "--spawn-flags", "--mesh 2x1",
           "--relaunch-budget", "2",
           "--unhealthy-deadline-s", "15",
           "--reshard-threshold", "0.9",
           "--revive-probes", "2",
           "--health-interval-s", "0.2", "--poll-s", "0.3",
           "--port", "0", "--telemetry-port", "0",
           "--ready-file", ready, "--record", record]
    with open(errlog, "w") as ef:
        proc = subprocess.Popen(cmd, stderr=ef,
                                stdout=subprocess.DEVNULL,
                                env=fh._repo_env(), cwd=out)
    doc = sc.await_ready(proc, ready, timeout_s=600, errlog=errlog)
    return proc, doc, errlog


class TrafficWave(threading.Thread):
    """Background open-loop waves against the router; collects every
    response for the none-lost / all-golden-or-rejected audit."""

    def __init__(self, port: int, golden_per_req):
        super().__init__(daemon=True)
        self.port = port
        self.golden_per_req = golden_per_req
        self.stop_flag = threading.Event()
        self.waves = 0
        self.lost = 0
        self.transport_errors = []
        self.rejected = 0
        self.wrong = 0
        self.latencies = []

    def run(self):
        while not self.stop_flag.is_set():
            res = sc.replay_open_loop(self.port, HEADER, REQS,
                                      speed=2.0)
            self.waves += 1
            if len(res) != len(REQS):
                self.lost += len(REQS) - len(res)
            ok = [r for r in res if r.get("ok")]
            for r in res:
                if r.get("ok"):
                    self.latencies.append(r["client_ms"])
                    continue
                err = str(r.get("error", ""))
                if "rejected" in err or "draining" in err:
                    self.rejected += 1
                else:
                    self.transport_errors.append(err)
            # ok responses must be base-oracle golden regardless of
            # which replica (old layout or grown) answered.
            got = sc.contract_text([r["checksums"] for r in ok])
            want_ids = [i for i, r in enumerate(res) if r.get("ok")]
            want = sc.contract_text(
                [self.golden_per_req[i] for i in want_ids])
            if got != want:
                self.wrong += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="outputs/fleet_chaos")
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    record = os.path.abspath(args.record) if args.record \
        else os.path.join(out, "FLEET_CHAOS_SMOKE.jsonl")
    if os.path.exists(record):
        os.remove(record)
    sc.clear_flight_dumps(out)
    # A previous run's ready files would make await_ready return a
    # dead process's port instantly — clear every stale one up front.
    for stale in os.listdir(out):
        if stale.endswith("_ready.json") or stale == "router_ready.json":
            os.remove(os.path.join(out, stale))

    corpus_txt = sc.corpus_text(HEADER)
    corpus_path = os.path.join(out, "corpus.in")
    with open(corpus_path, "w") as f:
        f.write(corpus_txt)
    corpus = parse_input_text(corpus_txt)
    golden = sc.golden_reference(corpus, HEADER, REQS)
    golden_text = sc.contract_text(golden)
    warm = ",".join(f"{q}x{k}" for q, k in
                    sc.warm_buckets_for_trace(REQS, BATCH_CAP))

    # ---- campaign 1 + 2 run on the SUPERVISED fleet -------------------------
    proc, ready, errlog = spawn_supervised_router(out, corpus_path,
                                                  warm, record)
    try:
        managed = ready.get("managed", [])
        if len(managed) != 2:
            fail(f"supervised router did not spawn 2 replicas: {ready}")
        st = router_stats(ready["port"])
        if st["healthy_replicas"] != 2:
            fail(f"fleet not healthy at ready: {st['replicas']}")
        # The replicas' own stamp: this parent holds no device (asking
        # jax here would claim the chip the replicas need).
        device = served_device(st)
        say(f"supervised fleet ready: router :{ready['port']}, "
            f"mesh 2x1 replicas "
            f"{[m['replica'] for m in managed]}")

        # -- 1. seeded replica kill mid-traffic ------------------------------
        victim = managed[0]
        res_box = {}

        def replay_wave():
            res_box["res"] = sc.replay(ready["port"], HEADER, REQS,
                                       connections=3)

        t0 = time.perf_counter()
        wave = threading.Thread(target=replay_wave, daemon=True)
        wave.start()
        time.sleep(0.15)
        os.kill(victim["pid"], signal.SIGKILL)
        wave.join(timeout=300)
        if wave.is_alive():
            fail("replay wave wedged after the seeded kill")
        kill_ms = (time.perf_counter() - t0) * 1e3
        res = res_box["res"]
        bad = [r for r in res if not r.get("ok")]
        if bad:
            fail(f"seeded kill lost/failed {len(bad)} requests: "
                 f"{bad[0]}")
        if sc.contract_text([r["checksums"] for r in res]) \
                != golden_text:
            fail("responses during the seeded kill differ from the "
                 "golden oracle")
        st = await_stats(
            ready["port"],
            lambda s: (s["scale"]["crashes"] >= 1
                       and s["scale"]["relaunches"] >= 1
                       and s["healthy_replicas"] >= 2),
            "crash detection + relaunch", proc=proc, errlog=errlog)
        res2 = sc.replay(ready["port"], HEADER, REQS[:6],
                         connections=2)
        if any(not r.get("ok") for r in res2) or \
                sc.contract_text([r["checksums"] for r in res2]) \
                != sc.contract_text(golden[:6]):
            fail("revived fleet does not serve golden")
        say(f"seeded kill OK: {len(res)} in-flight requests all "
            f"golden, crash detected, relaunched "
            f"(budget left "
            f"{st['supervisor']['relaunch_budget_left']})")
        lat = sorted(r["client_ms"] for r in res)
        RunRecord(
            kind="fleet", tool="tools.fleet_chaos_smoke",
            config={"level": "chaos_kill", "replicas": 2,
                    "mode": "seeded_sigkill"},
            metrics={"requests": len(res), "errors": 0,
                     "wave_ms": round(kill_ms, 3),
                     "p99_ms": round(lat[int(len(lat) * 0.99) - 1], 3),
                     "crashes": st["scale"]["crashes"],
                     "relaunches": st["scale"]["relaunches"]},
            device=device).append_jsonl(record)

        # -- 2. forced shard re-split under open-loop load -------------------
        traffic = TrafficWave(ready["port"], golden)
        traffic.start()
        rng = np.random.default_rng(7)
        far_labels, far_rows = [], []
        n0 = BASE_CORPUS["num_data"]
        fill = 35                       # 200 -> 235 >= 0.9 * 256
        for lo in range(0, fill, 5):
            labs = [int(v) for v in rng.integers(
                0, BASE_CORPUS["num_labels"], 5)]
            rows = rng.uniform(FAR_OFFSET, FAR_OFFSET + 50.0,
                               (5, BASE_CORPUS["num_attrs"]))
            far_labels += labs
            far_rows.append(rows)
            cli = sc.ServeClient(ready["port"])
            r = cli.ingest(labs, rows)
            cli.close()
            if not r.get("ok"):
                fail(f"far-row fill ingest failed: {r}")
        st = await_stats(
            ready["port"],
            lambda s: (s["scale"]["splits"] >= 2
                       and s.get("supervisor")
                       and len(s["supervisor"]["managed"]) >= 2
                       and all(m["capacity"] and m["capacity"] >= 512
                               for m in s["supervisor"]["managed"])),
            "both replicas re-split to the grown layout",
            timeout_s=600, proc=proc, errlog=errlog)
        traffic.stop_flag.set()
        traffic.join(timeout=120)
        if traffic.lost:
            fail(f"open-loop traffic lost {traffic.lost} responses "
                 "across the split")
        if traffic.transport_errors:
            fail(f"open-loop traffic saw non-rejection errors: "
                 f"{traffic.transport_errors[:3]}")
        if traffic.wrong:
            fail(f"{traffic.wrong} open-loop waves were not "
                 "byte-identical to the base oracle")
        grown = KNNInput(
            Params(n0 + fill, 0, BASE_CORPUS["num_attrs"]),
            np.concatenate([corpus.labels,
                            np.asarray(far_labels, np.int32)]),
            np.vstack([corpus.data_attrs] + far_rows),
            np.zeros(0, np.int32),
            np.zeros((0, BASE_CORPUS["num_attrs"])))
        res3 = sc.replay(ready["port"], HEADER, REQS[:6],
                         connections=2)
        want = sc.golden_reference(grown, HEADER, REQS[:6])
        if any(not r.get("ok") for r in res3) or \
                sc.contract_text([r["checksums"] for r in res3]) \
                != sc.contract_text(want):
            fail("post-split replay differs from the grown-corpus "
                 "oracle")
        sup = st["supervisor"]
        resharded_rcs = [e for e in sup["retired"]
                         if e["reason"] == "reshard"]
        if any(e["rc"] != 0 for e in resharded_rcs):
            fail(f"a re-split old replica exited nonzero: "
                 f"{resharded_rcs}")
        say(f"forced re-split OK: {st['scale']['splits']} staged "
            f"splits to capacity 512 under {traffic.waves} open-loop "
            f"waves ({traffic.rejected} explicit rejections, 0 lost), "
            f"grown-corpus replay golden, old replicas drained rc 0")
        tl = sorted(traffic.latencies) or [0.0]
        RunRecord(
            kind="fleet", tool="tools.fleet_chaos_smoke",
            config={"level": "chaos_split", "replicas": 2,
                    "mode": "forced_resplit_open_loop"},
            metrics={"requests": traffic.waves * len(REQS),
                     "errors": 0, "lost": 0,
                     "explicit_rejections": traffic.rejected,
                     "p99_ms": round(tl[int(len(tl) * 0.99) - 1], 3),
                     "splits": st["scale"]["splits"],
                     "grown_capacity_rows": 512,
                     "ingested_rows": fill},
            device=device).append_jsonl(record)

        # -- drain the supervised fleet (campaign 1+2 teardown) --------------
        cli = sc.ServeClient(ready["port"])
        cli.drain()
        cli.close()
        rc = proc.wait(timeout=300)
        if rc != 0:
            fail(f"supervised router drain exited {rc} (managed "
                 f"replica nonzero?); see {errlog}")
        say("supervised drain OK: router + every managed replica "
            "exited 0")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    # ---- campaign 3: injected ingest divergence (PR 7 fault site) ----------
    sched_path = os.path.join(out, "divergence_faults.json")
    with open(sched_path, "w") as f:
        json.dump({"schema": 1, "seed": 7, "faults": [
            {"site": "serve.ingest", "kind": "transient", "times": 1,
             "message": "seeded dropped ingest"}]}, f)
    ra = fh.spawn_replica(corpus_path, out, "replica_div_a", warm,
                          batch_cap=BATCH_CAP)
    rb = fh.spawn_replica(corpus_path, out, "replica_div_b", warm,
                          batch_cap=BATCH_CAP,
                          env_extra={"DMLP_TPU_FAULTS": sched_path})
    procs = [ra, rb]
    router = None
    try:
        for fp in (ra, rb):
            fh.await_replica(fp)
        ready_r = os.path.join(out, "router_div_ready.json")
        errlog_r = os.path.join(out, "router_div.err")
        cmd = [sys.executable, "-m", "dmlp_tpu.fleet",
               "--replicas",
               f"127.0.0.1:{ra.ready['port']},"
               f"127.0.0.1:{rb.ready['port']}",
               "--repair", "on", "--revive-probes", "2",
               "--health-interval-s", "0.2",
               "--port", "0", "--ready-file", ready_r,
               "--record", record]
        with open(errlog_r, "w") as ef:
            rproc = subprocess.Popen(cmd, stderr=ef,
                                     stdout=subprocess.DEVNULL,
                                     env=fh._repo_env(), cwd=out)
        router = fh.FleetProc("router_div", rproc, ready_r, errlog_r)
        router.ready = sc.await_ready(rproc, ready_r, timeout_s=120,
                                      errlog=errlog_r)
        procs.append(router)
        rng = np.random.default_rng(11)
        m = 6
        newl = [int(v) for v in rng.integers(
            0, BASE_CORPUS["num_labels"], m)]
        newa = rng.uniform(BASE_CORPUS["min_attr"],
                           BASE_CORPUS["max_attr"],
                           (m, BASE_CORPUS["num_attrs"]))
        cli = sc.ServeClient(router.ready["port"])
        r = cli.ingest(newl, newa)
        cli.close()
        if r.get("ok") or "diverged" not in str(r.get("error", "")):
            fail(f"seeded fault did not surface an ingest divergence: "
                 f"{r}")
        t_repair = time.perf_counter()
        st = await_stats(
            router.ready["port"],
            lambda s: (s["consistency"]["divergences"] >= 1
                       and s["consistency"]["repairs"] >= 1),
            "divergence detection + repair", timeout_s=120,
            proc=rproc, errlog=errlog_r)
        repair_ms = (time.perf_counter() - t_repair) * 1e3
        sigs = []
        for fp in (ra, rb):
            cli = sc.ServeClient(fp.ready["port"])
            doc = cli.call({"op": "corpus", "start": 0, "count": 0})
            cli.close()
            sigs.append((doc["corpus_rows"], doc["checksum"]))
        if sigs[0] != sigs[1] or sigs[0][0] != \
                BASE_CORPUS["num_data"] + m:
            fail(f"replicas did not converge after repair: {sigs}")
        grown = KNNInput(
            Params(BASE_CORPUS["num_data"] + m, 0,
                   BASE_CORPUS["num_attrs"]),
            np.concatenate([corpus.labels,
                            np.asarray(newl, np.int32)]),
            np.vstack([corpus.data_attrs, newa]),
            np.zeros(0, np.int32),
            np.zeros((0, BASE_CORPUS["num_attrs"])))
        res4 = sc.replay(router.ready["port"], HEADER, REQS[:8],
                         connections=2)
        want = sc.golden_reference(grown, HEADER, REQS[:8])
        if any(not r.get("ok") for r in res4) or \
                sc.contract_text([r["checksums"] for r in res4]) \
                != sc.contract_text(want):
            fail("post-repair replay differs from the grown-corpus "
                 "oracle")
        say(f"injected divergence OK: dropped ingest reported, "
            f"detected, and repaired in {repair_ms:.0f} ms "
            f"({st['consistency']['repaired_rows']} rows "
            "re-delivered), replay golden on the repaired fleet")
        RunRecord(
            kind="fleet", tool="tools.fleet_chaos_smoke",
            config={"level": "chaos_divergence", "replicas": 2,
                    "mode": "seeded_dropped_ingest"},
            metrics={"requests": len(res4), "errors": 0,
                     "divergences": st["consistency"]["divergences"],
                     "repairs": st["consistency"]["repairs"],
                     "repaired_rows":
                         st["consistency"]["repaired_rows"],
                     "repair_ms": round(repair_ms, 3)},
            device=device).append_jsonl(record)
        try:
            fh.drain_fleet(router, [ra, rb])
        except RuntimeError as e:
            fail(str(e))
    finally:
        fh.kill_all(procs)
    flights = sc.flight_dumps(out)
    if flights:
        fail(f"chaos campaigns left flight dumps: {flights}")
    say("divergence fleet drain OK: router + both replicas exited 0, "
        "no flight dumps")

    # ---- campaign 4: warm compile-cache relaunch ----------------------------
    # This campaign places the cache itself (cold arm = empty dir), so
    # an ambient placement, which would win over --compile-cache
    # (utils.compile_cache), is taken out of the replicas' environment.
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import shutil
    ccdir = os.path.join(out, "compile_cache")
    shutil.rmtree(ccdir, ignore_errors=True)   # cold arm = empty cache
    colds, counts, caches = [], [], []
    for gen in ("cold", "warm"):
        fp = fh.spawn_replica(corpus_path, out, f"replica_cc_{gen}",
                              warm, batch_cap=BATCH_CAP,
                              compile_cache=ccdir)
        try:
            fh.await_replica(fp)
            colds.append(fp.ready["cold_start_compile_ms"])
            counts.append(fp.ready["compile_count"])
            caches.append(fp.ready["compile_cache"])
            res5 = sc.replay(fp.ready["port"], HEADER, REQS[:4])
            if any(not r.get("ok") for r in res5) or \
                    sc.contract_text([r["checksums"] for r in res5]) \
                    != sc.contract_text(golden[:4]):
                fail(f"{gen}-cache replica does not serve golden")
            cli = sc.ServeClient(fp.ready["port"])
            cli.drain()
            cli.close()
            rc = fp.proc.wait(timeout=120)
            if rc != 0:
                fail(f"{gen}-cache replica drain exited {rc}; "
                     f"see {fp.errlog}")
        finally:
            fh.kill_all([fp])
    if counts[1] != counts[0]:
        fail(f"warm relaunch changed bucket compile_count: "
             f"{counts[0]} -> {counts[1]} (the cache must not alter "
             "which programs are built, only how fast)")
    # Counts, which repeat exactly; the two cold-start wall times are
    # recorded, not compared (two ~0.5 s CPU timings under a loaded
    # host swap places).
    cold, warm_c = caches
    if cold["hits"] or not cold["misses"] or warm_c["misses"] \
            or warm_c["hits"] != cold["misses"]:
        fail(f"warm relaunch was not served from the persistent "
             f"compile cache at {ccdir}: cold {cold} -> warm {warm_c}")
    say(f"warm-cache relaunch OK: {warm_c['hits']} programs served "
        f"from the cache, 0 compiled (cold start compiled "
        f"{cold['misses']}; {colds[0]:.0f} ms -> {colds[1]:.0f} ms), "
        f"compile_count flat at {counts[0]}, warm replies golden")
    RunRecord(
        kind="fleet", tool="tools.fleet_chaos_smoke",
        config={"level": "chaos_warm_cache", "replicas": 1,
                "mode": "persistent_compile_cache_relaunch"},
        metrics={"cold_start_compile_ms": colds[1],
                 "cold_start_compile_ms_cold": colds[0],
                 "compile_cache_hits": warm_c["hits"],
                 "compile_cache_misses": warm_c["misses"],
                 "compile_count": counts[1]},
        device=device).append_jsonl(record)

    # ---- the campaigns' records read back -----------------------------------
    back = {r.config.get("level"): r
            for r in RunRecord.load_all(record) if r.kind == "fleet"}
    for lvl, metric in (("chaos_kill", "p99_ms"),
                        ("chaos_split", "p99_ms"),
                        ("chaos_divergence", "repair_ms"),
                        ("chaos_warm_cache", "cold_start_compile_ms")):
        if lvl not in back or metric not in back[lvl].metrics:
            fail(f"no {lvl} RunRecord with {metric} "
                 f"(got {sorted(map(str, back))})")
    say(f"run records OK: {sorted(map(str, back))}")
    say("PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
