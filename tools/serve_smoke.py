#!/usr/bin/env python
"""Serve smoke: prove the online serving layer end to end on CPU.

The ``make serve-smoke`` checker (wired into ``make test``). Seven
proofs, every failure exits nonzero with the reason named:

1. **Cold start + ready contract** — the real daemon subprocess
   (``python -m dmlp_tpu.serve``) warms every bucket the replay can
   hit, writes the ready file with ``cold_start_compile_ms`` and the
   compile count, and announces the port.
2. **Replay bit-identity** — a short mixed-(nq, k) trace replayed over
   concurrent connections; every response's checksums must equal the
   float64 golden oracle's byte-for-byte (micro-batch padding/bucketing
   and per-request slicing change nothing).
3. **Compile-once** — the daemon's compile counter after the replay
   equals the ready-file value: steady-state serving never recompiles.
4. **Live scrape** — ``--telemetry-port``'s GET /metrics passes the
   OpenMetrics validator and carries the serve metric families.
5. **Admission shedding** — a fault schedule injects a memory squeeze
   (``serve.admit`` oom) mid-stream: the squeezed request is REJECTED
   (visible in the registry), the next request succeeds, and the
   degradation ladder never fires.
6. **Incremental ingestion** — rows appended over the wire; the next
   replay matches the golden oracle over the GROWN corpus with zero
   new solve compiles.
7. **Graceful drain** — SIGTERM finishes in-flight work, flushes the
   final snapshot + serve RunRecord, exits 0, leaves NO flight dump;
   the record loads (``RunRecord.load``) as kind ``serve`` with the
   daemon's request, latency and cold-start metrics.

Usage::

    python tools/serve_smoke.py --out outputs/serve \
        [--record outputs/serve/SERVE_SMOKE.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dmlp_tpu.io.grammar import KNNInput, Params, parse_input_text  # noqa: E402
from dmlp_tpu.obs.telemetry import validate_openmetrics   # noqa: E402
from dmlp_tpu.serve import client as sc                   # noqa: E402

CORPUS = dict(num_data=3000, num_queries=8, num_attrs=6, min_attr=0.0,
              max_attr=80.0, min_k=1, max_k=12, num_labels=5, seed=77)
HEADER = {"serve_trace_schema": 1, "corpus": CORPUS}
TRACE = [{"t_ms": i * 2, "nq": nq, "k": k, "seed": 7000 + i}
         for i, (nq, k) in enumerate(
             [(1, 1), (3, 7), (8, 8), (9, 9), (2, 12), (7, 3),
              (16, 5), (17, 2), (5, 11), (4, 8)])]
BATCH_CAP = 32


def fail(msg: str):
    print(f"serve_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"serve_smoke: {msg}")


def warm_spec() -> str:
    return ",".join(f"{q}x{k}" for q, k in
                    sc.warm_buckets_for_trace(TRACE, BATCH_CAP))


def scrape(port: int) -> str:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        return r.read().decode()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="outputs/serve")
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    record = os.path.abspath(args.record) if args.record \
        else os.path.join(out, "SERVE_SMOKE.jsonl")
    if os.path.exists(record):
        os.remove(record)

    corpus_txt = sc.corpus_text(HEADER)
    corpus_path = os.path.join(out, "corpus.in")
    with open(corpus_path, "w") as f:
        f.write(corpus_txt)
    corpus = parse_input_text(corpus_txt)
    golden = sc.golden_reference(corpus, HEADER, TRACE)

    # Injected memory squeeze: one oom fault at the admission site,
    # AFTER the replay's requests have all been admitted.
    faults_path = os.path.join(out, "squeeze_faults.json")
    with open(faults_path, "w") as f:
        json.dump({"schema": 1, "seed": 1, "faults": [
            {"site": "serve.admit", "kind": "oom", "times": 1,
             "after": len(TRACE)}]}, f)

    ready = os.path.join(out, "ready.json")
    telem = os.path.join(out, "serve_telemetry.prom")
    for stale in (ready, telem):
        if os.path.exists(stale):
            os.remove(stale)
    # Flight dumps left by a previous CRASHED run must not fail this
    # run's orderly-drain assertion.
    sc.clear_flight_dumps(out)
    errlog = os.path.join(out, "daemon.err")
    cmd = [sys.executable, "-m", "dmlp_tpu.serve",
           "--corpus", corpus_path, "--port", "0",
           "--ready-file", ready, "--warm-buckets", warm_spec(),
           "--max-batch-queries", str(BATCH_CAP),
           "--telemetry", telem, "--telemetry-port", "0",
           "--record", record, "--faults", faults_path,
           "--tick-ms", "2"]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,      # platform inherited from the caller
               PYTHONPATH=repo + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    with open(errlog, "w") as ef:
        proc = subprocess.Popen(cmd, stderr=ef,
                                stdout=subprocess.DEVNULL, env=env,
                                cwd=out)
    try:
        try:
            rdoc = sc.await_ready(proc, ready, timeout_s=300,
                                  errlog=errlog)
        except RuntimeError as e:
            fail(str(e))
        if not rdoc.get("cold_start_compile_ms"):
            fail("ready file carries no cold_start_compile_ms")
        say(f"ready: port={rdoc['port']} "
            f"cold_start={rdoc['cold_start_compile_ms']} ms, "
            f"{rdoc['compile_count']} bucket compiles")

        # 2. replay bit-identity
        res = sc.replay(rdoc["port"], HEADER, TRACE, connections=3)
        bad = [r for r in res if not r.get("ok")]
        if bad:
            fail(f"replay had {len(bad)} failed responses: {bad[0]}")
        if sc.contract_text([r["checksums"] for r in res]) != \
                sc.contract_text(golden):
            fail("replay responses differ from the golden oracle")
        say(f"replay OK: {len(TRACE)} mixed-(nq, k) requests "
            "byte-identical to the golden oracle")

        # 3. compile-once — the counter AND the schedule: a recompile
        # landing on a different program can't hide behind a
        # coincidentally flat compile_count (obs.hlo fingerprints).
        cli = sc.ServeClient(rdoc["port"])
        stats = cli.stats()["stats"]
        if stats["engine"]["compile_count"] != rdoc["compile_count"]:
            fail(f"compile counter moved {rdoc['compile_count']} -> "
                 f"{stats['engine']['compile_count']}: a request "
                 "recompiled")
        sched0 = rdoc.get("hlo_schedule", {})
        if stats["engine"].get("hlo_schedule", {}) != sched0:
            fail(f"per-bucket HLO schedule changed across the replay: "
                 f"{sched0} -> {stats['engine'].get('hlo_schedule')}")
        say(f"compile-once OK: counter pinned at "
            f"{rdoc['compile_count']} and {len(sched0)} bucket "
            "fingerprint(s) unchanged across the replay")

        # 4. live scrape
        http_port = None
        text = open(telem).read() if os.path.exists(telem) else ""
        for ln in text.splitlines():
            if ln.startswith("telemetry_http_port"):
                http_port = int(float(ln.split()[-1]))
        if http_port is None:
            # fall back: scrape port gauge via stats is not exposed;
            # the snapshot file must carry it
            fail("telemetry snapshot carries no telemetry_http_port")
        om = scrape(http_port)
        errs = validate_openmetrics(om)
        if errs:
            fail(f"OpenMetrics validation: {errs[:3]}")
        for want in ("serve_requests_completed", "serve_queue_depth",
                     "serve_request_latency_ms"):
            if want not in om:
                fail(f"scrape missing {want}")
        say("live scrape OK: OpenMetrics valid with serve metrics")

        # 5. admission shedding under the injected squeeze
        q1 = sc.materialize_queries({"nq": 2, "seed": 9901}, HEADER)
        r = cli.query(q1, k=3, req_id="squeezed")
        if r.get("ok") or "injected_squeeze" not in r.get("error", ""):
            fail(f"squeezed request was not shed: {r}")
        r = cli.query(q1, k=3, req_id="after-squeeze")
        if not r.get("ok"):
            fail(f"request after the squeeze failed: {r}")
        om = scrape(http_port)
        if 'serve_rejected_total{key="injected_squeeze"}' not in om:
            fail("rejection not visible in the registry scrape")
        for ln in om.splitlines():
            if ln.startswith("resilience_degradations_total") \
                    and float(ln.split()[-1]) > 0:
                fail("the degradation ladder fired under the squeeze")
        say("admission OK: injected squeeze shed the request "
            "(visible in the registry), no ladder degradation")

        # 6. incremental ingestion
        import numpy as np
        rng = np.random.default_rng(5)
        newl = rng.integers(0, CORPUS["num_labels"], 7).astype(int)
        newa = rng.uniform(CORPUS["min_attr"], CORPUS["max_attr"],
                          (7, CORPUS["num_attrs"]))
        r = cli.ingest([int(v) for v in newl], newa)
        if not r.get("ok") or r["corpus_rows"] != CORPUS["num_data"] + 7:
            fail(f"ingest failed: {r}")
        grown = KNNInput(
            Params(CORPUS["num_data"] + 7, 0, CORPUS["num_attrs"]),
            np.concatenate([corpus.labels, newl.astype(np.int32)]),
            np.vstack([corpus.data_attrs, newa]),
            np.zeros(0, np.int32), np.zeros((0, CORPUS["num_attrs"])))
        res2 = sc.replay(rdoc["port"], HEADER, TRACE[:4], connections=2)
        want = sc.golden_reference(grown, HEADER, TRACE[:4])
        if [r["checksums"] for r in res2] != want:
            fail("post-ingest responses differ from the golden oracle "
                 "over the grown corpus")
        stats = cli.stats()["stats"]
        if stats["engine"]["compile_count"] != rdoc["compile_count"]:
            fail("ingestion recompiled a solve program")
        if stats["engine"].get("hlo_schedule", {}) != sched0:
            fail("ingestion changed a bucket's compiled-program "
                 "fingerprint")
        say("ingestion OK: grown-corpus replay golden-identical, "
            "zero new solve compiles, schedule fingerprints unchanged")
        cli.close()

        # 7. graceful drain
        try:
            sc.sigterm_drain(proc, errlog=errlog)
        except RuntimeError as e:
            fail(str(e))
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    flights = sc.flight_dumps(out)
    if flights:
        fail(f"orderly drain left flight dumps: {flights}")
    if not os.path.exists(telem):
        fail("no final telemetry snapshot after drain")
    errs = validate_openmetrics(open(telem).read())
    if errs:
        fail(f"final snapshot invalid: {errs[:3]}")
    say("drain OK: exit 0, final snapshot valid, no flight dump")

    from dmlp_tpu.obs.run import RunRecord
    rec = RunRecord.load(record)
    if rec.kind != "serve":
        fail(f"serve RunRecord has kind {rec.kind!r}")
    for want in ("requests_per_sec", "request_latency_p50_ms",
                 "cold_start_compile_ms"):
        if want not in rec.metrics:
            fail(f"serve RunRecord lacks {want} "
                 f"(got {sorted(rec.metrics)})")
    say(f"serve RunRecord OK: {len(rec.metrics)} metrics")
    say("PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
