#!/usr/bin/env python
"""Fleet smoke: prove the serving fleet end to end on CPU.

The ``make fleet-smoke`` checker (wired into ``make test``). Eight
proofs over a REAL fleet — a plain resident replica + a MESH-RESIDENT
replica (``--mesh 2x1``, per-shard resident buffers with the
allgather merge as the micro-batch epilogue) behind the
``python -m dmlp_tpu.fleet`` router — every failure exits nonzero with
the reason named:

1. **Fleet ready** — both replicas warm their buckets and announce;
   the router probes them healthy.
2. **Routed byte-identity** — the committed paced trace
   (inputs/serve_trace2.jsonl) replayed closed-loop THROUGH the
   router: every response byte-identical to the float64 golden oracle,
   and both replicas actually served traffic.
3. **Compile-once across the fleet** — each replica's compile counter
   after the replay equals its ready-file value (the mesh-resident
   path included).
4. **Paced open-loop SLO curve** — the trace's t_ms schedule replayed
   open-loop at two offered-load multipliers; the per-level
   p50/p95/p99 land in one kind="fleet" RunRecord a level, read back
   at the end (``RunRecord.load_all``).
5. **Wide-k multipass serving** — a separate extract-path daemon
   serves k past the kernel's single-pass window through the
   multipass driver against its RESIDENT chunks: response golden,
   bucket path "multipass", passes > 1, compile counter flat.
6. **Fleet ingest** — rows ingested once through the router fan out
   to EVERY replica; the next routed replay matches the golden oracle
   over the GROWN corpus with zero new solve compiles on either
   replica.
7. **Aggregated scrape** — the router's /metrics merges both
   replicas' live scrapes (counters summed, histograms bucket-wise,
   per-replica gauges) into one exposition that passes
   validate_openmetrics; tools/fleet_scrape.py agrees. Trace
   validation teeth: check_serve_trace accepts the committed trace
   and rejects a non-monotonic one.
8. **Fleet drain** — one in-band drain propagates router -> replicas;
   every process exits 0, no flight dumps.

Usage::

    python tools/fleet_smoke.py --out outputs/fleet \
        [--record outputs/fleet/FLEET_SMOKE.jsonl]
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

from dmlp_tpu.fleet import harness as fh                  # noqa: E402
from dmlp_tpu.fleet import loadgen                        # noqa: E402
from dmlp_tpu.io.grammar import KNNInput, Params, parse_input_text  # noqa: E402
from dmlp_tpu.obs.telemetry import validate_openmetrics   # noqa: E402
from dmlp_tpu.serve import client as sc                   # noqa: E402

TRACE_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "inputs", "serve_trace2.jsonl")
BATCH_CAP = 32

WIDEK_CORPUS = dict(num_data=1408, num_queries=4, num_attrs=4,
                    min_attr=0.0, max_attr=60.0, min_k=1, max_k=8,
                    num_labels=5, seed=41)
WIDEK_HEADER = {"serve_trace_schema": 1, "corpus": WIDEK_CORPUS}
WIDEK_TRACE = [{"t_ms": 0, "nq": 2, "ks": [520, 600], "seed": 4100}]


def fail(msg: str):
    print(f"fleet_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"fleet_smoke: {msg}")


def scrape(port: int) -> str:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        return r.read().decode()


def replica_stats(port: int) -> dict:
    cli = sc.ServeClient(port)
    try:
        return cli.stats()["stats"]
    finally:
        cli.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="outputs/fleet")
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    record = os.path.abspath(args.record) if args.record \
        else os.path.join(out, "FLEET_SMOKE.jsonl")
    if os.path.exists(record):
        os.remove(record)
    sc.clear_flight_dumps(out)

    header, reqs = sc.load_trace(TRACE_PATH)
    corpus_txt = sc.corpus_text(header)
    corpus_path = os.path.join(out, "corpus.in")
    with open(corpus_path, "w") as f:
        f.write(corpus_txt)
    corpus = parse_input_text(corpus_txt)
    golden = sc.golden_reference(corpus, header, reqs)
    warm = ",".join(f"{q}x{k}" for q, k in
                    sc.warm_buckets_for_trace(reqs, BATCH_CAP))

    # 1. fleet up: plain resident replica + mesh-resident replica
    ra = fh.spawn_replica(corpus_path, out, "replica_a", warm,
                          batch_cap=BATCH_CAP)
    rb = fh.spawn_replica(
        corpus_path, out, "replica_b", warm, batch_cap=BATCH_CAP,
        flags=["--mesh", "2x1"],
        env_extra={"XLA_FLAGS":
                   "--xla_force_host_platform_device_count=2"})
    procs = [ra, rb]
    router = None
    try:
        for fp in (ra, rb):
            try:
                fh.await_replica(fp)
            except RuntimeError as e:
                fail(str(e))
        router = fh.spawn_router(out, [ra, rb])
        procs.append(router)
        say(f"fleet ready: router port={router.ready['port']} over "
            f"plain(:{ra.ready['port']}) + mesh 2x1(:{rb.ready['port']})"
            f", cold starts {ra.ready['cold_start_compile_ms']} / "
            f"{rb.ready['cold_start_compile_ms']} ms")

        # 2. routed byte-identity
        res = sc.replay(router.ready["port"], header, reqs,
                        connections=3)
        bad = [r for r in res if not r.get("ok")]
        if bad:
            fail(f"routed replay had {len(bad)} failures: {bad[0]}")
        if sc.contract_text([r["checksums"] for r in res]) != \
                sc.contract_text(golden):
            fail("routed responses differ from the golden oracle")
        st = replica_stats(router.ready["port"])
        served = {r["replica"]: r["requests"] for r in st["replicas"]}
        if any(v == 0 for v in served.values()):
            fail(f"a replica served nothing: {served}")
        say(f"routed replay OK: {len(reqs)} requests golden-identical, "
            f"fanned {served}")

        # 3. compile-once per replica (mesh-resident included)
        for fp in (ra, rb):
            eng = replica_stats(fp.ready["port"])["engine"]
            if eng["compile_count"] != fp.ready["compile_count"]:
                fail(f"{fp.name} compile counter moved "
                     f"{fp.ready['compile_count']} -> "
                     f"{eng['compile_count']}")
        say("compile-once OK on both replicas")

        # 4. paced open-loop SLO levels -> one fleet RunRecord a level
        recs = loadgen.run_levels(router.ready["port"], header, reqs,
                                  speeds=[2.0, 8.0], reps=2,
                                  replicas=2, trace="serve_trace2")
        for rec in recs:
            if rec.metrics.get("errors"):
                fail(f"open-loop level {rec.config['level']} had "
                     f"errors: {rec.metrics}")
            if "p99_ms" not in rec.metrics:
                fail(f"level {rec.config['level']} recorded no p99")
            rec.append_jsonl(record)
        say("open-loop OK: "
            + "; ".join(f"{r.config['level']}: offered "
                        f"{r.metrics.get('offered_qps')} qps, p99 "
                        f"{r.metrics['p99_ms']} ms" for r in recs))

        # 5. wide-k multipass serving (extract-path daemon, resident
        # chunks, k past the single-pass window)
        wk_txt = sc.corpus_text(WIDEK_HEADER)
        wk_path = os.path.join(out, "widek_corpus.in")
        with open(wk_path, "w") as f:
            f.write(wk_txt)
        wk_corpus = parse_input_text(wk_txt)
        wk_golden = sc.golden_reference(wk_corpus, WIDEK_HEADER,
                                        WIDEK_TRACE)
        wd = fh.spawn_replica(
            wk_path, out, "replica_widek", "2x600", batch_cap=8,
            flags=["--pallas", "--select", "extract",
                   "--data-block", "512"])
        procs.append(wd)
        try:
            fh.await_replica(wd, timeout_s=600)
            wres = sc.replay(wd.ready["port"], WIDEK_HEADER,
                             WIDEK_TRACE, connections=1)
            if not wres[0].get("ok"):
                fail(f"wide-k request failed: {wres[0]}")
            if [r["checksums"] for r in wres] != wk_golden:
                fail("wide-k response differs from the golden oracle")
            weng = replica_stats(wd.ready["port"])["engine"]
            if "multipass" not in weng["paths"].values():
                fail(f"wide-k bucket did not take the multipass path: "
                     f"{weng['paths']}")
            if weng["compile_count"] != wd.ready["compile_count"]:
                fail("wide-k replay recompiled")
            sc.sigterm_drain(wd.proc, errlog=wd.errlog)
        finally:
            fh.kill_all([wd])
        say(f"wide-k multipass OK: k=600 served golden on the resident "
            f"chunks ({weng['paths']}), compile flat")

        # 6. fleet ingest fan-out
        import numpy as np
        rng = np.random.default_rng(5)
        m = 7
        newl = rng.integers(0, header["corpus"]["num_labels"],
                            m).astype(int)
        newa = rng.uniform(header["corpus"]["min_attr"],
                           header["corpus"]["max_attr"],
                           (m, header["corpus"]["num_attrs"]))
        cli = sc.ServeClient(router.ready["port"])
        r = cli.ingest([int(v) for v in newl], newa)
        cli.close()
        n0 = header["corpus"]["num_data"]
        if not r.get("ok") or r.get("corpus_rows") != n0 + m:
            fail(f"fleet ingest failed: {r}")
        for fp in (ra, rb):
            eng = replica_stats(fp.ready["port"])["engine"]
            if eng["corpus_rows"] != n0 + m:
                fail(f"{fp.name} missed the ingest fan-out: "
                     f"{eng['corpus_rows']}")
        grown = KNNInput(
            Params(n0 + m, 0, header["corpus"]["num_attrs"]),
            np.concatenate([corpus.labels, newl.astype(np.int32)]),
            np.vstack([corpus.data_attrs, newa]),
            np.zeros(0, np.int32),
            np.zeros((0, header["corpus"]["num_attrs"])))
        res2 = sc.replay(router.ready["port"], header, reqs[:6],
                         connections=2)
        want = sc.golden_reference(grown, header, reqs[:6])
        if sc.contract_text([r["checksums"] for r in res2]) != \
                sc.contract_text(want):
            fail("post-ingest routed replay differs from the grown-"
                 "corpus oracle")
        for fp in (ra, rb):
            eng = replica_stats(fp.ready["port"])["engine"]
            if eng["compile_count"] != fp.ready["compile_count"]:
                fail(f"{fp.name}: ingest recompiled a solve program")
        say("fleet ingest OK: fanned to both replicas, grown-corpus "
            "replay golden, zero new compiles")

        # 7. aggregated scrape + trace-validation teeth
        om = scrape(router.ready["telemetry_port"])
        errs = validate_openmetrics(om)
        if errs:
            fail(f"fleet scrape invalid: {errs[:3]}")
        for want_m in ("serve_requests_completed",
                       "fleet_request_latency_ms",
                       'replica="127.0.0.1:'):
            if want_m not in om:
                fail(f"fleet scrape missing {want_m!r}")
        tools = os.path.dirname(os.path.abspath(__file__))
        rc = subprocess.call(
            [sys.executable, os.path.join(tools, "fleet_scrape.py"),
             f"http://127.0.0.1:{ra.scrape_port}/metrics",
             f"http://127.0.0.1:{rb.scrape_port}/metrics",
             "--out", os.path.join(out, "fleet_scrape.prom")],
            stdout=subprocess.DEVNULL, env=fh._repo_env())
        if rc != 0:
            fail("tools/fleet_scrape.py rejected the replica scrapes")
        rc = subprocess.call(
            [sys.executable, os.path.join(tools, "check_serve_trace.py"),
             TRACE_PATH], stdout=subprocess.DEVNULL,
            env=fh._repo_env())
        if rc != 0:
            fail("check_serve_trace rejected the committed trace")
        bad_path = os.path.join(out, "bad_trace.jsonl")
        with open(bad_path, "w") as f:
            f.write(json.dumps(
                {"serve_trace_schema": 1,
                 "corpus": header["corpus"]}) + "\n")
            f.write('{"t_ms": 5, "nq": 1, "k": 1, "seed": 1}\n')
            f.write('{"t_ms": 3, "nq": 1, "k": 1, "seed": 2}\n')
        rc = subprocess.call(
            [sys.executable, os.path.join(tools, "check_serve_trace.py"),
             bad_path], stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL, env=fh._repo_env())
        if rc == 0:
            fail("check_serve_trace accepted a non-monotonic trace")
        say("aggregated scrape OK (valid, per-replica labels); trace "
            "validation has teeth")

        # 8. fleet drain
        try:
            fh.drain_fleet(router, [ra, rb])
        except RuntimeError as e:
            fail(str(e))
    finally:
        fh.kill_all(procs)
    flights = sc.flight_dumps(out)
    if flights:
        fail(f"orderly fleet drain left flight dumps: {flights}")
    say("fleet drain OK: router + both replicas exited 0, no flight "
        "dumps")

    from dmlp_tpu.obs.run import RunRecord
    back = RunRecord.load_all(record)
    levels = {r.config.get("level"): r for r in back
              if r.kind == "fleet"}
    for lvl in ("x2", "x8"):
        if lvl not in levels:
            fail(f"no fleet RunRecord for level {lvl} "
                 f"(got {sorted(map(str, levels))})")
        for m in ("p99_ms", "offered_qps"):
            if m not in levels[lvl].metrics:
                fail(f"level {lvl} record lacks {m}")
    say(f"run records OK: {len(back)} fleet records, levels "
        f"{sorted(map(str, levels))}")
    say("PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
