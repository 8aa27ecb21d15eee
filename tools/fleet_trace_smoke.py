#!/usr/bin/env python
"""Fleet tracing smoke: request-scoped tracing proven end to end.

The ``make fleet-trace-smoke`` checker (wired into ``make test``).
Five proofs over a real 2-replica fleet on CPU — every failure exits
nonzero with the reason named:

1. **Untraced golden arm** — the committed paced trace
   (inputs/serve_trace2.jsonl) replayed open-loop through an UNTRACED
   fleet: zero errors, contract checksums golden-identical.
2. **Traced arm byte-identity** — the same fleet topology with
   ``--trace`` on both replicas + the router and a sync-stamped client
   Tracer, replayed at x2 and x8 offered load with rid-stamped
   requests: zero errors, every response echoes its rid, and the
   contract checksums are byte-identical to the untraced arm AND the
   golden oracle (tracing must never perturb the contract channel).
3. **Causal merge** — ``tools/merge_traces.py --fleet`` aligns the
   four trace files on their ``fleet.clock_sync`` markers and
   stitches per-rid causal trees; a designated x8 request must be
   reconstructable end-to-end (client fire -> route -> hop -> queue ->
   coalesce -> solve -> finalize -> write) and its replica phase sum
   must reconcile against the client-measured latency within the
   documented tolerance.
4. **Validation teeth** — ``tools/check_trace.py --fleet --json``
   passes (rid uniqueness, span parentage, retry-hop accounting,
   canonical phase order, reconcile fraction >= 0.9) on the merged
   trace, and REJECTS a tampered copy carrying a fabricated attempt-2
   retry hop on a non-retried request.
5. **Tail attribution** — ``tools/tail_attrib.py`` decomposes the
   per-level p50/p95/p99 into per-phase contributions, names the
   dominant phase per level, and writes one ``tailattrib`` RunRecord
   a level, read back here.

Usage::

    python tools/fleet_trace_smoke.py --out outputs/fleet_trace \
        [--record outputs/fleet_trace/TAILATTRIB.jsonl] [--round N]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dmlp_tpu.fleet import harness as fh                  # noqa: E402
from dmlp_tpu.io.grammar import parse_input_text          # noqa: E402
from dmlp_tpu.obs import trace as obs_trace               # noqa: E402
from dmlp_tpu.serve import client as sc                   # noqa: E402

TRACE_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "inputs", "serve_trace2.jsonl")
BATCH_CAP = 32
PHASES = ("queue", "coalesce", "solve", "finalize", "write")


def fail(msg: str):
    print(f"fleet_trace_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"fleet_trace_smoke: {msg}")


def _spawn_fleet(corpus_path: str, out: str, warm: str,
                 traced: bool):
    """-> (replicas, router) in ``out`` (traced: --trace on all)."""
    reps = []
    for i in range(2):
        flags = (["--trace",
                  os.path.join(out, f"trace-replica{i:02d}.json")]
                 if traced else None)
        reps.append(fh.spawn_replica(corpus_path, out, f"replica{i:02d}",
                                     warm, batch_cap=BATCH_CAP,
                                     flags=flags))
    for fp in reps:
        fh.await_replica(fp)
    rflags = (["--trace", os.path.join(out, "trace-router.json")]
              if traced else None)
    router = fh.spawn_router(out, reps, flags=rflags)
    return reps, router


def _replay(router, header, reqs, speed, rid_prefix=None):
    res = sc.replay_open_loop(router.ready["port"], header, reqs,
                              speed=speed, rid_prefix=rid_prefix,
                              level=speed if rid_prefix else None)
    bad = [r for r in res if not r.get("ok")]
    if bad:
        fail(f"open-loop x{speed:g} replay had {len(bad)} failures "
             f"(rid_prefix={rid_prefix!r}): {bad[0]}")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="outputs/fleet_trace")
    ap.add_argument("--record", default=None)
    ap.add_argument("--round", type=int, default=None,
                    help="measurement round stamped into the "
                         "tailattrib records")
    args = ap.parse_args(argv)
    out = os.path.abspath(args.out)
    record = os.path.abspath(args.record) if args.record \
        else os.path.join(out, "TAILATTRIB.jsonl")
    tools = os.path.dirname(os.path.abspath(__file__))
    udir = os.path.join(out, "untraced")
    tdir = os.path.join(out, "traced")
    for d in (udir, tdir):
        os.makedirs(d, exist_ok=True)
    if os.path.exists(record):
        os.remove(record)
    sc.clear_flight_dumps(udir)
    sc.clear_flight_dumps(tdir)

    header, reqs = sc.load_trace(TRACE_PATH)
    corpus_txt = sc.corpus_text(header)
    corpus_path = os.path.join(out, "corpus.in")
    with open(corpus_path, "w") as f:
        f.write(corpus_txt)
    golden = sc.contract_text(sc.golden_reference(
        parse_input_text(corpus_txt), header, reqs))
    warm = ",".join(f"{q}x{k}" for q, k in
                    sc.warm_buckets_for_trace(reqs, BATCH_CAP))

    # 1. untraced golden arm
    reps, router = _spawn_fleet(corpus_path, udir, warm, traced=False)
    try:
        res_u = _replay(router, header, reqs, 2.0)
        fh.drain_fleet(router, reps)
    finally:
        fh.kill_all(reps + [router])
    if any("rid" in r for r in res_u):
        fail("untraced responses carry a rid key")
    cs_u = sc.contract_text([r["checksums"] for r in res_u])
    if cs_u != golden:
        fail("untraced arm responses differ from the golden oracle")
    say(f"untraced arm OK: {len(reqs)} responses golden-identical")

    # 2. traced arm: --trace fleet + sync-stamped client tracer
    reps, router = _spawn_fleet(corpus_path, tdir, warm, traced=True)
    client_tracer = obs_trace.install(obs_trace.Tracer())
    client_tracer.sync_instant("fleet.clock_sync")
    try:
        res_x2 = _replay(router, header, reqs, 2.0, rid_prefix="x2-")
        res_x8 = _replay(router, header, reqs, 8.0, rid_prefix="x8-")
        client_tracer.write(os.path.join(tdir, "trace-client.json"),
                            process_name="client")
        fh.drain_fleet(router, reps)
    finally:
        if obs_trace.active() is client_tracer:
            obs_trace.uninstall()
        fh.kill_all(reps + [router])
    for prefix, res in (("x2-", res_x2), ("x8-", res_x8)):
        for i, r in enumerate(res):
            if r.get("rid") != f"{prefix}{i}":
                fail(f"response {i} did not echo its rid: "
                     f"{r.get('rid')!r} != {prefix}{i!r}")
            if "hops" in r and int(r["hops"]) < 2:
                fail(f"rid {prefix}{i}: hops={r['hops']} surfaced on "
                     "a non-retried request")
    for tag, res in (("x2", res_x2), ("x8", res_x8)):
        if sc.contract_text([r["checksums"] for r in res]) != golden:
            fail(f"traced {tag} responses differ from the golden "
                 "oracle — tracing perturbed the contract channel")
    say("traced arm OK: x2 + x8 rid-echoed, checksums byte-identical "
        "to the untraced arm and the golden oracle")

    # 3. causal merge + end-to-end reconstruction of one x8 request
    merged_path = os.path.join(tdir, "trace-fleet-merged.json")
    rc = subprocess.call(
        [sys.executable, os.path.join(tools, "merge_traces.py"),
         tdir, "--fleet", "-o", merged_path], env=fh._repo_env())
    if rc != 0:
        fail("merge_traces --fleet failed")
    with open(merged_path) as f:
        merged = json.load(f)
    fleet = merged["fleet"]
    if sorted(fleet["processes"]) != ["client", "replica00",
                                      "replica01", "router"]:
        fail(f"merge missed a process: {sorted(fleet['processes'])}")
    probe = "x8-0"
    ent = fleet["requests"].get(probe)
    if not ent:
        fail(f"rid {probe} absent from the merged per-rid table")
    if not ent.get("client") or not ent.get("route") \
            or not ent.get("hops"):
        fail(f"rid {probe} causal tree incomplete: {ent}")
    missing = [p for p in PHASES if p not in ent.get("phases", {})]
    if missing:
        fail(f"rid {probe} lacks phase span(s) {missing}: {ent}")
    if ent.get("reconciled") is not True:
        fail(f"rid {probe} failed the phase-sum reconcile: {ent}")
    say(f"causal merge OK: {probe} reconstructed client->route->hop->"
        f"{'->'.join(PHASES)} (client {ent['client']['client_ms']} ms, "
        f"phase sum {ent['phase_sum_ms']} ms, residual "
        f"{ent['residual_ms']} ms)")

    # 4. check_trace --fleet passes; a tampered trace fails
    cp = subprocess.run(
        [sys.executable, os.path.join(tools, "check_trace.py"),
         "--fleet", merged_path, "--json", "--min-reconciled", "0.9"],
        capture_output=True, text=True, env=fh._repo_env())
    if cp.returncode != 0:
        fail(f"check_trace --fleet rejected the merged trace: "
             f"{cp.stderr.strip()[-500:]}")
    verdict = json.loads(cp.stdout)
    if verdict["rids"] < 2 * len(reqs):
        fail(f"check verdict covers {verdict['rids']} rids, expected "
             f">= {2 * len(reqs)}")
    tampered = dict(merged)
    tampered["traceEvents"] = list(merged["traceEvents"]) + [{
        "name": "fleet.hop", "ph": "X", "ts": 1.0, "dur": 1.0,
        "pid": 1, "tid": 0,
        "args": {"rid": probe, "attempt": 2, "replica": "fake",
                 "outcome": "ok"}}]
    tampered_path = os.path.join(tdir, "trace-tampered.json")
    with open(tampered_path, "w") as f:
        json.dump(tampered, f)
    rc = subprocess.call(
        [sys.executable, os.path.join(tools, "check_trace.py"),
         "--fleet", tampered_path], stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL, env=fh._repo_env())
    if rc == 0:
        fail("check_trace --fleet accepted a fabricated retry hop on "
             "a non-retried request")
    say(f"validation teeth OK: merged trace passes "
        f"({verdict['rids']} rids, reconcile fraction "
        f"{verdict['reconcile'].get('fraction')}), tampered trace "
        "rejected")

    # 5. tail attribution -> one tailattrib RunRecord a level
    cmd = [sys.executable, os.path.join(tools, "tail_attrib.py"),
           merged_path, "--record", record, "--json"]
    if args.round is not None:
        cmd += ["--round", str(args.round)]
    kind = (reps[0].ready.get("device") or {}).get("device_kind")
    if kind:
        cmd += ["--device", kind]
    cp = subprocess.run(cmd, capture_output=True, text=True,
                        env=fh._repo_env())
    if cp.returncode != 0:
        fail(f"tail_attrib failed: {cp.stderr.strip()[-500:]}")
    att = json.loads(cp.stdout)["levels"]
    if sorted(att) != ["x2", "x8"]:
        fail(f"tail_attrib levels {sorted(att)} != ['x2', 'x8']")
    for lvl, a in att.items():
        if a["dominant_p99"] not in PHASES:
            fail(f"{lvl}: dominant phase {a['dominant_p99']!r} is not "
                 "a known phase")
    from dmlp_tpu.obs.run import RunRecord
    back = {r.config.get("level"): r
            for r in RunRecord.load_all(record) if r.kind == "tailattrib"}
    for lvl, metric in (("x8", "queue_p99_ms"), ("x8", "solve_p99_ms"),
                        ("x2", "coalesce_p99_ms")):
        if lvl not in back or metric not in back[lvl].metrics:
            fail(f"no tailattrib RunRecord for {lvl} with {metric} "
                 f"(got {sorted(map(str, back))})")
    doms = {lvl: a["dominant_p99"] for lvl, a in sorted(att.items())}
    say(f"tail attribution OK: dominant phases {doms}, "
        f"{len(back)} tailattrib records -> {record}")

    flights = sc.flight_dumps(udir) + sc.flight_dumps(tdir)
    if flights:
        fail(f"orderly drains left flight dumps: {flights}")
    say("PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
