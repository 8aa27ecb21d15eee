"""Request-scoped fleet tracing: rid plumbing under races, the causal
merge, and tail attribution.

The integration tests run a REAL in-process fleet (daemons + router
share this process's Tracer — complete_at spans from every layer land
in one event list) and race it: a replica crash mid-request, a drain
racing a query wave, ingest concurrent with queries. The tool tests
drive tools/merge_traces.py --fleet, tools/check_trace.py --fleet and
tools/tail_attrib.py on synthetic per-process traces with KNOWN clock
offsets and phase durations, so alignment and reconcile arithmetic are
asserted exactly, not just smoke-level.
"""

import json
import socket
import threading

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.fleet.router import FleetRouter
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve import client as sc
from dmlp_tpu.serve.daemon import ServeDaemon


def make_corpus(n=300, na=4, labels=4, seed=3, spread=50.0) -> KNNInput:
    rng = np.random.default_rng(seed)
    return KNNInput(Params(n, 0, na),
                    rng.integers(0, labels, n).astype(np.int32),
                    rng.uniform(0.0, spread, (n, na)),
                    np.zeros(0, np.int32), np.zeros((0, na)))


def _start_daemon(corpus, **kw):
    kw.setdefault("tick_s", 0.001)
    d = ServeDaemon(corpus, kw.pop("config", EngineConfig()), port=0,
                    **kw)
    d.start()
    return d


def _query(port, corpus, rid, nq=2, seed=61, k=8):
    rng = np.random.default_rng(seed)
    q = rng.uniform(0.0, 50.0, (nq, corpus.params.num_attrs))
    cli = sc.ServeClient(port)
    try:
        return cli.call({"op": "query", "id": rid, "rid": rid,
                         "queries": q.tolist(), "k": k})
    finally:
        cli.close()


@pytest.fixture
def tracer():
    t = obs_trace.install(obs_trace.Tracer())
    t.sync_instant("fleet.clock_sync")
    yield t
    obs_trace.uninstall()


def _spans(tracer, name, rid=None):
    out = []
    for e in tracer.to_dict()["traceEvents"]:
        if e.get("ph") != "X" or e.get("name") != name:
            continue
        if rid is not None and e.get("args", {}).get("rid") != rid:
            continue
        out.append(e)
    return out


class _CrashingReplica:
    """Healthy to stats probes, closes the connection on any query."""

    def __init__(self):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            with conn:
                try:
                    doc = json.loads(conn.makefile("rb").readline())
                    if doc.get("op") == "stats":
                        conn.sendall(json.dumps(
                            {"ok": True, "stats": {"admission":
                             {"draining": False}}}).encode() + b"\n")
                    elif doc.get("op") == "drain":
                        conn.sendall(b'{"ok": true, "draining": true}\n')
                except (OSError, ValueError):
                    pass

    def close(self):
        self.sock.close()


# ---------------------------------------------------------------------------
# races
# ---------------------------------------------------------------------------


def test_rid_survives_crash_retry_with_two_hop_spans(tracer):
    """One rid, one crashed attempt, one successful retry: the causal
    tree shows BOTH replica attempts as child hop spans of one route
    span, and the response admits hops=2."""
    corpus = make_corpus()
    d1 = _start_daemon(corpus, warm_buckets=[(2, 8)])
    crasher = _CrashingReplica()
    router = FleetRouter([("127.0.0.1", crasher.port),
                          ("127.0.0.1", d1.port)], port=0,
                         health_interval_s=600)
    router.start()
    try:
        # Route until one request actually hits the crasher first (the
        # picker balances by load, so the first try may land healthy).
        retried = None
        for i in range(6):
            r = _query(router.port, corpus, f"race-{i}")
            assert r["ok"], r
            assert r["rid"] == f"race-{i}"
            if r.get("hops"):
                retried = r
                break
        assert retried is not None, "no request was ever retried"
        rid = retried["rid"]
        assert retried["hops"] == 2
        hops = _spans(tracer, "fleet.hop", rid=rid)
        assert len(hops) == 2, hops
        assert sorted(h["args"]["attempt"] for h in hops) == [1, 2]
        outcomes = [h["args"]["outcome"] for h in hops]
        assert outcomes[0].startswith("error_"), outcomes
        assert outcomes[1] == "ok", outcomes
        (route,) = _spans(tracer, "fleet.route", rid=rid)
        assert route["args"]["hops"] == 2
        assert route["args"]["outcome"] == "ok"
        # The surviving replica's phase spans carry the same rid.
        assert _spans(tracer, "serve.phase.solve", rid=rid)
        assert _spans(tracer, "serve.phase.queue", rid=rid)
    finally:
        router.close()
        d1.close()
        crasher.close()


def test_drain_racing_query_wave_sheds_with_terminal_spans(tracer):
    """Requests shed by a draining router still produce their terminal
    fleet.route span — the merged tree explains every rid."""
    corpus = make_corpus()
    d1 = _start_daemon(corpus, warm_buckets=[(2, 8)])
    router = FleetRouter([("127.0.0.1", d1.port)], port=0,
                         health_interval_s=600)
    router.start()
    try:
        out = {}

        def worker(rid):
            out[rid] = _query(router.port, corpus, rid)

        pre = [threading.Thread(target=worker, args=(f"w-{i}",))
               for i in range(3)]
        for t in pre:
            t.start()
        for t in pre:
            t.join(timeout=60)
        with router._lock:          # the drain hits mid-wave
            router._draining = True
        post = [threading.Thread(target=worker, args=(f"w-{i}",))
                for i in range(3, 6)]
        for t in post:
            t.start()
        for t in post:
            t.join(timeout=60)
        assert len(out) == 6
        for i in range(6):
            rid = f"w-{i}"
            routes = _spans(tracer, "fleet.route", rid=rid)
            assert len(routes) == 1, (rid, routes)
            if i < 3:
                assert out[rid]["ok"], out[rid]
                assert routes[0]["args"]["outcome"] == "ok"
            else:
                assert not out[rid]["ok"]
                assert "draining" in out[rid]["error"]
                assert routes[0]["args"]["outcome"] == \
                    "rejected_draining"
    finally:
        router.close()
        d1.close()


def test_concurrent_ingest_and_queries_never_share_a_rid(tracer):
    """Ingest fan-out is traced (fanout hop spans + replica ingest
    phases) but its rid never mixes with query rids — the cross-op
    uniqueness check_trace --fleet enforces."""
    corpus = make_corpus()
    d1 = _start_daemon(corpus, warm_buckets=[(2, 8)])
    d2 = _start_daemon(corpus, warm_buckets=[(2, 8)])
    router = FleetRouter([("127.0.0.1", d1.port),
                          ("127.0.0.1", d2.port)], port=0,
                         health_interval_s=600)
    router.start()
    try:
        rng = np.random.default_rng(7)
        rows = rng.uniform(0.0, 50.0, (5, corpus.params.num_attrs))
        results = {}

        def do_ingest():
            cli = sc.ServeClient(router.port)
            try:
                results["ing"] = cli.call(
                    {"op": "ingest", "id": "ing", "rid": "ing-0",
                     "labels": [0, 1, 2, 3, 0],
                     "rows": rows.tolist()})
            finally:
                cli.close()

        def do_query(i):
            results[f"q-{i}"] = _query(router.port, corpus, f"q-{i}")

        threads = [threading.Thread(target=do_ingest)] + \
            [threading.Thread(target=do_query, args=(i,))
             for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert results["ing"]["ok"], results["ing"]
        assert results["ing"]["rid"] == "ing-0"
        ing_hops = _spans(tracer, "fleet.hop", rid="ing-0")
        assert len(ing_hops) == 2            # fan-out to BOTH replicas
        assert all(h["args"].get("fanout") for h in ing_hops)
        assert _spans(tracer, "serve.phase.ingest", rid="ing-0")
        query_rids = set()
        for h in _spans(tracer, "fleet.hop"):
            if "attempt" in h["args"]:
                query_rids.add(h["args"]["rid"])
        assert query_rids == {f"q-{i}" for i in range(4)}
        assert "ing-0" not in query_rids
        for i in range(4):
            assert results[f"q-{i}"]["ok"]
    finally:
        router.close()
        d1.close()
        d2.close()


def test_untraced_requests_emit_no_spans_and_echo_no_rid():
    """Zero-cost default: no sink installed, no rid sent — the daemon
    answers byte-identically to the pre-rid protocol and the tracer
    hook stays cold."""
    assert not obs_trace.sinks_active()
    corpus = make_corpus()
    d1 = _start_daemon(corpus, warm_buckets=[(2, 8)])
    try:
        rng = np.random.default_rng(61)
        q = rng.uniform(0.0, 50.0, (2, corpus.params.num_attrs))
        cli = sc.ServeClient(d1.port)
        r = cli.call({"op": "query", "id": "0", "queries": q.tolist(),
                      "k": 8})
        cli.close()
        assert r["ok"]
        assert "rid" not in r
    finally:
        d1.close()


# ---------------------------------------------------------------------------
# merge / check / attribution tools on synthetic traces
# ---------------------------------------------------------------------------


def _doc(pid, pname, sync_ts, sync_unix_ms, events):
    evs = [{"name": "process_name", "ph": "M", "pid": pid,
            "args": {"name": pname}},
           {"name": "fleet.clock_sync", "ph": "i", "ts": sync_ts,
            "s": "t", "pid": pid, "tid": 0,
            "args": {"unix_ms": sync_unix_ms}}]
    return {"traceEvents": evs + events, "displayTimeUnit": "ms",
            "clock": {"source": "monotonic"}}


def _x(name, ts, dur, pid, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": pid,
            "tid": 0, "args": args}


def _write_fleet_dir(tmp_path, client_ms=20.0, phases=None):
    phases = phases or {"queue": 2.0, "coalesce": 1.0, "solve": 10.0,
                        "finalize": 1.0, "write": 0.5}
    rid = "r-0"
    client = _doc(4242, "client", 0.0, 999.9, [
        _x("client.request", 1000.0, client_ms * 1e3, 4242, rid=rid,
           lag_ms=0.5, ok=True, hops=1, level=4.0)])
    router = _doc(4343, "router", 500.0, 1000.0, [
        _x("fleet.route", 2000.0, 18000.0, 4343, op="query", rid=rid,
           outcome="ok", hops=1),
        _x("fleet.hop", 2100.0, 17000.0, 4343, attempt=1,
           replica="127.0.0.1:1", outcome="ok", rid=rid)])
    t = 99000.0
    pevs = []
    for ph in ("queue", "coalesce", "solve", "finalize", "write"):
        pevs.append(_x(f"serve.phase.{ph}", t, phases[ph] * 1e3, 4444,
                       rid=rid))
        t += phases[ph] * 1e3
    replica = _doc(4444, "serve:1", 99000.0, 1000.2, pevs)
    for fname, doc in (("trace-client.json", client),
                       ("trace-router.json", router),
                       ("trace-replica00.json", replica)):
        (tmp_path / fname).write_text(json.dumps(doc))
    return rid


def test_merge_fleet_aligns_clocks_and_reconciles(tmp_path):
    from tools.merge_traces import merge_fleet
    rid = _write_fleet_dir(tmp_path)
    merged = merge_fleet(str(tmp_path))
    off = merged["fleet"]["clock_offsets_us"]
    # off_p = ts_sync_ref - ts_sync_p + (unix_p - unix_ref) * 1000
    assert off["router"] == 0.0
    assert off["client"] == pytest.approx(500.0 - 0.0 - 100.0)
    assert off["replica00"] == pytest.approx(500.0 - 99000.0 + 200.0)
    assert all(e["ts"] >= 0 for e in merged["traceEvents"]
               if "ts" in e)
    # pids reassigned: client 0, router 1, replica 10
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert pids == {0, 1, 10}
    ent = merged["fleet"]["requests"][rid]
    assert ent["client"]["client_ms"] == pytest.approx(20.0)
    assert ent["phase_sum_ms"] == pytest.approx(14.5)
    # residual = 20.0 - 0.5 - 14.5
    assert ent["residual_ms"] == pytest.approx(5.0)
    assert ent["reconciled"] is True
    rec = merged["fleet"]["reconcile"]
    assert (rec["n_requests"], rec["n_reconciled"]) == (1, 1)


def test_merge_fleet_flags_out_of_tolerance_residual(tmp_path):
    from tools.merge_traces import merge_fleet
    # 400 ms client latency over a 14.5 ms phase sum: the residual
    # blows every default budget -> reconciled False, fraction 0.
    rid = _write_fleet_dir(tmp_path, client_ms=400.0)
    merged = merge_fleet(str(tmp_path))
    ent = merged["fleet"]["requests"][rid]
    assert ent["reconciled"] is False
    assert merged["fleet"]["reconcile"]["fraction"] == 0.0
    # ...and a widened absolute budget accepts it (CLI-overridable).
    merged = merge_fleet(str(tmp_path), tol_abs_ms=500.0)
    assert merged["fleet"]["requests"][rid]["reconciled"] is True


def test_merge_fleet_without_client_marks_unavailable(tmp_path):
    from tools.merge_traces import merge_fleet
    _write_fleet_dir(tmp_path)
    (tmp_path / "trace-client.json").unlink()
    merged = merge_fleet(str(tmp_path))
    rec = merged["fleet"]["reconcile"]
    assert "reconcile_unavailable" in rec
    assert "fraction" not in rec


def test_check_fleet_passes_good_and_rejects_tampered(tmp_path, capsys):
    from tools.check_trace import check_fleet_trace
    from tools.merge_traces import merge_fleet
    rid = _write_fleet_dir(tmp_path)
    merged = merge_fleet(str(tmp_path))
    good = tmp_path / "merged.json"
    good.write_text(json.dumps(merged))
    check_fleet_trace(str(good))          # must not exit
    capsys.readouterr()
    # orphan phase span: a rid with no fleet.route root
    bad = dict(merged)
    bad["traceEvents"] = merged["traceEvents"] + [
        _x("serve.phase.solve", 1.0, 1.0, 10, rid="ghost")]
    p = tmp_path / "orphan.json"
    p.write_text(json.dumps(bad))
    with pytest.raises(SystemExit):
        check_fleet_trace(str(p))
    # fabricated retry hop on a single-hop request
    bad["traceEvents"] = merged["traceEvents"] + [
        _x("fleet.hop", 1.0, 1.0, 1, rid=rid, attempt=2,
           replica="fake", outcome="ok")]
    p.write_text(json.dumps(bad))
    with pytest.raises(SystemExit):
        check_fleet_trace(str(p))
    # duplicated rid: two client.request spans
    bad["traceEvents"] = merged["traceEvents"] + [
        _x("client.request", 1.0, 1.0, 0, rid=rid, lag_ms=0.0,
           ok=True, hops=1)]
    p.write_text(json.dumps(bad))
    with pytest.raises(SystemExit):
        check_fleet_trace(str(p))


def test_tail_attrib_names_the_dominant_phase(tmp_path):
    from tools.merge_traces import merge_fleet
    from tools.tail_attrib import attribute
    _write_fleet_dir(tmp_path)
    merged = merge_fleet(str(tmp_path))
    levels = attribute(merged)
    assert sorted(levels) == ["x4"]
    att = levels["x4"]
    assert att["n"] == 1
    p99 = att["quantiles"]["p99"]
    assert p99["phases"]["solve"] == pytest.approx(10.0)
    assert att["dominant_p99"] == "solve"
    # client_ms excludes the pacing lag; residual is the un-phased rest
    assert p99["client_ms"] == pytest.approx(19.5)
    assert p99["residual_ms"] == pytest.approx(5.0)


def test_tailattrib_records_carry_level_and_phase_quantiles(tmp_path):
    from dmlp_tpu.obs.run import RunRecord
    from tools.merge_traces import merge_fleet
    from tools.tail_attrib import attribute, emit_records
    _write_fleet_dir(tmp_path)
    levels = attribute(merge_fleet(str(tmp_path)))
    path = tmp_path / "TAILATTRIB.jsonl"
    assert emit_records(levels, str(path), "merged.json", round_=16,
                        device="cpu") == 1
    back = RunRecord.load(str(path))
    assert (back.kind, back.tool) == ("tailattrib", "tools.tail_attrib")
    assert back.config["level"] == "x4"
    assert back.config["dominant_p99"] == "solve"
    assert back.metrics["solve_p99_ms"] == pytest.approx(10.0)
    assert "queue_p99_ms" in back.metrics
    assert (back.round, back.device) == (16, "cpu")
