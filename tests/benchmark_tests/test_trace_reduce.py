"""The trace-to-metrics reduction on a hand-built trace with hand-computed
answers, and the traffic generators: seeded schedules repeat exactly,
open-loop latency counts from the due time, a refused request misses."""

import json
import os
import socketserver
import threading
import time

import pytest

from benchmark import kernel_cost, loadgen, spec, trace_reduce
from benchmark.readers import percentile, stat_of
from benchmark.traffic import closed_loop, open_loop

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(spec.HERE, "testdata", "trace_small.json")) as f:
        return json.load(f)


def test_union_merges_overlaps_and_drops_empty():
    assert trace_reduce.union([(5, 6), (1, 3), (2.5, 3.5), (7, 7)]) == \
        [(1, 3.5), (5, 6)]


def test_busy_is_the_union_clipped_to_the_window_averaged_over_chips(small):
    b = trace_reduce.busy(small)
    # TPU:0: [1,3.5] + [5,6] + [9.5,10] = 4.0 ms; TPU:1: [0,5] = 5.0 ms
    assert b["per_device_s"]["/device:TPU:0"] == pytest.approx(4.0e-3)
    assert b["per_device_s"]["/device:TPU:1"] == pytest.approx(5.0e-3)
    assert b["busy_s"] == pytest.approx(4.5e-3)
    assert b["window_s"] == pytest.approx(10e-3)
    assert b["devices"] == 2
    assert b["gaps_ns"] == [(0, 1e6), (3.5e6, 5e6), (6e6, 9.5e6)]
    assert trace_reduce.idle_pct(small) == pytest.approx(55.0)


def test_only_ops_lines_of_device_planes_count(small):
    planes = trace_reduce.device_ops(small)
    assert sorted(planes) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(planes["/device:TPU:0"]) == 4     # no Modules, no Async line


def test_kernel_pattern_finds_whole_events_only(small):
    evs = trace_reduce.kernel_events(small, KERNEL)
    # the third kernel event is cut by the window's edge and left out
    assert [e["start_ns"] for e in evs] == [1e6, 5e6]
    assert sum(e["dur_ns"] for e in evs) == 3e6
    assert trace_reduce.kernel_events(small, "no_such_kernel") == []


def test_top_ops_rank_by_time_inside_the_window(small):
    top = trace_reduce.top_ops(small, limit=2)
    # fusion.1: 1 ms on TPU:0 + 5 ms on TPU:1 = 6 ms over two chips;
    # the kernel: 2 + 1 + 0.5 (clipped) = 3.5 ms over two chips
    assert top[0][0].startswith("%fusion.1") and \
        top[0][1] == pytest.approx(3.0e-3)
    assert KERNEL in top[1][0] and top[1][1] == pytest.approx(1.75e-3)


def test_gaps_go_to_the_innermost_span_that_covers_them(small):
    spans = trace_reduce.spans_on_trace_clock(
        small["host_spans"], small["sync_pc_s"], small["sync_ns"])
    assert spans[1]["start_ns"] == pytest.approx(0.0, abs=1e-3)
    assert spans[1]["end_ns"] == pytest.approx(1.2e6)
    got = dict(trace_reduce.attribute_gaps(
        trace_reduce.busy(small)["gaps_ns"], spans))
    # [0,1] enqueue covers all of it, as outer does: the shorter wins;
    # [3.5,5] fetch; [6,9.5] finalize covers 3.35 of 3.5 ms (> 95%)
    assert got == {"finalize": pytest.approx(3.5e-3),
                   "fetch": pytest.approx(1.5e-3),
                   "enqueue": pytest.approx(1.0e-3)}
    loose = [dict(s) for s in spans]
    loose[3]["end_ns"] = 9.0e6            # finalize now covers 83%: outer
    got = dict(trace_reduce.attribute_gaps([(6e6, 9.5e6), (20e6, 21e6)],
                                           loose))
    assert got == {"outer": pytest.approx(3.5e-3),
                   "(no span)": pytest.approx(1.0e-3)}


def test_kernel_cost_and_roofline_by_hand():
    cost = kernel_cost.topk_scan_cost(nq=1024, n=1 << 20, na=128, kc=120,
                                      itemsize=2, dispatches=21)
    assert cost["flops"] == 2.0 * 1024 * (1 << 20) * 128
    per_dispatch = 1024 * 128 * 2 + 2 * 1024 * 120 * 8
    assert cost["bytes"] == (1 << 20) * 128 * 2 + 21 * per_dispatch
    roof = kernel_cost.roofline({"flops": 2e12, "bytes": 1e9},
                                {"flops_per_s": 200e12,
                                 "hbm_bytes_per_s": 800e9}, 0.1)
    assert roof["bound"] == "compute" and roof["pct"] == pytest.approx(10.0)
    roof = kernel_cost.roofline({"flops": 2e9, "bytes": 8e9},
                                {"flops_per_s": 200e12,
                                 "hbm_bytes_per_s": 800e9}, 0.1)
    assert roof["bound"] == "memory" and roof["pct"] == pytest.approx(10.0)


def test_peaks_table_knows_v5e_and_refuses_the_rest():
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError, match="not in benchmark/peaks.json"):
        spec.peaks("TPU v9 imaginary")


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert percentile(vals, 50) == 50 and percentile(vals, 95) == 95
    assert percentile([7.0], 95) == 7.0
    assert stat_of([3, 1, 2], "median") == 2 and stat_of([], "p95") is None


# -- traffic ------------------------------------------------------------------

STEADY = {"rate_per_s": 50.0, "sizes": [1, 2, 4, 8, 16, 32, 64],
          "weights": [1.0, 0.70710678, 0.5, 0.35355339, 0.25, 0.1767767,
                      0.125], "senders": 8, "debug_every": 16}


def test_open_loop_schedule_repeats_and_seeds_offer_the_same_work():
    a, b = open_loop.plan(STEADY, 5, 30), open_loop.plan(STEADY, 5, 30)
    c = open_loop.plan(STEADY, 6, 30)
    assert a == b
    assert a["due_s"] != c["due_s"] and a["sizes"] != c["sizes"]
    assert sorted(a["sizes"]) == sorted(c["sizes"])       # same multiset
    gaps = lambda p: sorted(round(y - x, 9) for x, y in
                            zip([0.0] + p["due_s"], p["due_s"]))
    assert gaps(a) == pytest.approx(gaps(c), abs=1e-6)
    assert len(a["due_s"]) == 1500 and 0 < a["due_s"][0] \
        and a["due_s"][-1] < 30
    assert sorted(a["sizes"])[len(a["sizes"]) // 2] == 2  # median 2
    assert sum(a["sizes"]) / 1500 == pytest.approx(8.0, abs=0.1)
    assert sum(a["debug"]) == 1500 // 16


def test_closed_loop_plan():
    plan = closed_loop.plan({"clients": 4, "queries_per_request": 1024,
                             "payload_pool": 16, "debug_every": 8}, 1, 30)
    assert plan["mode"] == "closed" and plan["clients"] == 4
    assert plan["sizes"] == [1024] * 16 and sum(plan["debug"]) == 2


def test_payloads_come_from_the_seed():
    cfg = {"num_attrs": 4, "values": {"low": 0, "high": 255,
                                      "float32": True}}
    plan = {"sizes": [2, 3], "debug": [False, True]}
    a = loadgen.encode_payloads(cfg, plan, 9, 10)
    assert a == loadgen.encode_payloads(cfg, plan, 9, 10)
    assert a != loadgen.encode_payloads(cfg, plan, 10, 10)
    first, second = (json.loads(p) for p in a)
    assert len(first["queries"]) == 2 and "debug" not in first
    assert second["debug"] is True and second["k"] == 10


class _Slow(socketserver.StreamRequestHandler):
    """Answers a request 50 ms after it has read it; refuses id "1"."""

    def handle(self):
        for raw in self.rfile:
            req = json.loads(raw)
            time.sleep(0.05)
            if req["id"] == "1":
                resp = {"ok": False, "error": "rejected: queue full"}
            else:
                resp = {"ok": True, "labels": [0], "checksums": [1]}
            self.wfile.write((json.dumps(resp) + "\n").encode())


@pytest.fixture()
def slow_server():
    class Server(socketserver.ThreadingTCPServer):
        daemon_threads = True
        allow_reuse_address = True
    srv = Server(("127.0.0.1", 0), _Slow)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address[1]
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5)
    assert not t.is_alive()


def test_open_loop_latency_counts_from_the_due_time(slow_server):
    # one sender, three requests due together: the second and third wait
    # for the sender, and that wait is in their latency and their lag
    plan = {"mode": "open", "clients": 1, "sizes": [1, 1, 1],
            "debug": [False] * 3, "due_s": [0.0, 0.0, 0.0]}
    payloads = [(json.dumps({"id": str(i)}) + "\n").encode()
                for i in range(3)]
    recs, answers, _ = loadgen.run_open(plan, payloads, slow_server, 5.0)
    lat = [r["latency_ms"] for r in recs]
    assert 50 <= lat[0] < 120 and 100 <= lat[1] < 200 and 150 <= lat[2] < 300
    assert recs[2]["lag_ms"] >= 100 and recs[0]["lag_ms"] < 60
    assert [r["ok"] for r in recs] == [True, False, True]
    assert "rejected" in recs[1]["error"] and "1" not in answers
    assert answers["0"] == {"labels": [0], "checksums": [1]}


def test_closed_loop_sends_the_next_when_the_last_returned(slow_server):
    plan = {"mode": "closed", "clients": 2, "sizes": [1, 1, 1, 1],
            "debug": [False] * 4, "due_s": None}
    payloads = [(json.dumps({"id": "0"}) + "\n").encode()] * 4
    recs, answers, _ = loadgen.run_closed(plan, payloads, slow_server,
                                          0.5, 5.0)
    # 2 clients x 0.5 s / 50 ms a request: about 20, none refused
    assert 10 <= len(recs) <= 24 and all(r["ok"] for r in recs)
    assert [r["seq"] for r in recs] == list(range(len(recs)))
    assert all(r["latency_ms"] >= 50 for r in recs)
    assert len(answers) == len(recs)
