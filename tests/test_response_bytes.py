"""PR 52: a query response is built from the batch's arrays and leaves in
pieces, and the bytes on the wire are the parent's, to the byte.

The parent's way is kept here as the reference: ``query_response`` as
comprehensions over Python numbers with the scalar checksum, and ONE
``json.dumps`` of the whole dictionary. What is asserted besides
equality is counted, never timed: how many pieces a body leaves in, how
large a piece may be, and that ``serve.phase.write`` says the same."""

from __future__ import annotations

import json
import socket
import threading

import numpy as np
import pytest

from dmlp_tpu.io.report import QueryResult
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve import protocol
from dmlp_tpu.serve.batching import Request
from dmlp_tpu.serve.daemon import _Handler, _Server


# -- the parent's response, kept as the reference -----------------------------

def parent_query_response(req: Request) -> dict:
    def echo(out):
        if req.rid:
            out["rid"] = req.rid
        return out
    if req.error is not None:
        return echo({"id": req.req_id, "ok": False, "error": req.error})
    out = {
        "id": req.req_id, "ok": True,
        "labels": [int(r.predicted_label) for r in req.results],
        "checksums": [int(r.checksum()) for r in req.results],
        "latency_ms": round(req.latency_ms, 3),
    }
    if req.debug:
        out["neighbors"] = [[int(i) for i in r.neighbor_ids]
                            for r in req.results]
        out["dists"] = [[float(d) for d in r.neighbor_dists]
                        for r in req.results]
    return echo(out)


def parent_encode(obj: dict) -> bytes:
    return (json.dumps(obj, separators=(",", ":"),
                       sort_keys=True) + "\n").encode()


# -- completed requests, as the batcher leaves them ---------------------------

def completed(ks, score="l2", debug=False, rid="", req_id="q", short=0,
              seed=52) -> Request:
    """A finished query request: a list a query of its own k, sorted
    as the score sorts, the last ``short`` slots of every list padded
    as a corpus smaller than k pads them (id -1; +inf, or -inf under
    inner product)."""
    rng = np.random.default_rng([seed, len(ks), int(max(ks))])
    results = []
    for qi, k in enumerate(ks):
        ids = rng.integers(0, 1 << 22, k).astype(np.int64)
        dists = np.sort(rng.random(k) * 1e4)
        if score == "ip":
            dists = -dists[::-1] + 5e3      # products, descending
        elif score == "cosine":
            dists = dists / 1e4             # 1 - s, ascending
        pad = min(short, k)
        if pad:
            ids[k - pad:] = -1
            dists[k - pad:] = -np.inf if score == "ip" else np.inf
        results.append(QueryResult(qi, int(k), int(rng.integers(-1, 10)),
                                   ids, dists.astype(np.float64)))
    req = Request(kind="query", req_id=req_id, rid=rid,
                  query_attrs=np.zeros((len(ks), 2)),
                  ks=np.asarray(ks, np.int32), debug=debug)
    req.results = results
    req.latency_ms = 12.3456789
    return req


def failed(rid="") -> Request:
    req = completed([3], rid=rid)
    req.results, req.error = None, 'rejected: queue full ("é")'
    return req


CASES = {
    "plain": lambda: completed([10] * 64),
    "debug": lambda: completed([10] * 64, debug=True),
    "rid": lambda: completed([10] * 5, rid="r-000017"),
    "rid_debug": lambda: completed([10] * 5, rid="r-000017", debug=True),
    "no_id": lambda: completed([10] * 5, req_id="", debug=True),
    "error": failed,
    "error_rid": lambda: failed(rid="r-9"),
    "one_query": lambda: completed([1]),
    "one_query_debug": lambda: completed([7], debug=True),
    "mixed_k": lambda: completed([1, 1000, 3, 512, 1, 64], debug=True),
    "mixed_k_plain": lambda: completed([1, 1000, 3, 512, 1, 64]),
    "l2_padded": lambda: completed([16] * 9, "l2", debug=True, short=5),
    "ip_padded": lambda: completed([16] * 9, "ip", debug=True, short=5),
    "cosine_padded": lambda: completed([16] * 9, "cosine", debug=True,
                                       short=5),
    "all_padding": lambda: completed([4] * 3, "ip", debug=True, short=4),
    "bulk_k10": lambda: completed([10] * 1024),
    "bulk_k10_debug": lambda: completed([10] * 1024, debug=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_wire_bytes_are_the_parents(case):
    req = CASES[case]()
    want = parent_query_response(req)
    resp = protocol.query_response(req)
    assert resp == want
    # equal AND of the same types: a NumPy number would encode otherwise
    for key in ("labels", "checksums"):
        assert all(type(v) is int for v in resp.get(key, []))
    for row in resp.get("neighbors", []):
        assert all(type(v) is int for v in row)
    for row in resp.get("dists", []):
        assert all(type(v) is float for v in row)
    pieces = list(protocol.encode_pieces(resp))
    assert b"".join(pieces) == parent_encode(want) == protocol.encode(resp)
    assert len(pieces) == 1             # none of these fills a piece


@pytest.mark.parametrize("obj", [
    {}, {"ok": True}, {"ok": False, "error": "request line exceeds"},
    {"ok": True, "stats": {"b": {"z": [1, 2.5, None], "a": [[1], [2]]}}},
    {"ok": True, "rows": [[0.5, -0.0], [1e-300, 1e300]], "labels": [1, 2],
     "start": 0, "corpus_rows": 2, "checksum": 2 ** 64 - 1, "epoch": 3},
    {"rows": [[1]], "tail": [[2]]},
    {"a": [[1], [2, 3]], "b": [], "c": [[]], "d": [[], [4]], "e": (5, 6)},
    {"a": [[1], 7, "x", [2, 3], None, {"k": [8]}]},
    {"neighbors": [list(range(700))] * 5, "z": "after"},
], ids=["empty", "ok", "error", "stats", "corpus", "rows_twice", "odd_lists",
        "odd_rows", "groups_close_inside"])
def test_any_wire_object_encodes_as_one_dumps_does(obj):
    assert b"".join(protocol.encode_pieces(obj)) == parent_encode(obj)


# -- a 26 MB body: counts -----------------------------------------------------

@pytest.fixture(scope="module")
def deep():
    """bigann-gt1000.bulk's ``debug`` response: 1024 queries, k = 1000."""
    req = completed([1000] * 1024, debug=True, rid="r-deep", short=2)
    resp = protocol.query_response(req)
    return {"req": req, "resp": resp,
            "pieces": list(protocol.encode_pieces(resp))}


def test_the_deep_response_is_the_parents_to_the_byte(deep):
    want = parent_query_response(deep["req"])
    assert deep["resp"] == want
    assert b"".join(deep["pieces"]) == parent_encode(want)


def test_a_26_mb_body_leaves_in_megabyte_pieces(deep):
    sizes = [len(p) for p in deep["pieces"]]
    total = sum(sizes)
    assert total > 24 << 20
    # every piece but the last is full, and none is much over: a piece
    # closes with the group (one row of 1000 numbers here) that fills it
    row = max(len(json.dumps(r)) for r in deep["resp"]["dists"])
    assert all(protocol.PIECE_BYTES <= n <= protocol.PIECE_BYTES + row + 64
               for n in sizes[:-1])
    assert 0 < sizes[-1] <= protocol.PIECE_BYTES + row + 64
    assert len(sizes) in (total // protocol.PIECE_BYTES,
                          total // protocol.PIECE_BYTES + 1)
    assert len(sizes) > 20


def test_no_dumps_call_takes_more_than_a_few_rows(deep, monkeypatch):
    """The lock is held a ``json.dumps`` call at a time: count the
    numbers each call is handed."""
    handed = []
    dumps = protocol._dumps

    def counted(obj):
        if isinstance(obj, list):
            handed.append(sum(len(r) if isinstance(r, list) else 1
                              for r in obj))
        return dumps(obj)
    monkeypatch.setattr(protocol, "_dumps", counted)
    assert sum(len(p) for p in protocol.encode_pieces(deep["resp"])) \
        == sum(len(p) for p in deep["pieces"])
    assert len(handed) == 2 * 1024          # a row a call, at k = 1000
    assert max(handed) == 1000
    handed.clear()
    small = protocol.query_response(completed([10] * 1024, debug=True))
    list(protocol.encode_pieces(small))
    # 52 rows of 10 reach the 512 numbers that close a group
    assert max(handed) == 520 and len(handed) == 2 * 20


def test_a_23_kb_body_is_one_piece():
    resp = protocol.query_response(completed([10] * 1024))
    (piece,) = protocol.encode_pieces(resp)
    assert 20_000 < len(piece) < 30_000


# -- the handler: the same pieces onto a socket, inside one bracket -----------

class _Daemon:
    """What ``_Handler`` needs of a ServeDaemon, answering every line
    with one canned response."""

    def __init__(self, resp, tracer):
        self.resp, self.tracer = resp, tracer
        self.inflight = 0
        #: at each release: was the write span already recorded?
        self.write_seen_at_release = []

    def serve_line(self, raw, t_read, c_read):
        return self.resp, None

    def _track_inflight(self, delta):
        self.inflight += delta
        if delta < 0:
            self.write_seen_at_release.append(any(
                e.get("name") == "serve.phase.write"
                for e in self.tracer.events()))


def written_by_the_handler(resp):
    tracer = obs_trace.install(obs_trace.Tracer())
    server = _Server(("127.0.0.1", 0), _Handler)
    server.daemon = stub = _Daemon(resp, tracer)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        with socket.create_connection(server.server_address,
                                      timeout=120) as s:
            s.sendall(b'{"op":"stats"}\n')
            line = s.makefile("rb").readline()
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=30)
        obs_trace.uninstall()
    assert not thread.is_alive()
    (write,) = [e for e in tracer.events()
                if e.get("name") == "serve.phase.write"]
    return line, write["args"], stub


def test_the_handler_writes_the_deep_body_piece_by_piece(deep):
    line, args, stub = written_by_the_handler(deep["resp"])
    assert line == b"".join(deep["pieces"])
    assert args["pieces"] == len(deep["pieces"]) > 1
    assert args["bytes"] == len(line)
    # the in-flight bracket closed once, after the last piece had gone
    assert stub.inflight == 0 and stub.write_seen_at_release == [True]


def test_the_handler_writes_a_small_body_in_one_piece():
    resp = protocol.query_response(completed([10] * 1024, rid="r-1"))
    line, args, stub = written_by_the_handler(resp)
    assert line == parent_encode(resp)
    assert (args["pieces"], args["bytes"], args["rid"]) \
        == (1, len(line), "r-1")
    assert stub.inflight == 0 and stub.write_seen_at_release == [True]


# -- a small response keeps the interpreter lock ------------------------------

def test_a_small_response_never_lets_go_of_the_interpreter_lock():
    """A handler that answers a 2-query request must not hand the lock
    over on the way: a NumPy sort or fancy index lets go of it at any
    size, whichever thread waits takes it, and the handler waits for
    its return (``bigann.steady`` read ``p50_ms`` +4 to +7% on the chip
    under a fold that sorted its queries by k). Counted, not timed:
    with the switch interval out of reach nothing takes the lock from a
    thread that does not let go, so a second thread that counts its own
    turns gets none while the response is built and encoded."""
    import sys
    import time

    turns = [0]
    stop = []

    def counter():
        while not stop:
            turns[0] += 1
            time.sleep(0)       # lets go of the lock, then waits for it

    reqs = [completed([10] * 2), completed([10] * 16, debug=True),
            completed([1, 10, 3])]
    interval = sys.getswitchinterval()
    thread = threading.Thread(target=counter, daemon=True)
    sys.setswitchinterval(1000.0)
    try:
        thread.start()
        while not turns[0]:
            time.sleep(0.001)               # the counter has the lock
        before = turns[0]
        for _ in range(1000):
            for req in reqs:
                protocol.encode(protocol.query_response(req))
        during = turns[0] - before
        time.sleep(0.01)                    # the control: now it may run
        after = turns[0] - before - during
    finally:
        stop.append(True)
        sys.setswitchinterval(interval)
        thread.join(timeout=30)
    assert not thread.is_alive()
    assert during == 0 and after > 0
