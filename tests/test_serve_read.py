"""A request line off its socket (PR 50): ``protocol.LineReader``.

The reader alone over a ``socket.socketpair()`` (framing, the cap at
the read, the end of the stream, the buffer it keeps), then the same
through a live daemon on the CPU: the ``serve.phase.read`` span's
``bytes`` and ``pieces``, the refusal of an oversized line at the
socket, two requests pipelined on one connection, and the always-on
histogram beside ``serve.phase_ms.read``.
"""

from __future__ import annotations

import json
import socket
import threading

import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.obs import telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve import protocol
from dmlp_tpu.serve.daemon import ServeDaemon
from dmlp_tpu.serve.protocol import LineReader
from tests.test_batcher_cycle import corpus_of

FIRST = LineReader.FIRST_BYTES


class Handed:
    """The reader's end of a socketpair, handing over at most ``most``
    bytes a receive and keeping count: calls, bytes, the most asked."""

    def __init__(self, sock, most=None):
        self.sock, self.most = sock, most
        self.calls = self.bytes = self.asked = 0

    def recv_into(self, view):
        self.calls += 1
        self.asked = max(self.asked, len(view))
        got = self.sock.recv_into(view[:self.most] if self.most else view)
        self.bytes += got
        return got


def sent(tx, data: bytes, close: bool = False) -> threading.Thread:
    """``data`` on its way from a thread of its own (a socketpair's
    buffer holds ~200 KB: a longer line needs the reader draining;
    a reader that refuses the line may hang up first)."""
    def run():
        try:
            tx.sendall(data)
            if close:
                tx.shutdown(socket.SHUT_WR)
        except OSError:
            pass
    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def many_small_pieces(tx, rx, _patch):
    line = bytes(range(48, 58)) * 100 + b"\n"
    sent(tx, line)
    h = Handed(rx, most=7)
    r = LineReader(h)
    assert r.readline() == line
    assert r.pieces == h.calls == -(-len(line) // 7) and r.t_first > 0


def two_lines_in_one_piece(tx, rx, _patch):
    tx.sendall(b'{"op":"stats"}\nsecond\n')
    h = Handed(rx)
    r = LineReader(h)
    assert (r.readline(), r.pieces) == (b'{"op":"stats"}\n', 1)
    t1 = r.t_first
    # the second is served from what followed the first: no receive
    assert (r.readline(), r.pieces, h.calls) == (b"second\n", 0, 1)
    assert r.t_first >= t1


def a_piece_that_ends_on_the_newline(tx, rx, _patch):
    tx.sendall(b"abc\n")
    h = Handed(rx)
    r = LineReader(h)
    assert (r.readline(), r.pieces, r._have) == (b"abc\n", 1, 0)
    tx.sendall(b"de")
    t = sent(tx, b"f\n")
    assert r.readline() == b"def\n" and 1 <= r.pieces <= 2
    t.join(10)


def a_line_of_exactly_the_cap(tx, rx, patch):
    patch.setattr(protocol, "MAX_LINE_BYTES", 64)
    line = b"x" * 63 + b"\n"
    tx.sendall(line + b"next\n")
    r = LineReader(Handed(rx))
    got = r.readline()
    assert got == line and len(got) <= protocol.MAX_LINE_BYTES
    assert r.readline() == b"next\n"


def a_line_one_past_the_cap(tx, rx, patch):
    patch.setattr(protocol, "MAX_LINE_BYTES", 64)
    tx.sendall(b"x" * 64 + b"\n" + b"y" * 135)
    h = Handed(rx)
    r = LineReader(h)
    got = r.readline()
    # cap + 1 bytes, handed on for the caller to refuse; the rest of
    # what was sent is still the kernel's
    assert got == b"x" * 64 + b"\n" and len(got) > protocol.MAX_LINE_BYTES
    assert h.bytes == h.asked == 65 and r._have == 0


def an_unterminated_line_past_a_cap_over_the_first_buffer(tx, rx, patch):
    cap = 100_000
    assert cap > FIRST
    patch.setattr(protocol, "MAX_LINE_BYTES", cap)
    t = sent(tx, b"z" * (3 * cap), close=True)
    h = Handed(rx)
    r = LineReader(h)
    got = r.readline()
    assert got == b"z" * (cap + 1)
    # never more than cap + 1 held: not asked for, not received, and
    # the buffer did not grow past it
    assert h.bytes == cap + 1 and h.asked <= cap + 1
    assert len(r._buf) == cap + 1
    rx.close()                      # the caller drops the connection
    t.join(10)


def the_end_of_the_stream_with_nothing(tx, rx, _patch):
    tx.close()
    r = LineReader(Handed(rx))
    assert (r.readline(), r.pieces) == (b"", 1)


def the_end_of_the_stream_with_a_partial_line(tx, rx, _patch):
    sent(tx, b"whole\npart", close=True).join(10)
    r = LineReader(Handed(rx))
    assert r.readline() == b"whole\n"
    assert r.readline() == b"part"      # as BufferedReader.readline
    assert r.readline() == b""


def a_blank_line(tx, rx, _patch):
    tx.sendall(b"\n\nafter\n")
    r = LineReader(Handed(rx))
    assert [r.readline() for _ in range(3)] == [b"\n", b"\n", b"after\n"]


def the_buffer_is_kept_across_equal_lines(tx, rx, _patch):
    line = b"7" * (5 * FIRST) + b"\n"
    r = LineReader(Handed(rx))
    buf = r._buf
    for _ in range(2):
        t = sent(tx, line)
        assert r.readline() == line
        t.join(10)
        assert r._buf is buf and len(buf) == 8 * FIRST   # 64 KB doubled


def a_short_line_never_grows_the_buffer(tx, rx, _patch):
    tx.sendall(b"hi\n" + b"k" * 4000 + b"\n")
    r = LineReader(Handed(rx))
    buf = r._buf
    assert r.readline() == b"hi\n" and len(r.readline()) == 4001
    assert r._buf is buf and len(buf) == FIRST


CASES = [many_small_pieces, two_lines_in_one_piece,
         a_piece_that_ends_on_the_newline, a_line_of_exactly_the_cap,
         a_line_one_past_the_cap,
         an_unterminated_line_past_a_cap_over_the_first_buffer,
         the_end_of_the_stream_with_nothing,
         the_end_of_the_stream_with_a_partial_line, a_blank_line,
         the_buffer_is_kept_across_equal_lines,
         a_short_line_never_grows_the_buffer]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_the_reader_alone(case, monkeypatch):
    tx, rx = socket.socketpair()
    tx.settimeout(30)
    rx.settimeout(30)
    try:
        case(tx, rx, monkeypatch)
    finally:
        tx.close()
        rx.close()


# -- through a live daemon -------------------------------------------------------

BIG = 8 << 20


def query_line(corpus, rid: str, pad: int = 0) -> bytes:
    """A query request as its wire line, ``pad`` bytes of JSON
    whitespace before the closing brace (a line's length is the
    read's business, whatever the bytes are)."""
    body = json.dumps({"op": "query", "id": rid, "k": 4, "rid": rid,
                       "queries": corpus.data_attrs[:3].tolist()})
    return (body[:-1] + " " * pad + "}\n").encode()


@pytest.fixture(scope="module")
def served():
    """One daemon under a tracer: an 8 MB query line twice on one
    connection, then two requests pipelined in one send."""
    corpus = corpus_of(600)
    tracer = obs_trace.install(obs_trace.Tracer())
    daemon = ServeDaemon(corpus, EngineConfig(), warm_buckets=[(3, 4)])
    out = {"corpus": corpus, "daemon": daemon, "tracer": tracer}
    try:
        daemon.start()
        big = query_line(corpus, "big", pad=BIG)
        out["big_bytes"] = len(big)
        with socket.create_connection(("127.0.0.1", daemon.port),
                                      timeout=120) as s:
            f = s.makefile("rb")
            out["big"] = []
            for _ in range(2):
                s.sendall(big)
                out["big"].append(json.loads(f.readline()))
        with socket.create_connection(("127.0.0.1", daemon.port),
                                      timeout=120) as s:
            f = s.makefile("rb")
            s.sendall(query_line(corpus, "p-1") + query_line(corpus, "p-2"))
            out["pipelined"] = [json.loads(f.readline()) for _ in range(2)]
        out["openmetrics"] = telemetry.registry().to_openmetrics()
        yield out
    finally:
        daemon.close()
        obs_trace.uninstall()


@pytest.fixture(scope="module")
def spans(served):
    """The spans of ``served``'s exchanges (each recorded before its
    response was written, so all are in by now)."""
    return [e for e in served["tracer"].events() if e.get("ph") == "X"]


def read_args(spans, rid):
    return [e["args"] for e in spans if e["name"] == "serve.phase.read"
            and e["args"].get("rid") == rid]


def test_a_long_line_is_read_in_large_pieces(served, spans):
    """How many receives a line takes is the kernel's to say: a reader
    that keeps up with a sender the scheduler holds back gets loopback's
    64 KB segments one at a time, one that waits for the interpreter
    lock gets megabytes (on a quiet machine this line reads in 1-7
    pieces, 11-18 the first time, while the buffer doubles up from
    64 KB; 44 was the most under eight spinning processes on eight
    cores). What is held is the floor: never less than a segment a
    receive on the average, where a ``BufferedReader`` of 8 KB takes
    1 024 raw reads for the same line."""
    assert all(r["ok"] for r in served["big"]), served["big"]
    first, second = read_args(spans, "big")
    n = served["big_bytes"]
    assert first["bytes"] == second["bytes"] == n > BIG
    assert 1 <= second["pieces"] <= n / 2 ** 16
    assert 8 <= first["pieces"] <= n / 2 ** 16 + 8


def test_pipelined_requests_are_answered_in_order(served, spans):
    assert [r["id"] for r in served["pipelined"]] == ["p-1", "p-2"]
    assert all(r["ok"] for r in served["pipelined"])
    (one,), (two,) = read_args(spans, "p-1"), read_args(spans, "p-2")
    assert one["pieces"] >= 1
    # the second line came with the first or after it, never lost
    assert two["bytes"] == len(query_line(served["corpus"], "p-2"))


def test_an_oversized_line_is_refused_at_the_socket(served, monkeypatch):
    cap = 1 << 20
    monkeypatch.setattr(protocol, "MAX_LINE_BYTES", cap)
    with socket.create_connection(("127.0.0.1", served["daemon"].port),
                                  timeout=60) as s:
        f = s.makefile("rb")
        s.sendall(b"x" * (cap + 1))     # all the reader will take
        assert json.loads(f.readline()) == {
            "ok": False, "error": "request line exceeds the size cap"}
        assert f.readline() == b""      # and the connection is dropped


@pytest.mark.parametrize("series", ["serve_read_pieces_count 4",
                                    "serve_phase_ms_read_count 4"])
def test_the_pieces_are_counted_with_no_sink_too(served, series):
    text = served["openmetrics"]
    assert telemetry.validate_openmetrics(text) == []
    assert any(line.startswith(series) for line in text.splitlines())
