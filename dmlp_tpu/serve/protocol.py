"""The daemon's wire protocol: newline-delimited JSON over TCP.

One request object per line, one response object per line, strictly in
per-connection order (concurrency comes from multiple connections —
the micro-batcher coalesces across all of them). Shapes:

- ``{"op": "query", "id"?, "k": K | "ks": [...], "queries": [[...]]}``
  -> ``{"id", "ok": true, "labels": [...], "checksums": [...],
  "latency_ms"}`` (+ ``"neighbors"``/``"dists"`` with ``"debug": true``).
  ``checksums`` are the engines' contract FNV-1a values — the replay
  client reassembles the exact contract stdout (``Query N checksum:
  C``) and byte-compares it against the golden oracle. What ``dists``
  carries is the daemon's score (``--score``, echoed as ``device.score``
  in ``stats``): squared L2 distances, ascending, padded slots
  ``Infinity``; of a corpus ranked by inner product the products s
  themselves, DESCENDING (FAISS ``IndexFlatIP``'s convention), padded
  slots ``-Infinity``; of a corpus ranked by cosine the angular
  distances ``1 - s``, ascending (ann-benchmarks' ``angular``), padded
  slots ``Infinity``. ``neighbors`` follows the same order, larger id
  first on ties. A score is a property of the corpus, never of a
  request.
- ``{"op": "ingest", "labels": [...], "rows": [[...]], "start"?: S}``
  -> ``{"ok": true, "corpus_rows": N}``; capacity overflow is a clean
  ``ok: false`` with the reason. ``start`` makes the write an
  IDEMPOTENT row-write keyed by global row id (``start <= corpus
  rows``; re-delivering the same rows at the same positions is a
  no-op) — the fleet's consistency repair and re-shard replay speak
  this form; plain appends omit it.
- ``{"op": "corpus", "start": S, "count": C}`` -> ``{"ok": true,
  "start": S, "labels": [...], "rows": [[...]], "corpus_rows": N,
  "checksum": H, "epoch": E}`` — the consistency/replay read side:
  host rows ``[S, S+C)`` (clamped; ``count`` capped at
  ``CORPUS_FETCH_MAX`` per line) plus the live corpus signature.
  ``count: 0`` is the cheap signature probe.
- ``{"op": "stats"}`` -> engine/admission/registry snapshot (now
  including the ``corpus`` signature block the fleet prober compares
  across replicas).
- ``{"op": "drain"}`` -> acknowledges and initiates the graceful
  drain (the in-band SIGTERM).

Rejections and errors are ``{"ok": false, "error": "..."}`` — the
connection stays usable.

Tracing envelope: every request MAY carry ``"rid"`` (an opaque
request-id string the client stamps at fire time) and ``"trace"`` (a
small dict of client-side context, e.g. the scheduled-fire wall
clock). Both are optional and advisory: a replica echoes a non-empty
``rid`` back in the response and tags its internal phase spans with
it, the fleet router annotates its routing spans with it, and
``tools/merge_traces.py --fleet`` stitches the per-process spans into
one causal tree keyed on it. Requests without ``rid`` serve exactly
as before, responses without it are byte-identical to the pre-rid
wire format, and no clock is read for it unless a trace sink is
installed — the contract channel never sees the difference.
"""

from __future__ import annotations

import json
import re
import socket
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple, Union

import numpy as np

from dmlp_tpu.io import native
from dmlp_tpu.io.checksum import fnv1a_checksum_batch
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve.batching import Request

#: protocol schema version, echoed in hello/stats
PROTOCOL_VERSION = 1

#: request-line size cap. The daemon's connection handler enforces it
#: AT THE READ (:class:`LineReader` never holds more than cap + 1
#: bytes) so an oversized line never buffers past the cap; the
#: re-check in parse_request covers non-socket callers.
MAX_LINE_BYTES = 64 << 20

#: the size a response line is encoded and written in pieces of
#: (:func:`encode_pieces`), about what one of :class:`LineReader`'s
#: receives brings of a large line: each piece is a socket write, which
#: hands the interpreter lock over
PIECE_BYTES = 1 << 20

#: numbers after which :func:`encode_pieces` closes a group of rows, one
#: ``json.dumps`` call: the C encoder cannot hand the interpreter lock
#: over inside a call, and a float takes it about a microsecond, so a
#: call is kept to a fraction of a millisecond (a row of 1000 ids alone,
#: 52 rows of 10)
_GROUP_NUMBERS = 512

#: per-request row cap of the ``corpus`` read op (bounds one response
#: line; replay loops page through larger ranges)
CORPUS_FETCH_MAX = 65536


class ProtocolError(ValueError):
    """A malformed request line (the response names the defect)."""


class LineReader:
    """One connection's lines, received in pieces as large as the
    kernel hands over.

    A 31 MB query line read through ``socketserver``'s 8 KB
    ``BufferedReader`` is ~3 800 raw reads, each of which drops the
    interpreter lock and has to take it back from the batcher thread
    and the other handlers; here it is tens of ``recv_into`` calls
    into ONE buffer the connection keeps between lines. The buffer
    starts at :attr:`FIRST_BYTES`, doubles when a line fills it, and is
    never shrunk or reallocated: a connection that sent one 31 MB line
    has room for the next, one that sends KB lines never grows. The
    reader must own the socket alone (a ``makefile`` reader beside it
    would keep bytes in a buffer of its own).

    ``readline`` mirrors ``BufferedReader.readline(MAX_LINE_BYTES +
    1)``: the line with its newline; at the end of the stream what
    arrived without one, ``b""`` when that is nothing; and never more
    than ``MAX_LINE_BYTES + 1`` bytes held, a line that long handed
    on unterminated for the caller to refuse. Bytes that followed a
    newline (a client may pipeline) start the next line."""

    FIRST_BYTES = 64 << 10

    def __init__(self, sock: socket.socket):
        self._sock = sock
        self._buf = bytearray(self.FIRST_BYTES)
        self._have = 0          # bytes of the next line already here
        #: receive calls the last line took (0: whole in the carry-over)
        self.pieces = 0
        #: perf_counter when the last line's first bytes were in hand,
        #: and the thread's CPU time then while a sink is installed
        self.t_first = 0.0
        self.c_first: Optional[float] = None

    def readline(self) -> bytes:
        buf, have = self._buf, self._have
        cap = MAX_LINE_BYTES + 1
        self.pieces = 0
        if have:
            self._stamp_first()
        seen = 0                # a newline is sought in new bytes only
        while True:
            nl = buf.find(b"\n", seen, have)
            if nl >= 0 or have >= cap:
                break
            seen = have
            if have == len(buf):
                buf.extend(bytes(min(len(buf), cap - len(buf))))
            with memoryview(buf) as whole, \
                    whole[have:min(len(buf), cap)] as free:
                got = self._sock.recv_into(free)
            self.pieces += 1
            if not got:         # end of stream: what came is the line
                break
            if not have:
                self._stamp_first()
            have += got
        end = nl + 1 if nl >= 0 else have
        with memoryview(buf) as whole:
            line = bytes(whole[:end])       # the one copy of the line
        buf[:have - end] = buf[end:have]
        self._have = have - end
        return line

    def _stamp_first(self) -> None:
        self.t_first = time.perf_counter()
        self.c_first = obs_trace.thread_cpu()


def _is_int(v) -> bool:
    """A real JSON integer — bool is an int subclass in Python, and
    ``{"k": true}`` silently served as k=1 is not the ProtocolError
    the parser promises for malformed requests."""
    return isinstance(v, int) and not isinstance(v, bool)


_QUERIES_KEY = re.compile(rb'"queries"[ \t\n\r]*:')


def _scan_queries(line: bytes
                  ) -> Optional[Tuple[Dict[str, Any], np.ndarray]]:
    """A query line's object without its ``"queries"`` member, and
    that member as the float64 matrix ``json.loads`` + ``np.asarray``
    would make of it, decoded by the native scanner with the
    interpreter lock released (:func:`native.parse_json_matrix`; a
    1024 x 960 request is 19 MB of digits, and ~1 M Python floats the
    other way). None unless all of it is proven: the first
    ``"queries":`` of the line is followed by an array the scanner
    takes whole; the bytes before it end where a member of the line's
    top-level object begins, and the bytes after it continue that
    object to its end (``json`` parses each, closed by a dummy
    member); no other spelling of the key stands in either (JSON keeps
    a repeated key's last value); the op is "query". Every other line
    — and every line when no library is loaded — is the caller's to
    parse whole, so what is accepted, what is refused and with which
    words never depends on the scanner."""
    m = _QUERIES_KEY.search(line)
    if m is None:
        return None
    got = native.parse_json_matrix(line, m.end())
    if got is None:
        return None
    q, end = got
    try:
        obj = json.loads(line[:m.start()].decode("utf-8") + '"":null}')
        rest = json.loads('{"":null' + line[end:].decode("utf-8"))
    except (ValueError, RecursionError):
        return None
    if "queries" in obj or "queries" in rest:
        return None
    obj.update(rest)
    if obj.get("op", "query") != "query":
        return None
    return obj, q


def parse_request(line: Union[str, bytes], num_attrs: int
                  ) -> Union[Request, Dict[str, Any], None]:
    """One wire line -> a validated :class:`Request` (op "query" |
    "ingest" | "corpus") or a control dict for "stats"/"drain". Raises
    :class:`ProtocolError` with a client-presentable message. A line
    still in its wire ``bytes`` has its query matrix decoded natively
    where :func:`_scan_queries` can; where not it is decoded and
    stripped here, and None says it was blank (no response is due)."""
    if len(line) > MAX_LINE_BYTES:
        raise ProtocolError("request line exceeds the size cap")
    q = None
    if isinstance(line, bytes):
        scanned = _scan_queries(line)
        if scanned is not None:
            obj, q = scanned
        else:
            try:
                line = line.decode("utf-8", errors="strict").strip()
            except UnicodeDecodeError:
                raise ProtocolError("request is not UTF-8") from None
            if not line:
                return None
    if q is None:
        try:
            obj = json.loads(line)
        except json.JSONDecodeError as e:
            raise ProtocolError(f"bad JSON: {e}") from None
    if not isinstance(obj, dict):
        raise ProtocolError("request must be a JSON object")
    op = obj.get("op", "query")
    if op in ("stats", "drain"):
        return obj
    req_id = str(obj.get("id", ""))
    rid = str(obj.get("rid", "") or "")
    if op == "query":
        parsed_native = q is not None
        if not parsed_native:
            queries = obj.get("queries")
            if not isinstance(queries, list) or not queries:
                raise ProtocolError("query op needs a non-empty 'queries' "
                                    "list of attribute rows")
            try:
                q = np.asarray(queries, np.float64)
            except (TypeError, ValueError):
                raise ProtocolError("'queries' rows must be numeric and "
                                    "rectangular") from None
        if q.ndim != 2 or q.shape[1] != num_attrs:
            raise ProtocolError(
                f"'queries' must be (nq, {num_attrs}), got {q.shape}")
        ks = obj.get("ks")
        if ks is None:
            k = obj.get("k")
            if not _is_int(k) or k < 1:
                raise ProtocolError("need 'k' (positive int) or 'ks'")
            ks_arr = np.full(len(q), k, np.int32)
        else:
            if (not isinstance(ks, list) or len(ks) != len(q)
                    or not all(_is_int(v) and v >= 1 for v in ks)):
                raise ProtocolError("'ks' must list one positive int "
                                    "per query row")
            ks_arr = np.asarray(ks, np.int32)
        return Request(kind="query", req_id=req_id, rid=rid,
                       query_attrs=q, ks=ks_arr,
                       debug=bool(obj.get("debug")),
                       parsed_native=parsed_native)
    if op == "ingest":
        rows = obj.get("rows")
        labels = obj.get("labels")
        if not isinstance(rows, list) or not rows:
            raise ProtocolError("ingest op needs a non-empty 'rows' list")
        try:
            attrs = np.asarray(rows, np.float64)
        except (TypeError, ValueError):
            raise ProtocolError("'rows' must be numeric and "
                                "rectangular") from None
        if attrs.ndim != 2 or attrs.shape[1] != num_attrs:
            raise ProtocolError(
                f"'rows' must be (m, {num_attrs}), got {attrs.shape}")
        if (not isinstance(labels, list) or len(labels) != len(rows)
                or not all(_is_int(v) for v in labels)):
            raise ProtocolError("'labels' must list one int per row")
        start = obj.get("start")
        if start is not None and (not _is_int(start) or start < 0):
            raise ProtocolError("'start' must be a non-negative int "
                                "(the global row id of the first row)")
        return Request(kind="ingest", req_id=req_id, rid=rid,
                       labels=np.asarray(labels, np.int32), attrs=attrs,
                       start=start)
    if op == "corpus":
        start = obj.get("start", 0)
        count = obj.get("count", 0)
        if not _is_int(start) or start < 0:
            raise ProtocolError("corpus op 'start' must be a "
                                "non-negative int")
        if not _is_int(count) or count < 0:
            raise ProtocolError("corpus op 'count' must be a "
                                "non-negative int")
        return Request(kind="corpus", req_id=req_id, rid=rid,
                       start=start, count=min(count, CORPUS_FETCH_MAX))
    raise ProtocolError(f"unknown op {op!r}")


def _rid_echo(req: Request, out: Dict[str, Any]) -> Dict[str, Any]:
    """Echo a non-empty rid; rid-less responses keep the exact pre-rid
    key set (the traced/untraced byte-identity contract)."""
    if req.rid:
        out["rid"] = req.rid
    return out


def _checksums(labels: np.ndarray, results: List) -> np.ndarray:
    """The contract checksum of each QueryResult
    (``QueryResult.checksum()``: its label, then every id of its
    list), from the lists stacked into one padded array: 1024 x 1000
    ids are ~1000 array steps, not a million Python ones."""
    counts = [len(r.neighbor_ids) for r in results]
    ids = np.full((len(results), max(counts, default=0)), -1, np.int64)
    for row, r, n in zip(ids, results, counts):
        row[:n] = r.neighbor_ids
    return fnv1a_checksum_batch(labels, ids, counts)


def query_response(req: Request, debug: bool = False) -> Dict[str, Any]:
    """The completed query Request -> its wire response. Numbers leave
    their arrays by ``tolist()`` (the Python ints and floats
    ``json.dumps`` writes), the lists a query's at a time."""
    if req.error is not None:
        return _rid_echo(req, {"id": req.req_id, "ok": False,
                               "error": req.error})
    labels = np.array([r.predicted_label for r in req.results], np.int64)
    out: Dict[str, Any] = {
        "id": req.req_id, "ok": True,
        "labels": labels.tolist(),
        "checksums": _checksums(labels, req.results).tolist(),
        "latency_ms": round(req.latency_ms, 3),
    }
    if debug or req.debug:
        out["neighbors"] = [r.neighbor_ids.tolist() for r in req.results]
        out["dists"] = [r.neighbor_dists.tolist() for r in req.results]
    return _rid_echo(req, out)


def ingest_response(req: Request) -> Dict[str, Any]:
    if req.error is not None:
        return _rid_echo(req, {"id": req.req_id, "ok": False,
                               "error": req.error})
    return _rid_echo(req, {"id": req.req_id, "ok": True,
                           "corpus_rows": int(req.corpus_rows)})


def corpus_response(req: Request) -> Dict[str, Any]:
    """The completed ``corpus`` read -> its wire response (payload is
    assembled on the batcher thread, so the rows and the signature are
    one consistent snapshot — never torn by a concurrent ingest)."""
    if req.error is not None:
        return _rid_echo(req, {"id": req.req_id, "ok": False,
                               "error": req.error})
    return _rid_echo(req, {"id": req.req_id, "ok": True,
                           **(req.payload or {})})


#: ``json.dumps(obj, separators=(",", ":"), sort_keys=True)``, built once
_dumps = json.JSONEncoder(separators=(",", ":"), sort_keys=True).encode


def _groups(rows: List) -> Iterator[List]:
    """``rows`` in order, in slices that each hold ``_GROUP_NUMBERS``
    numbers or little more (a row that is a list counts its length)."""
    start = numbers = 0
    for i, row in enumerate(rows):
        numbers += len(row) if isinstance(row, list) else 1
        if numbers >= _GROUP_NUMBERS:
            yield rows[start:i + 1]
            start, numbers = i + 1, 0
    if start < len(rows):
        yield rows[start:]


def _texts(obj: Dict[str, Any]) -> Iterator[str]:
    """The wire text of ``obj`` in order, in fragments none of which
    took one ``json.dumps`` call long: a member that is a list of lists
    (``neighbors``, ``dists``, a ``corpus`` read's ``rows``) a group of
    its rows a call, the members between two such in one call. Keys are
    strings, as every wire object's are, in sorted order."""
    sep, plain = "{", {}
    for key in sorted(obj):
        value = obj[key]
        if not (isinstance(value, list) and value
                and isinstance(value[0], list)):
            plain[key] = value
            continue
        if plain:
            yield sep + _dumps(plain)[1:-1]
            sep, plain = ",", {}
        sep += _dumps(key) + ":["
        for group in _groups(value):
            yield sep + _dumps(group)[1:-1]
            sep = ","
        sep = "],"
    if plain or sep == "{":
        yield sep + _dumps(plain)[1:-1] + "}\n"
    else:
        yield "]}\n"


def encode_pieces(obj: Dict[str, Any]) -> Iterator[bytes]:
    """A wire line in pieces of about ``PIECE_BYTES``: joined, they
    are ``json.dumps(obj, separators=(",", ":"), sort_keys=True)`` and
    a newline, to the byte. A ``debug`` response at k = 1000 is 26 MB;
    encoded by one ``json.dumps`` its handler holds the interpreter
    lock for over a second, the batcher thread beside it waits that
    long to take it back after a NumPy call, and the chip waits for the
    batcher. Here the text is made a fragment at a time
    (:func:`_texts`) and leaves as soon as a piece is full, for the
    caller to write before the next is encoded. A small line is one
    ``json.dumps`` and one piece."""
    held: List[str] = []
    size = 0
    for text in _texts(obj):
        held.append(text)
        size += len(text)       # ASCII: json escapes the rest
        if size >= PIECE_BYTES:
            yield "".join(held).encode()
            held, size = [], 0
    if held:
        yield "".join(held).encode()


def encode(obj: Dict[str, Any]) -> bytes:
    return b"".join(encode_pieces(obj))
