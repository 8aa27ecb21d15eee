"""The native decode of a request line's query matrix
(native/fastparse.cpp dmlp_parse_json_matrix through
serve/protocol.py): the same float64 bits as json.loads + np.asarray,
the same Requests, and for every line it does not take whole the same
outcome and the same words as with no library at all
(DMLP_TPU_NO_NATIVE)."""

import ctypes
import json
import socket
import subprocess

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.io import native
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve import protocol
from dmlp_tpu.serve.daemon import ServeDaemon

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="g++ unavailable")


def drop_library(monkeypatch):
    """The process as DMLP_TPU_NO_NATIVE=1 starts it: the variable is
    read once, at the first load."""
    monkeypatch.setenv("DMLP_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_lib", None)
    assert not native.native_available()


@pytest.fixture(scope="module")
def strtod_lib(tmp_path_factory):
    """The library as a toolchain without floating-point from_chars
    builds it."""
    so = str(tmp_path_factory.mktemp("fastparse") / "_strtod.so")
    subprocess.run(["g++", "-O3", "-shared", "-fPIC",
                    "-DDMLP_FASTPARSE_NO_FROM_CHARS", "-o", so,
                    native._SRC], check=True, capture_output=True)
    return native._bind(ctypes.CDLL(so))


def bits(a):
    return np.ascontiguousarray(a, np.float64).view(np.uint64)


def reference(text):
    return np.asarray(json.loads(text), np.float64)


def f32_exact(shape, seed):
    rng = np.random.default_rng(seed)
    return rng.random(shape, dtype=np.float32).astype(np.float64)


def full_f64(shape, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(shape) * 10.0 ** rng.integers(-8, 9, shape)


def spaced(text):
    """The same JSON with whitespace around every token."""
    return (text.replace("[", " [\t").replace(",", "\n , ")
            .replace("]", "\r ]  "))


MATRICES = {
    # the benchmark's payload form: float32-exact values as tolist()
    # prints them, 16-18 digits
    "f32_exact_1x1": json.dumps(f32_exact((1, 1), 1).tolist()),
    "f32_exact_3x128": json.dumps(f32_exact((3, 128), 2).tolist()),
    "f32_exact_64x960": json.dumps(f32_exact((64, 960), 3).tolist(),
                                   separators=(",", ":")),
    "full_f64_3x128": json.dumps(full_f64((3, 128), 4).tolist()),
    "full_f64_64x960": json.dumps(full_f64((64, 960), 5).tolist(),
                                  separators=(",", ":")),
    "integers": "[[0,1,-1,7,255],[10,-20,300,4000,123456789]]",
    "negatives": "[[-1.5,-0.25,-3e2],[-2.5E-3,-100,-7.125]]",
    # the INTEGER -0 is the int 0 to json.loads, so +0.0; -0.0 keeps
    # its sign
    "minus_zero": "[[-0.0,-0,0,0.0,-0e0,-0.0e5]]",
    "exponents": "[[1e5,2.5E-3,1E+2,1e-2,6.02e23,1.0e0,0e0,1E5]]",
    "subnormals": "[[5e-324,4.9406564584124654e-324,2.2250738585072011e-308,"
                  "1e-310,-3e-320,1e-400,2.4703282292062327e-324]]",
    "long_tokens": "[[0.12345678901234567890123,3.141592653589793238462643,"
                   "1234567890123456789012,0.1000000000000000055511151231257"
                   "827021181583404541015625,9007199254740993,"
                   "123456789012345678.5,1.7976931348623157e308,"
                   "0.000000000000000000000000000001234567890123456789]]",
    "halfway_cases": "[[9007199254740993,9007199254740995,"
                     "1.00000000000000011102230246251565404236316680908203125,"
                     "1.00000000000000011102230246251565404236316680908203124,"
                     "1.00000000000000011102230246251565404236316680908203126,"
                     "8.41e21,5e-324,2.47e-324,2.48e-324]]",
    "fifteen_and_sixteen_digits": "[[123456789012345,1234567890123456,"
                                  "0.123456789012345,0.1234567890123456,"
                                  "12345678.9012345,1.23456789012345e0]]",
}


@pytest.mark.parametrize("ws", [False, True], ids=["compact", "spaced"])
@pytest.mark.parametrize("name", sorted(MATRICES))
def test_scanner_bit_identical_to_json_loads(name, ws):
    text = spaced(MATRICES[name]) if ws else MATRICES[name]
    raw = b'{"queries":' + text.encode() + b',"k":1}'
    got = native.parse_json_matrix(raw, len(b'{"queries":'))
    assert got is not None
    q, end = got
    want = reference(text)
    assert q.shape == want.shape and q.dtype == np.float64
    assert np.array_equal(bits(q), bits(want))
    assert raw[end:].lstrip() == b',"k":1}' and raw[end - 1:end] == b"]"


@pytest.mark.parametrize("name", sorted(MATRICES))
def test_a_build_without_from_chars_decodes_the_same_bits(
        name, strtod_lib, monkeypatch):
    assert native.float_converter() in ("from_chars", "strtod")
    monkeypatch.setattr(native, "_lib", strtod_lib)
    assert native.float_converter() == "strtod"
    raw = MATRICES[name].encode() + b"}"
    q, end = native.parse_json_matrix(raw, 0)
    assert np.array_equal(bits(q), bits(reference(MATRICES[name])))
    assert raw[end:] == b"}"


@pytest.mark.parametrize("text", [
    "[]", "[[]]", "[[1],[]]", "[[1,2],[3]]", "[[1],[2,3]]", "[1,2]",
    "[[[1]]]", "[[NaN]]", "[[Infinity]]", "[[-Infinity]]", "[[nan]]",
    "[[+1]]", "[[.5]]", "[[5.]]", "[[01]]", "[[-01]]", "[[00]]",
    "[[0x10]]", "[[1e]]", "[[1e+]]", "[[1.e5]]", "[[--1]]", "[[-]]",
    '[["1"]]', "[[true]]", "[[null]]", "[[1,]]", "[[1],]", "[[,1]]",
    "[[1 2]]", "[[1]", "[[1", "[[1]]"[:-1], "[[1e999]]", "[[-1e999]]",
    "[[" + "9" * 400 + "]]", "[[1_0]]", "[[1.5f]]", "[[1]\x0b]",
    "[[1\xa0]]", "{}", "",
])
def test_scanner_refuses_what_is_not_a_number_matrix(text):
    raw = text.encode("utf-8")
    assert native.parse_json_matrix(raw, 0) is None
    # and with bytes after it, where those could complete nothing
    assert native.parse_json_matrix(raw + b" ", 0) is None


def test_scanner_never_reads_or_writes_past_its_range():
    raw = b"[[1,2],[3,4]]"
    assert native.parse_json_matrix(raw, 0)[1] == len(raw)
    # the same digits cut short by the end of the range
    lib = native._load()
    out3 = (ctypes.c_long * 3)()
    buf = np.full(8, -7.0)
    for cut in range(len(raw)):
        assert lib.dmlp_parse_json_matrix(raw, 0, cut, buf, 4, out3) != 0
    # a buffer too small is refused, and nothing past it is written
    buf = np.full(8, -7.0)
    assert lib.dmlp_parse_json_matrix(raw, 0, len(raw), buf, 3, out3) == 2
    assert list(buf[3:]) == [-7.0] * 5


# -- parse_request through both paths ----------------------------------------

def request_fields(req):
    if req.kind != "query":
        return (req.kind, req.req_id, req.rid, req.attrs.tolist(),
                req.labels.tolist(), req.start)
    return (req.kind, req.req_id, req.rid, req.debug,
            bits(req.query_attrs).tolist(), req.query_attrs.shape,
            req.ks.tolist(), str(req.ks.dtype))


def both_paths(line, num_attrs, monkeypatch):
    """(outcome with the library, outcome without): a Request's fields,
    a control dict, None, or the error's type and text."""
    def outcome(as_bytes):
        try:
            got = protocol.parse_request(
                line if as_bytes else line.decode("utf-8").strip(),
                num_attrs)
        except Exception as e:  # noqa: BLE001 - the text is compared
            return (type(e).__name__, str(e))
        if isinstance(got, protocol.Request):
            return request_fields(got) + (got.parsed_native,)
        return got
    with_lib = outcome(True)
    drop_library(monkeypatch)
    without_bytes, without_str = outcome(True), outcome(False)
    return with_lib, without_bytes, without_str


QUERY_LINES = {
    "k": {"op": "query", "id": "a7", "k": 3,
          "queries": f32_exact((4, 6), 11).tolist()},
    "ks": {"op": "query", "id": 12, "ks": [1, 2, 3, 4],
           "queries": full_f64((4, 6), 12).tolist()},
    "rid_debug_trace": {"id": "x", "rid": "r-9", "debug": True, "k": 2,
                        "trace": {"fire": 1.5, "queries": [[9, 9]]},
                        "queries": f32_exact((2, 6), 13).tolist()},
    "no_op_no_id": {"k": 1, "queries": [[1, 2, 3, 4, 5, -0]]},
    "keys_after": {"queries": [[1, 2, 3, 4, 5, 6]], "rid": "queries",
                   "k": 1, "op": "query"},
    "unicode_rid": {"rid": "résumé ☃", "k": 1,
                    "queries": [[0.5, 1, 2, 3, 4, 5]]},
}


@pytest.mark.parametrize("sort_keys", [False, True])
@pytest.mark.parametrize("name", sorted(QUERY_LINES))
def test_parse_request_native_equals_fallback(name, sort_keys, monkeypatch):
    line = (json.dumps(QUERY_LINES[name], sort_keys=sort_keys,
                       ensure_ascii=False) + "\n").encode("utf-8")
    with_lib, without_bytes, without_str = both_paths(line, 6, monkeypatch)
    # as written the trace's own "queries" stands before the request's
    # (sorted, after it): the line's first "queries": is then not the
    # top-level member, and the line is unproven
    nested_first = name == "rid_debug_trace" and not sort_keys
    assert with_lib[-1] is (not nested_first)
    assert without_bytes[-1] is False
    assert with_lib[:-1] == without_bytes[:-1] == without_str[:-1]


UNPROVEN = {
    "ragged": b'{"k":1,"queries":[[1,2],[3]]}',
    "empty_row": b'{"k":1,"queries":[[]]}',
    "empty_matrix": b'{"k":1,"queries":[]}',
    "nan": b'{"k":1,"queries":[[NaN,1]]}',
    "string_cell": b'{"k":1,"queries":[["1",2]]}',
    "bool_cell": b'{"k":1,"queries":[[true,2]]}',
    "leading_zero": b'{"k":1,"queries":[[01,2]]}',
    "one_dimension": b'{"k":1,"queries":[1,2]}',
    "queries_null": b'{"k":1,"queries":null}',
    "inside_rid": b'{"k":1,"rid":"\\"queries\\":[[1,2]]","queries":[[3,4]]}',
    "rid_is_the_word": b'{"rid":"queries","k":1,"queries":[[3,4]]}',
    "twice": b'{"k":1,"queries":[[1,2]],"queries":[[3,4]]}',
    "twice_then_null": b'{"k":1,"queries":[[1,2]],"queries":null}',
    "twice_escaped_after": b'{"k":1,"queries":[[1,2]],"\\u0071ueries":[[3]]}',
    "twice_escaped_before": b'{"k":1,"\\u0071ueries":[[3]],"queries":[[1,2]]}',
    "nested_only": b'{"k":1,"trace":{"queries":[[1,2]]}}',
    "nested_then_null": b'{"k":1,"trace":{"queries":[[1,2]]},"queries":null}',
    "nested_then_real": b'{"k":1,"trace":{"queries":[[9,9]]},"queries":[[1,2]]}',
    "in_an_array": b'[{"k":1,"queries":[[1,2]]}]',
    "wrong_width": b'{"k":1,"queries":[[1,2,3]]}',
    "trailing_fraction": b'{"k":1,"queries":[[1,2]].5}',
    "trailing_garbage": b'{"k":1,"queries":[[1,2]]}x',
    "no_comma_before": b'{"k":1 "queries":[[1,2]]}',
    "no_brace": b'"k":1,"queries":[[1,2]]}',
    "unclosed": b'{"k":1,"queries":[[1,2]]',
    "other_op": b'{"op":"ingest","queries":[[1,2]],"rows":[[1,2]],"labels":[1]}',
    "stats_with_queries": b'{"op":"stats","queries":[[1,2]]}',
    "unknown_op": b'{"op":"wat","queries":[[1,2]]}',
    "k_missing": b'{"queries":[[1,2]]}',
    "k_bool": b'{"k":true,"queries":[[1,2]]}',
    "ks_short": b'{"ks":[1],"queries":[[1,2],[3,4]]}',
    "huge_int": b'{"k":1,"queries":[[' + b"9" * 400 + b',2]]}',
    "overflow_float": b'{"k":1,"queries":[[1e999,2]]}',
    "bom": b'\xef\xbb\xbf{"k":1,"queries":[[1,2]]}',
    "not_utf8_before": b'{"rid":"\xff","k":1,"queries":[[1,2]]}',
    "not_utf8_after": b'{"k":1,"queries":[[1,2]],"rid":"\xff"}',
    "unicode_space_around": b'\x1c{"k":1,"queries":[[1,2]]}\xc2\x85',
    "blank_unicode": b"\xc2\x85\x1c \n",
    "valid_spaced": b' { "k" : 1 , "queries" : [ [ 1 , 2 ] ] } \r\n',
    "valid_compact": b'{"k":1,"queries":[[1,2]]}\n',
}


@pytest.mark.parametrize("name", sorted(UNPROVEN))
def test_same_outcome_and_words_with_and_without_the_library(
        name, monkeypatch):
    line = UNPROVEN[name]
    with_lib, without_bytes, _ = both_paths(line, 2, monkeypatch)
    # of the lines that make a Request, the scanner took these: the
    # word inside a string never matches the key (its quotes are
    # escaped, or a comma follows it)
    took = name in ("valid_spaced", "valid_compact", "inside_rid",
                    "rid_is_the_word")
    if isinstance(with_lib, tuple) and len(with_lib) > 2:
        assert with_lib[-1] is took
        with_lib, without_bytes = with_lib[:-1], without_bytes[:-1]
    assert with_lib == without_bytes
    try:
        text = line.decode("utf-8").strip()
    except UnicodeDecodeError:
        assert with_lib == ("ProtocolError", "request is not UTF-8")
        return
    if not text:
        assert with_lib is None
        return
    # and that outcome is the one the parent's whole-line parse gives
    try:
        want = protocol.parse_request(text, 2)
    except Exception as e:  # noqa: BLE001
        assert with_lib == (type(e).__name__, str(e))
    else:
        assert with_lib == (want if isinstance(want, dict)
                            else request_fields(want))


MUTATION_SEEDS = [
    b'{"op":"query","id":"a","k":3,"queries":[[1.5,2],[3,-4e1]]}\n',
    b'{"k":1,"queries":[[0.1234567890123456789,-0]],"rid":"queries",'
    b'"debug":true}\n',
    b' { "ks" : [1,2] , "queries" : [ [ 1 , 2 ] , [ 3 , 4 ] ] , '
    b'"trace":{"queries":[[1,2]]} }\r\n',
    b'{"trace":{"queries":[[1,2]]},"queries":[[5,6]],"k":2}\n',
    b'{"queries":[[1,2]],"k":1,"queries":[[3,4]]}',
]


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_mutated_lines_never_tell_the_two_paths_apart(seed, monkeypatch):
    """One to three random byte edits of a valid line, 500 lines a
    seed: with the library and without it, the same Request or the
    same error, and that of the parent's decode-strip-parse."""
    import random
    rnd = random.Random(seed)
    alphabet = list(b'[]{},:"\\ \t\n0123456789.eE+-xqueris\xc2\x85\xff')
    lib = native._load()
    monkeypatch.setattr(native, "_tried", True)

    def outcome(line):
        try:
            got = protocol.parse_request(line, 2)
        except Exception as e:  # noqa: BLE001 - the text is compared
            return (type(e).__name__, str(e))
        return request_fields(got) if isinstance(got, protocol.Request) \
            else got

    taken = 0
    for _ in range(500):
        b = bytearray(rnd.choice(MUTATION_SEEDS))
        for _ in range(rnd.randint(1, 3)):
            op, pos = rnd.random(), rnd.randrange(len(b) + 1)
            if op < 0.4 and b:
                del b[min(pos, len(b) - 1)]
            elif op < 0.8:
                b.insert(pos, rnd.choice(alphabet))
            elif b:
                b[min(pos, len(b) - 1)] = rnd.choice(alphabet)
        line = bytes(b)
        monkeypatch.setattr(native, "_lib", lib)
        taken += protocol._scan_queries(line) is not None
        with_lib = outcome(line)
        monkeypatch.setattr(native, "_lib", None)
        assert outcome(line) == with_lib, line
        try:
            text = line.decode("utf-8").strip()
        except UnicodeDecodeError:
            assert with_lib == ("ProtocolError", "request is not UTF-8")
        else:
            assert with_lib == (outcome(text) if text else None), line
    assert 20 < taken < 480     # both paths were walked


def test_over_the_size_cap_is_refused_before_any_decode(monkeypatch):
    monkeypatch.setattr(protocol, "MAX_LINE_BYTES", 64)
    line = b'{"k":1,"queries":[[' + b"1," * 40 + b'1]]}'
    with_lib, without_bytes, without_str = both_paths(line, 41, monkeypatch)
    assert with_lib == without_bytes == without_str == (
        "ProtocolError", "request line exceeds the size cap")


# -- the daemon: counters, span argument, stats, byte-identical answers ------

def make_corpus(n=300, na=6, seed=5):
    rng = np.random.default_rng(seed)
    return KNNInput(Params(n, 0, na),
                    rng.integers(0, 4, n).astype(np.int32),
                    rng.uniform(-5, 5, (n, na)),
                    np.zeros(0, np.int32), np.zeros((0, na), np.float64))


def exchange(port, lines):
    """Send the lines on one connection, one response line each."""
    out = []
    with socket.create_connection(("127.0.0.1", port), timeout=120) as s:
        f = s.makefile("rb")
        for line in lines:
            s.sendall(line)
            out.append(f.readline())
    return out


def scrub(resp_line):
    doc = json.loads(resp_line)
    doc.pop("latency_ms", None)
    return doc


def serve_lines(lines, trace=False):
    tracer = obs_trace.install(obs_trace.Tracer()) if trace else None
    d = ServeDaemon(make_corpus(), EngineConfig(), port=0,
                    warm_buckets=[(8, 8)])
    d.start()
    try:
        resps = exchange(d.port, lines)
        stats = d.stats()
        spans = [e for e in (tracer.events() if tracer else [])
                 if e.get("name") == "serve.phase.parse"]
    finally:
        d.close()
        if tracer is not None:
            obs_trace.uninstall()
    return resps, stats, spans


def wire_lines():
    q = f32_exact((5, 6), 21)
    return [
        (json.dumps({"op": "query", "id": "n0", "k": 3, "debug": True,
                     "queries": q.tolist()}) + "\n").encode(),
        (json.dumps({"op": "query", "id": "n1", "rid": "r1",
                     "ks": [1, 2, 3], "queries": q[:3].tolist()},
                    separators=(",", ":")) + "\n").encode(),
        # unproven: a second "queries" -> the whole line through json
        b'{"id":"f0","k":2,"queries":[[0,0,0,0,0,0]],"queries":'
        + json.dumps(q[:2].tolist()).encode() + b'}\n',
        b'{"id":"bad","k":2,"queries":[[1,2],[3]]}\n',
        b'{"id":"w","k":2,"queries":[[1,2]]}\n',
        b'{"k":1,"rid":"\xff","queries":[[1,2,3,4,5,6]]}\n',
        b'not json\n',
    ]


def test_daemon_counts_the_decodes_and_tags_the_span():
    resps, stats, spans = serve_lines(wire_lines(), trace=True)
    assert [json.loads(r)["ok"] for r in resps] == \
        [True, True, True, False, False, False, False]
    assert stats["parse"] == {"native_requests": 2, "fallback_requests": 1,
                              "converter": native.float_converter()}
    assert native.float_converter() in ("from_chars", "strtod")
    by_id = sorted((s["args"]["queries"], s["args"]["native_queries"],
                    s["args"]["bytes"]) for s in spans)
    lines = wire_lines()
    assert by_id == sorted([(5, 5, len(lines[0])), (3, 3, len(lines[1])),
                            (2, 0, len(lines[2]))])
    reg = telemetry.registry().counter("serve.parse_requests")
    assert reg.by_label() == {"native": 2.0, "fallback": 1.0}


def test_daemon_without_the_library_answers_byte_for_byte(monkeypatch):
    with_lib, stats, _ = serve_lines(wire_lines())
    assert stats["parse"]["native_requests"] == 2
    drop_library(monkeypatch)
    without, stats, _ = serve_lines(wire_lines())
    assert stats["parse"] == {"native_requests": 0, "fallback_requests": 3,
                              "converter": None}
    assert [scrub(a) for a in with_lib] == [scrub(b) for b in without]
    for a, b in zip(with_lib, without):
        if b'"latency_ms"' not in a:
            assert a == b


def test_blank_lines_get_no_response():
    d = ServeDaemon(make_corpus(), EngineConfig(), port=0,
                    warm_buckets=[(8, 8)])
    d.start()
    try:
        with socket.create_connection(("127.0.0.1", d.port),
                                      timeout=120) as s:
            f = s.makefile("rb")
            # blank to bytes.isspace, blank only once decoded (NEL, FS),
            # then a request: one response in all, and it is the
            # request's
            s.sendall(b"\n  \r\n\xc2\x85\x1c\n"
                      b'{"id":"q","k":1,"queries":[[1,2,3,4,5,6]]}\n'
                      b"\xc2\xa0\n" b'{"op":"stats"}\n')
            first, second = json.loads(f.readline()), json.loads(f.readline())
    finally:
        d.close()
    assert first["ok"] is True and first["id"] == "q"
    assert second["stats"]["parse"]["native_requests"] == 1
