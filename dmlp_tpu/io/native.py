"""ctypes bridge to the native C++ grammar parser (native/fastparse.cpp).

The reference's harness is native C++ (common.cpp); the analog here is a
C++ tokenizer for the same grammar that fills the flat SoA arrays the
device pipeline consumes — ~20x the pure-Python parser on benchmark-size
inputs, bit-identical output (strtod and Python float() round identically).

The shared library is built on demand with g++ (no pybind11 in the image;
plain ``extern "C"`` + ctypes). Everything degrades gracefully: if the
toolchain or the build is unavailable, callers fall back to the Python
parser (grammar.parse_input_text).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from dmlp_tpu.io.grammar import KNNInput, Params, ParseError

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "fastparse.cpp")
_LIB = os.path.join(_REPO_ROOT, "native", "_fastparse.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    """g++-compile the parser if the .so is missing or stale."""
    if not os.path.exists(_SRC):
        return False
    if (os.path.exists(_LIB)
            and os.path.getmtime(_LIB) >= os.path.getmtime(_SRC)):
        return True
    # Built beside the target and renamed over it: another process that
    # loads or builds at the same moment never sees half a library.
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB)
        return True
    except (OSError, subprocess.SubprocessError):
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare every entry's signature (ctypes assumes int otherwise)."""
    lib.dmlp_parse_header.restype = ctypes.c_int
    lib.dmlp_parse_header.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_long)]
    lib.dmlp_parse_body.restype = ctypes.c_int
    lib.dmlp_parse_body.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_long, ctypes.c_long,
        ctypes.c_long,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        ctypes.c_char_p, ctypes.c_size_t]
    lib.dmlp_float_converter.restype = ctypes.c_char_p
    lib.dmlp_float_converter.argtypes = []
    lib.dmlp_parse_json_matrix.restype = ctypes.c_int
    lib.dmlp_parse_json_matrix.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_size_t,
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        ctypes.c_size_t, ctypes.POINTER(ctypes.c_long)]
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("DMLP_TPU_NO_NATIVE"):
            return None
        # check: allow-concurrency=R703 — the g++ build under the lock
        # is the once-guard: this runs at most ONCE per process (_tried
        # flips first), and concurrent parsers must block until the .so
        # exists rather than racing the compiler or falling back to the
        # slow Python parser mid-build.
        if not _build():
            return None
        try:
            _lib = _bind(ctypes.CDLL(_LIB))
        except (OSError, AttributeError):
            # Corrupt/wrong-arch/half-written .so, or one that lacks an
            # entry: degrade to the Python parser rather than poisoning
            # every large parse_input call.
            return None
        return _lib


def native_available() -> bool:
    return _load() is not None


def float_converter() -> Optional[str]:
    """What the loaded library converts tokens past its 15-digit fast
    path with: "from_chars" or "strtod" (both correctly rounded); None
    with no library."""
    lib = _load()
    return None if lib is None else lib.dmlp_float_converter().decode()


def parse_json_matrix(raw: bytes, start: int
                      ) -> Optional[Tuple[np.ndarray, int]]:
    """The JSON array of equal-length number arrays at ``raw[start:]``
    as a float64 ``(rows, cols)`` array, bit-identical to ``json.loads``
    + ``np.asarray``, and the offset just past its closing bracket. None
    when no library is loaded or the scanner does not take the array
    whole (fastparse.cpp dmlp_parse_json_matrix says what it takes). The
    scan runs with the interpreter lock released."""
    lib = _load()
    if lib is None:
        return None
    # a number and its separator are two bytes at least; pages the scan
    # never writes are never mapped
    buf = np.empty((len(raw) - start) // 2 + 1, np.float64)
    out3 = (ctypes.c_long * 3)()
    if lib.dmlp_parse_json_matrix(raw, start, len(raw), buf, len(buf), out3):
        return None
    rows, cols, end = out3
    return buf[:rows * cols].reshape(rows, cols), end


def parse_input_text_native(text) -> KNNInput:
    """Parse via the C++ tokenizer; raises ValueError like the Python parser
    (same messages: "Line is empty" / "Line is wrongly formatted").

    Accepts str or bytes; pass bytes for large payloads to skip a full
    decode/encode round-trip (the C parser works on the raw buffer).
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native parser unavailable")
    raw = text if isinstance(text, bytes) else text.encode("ascii")
    hdr = (ctypes.c_long * 3)()
    if lib.dmlp_parse_header(raw, len(raw), hdr) != 0:
        raise ParseError("malformed header line", line=1, byte_offset=0)
    nd, nq, na = int(hdr[0]), int(hdr[1]), int(hdr[2])
    if nd < 0 or nq < 0 or na < 0:
        raise ParseError("negative sizes in header", line=1, byte_offset=0)

    labels = np.empty(nd, np.int32)
    data_attrs = np.empty((nd, na), np.float64)
    ks = np.empty(nq, np.int32)
    query_attrs = np.empty((nq, na), np.float64)
    errbuf = ctypes.create_string_buffer(128)
    rc = lib.dmlp_parse_body(raw, len(raw), nd, nq, na, labels,
                             data_attrs.reshape(-1), ks,
                             query_attrs.reshape(-1), errbuf, len(errbuf))
    if rc != 0:
        raise _located_error(errbuf.value.decode("ascii"), rc)
    return KNNInput(Params(nd, nq, na), labels, data_attrs, ks, query_attrs,
                    parser="native")


def _located_error(msg: str, rc: int) -> ParseError:
    """The C side reports '<message> (byte offset N)' (fastparse.cpp
    set_err); lift the offset into the structured ParseError field so
    Python callers need no string parsing. Unknown shapes (an old .so
    from before offsets existed) degrade to an unlocated ParseError."""
    import re
    m = re.search(r"^(.*) \(byte offset (\d+)\)$", msg or "")
    if m:
        return ParseError(m.group(1), byte_offset=int(m.group(2)))
    return ParseError(msg or f"parse error {rc}")
