"""Persisted variant cache for the measured autotuner.

One small versioned JSON file maps (kernel, device kind, data-rows shape
bucket, kc, dtype, precision) -> the fastest measured kernel variant.
The file is written by the sweep (``python -m dmlp_tpu.tune``) and read
on the hot path by ``ops.pallas_extract._resolve_variant`` (kernel
"extract_topk") and ``ops.pallas_fused._resolve_variant`` (kernel
"fused_topk") through :func:`lookup_variant`. Schema 2 added the
per-entry kernel namespace: the fused megakernel's MXU gate shifts
which tiles win, so the two kernels sweep and cache independently.
Schema 3 added the first-pass precision axis: a bf16 dot spends one
MXU pass per tile where the split "bf16x3" form spends three and one
HIGHEST-precision f32 dot six (measured on v5e, PR 36: PERF.md
section 6), which moves the compute/traffic balance — and hence the
winning tile — so the forms sweep and cache independently (an "f32"
entry is a measurement of the one HIGHEST dot: the exact engines, which
run "bf16x3" at float32 staging, do not look under it). Old files still LOAD:
schema-1 keys upgrade to the extract namespace, schema-1 AND schema-2
keys take the "f32" precision suffix in memory (every pre-schema-3
measurement WAS an f32-pass measurement); saves always write schema 3.

Design constraints, in order:

- **Absent cache == today.** When the file does not exist the lookup
  returns None without importing jax or touching a backend — CPU/CI
  resolution stays bit-identical to the frozen heuristics (and a read
  never initialises a backend just to learn the device kind; the kind
  is only needed once a file with entries exists).
- **Keys are buckets, not exact shapes.** Data-row and attribute-width
  counts bucket to the next power of two: the variant ranking moves
  with the block-sweep regime (how many blocks amortize the warm-up)
  and with the VMEM footprint `a` drives, not with every ±5% of rows,
  and exact-shape keys would make every new dataset a cache miss. kc
  is already discrete (resolve_kcap rounds to 8) and keys directly.
- **A cache entry must never disable the kernel.** The envelope is
  validated on load (schema/kernel), each entry is re-validated at
  lookup (one corrupt entry misses itself, it does not poison the
  file's other winners), ne-alignment is re-checked against the
  concrete ``b``, and the resolver re-runs the full supports gate
  (VMEM included) on a cache hit — anything that fails falls through
  to the heuristic instead of erroring or flipping supports() False.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Dict, Optional, Tuple

#: bump on any backward-incompatible cache field change (2: per-entry
#: kernel namespace — extract_topk vs the fused megakernel; 3: the
#: first-pass precision key axis — f32 vs bf16 winners cached apart)
CACHE_SCHEMA = 3

#: legal first-pass precision key segments (config.EngineConfig
#: .precision resolved; int8 is the gated ROADMAP follow-on)
_PRECISIONS = ("f32", "bf16x3", "bf16")

#: the schema-2 envelope family; per-entry keys carry the concrete kernel
_KERNEL_FAMILY = "pallas_topk"
#: legal per-entry kernel namespaces ("prune_score" is the pruned
#: two-stage solve's block-scoring pass — ops.summaries resolves its
#: block-chunk tiling through the same contract)
_KERNELS = ("extract_topk", "fused_topk", "prune_score")
#: the schema-1 envelope value (extract-only caches; lenient load)
_KERNEL_V1 = "extract_topk"

#: legal extraction-candidates-per-pass values (quarter layout: ne must
#: divide the block into whole 128-lane sub-blocks)
_NE_CHOICES = (1, 2, 4, 8)


def cache_path() -> str:
    """The cache file location: ``$DMLP_TPU_TUNE_CACHE`` wins, else
    ``~/.cache/dmlp_tpu/extract_variants.json``."""
    env = os.environ.get("DMLP_TPU_TUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "dmlp_tpu",
                        "extract_variants.json")


def shape_bucket(b: int) -> int:
    """Data-row count -> power-of-two bucket (the smallest power of two
    >= b). 12800 and 16000 share a bucket; 12800 and 51200 do not."""
    if b <= 1:
        return 1
    return 1 << (b - 1).bit_length()


def _key(kernel: str, device_kind: str, b_bucket: int, a_bucket: int,
         kc: int, dtype: str, precision: str = "f32") -> str:
    return (f"{kernel}|{device_kind}|b{b_bucket}|a{a_bucket}|kc{kc}"
            f"|{dtype}|{precision}")


def validate_variant(v: Any) -> bool:
    """Structural sanity of one variant dict (no jax, no shape context):
    tile_q a positive multiple of 8, ne a legal quarter count, unroll a
    small positive int, optional tile_n a positive multiple of 128*ne."""
    if not isinstance(v, dict):
        return False
    tq, ne, unroll = v.get("tile_q"), v.get("ne"), v.get("unroll", 1)
    if not (isinstance(tq, int) and tq > 0 and tq % 8 == 0):
        return False
    if ne not in _NE_CHOICES:
        return False
    if not (isinstance(unroll, int) and 1 <= unroll <= 8):
        return False
    tn = v.get("tile_n")
    if tn is not None and not (isinstance(tn, int) and tn > 0
                               and tn % (128 * ne) == 0):
        return False
    return True


def variant_fits(v: Dict[str, Any], b: int, kc: int) -> bool:
    """Alignment gate for a concrete dispatch: the variant's ne must tile
    ``b`` into whole 128-lane sub-blocks and kc must fit one block (the
    fresh-seed slice reads the first kc columns). The VMEM bound is
    enforced downstream by supports()/extract_topk with this same
    variant — this gate only rejects what could not even tile."""
    if b % (128 * v["ne"]) != 0:
        return False
    tn = v.get("tile_n")
    if tn is not None and kc > tn:
        return False
    return True


class VariantCache:
    """In-memory form of the cache file; save()/load() round-trip it."""

    def __init__(self, entries: Optional[Dict[str, Dict]] = None,
                 created_unix: Optional[float] = None):
        self.entries: Dict[str, Dict] = dict(entries or {})
        self.created_unix = (time.time() if created_unix is None
                             else created_unix)

    # -- mutation ------------------------------------------------------------
    def put(self, device_kind: str, b: int, kc: int, variant: Dict, *,
            a: int, dtype: str = "float32",
            kernel: str = "extract_topk", precision: str = "f32",
            measured_ms: Optional[float] = None,
            swept: Optional[int] = None,
            shape: Optional[Tuple[int, int, int]] = None) -> str:
        """Record the winning ``variant`` for (kernel, device, bucket(b),
        bucket(a), kc, dtype, precision); returns the entry key. ``a``
        (the swept attribute width) is part of the key: the VMEM
        footprint — and hence which variants even fit — scales with it.
        ``precision`` is the first-pass dot precision the measurement
        ran at (MXU passes per tile differ, so winners do too). Raises
        ValueError on a variant that fails structural validation — a
        sweep must never persist a variant the hot path would have to
        reject — or on an unknown kernel namespace or precision."""
        if not validate_variant(variant):
            raise ValueError(f"invalid variant {variant!r}")
        if kernel not in _KERNELS:
            raise ValueError(f"unknown kernel namespace {kernel!r}")
        if precision not in _PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        key = _key(kernel, device_kind, shape_bucket(b), shape_bucket(a),
                   kc, dtype, precision)
        entry: Dict[str, Any] = {"variant": dict(variant),
                                 "created_unix": time.time()}
        if measured_ms is not None:
            entry["measured_ms"] = round(float(measured_ms), 4)
        if swept is not None:
            entry["swept"] = int(swept)
        if shape is not None:
            entry["shape"] = list(shape)
        self.entries[key] = entry
        return key

    # -- read ----------------------------------------------------------------
    def get(self, device_kind: str, b: int, kc: int, *, a: int,
            dtype: str = "float32", kernel: str = "extract_topk",
            precision: str = "f32") -> Optional[Dict]:
        """The cached variant for (kernel, device, bucket(b), bucket(a),
        kc, dtype, precision), after per-entry validation and the
        per-dispatch alignment gate — None on miss, corrupt entry, or
        misfit."""
        e = self.entries.get(
            _key(kernel, device_kind, shape_bucket(b), shape_bucket(a),
                 kc, dtype, precision))
        if not isinstance(e, dict):
            return None
        v = e.get("variant")
        if not validate_variant(v) or not variant_fits(v, b, kc):
            return None
        return dict(v)

    # -- persistence ---------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {"schema": CACHE_SCHEMA, "kernel": _KERNEL_FAMILY,
                "created_unix": self.created_unix, "entries": self.entries}

    def save(self, path: Optional[str] = None) -> str:
        path = path or cache_path()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return path

    @staticmethod
    def validate_doc(doc: Any) -> None:
        """Raise ValueError naming the first schema violation (the
        tune-smoke CI gate calls this on the file it just wrote).
        Accepts schema 3 (kernel-namespaced, precision-suffixed keys)
        and grandfathered schema-1 (extract-only) / schema-2
        (no precision axis) files."""
        if not isinstance(doc, dict):
            raise ValueError("cache is not a JSON object")
        schema = doc.get("schema")
        if schema not in (1, 2, CACHE_SCHEMA):
            raise ValueError(f"cache schema {schema!r} not in "
                             f"(1, 2, {CACHE_SCHEMA}) "
                             "(regenerate with python -m dmlp_tpu.tune)")
        want_kernel = _KERNEL_V1 if schema == 1 else _KERNEL_FAMILY
        if doc.get("kernel") != want_kernel:
            raise ValueError(f"cache kernel {doc.get('kernel')!r} != "
                             f"{want_kernel!r}")
        entries = doc.get("entries")
        if not isinstance(entries, dict):
            raise ValueError("cache entries block missing or not a dict")
        for key, e in entries.items():
            if schema >= 2 and key.split("|", 1)[0] not in _KERNELS:
                raise ValueError(f"entry {key!r} has no kernel namespace")
            if schema == CACHE_SCHEMA \
                    and key.rsplit("|", 1)[-1] not in _PRECISIONS:
                raise ValueError(f"entry {key!r} has no precision "
                                 "suffix")
            if not isinstance(e, dict) or not validate_variant(
                    e.get("variant")):
                raise ValueError(f"entry {key!r} carries an invalid "
                                 f"variant: {e!r}")

    @classmethod
    def load(cls, path: Optional[str] = None) -> "VariantCache":
        """Load with ENVELOPE validation only (schema/kernel/entries
        shape) — raises on an unreadable or wrong-schema file, but a
        single corrupt ENTRY does not poison the rest: per-entry
        validation happens at ``get()``, so the file's other winners
        stay live. Old files load LENIENTLY so a tuned machine keeps
        its winners across a schema bump (the next sweep re-saves at
        the current schema): schema-1 keys (extract-only, pre-fused)
        upgrade to the extract_topk namespace, and schema-1/2 keys
        (pre-precision-axis) take the "f32" suffix — every measurement
        they carry was an f32-pass measurement, so the upgrade changes
        the key, never the meaning. The strict whole-file check (every
        entry valid) is :meth:`validate_doc` — the ``--validate`` CI
        gate."""
        path = path or cache_path()
        with open(path) as f:
            doc = json.load(f)
        if isinstance(doc, dict) and doc.get("schema") == 1 \
                and doc.get("kernel") == _KERNEL_V1 \
                and isinstance(doc.get("entries"), dict):
            entries = {f"{_KERNEL_V1}|{k}|f32": e
                       for k, e in doc["entries"].items()}
            return cls(entries=entries,
                       created_unix=doc.get("created_unix"))
        if isinstance(doc, dict) and doc.get("schema") == 2 \
                and doc.get("kernel") == _KERNEL_FAMILY \
                and isinstance(doc.get("entries"), dict):
            entries = {f"{k}|f32": e for k, e in doc["entries"].items()}
            return cls(entries=entries,
                       created_unix=doc.get("created_unix"))
        if not isinstance(doc, dict) or doc.get("schema") != CACHE_SCHEMA \
                or doc.get("kernel") != _KERNEL_FAMILY \
                or not isinstance(doc.get("entries"), dict):
            raise ValueError(
                f"{path}: not a schema-{CACHE_SCHEMA} {_KERNEL_FAMILY} "
                "variant cache (regenerate with python -m dmlp_tpu.tune)")
        return cls(entries=doc["entries"],
                   created_unix=doc.get("created_unix"))


# -- hot-path lookup (memoized, never raises) --------------------------------
_memo: Dict[str, Optional[VariantCache]] = {}
_device_kind_memo: Dict[str, str] = {}
#: cache files already said to hold "f32" winners only for a shape the
#: exact engines look up under "bf16x3" (lookup_variant says it once)
_orphans_noted: set = set()

#: >0 = lookups disabled (the degradation ladder's "heuristic" rung:
#: after a device OOM the first thing to give back is a swept variant's
#: larger tiles — resilience.degrade enters this context for the
#: retried solve, and resolution falls to the bit-identical heuristic).
_suppress_depth = 0


@contextlib.contextmanager
def suppressed():
    """Context manager disabling cache lookups for its duration."""
    global _suppress_depth
    _suppress_depth += 1
    try:
        yield
    finally:
        _suppress_depth -= 1


def clear_lookup_memo() -> None:
    """Drop the per-process cache/device memo (tests, or after a sweep
    rewrites the file mid-process)."""
    _memo.clear()
    _device_kind_memo.clear()
    _orphans_noted.clear()


def _current_device_kind() -> str:
    """The backend's device kind ("TPU v5 lite", "cpu", ...), memoized.
    Only called once a cache file with entries exists — a missing cache
    must never be the thing that initializes a backend."""
    kind = _device_kind_memo.get("kind")
    if kind is None:
        import jax
        d = jax.devices()[0]
        kind = d.device_kind if d.platform == "tpu" else d.platform
        _device_kind_memo["kind"] = kind
    return kind


def lookup_variant(kc: int, b: int, a: Optional[int] = None,
                   dtype: str = "float32",
                   device_kind: Optional[str] = None,
                   path: Optional[str] = None,
                   kernel: str = "extract_topk",
                   precision: str = "f32") -> Optional[Dict]:
    """The hot-path read: cached variant for this dispatch, or None.

    ``kernel`` selects the namespace ("extract_topk" | "fused_topk" —
    the fused megakernel sweeps and caches separately); ``precision``
    the first-pass-dot key axis (f32 and bf16 winners cached apart —
    the MXU pass count per tile differs). Never raises;
    returns None when ``a`` is unknown (the attribute width is part of
    the key — every real dispatch site knows it), the cache file is
    absent, unreadable, schema-invalid, keyed for a different device
    kind, the matched entry is corrupt, or its variant cannot tile this
    ``b`` (alignment rejection) — the caller then uses the
    deterministic heuristic."""
    if _suppress_depth or a is None:
        return None
    path = path or cache_path()
    if path not in _memo:
        if not os.path.exists(path):
            _memo[path] = None
        else:
            try:
                _memo[path] = VariantCache.load(path)
            except Exception:
                _memo[path] = None
    cache = _memo[path]
    if cache is None or not cache.entries:
        return None
    if device_kind is None:
        device_kind = _current_device_kind()
    hit = cache.get(device_kind, b, kc, a=a, dtype=dtype, kernel=kernel,
                    precision=precision)
    if hit is None and precision == "bf16x3" and path not in _orphans_noted \
            and cache.get(device_kind, b, kc, a=a, dtype=dtype,
                          kernel=kernel, precision="f32") is not None:
        # A file swept before the split form existed (or with
        # --precision f32) measured the one HIGHEST dot: six passes a
        # tile, another balance. The exact engines do not run its
        # winner; they say so once a file, instead of silently taking
        # the heuristic on the "tuned" rung.
        _orphans_noted.add(path)
        import warnings
        warnings.warn(
            f"tune cache {path}: {kernel} at kc={kc} b={b} a={a} is "
            'measured under "f32" (one HIGHEST dot) only; the exact '
            'engines\' float32 pass is "bf16x3" and takes the heuristic '
            "tiles until `python -m dmlp_tpu.tune` (default "
            "--precision bf16x3) has swept it", RuntimeWarning,
            stacklevel=2)
    return hit
