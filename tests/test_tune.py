"""The measured autotuner (dmlp_tpu.tune): cache round-trip, shape-bucket
keying, heuristic fallback (absent cache / foreign device kind), and
alignment rejection — plus the hot-path integration: pallas_extract
resolves variants through the cache, and an uncached process is
bit-identical to the pre-tuner heuristics.

Every test isolates the cache via $DMLP_TPU_TUNE_CACHE (monkeypatch) and
clears the per-process lookup memo on both sides — the suite must never
read or write a developer's real ~/.cache file.
"""

import json
import os

import numpy as np
import pytest

from dmlp_tpu.tune import (VariantCache, cache_path, clear_lookup_memo,
                           lookup_variant, shape_bucket)
from dmlp_tpu.tune.cache import validate_variant, variant_fits


@pytest.fixture
def tune_cache_path(tmp_path, monkeypatch):
    path = str(tmp_path / "variants.json")
    monkeypatch.setenv("DMLP_TPU_TUNE_CACHE", path)
    clear_lookup_memo()
    yield path
    clear_lookup_memo()


# ---------------------------------------------------------------------------
# cache round-trip + keying
# ---------------------------------------------------------------------------

def test_cache_roundtrip_write_reload_hit(tune_cache_path):
    cache = VariantCache()
    v = {"tile_q": 64, "tile_n": 6144, "ne": 4, "unroll": 1}
    cache.put("TPU v5 lite", 51200, 40, v, a=64, measured_ms=12.3,
              swept=17, shape=(10240, 51200, 64))
    saved = cache.save(tune_cache_path)
    assert saved == tune_cache_path

    reloaded = VariantCache.load(tune_cache_path)
    assert reloaded.get("TPU v5 lite", 51200, 40, a=64) == v
    # and through the memoized hot-path read, with explicit device kind
    assert lookup_variant(40, 51200, a=64,
                          device_kind="TPU v5 lite") == v


def test_cache_file_is_schema_validated(tune_cache_path):
    VariantCache().save(tune_cache_path)
    doc = json.load(open(tune_cache_path))
    assert doc["schema"] == 3
    assert doc["kernel"] == "pallas_topk"
    VariantCache.validate_doc(doc)  # round-trips its own schema

    doc["schema"] = 99
    with pytest.raises(ValueError):
        VariantCache.validate_doc(doc)
    with pytest.raises(ValueError):
        VariantCache.validate_doc({"schema": 1, "kernel": "extract_topk",
                                   "entries": {"k": {"variant":
                                                     {"tile_q": 7}}}})
    # schema-2 entry keys must carry a known kernel namespace
    with pytest.raises(ValueError):
        VariantCache.validate_doc(
            {"schema": 2, "kernel": "pallas_topk",
             "entries": {"cpu|b16384|a8|kc16|float32":
                         {"variant": {"tile_q": 64, "ne": 2,
                                      "unroll": 1}}}})


def test_schema1_cache_loads_leniently_into_extract_namespace(
        tune_cache_path):
    """A pre-fused (schema-1, extract-only) cache file still loads: its
    keys upgrade to the extract_topk namespace in memory, so a tuned
    machine keeps its winners across the schema bump — and the fused
    namespace stays empty (never inherits extract winners)."""
    v = {"tile_q": 64, "ne": 4, "unroll": 1}
    with open(tune_cache_path, "w") as f:
        json.dump({"schema": 1, "kernel": "extract_topk",
                   "entries": {"cpu|b16384|a8|kc16|float32":
                               {"variant": v}}}, f)
    VariantCache.validate_doc(json.load(open(tune_cache_path)))
    clear_lookup_memo()
    assert lookup_variant(16, 12800, a=8, device_kind="cpu") == v
    assert lookup_variant(16, 12800, a=8, device_kind="cpu",
                          kernel="fused_topk") is None


def test_an_f32_only_cache_says_once_that_exact_engines_pass_it_by(
        tune_cache_path):
    """A file swept before the split form existed holds "f32" winners
    (older schemas upgrade to that suffix): measurements of the one
    HIGHEST dot, which an exact engine's "bf16x3" lookup does not take.
    It misses, and says so once a file; a shape with no entry under
    either key, and a file that has the "bf16x3" entry, say nothing."""
    import warnings
    v = {"tile_q": 64, "ne": 4, "unroll": 1}
    with open(tune_cache_path, "w") as f:
        json.dump({"schema": 1, "kernel": "extract_topk",
                   "entries": {"cpu|b16384|a8|kc16|float32":
                               {"variant": v}}}, f)
    clear_lookup_memo()
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # no entry at all: silent
        assert lookup_variant(32, 12800, a=8, device_kind="cpu",
                              precision="bf16x3") is None
    with pytest.warns(RuntimeWarning, match='under "f32"') as said:
        assert lookup_variant(16, 12800, a=8, device_kind="cpu",
                              precision="bf16x3") is None
    assert tune_cache_path in str(said[0].message)
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # ... once
        assert lookup_variant(16, 12800, a=8, device_kind="cpu",
                              precision="bf16x3") is None
        assert lookup_variant(16, 12800, a=8, device_kind="cpu") == v
        cache = VariantCache.load(tune_cache_path)
        cache.put("cpu", 12800, 16, v, a=8, precision="bf16x3")
        cache.save(tune_cache_path)
        clear_lookup_memo()
        assert lookup_variant(16, 12800, a=8, device_kind="cpu",
                              precision="bf16x3") == v


def test_precision_both_sweeps_every_form(tune_cache_path, monkeypatch,
                                          capsys):
    """``--precision both`` kept its name from when there were two: it
    sweeps the form the exact engines look up as well."""
    from dmlp_tpu.tune import __main__ as tune_main
    from dmlp_tpu.tune import sweep as tune_sweep
    swept = []

    def sweep(n, nq, a, kcs, *, kernel, precision, **kw):
        swept.append((kernel, precision))
        return [], []

    monkeypatch.setattr(tune_sweep, "sweep_extract", sweep)
    assert tune_main.main(["--smoke", "--kernel", "extract",
                           "--precision", "both",
                           "--out", tune_cache_path]) == 1   # no winner
    assert swept == [("extract", "f32"), ("extract", "bf16x3"),
                     ("extract", "bf16")]


def test_fused_namespace_is_keyed_separately(tune_cache_path):
    """Winners cached under kernel="fused_topk" resolve only through the
    fused lookup; the extract namespace at the same (device, b, a, kc)
    key is independent."""
    vf = {"tile_q": 32, "tile_n": 256, "ne": 2, "unroll": 1}
    ve = {"tile_q": 64, "ne": 4, "unroll": 1}
    cache = VariantCache()
    cache.put("cpu", 12800, 16, vf, a=8, kernel="fused_topk")
    cache.put("cpu", 12800, 16, ve, a=8)
    cache.save(tune_cache_path)
    clear_lookup_memo()
    assert lookup_variant(16, 12800, a=8, device_kind="cpu",
                          kernel="fused_topk") == vf
    assert lookup_variant(16, 12800, a=8, device_kind="cpu") == ve
    with pytest.raises(ValueError):
        cache.put("cpu", 12800, 16, ve, a=8, kernel="mystery_kernel")


def test_put_rejects_invalid_variants():
    cache = VariantCache()
    for bad in ({"tile_q": 7, "ne": 2, "unroll": 1},      # tq not mult 8
                {"tile_q": 64, "ne": 3, "unroll": 1},     # illegal ne
                {"tile_q": 64, "ne": 2, "unroll": 0},     # unroll < 1
                {"tile_q": 64, "ne": 4, "unroll": 1,
                 "tile_n": 640}):                         # tn % 512 != 0
        assert not validate_variant(bad)
        with pytest.raises(ValueError):
            cache.put("cpu", 1024, 16, bad, a=8)


def test_shape_bucket_keying(tune_cache_path):
    assert shape_bucket(12800) == shape_bucket(16000) == 16384
    assert shape_bucket(51200) == 65536
    cache = VariantCache()
    v = {"tile_q": 128, "ne": 2, "unroll": 1}
    cache.put("cpu", 12800, 16, v, a=8)
    cache.save(tune_cache_path)
    # same b and a buckets: hit for a DIFFERENT (256-aligned) row count
    assert lookup_variant(16, 16128, a=8, device_kind="cpu") == v
    # different b bucket: miss
    assert lookup_variant(16, 51200, a=8, device_kind="cpu") is None
    # different kc: miss
    assert lookup_variant(24, 12800, a=8, device_kind="cpu") is None
    # different a bucket (VMEM regime): miss
    assert lookup_variant(16, 12800, a=64, device_kind="cpu") is None
    # unknown a never matches (every real dispatch site passes it)
    assert lookup_variant(16, 12800, device_kind="cpu") is None


# ---------------------------------------------------------------------------
# fallback-to-heuristic
# ---------------------------------------------------------------------------

def test_lookup_absent_cache_is_none_and_resolution_matches_heuristic(
        tmp_path, monkeypatch):
    monkeypatch.setenv("DMLP_TPU_TUNE_CACHE",
                       str(tmp_path / "does-not-exist.json"))
    clear_lookup_memo()
    try:
        assert lookup_variant(40, 51200, a=64) is None
        from dmlp_tpu.ops.pallas_extract import (_resolve_variant,
                                                 tuned_variant)
        # bit-identical to the pre-tuner heuristics, both regimes
        assert _resolve_variant(40, 51200) == tuned_variant(40)
        assert _resolve_variant(136, 51200) == tuned_variant(136)
        # and the heuristic's own ne-alignment fallback still applies
        assert _resolve_variant(136, 128 * 2 * 7)["ne"] == 2
    finally:
        clear_lookup_memo()


def test_lookup_device_kind_mismatch_falls_back(tune_cache_path):
    cache = VariantCache()
    cache.put("TPU v5 lite", 12800, 16,
              {"tile_q": 64, "ne": 4, "unroll": 2}, a=8)
    cache.save(tune_cache_path)
    clear_lookup_memo()
    # the current backend is CPU (tier-1 env) — the TPU entry must not hit
    assert lookup_variant(16, 12800, a=8) is None
    from dmlp_tpu.ops.pallas_extract import _resolve_variant, tuned_variant
    assert _resolve_variant(16, 12800) == tuned_variant(16)


def test_lookup_unreadable_cache_is_none(tune_cache_path):
    with open(tune_cache_path, "w") as f:
        f.write("{not json")
    clear_lookup_memo()
    assert lookup_variant(16, 12800, a=8, device_kind="cpu") is None


# ---------------------------------------------------------------------------
# alignment rejection
# ---------------------------------------------------------------------------

def test_alignment_rejection_ne_cannot_tile_b(tune_cache_path):
    v4 = {"tile_q": 64, "ne": 4, "unroll": 1}
    cache = VariantCache()
    cache.put("cpu", 12800, 16, v4, a=8)
    cache.save(tune_cache_path)
    clear_lookup_memo()
    # 12800 % 512 == 0: fits
    assert lookup_variant(16, 12800, a=8, device_kind="cpu") == v4
    # 12544 = 128*98 (same bucket, % 512 != 0): the ne=4 entry cannot
    # tile it — lookup rejects, resolution falls back to the heuristic
    assert not variant_fits(v4, 12544, 16)
    assert lookup_variant(16, 12544, a=8, device_kind="cpu") is None
    from dmlp_tpu.ops.pallas_extract import _resolve_variant
    assert _resolve_variant(16, 12544)["ne"] == 2

    # kc wider than the entry's tile_n is a misfit too
    cache.put("cpu", 12800, 320,
              {"tile_q": 64, "tile_n": 256, "ne": 2, "unroll": 1}, a=8)
    cache.save(tune_cache_path)
    clear_lookup_memo()
    assert lookup_variant(320, 12800, a=8, device_kind="cpu") is None


# ---------------------------------------------------------------------------
# the sweep machinery + end-to-end resolution through a written cache
# ---------------------------------------------------------------------------

def test_variant_space_only_yields_supported_variants():
    from dmlp_tpu.ops.pallas_extract import variant_supports

    space = __import__("dmlp_tpu.tune.sweep",
                       fromlist=["variant_space"]).variant_space(
        128, 12800, 8, 16)
    assert space, "space must not be empty at a tileable shape"
    seen = set()
    for v in space:
        key = (v["tile_q"], v["tile_n"], v["ne"], v["unroll"])
        assert key not in seen       # no duplicates
        seen.add(key)
        assert validate_variant(v)
        assert variant_supports(128, 12800, 8, 16, v)
    # ne=8 cannot tile 12800 (12800 % 1024 != 0) — must be absent
    assert all(v["ne"] != 8 for v in space)


def test_time_variant_measures_interpret_kernel():
    import jax.numpy as jnp
    from dmlp_tpu.tune.sweep import time_variant_ms

    rng = np.random.default_rng(3)
    d = jnp.asarray(rng.uniform(0, 10, (1024, 4)), jnp.float32)
    q = jnp.asarray(rng.uniform(0, 10, (16, 4)), jnp.float32)
    ms = time_variant_ms(q, d, 1000, 8,
                         {"tile_q": 16, "tile_n": 256, "ne": 2,
                          "unroll": 1}, reps=1, interpret=True)
    assert ms > 0


def test_written_cache_drives_engine_resolution_and_parity(
        tune_cache_path):
    """End to end: a cache pinning a non-default variant (small tile_n →
    multiple in-kernel blocks) changes HOW the engine's kernel tiles but
    not WHAT it returns — golden parity with the tuned variant active,
    and the resolution visibly differs from the heuristic."""
    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.engine.single import SingleChipEngine, resolve_kcap
    from dmlp_tpu.golden.reference import knn_golden
    from dmlp_tpu.io.grammar import KNNInput, Params
    from dmlp_tpu.ops.pallas_extract import resolve_variant, tuned_variant
    from tests.test_engine_single import assert_same_results

    rng = np.random.default_rng(11)
    n, nq, na = 700, 9, 4
    inp = KNNInput(Params(n, nq, na),
                   rng.integers(0, 4, n).astype(np.int32),
                   rng.uniform(-20, 20, (n, na)),
                   rng.integers(1, 24, nq).astype(np.int32),
                   rng.uniform(-20, 20, (nq, na)))
    kc = resolve_kcap(EngineConfig(), int(inp.ks.max()), "extract",
                      1 << 30, staging="float32")
    pinned = {"tile_q": 32, "tile_n": 256, "ne": 2, "unroll": 1}
    cache = VariantCache()
    # engine dispatch: chunk_rows 12800, qpad 128 (QUERY_TILE), a = na.
    # The engine prefers the fused megakernel, which resolves through
    # the fused_topk namespace — pin BOTH so whichever kernel dispatches
    # sees the tuned tiles (and the span proves which one resolved).
    # The precision axis: the exact engine's float32 pass is the split
    # "bf16x3" form, which looks under its own key (an "f32" entry is a
    # measurement of fast mode's one HIGHEST dot).
    cache.put("cpu", 12800, kc, pinned, a=na, precision="bf16x3")
    cache.put("cpu", 12800, kc, pinned, a=na, kernel="fused_topk",
              precision="bf16x3")
    cache.save(tune_cache_path)
    clear_lookup_memo()

    assert resolve_variant(kc, 12800, 128, na, "bf16x3") == pinned
    assert resolve_variant(kc, 12800, 128, na, "bf16x3") \
        != tuned_variant(kc)
    assert resolve_variant(kc, 12800, 128, na, "f32") == tuned_variant(kc)
    from dmlp_tpu.obs import trace as obs_trace
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        eng = SingleChipEngine(EngineConfig(select="extract",
                                            use_pallas=True))
        got = eng.run(inp)
    finally:
        obs_trace.uninstall()
    assert eng._last_select == "extract"
    # the span records the variant the dispatch RESOLVED (and, with the
    # resolution hoisted out of the jit, the one it actually compiled)
    spans = [e for e in tracer.to_dict()["traceEvents"]
             if e.get("name") == "single.enqueue_extract"]
    assert spans and spans[0]["args"]["variant"] == pinned
    assert spans[0]["args"]["impl"] == eng.last_extract_impl
    assert_same_results(got, knn_golden(inp), check_dists=False)


def test_tune_cli_validate(tune_cache_path, capsys):
    from dmlp_tpu.tune.__main__ import main

    VariantCache().save(tune_cache_path)
    assert main(["--validate", tune_cache_path]) == 0
    with open(tune_cache_path, "w") as f:
        json.dump({"schema": 0}, f)
    assert main(["--validate", tune_cache_path]) == 1


def test_default_cache_path_honors_env(monkeypatch):
    monkeypatch.setenv("DMLP_TPU_TUNE_CACHE", "/tmp/x.json")
    assert cache_path() == "/tmp/x.json"
    monkeypatch.delenv("DMLP_TPU_TUNE_CACHE")
    assert cache_path().endswith(
        os.path.join(".cache", "dmlp_tpu", "extract_variants.json"))
