"""Extraction-kernel tests (ops.pallas_extract) — interpret mode on CPU.

Kernel-level checks use integer-valued attrs so f32 distance arithmetic is
exact and any mismatch is algorithmic, not numeric (the norm-expansion
formula differs from a NumPy oracle by ULPs otherwise). Engine-level
checks run the full differential pipeline vs the float64 golden model with
select="extract", the flagship TPU path.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dmlp_tpu.config import EngineConfig  # noqa: E402
from dmlp_tpu.engine.single import SingleChipEngine  # noqa: E402
from dmlp_tpu.golden.reference import knn_golden  # noqa: E402
from dmlp_tpu.io.datagen import generate_input_text  # noqa: E402
from dmlp_tpu.io.grammar import KNNInput, Params, parse_input_text  # noqa: E402
from dmlp_tpu.ops.pallas_extract import extract_topk, supports  # noqa: E402
from tests.test_engine_single import assert_same_results  # noqa: E402


def _int_attrs(rng, shape, hi=50):
    return jnp.asarray(rng.integers(0, hi, shape), jnp.float32)


def _oracle_topk_dists(q, chunks_real, kc):
    """Sorted k smallest exact squared distances per query (float64)."""
    alld = np.concatenate(chunks_real).astype(np.float64)
    tile = ((np.asarray(q, np.float64)[:, None, :] - alld[None]) ** 2).sum(-1)
    full = np.sort(tile, axis=1)
    out = np.full((tile.shape[0], kc), np.inf)
    w = min(kc, full.shape[1])
    out[:, :w] = full[:, :w]
    return out


#: every first-pass form: small integers are bf16 values, so the split
#: form's low planes are zero and one bf16 pass is exact too
FORMS = pytest.mark.parametrize("precision", ["f32", "bf16x3", "bf16"])


def _check(q, chunks, nreals, kc, precision="f32"):
    od = oi = None
    base = 0
    for d, nr in zip(chunks, nreals):
        od, oi, _ = extract_topk(q, d, od, oi, n_real=nr, id_base=base,
                                 kc=kc, interpret=True, precision=precision)
        base += nr
    od, oi = np.asarray(od), np.asarray(oi)
    ref = _oracle_topk_dists(q, [np.asarray(d)[:nr]
                                 for d, nr in zip(chunks, nreals)], kc)
    got = np.sort(od, axis=-1)
    assert np.array_equal(got, ref), "distances mismatch"
    # ids must reproduce their distances (and be -1 exactly on padding)
    alld = np.concatenate([np.asarray(d)[:nr]
                           for d, nr in zip(chunks, nreals)]).astype(np.float64)
    valid = oi >= 0
    assert np.array_equal(valid, np.isfinite(od))
    rec = ((np.asarray(q, np.float64)[:, None, :]
            - alld[np.clip(oi, 0, len(alld) - 1)]) ** 2).sum(-1)
    assert np.array_equal(np.where(valid, rec, np.inf),
                          np.where(valid, od.astype(np.float64), np.inf))


@FORMS
def test_fresh_single_chunk(precision):
    rng = np.random.default_rng(7)
    q = _int_attrs(rng, (64, 8))
    d = _int_attrs(rng, (1024, 8))
    assert supports(64, 1024, 8, 16)
    _check(q, [d], [900], 16, precision)


@FORMS
def test_carry_across_chunks(precision):
    rng = np.random.default_rng(3)
    q = _int_attrs(rng, (16, 4))
    _check(q, [_int_attrs(rng, (1024, 4)), _int_attrs(rng, (1536, 4))],
           [1000, 1536], 24, precision)


@FORMS
def test_duplicate_heavy_ties(precision):
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.integers(0, 3, (16, 4)), jnp.float32)
    d = jnp.asarray(rng.integers(0, 3, (1024, 4)), jnp.float32)
    _check(q, [d], [1024], 24, precision)


@FORMS
def test_fewer_real_rows_than_kc(precision):
    rng = np.random.default_rng(9)
    q = _int_attrs(rng, (16, 4))
    _check(q, [_int_attrs(rng, (512, 4))], [10], 24, precision)
    _check(q, [_int_attrs(rng, (512, 4)), _int_attrs(rng, (512, 4))],
           [10, 12], 24, precision)


@pytest.mark.parametrize("na", [24, 128, 256])
@pytest.mark.parametrize("gate", [False, True], ids=["two_pass", "fused"])
def test_stacked_passes_agree_with_the_one_dot_on_exact_operands(gate, na):
    """The "bf16x3" form's three passes are ONE dot over the halves
    stacked along the contraction, whether the row is whole lanes (128,
    256) or not (24). On rows whose halves and partial sums are exact in
    float32 (integers below 2^9: hi holds 8 bits, lo the ninth) it drops
    nothing, and gives the one-dot "f32" form's lists to the bit,
    carried and floored alike."""
    rng = np.random.default_rng(17)
    q = np.zeros((16, na), np.float32)
    d = np.zeros((1280, na), np.float32)
    q[:, :24] = rng.integers(0, 256, (16, 24))
    d[:, :24] = rng.integers(0, 512, (1280, 24))
    q, d = jnp.asarray(q), jnp.asarray(d)
    floor = jnp.asarray(rng.uniform(0, 4e5, (16, 1)), jnp.float32)
    lists = {}
    for prec in ("f32", "bf16x3"):
        od, oi, _ = extract_topk(q, d[:768], n_real=700, kc=24,
                                 interpret=True, mxu_gate=gate,
                                 precision=prec)
        od, oi, _ = extract_topk(q, d[768:], od, oi, n_real=512,
                                 id_base=700, kc=24, interpret=True,
                                 mxu_gate=gate, floor=floor,
                                 precision=prec)
        lists[prec] = (np.asarray(od), np.asarray(oi))
    assert np.isfinite(lists["f32"][0]).any()
    for a, b in zip(lists["f32"], lists["bf16x3"]):
        assert np.array_equal(a, b)


def test_unknown_form_is_refused():
    q = jnp.zeros((8, 4), jnp.float32)
    d = jnp.zeros((256, 4), jnp.float32)
    with pytest.raises(ValueError, match="first-pass precision"):
        extract_topk(q, d, n_real=256, kc=8, interpret=True,
                     precision="int8")


def test_supports_gates():
    assert not supports(7, 1024, 8, 16)      # queries not /8
    assert not supports(64, 1000, 8, 16)     # data not /512
    assert not supports(64, 1024, 8, 1024)   # kc wider than a block


def _engine(select="extract", **kw):
    return SingleChipEngine(EngineConfig(select=select, use_pallas=True, **kw))


def test_engine_extract_matches_golden():
    text = generate_input_text(1100, 40, 8, -10, 10, 1, 12, 5, seed=21)
    inp = parse_input_text(text)
    eng = _engine(data_block=512)
    got = eng.run(inp)
    assert eng._last_select == "extract"
    # the exact engine's float32 pass is the three-pass split form
    assert eng.last_precision["active"] == "bf16x3"
    assert_same_results(got, knn_golden(inp))


def test_engine_extract_multichunk_matches_golden():
    text = generate_input_text(20000, 25, 6, -5, 5, 1, 16, 4, seed=22)
    inp = parse_input_text(text)
    eng = _engine(data_block=8192)   # 2 chunks with carry folding
    got = eng.run(inp)
    assert eng._last_select == "extract"
    assert eng.last_precision["active"] == "bf16x3"
    assert_same_results(got, knn_golden(inp))


@pytest.mark.parametrize("scale", [1.0, 255.0])
def test_engine_extract_split_form_debug_output_is_the_oracles(scale):
    """Byte identity where the values are NOT bf16-exact: uniform reals
    at the cells' coordinate scales, checksums and the human-readable
    (debug) distances against the float64 oracle, the three-pass form
    against fast mode's one dot on the same input."""
    from dmlp_tpu.io.report import format_results
    rng = np.random.default_rng(int(scale) + 40)
    n, nq, na = 9000, 24, 16
    data = rng.uniform(0, scale, (n, na)).astype(np.float32)
    queries = rng.uniform(0, scale, (nq, na)).astype(np.float32)
    inp = KNNInput(Params(n, nq, na),
                   rng.integers(0, 5, n).astype(np.int32),
                   data.astype(np.float64),
                   rng.integers(1, 33, nq).astype(np.int32),
                   queries.astype(np.float64))
    gold = knn_golden(inp)
    eng = _engine(data_block=4096)
    got = eng.run(inp)
    assert eng._last_select == "extract"
    assert eng.last_precision["active"] == "bf16x3"
    assert_same_results(got, gold)
    assert format_results(got, debug=True) \
        == format_results(gold, debug=True)
    fast = _engine(exact=False, data_block=4096)
    got_fast = fast.run(inp)
    assert fast.last_precision["active"] == "f32"
    assert_same_results(got_fast, gold, check_dists=False)


def test_engine_extract_duplicate_ties_fast_mode():
    # Integer grid => exact f32; fast mode (no rescore) must still match
    # via the boundary-overflow repair.
    rng = np.random.default_rng(8)
    data = rng.integers(0, 4, size=(1024, 2)).astype(np.float64)
    queries = rng.integers(0, 4, size=(24, 2)).astype(np.float64)
    labels = rng.integers(0, 3, size=1024).astype(np.int32)
    ks = rng.integers(1, 20, size=24).astype(np.int32)
    inp = KNNInput(Params(1024, 24, 2), labels, data, ks, queries)
    eng = _engine(exact=False, data_block=512)
    got = eng.run(inp)
    assert eng._last_select == "extract"
    assert_same_results(got, knn_golden(inp), check_dists=False)


def test_engine_extract_unsupported_shape_falls_back(monkeypatch):
    # A shape the kernel can't tile (the VMEM bound in supports()): since
    # PR 31 the data block follows the row width, so na=2000 itself tiles
    # (by 256 rows here) and runs the kernel; under a bound no tile of it
    # fits, _solve_extract — and the multi-pass driver, which shares the
    # gate — must decline and the chunk-fold driver takes over; still
    # golden. (k beyond the 512 cap no longer falls back: that case now
    # runs the multi-pass extraction,
    # test_engine_single.TestMultipassExtract.)
    from dmlp_tpu.ops import pallas_extract
    text = generate_input_text(900, 6, 2000, 0, 1, 8, 16, 3, seed=5)
    inp = parse_input_text(text)
    eng = _engine()
    got = eng.run(inp)
    assert eng._last_select == "extract"
    assert_same_results(got, knn_golden(inp))
    monkeypatch.setattr(pallas_extract, "_VMEM_BOUND", 2**20)
    eng = _engine()
    got = eng.run(inp)
    assert eng._last_select != "extract"
    assert_same_results(got, knn_golden(inp))


def test_engine_extract_forced_on_small_shape():
    # Explicit --select extract on a supported small shape keeps parity.
    text = generate_input_text(300, 10, 3, 0, 1, 1, 37, 3, seed=5)
    inp = parse_input_text(text)
    eng = _engine()
    got = eng.run(inp)
    assert_same_results(got, knn_golden(inp))


def test_sharded_engine_extract_matches_golden():
    """The mesh engines run the extraction kernel per shard (SMEM runtime
    scalars make per-shard id_base/n_real traced): allgather and ring
    merges, 8-device (4,2) CPU mesh, golden parity."""
    from dmlp_tpu.engine.ring import RingEngine
    from dmlp_tpu.engine.sharded import ShardedEngine
    from dmlp_tpu.parallel.mesh import make_mesh

    # AUTO_SELECT_THRESHOLD is per-shard; force extract explicitly.
    text = generate_input_text(2000, 48, 6, -8, 8, 1, 14, 5, seed=33)
    inp = parse_input_text(text)
    want = knn_golden(inp)
    for cls in (ShardedEngine, RingEngine):
        eng = cls(EngineConfig(mode="sharded", select="extract",
                               use_pallas=True), mesh=make_mesh())
        got = eng.run(inp)
        assert eng._last_select == "extract", cls.__name__
        assert_same_results(got, want)


def test_sharded_engine_extract_duplicate_ties():
    from dmlp_tpu.engine.sharded import ShardedEngine
    from dmlp_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(23)
    data = rng.integers(0, 3, size=(512, 3)).astype(np.float64)
    queries = rng.integers(0, 3, size=(16, 3)).astype(np.float64)
    labels = rng.integers(0, 4, size=512).astype(np.int32)
    ks = rng.integers(1, 20, size=16).astype(np.int32)
    inp = KNNInput(Params(512, 16, 3), labels, data, ks, queries)
    eng = ShardedEngine(EngineConfig(mode="sharded", select="extract",
                                     use_pallas=True), mesh=make_mesh())
    got = eng.run(inp)
    assert eng._last_select == "extract"
    assert_same_results(got, knn_golden(inp), check_dists=False)


def test_plan_shard_prefers_extract_when_supported():
    """The pre-placed-array plan (multi-host path) picks the extraction
    kernel when the feed's fixed per-shard shapes can tile it, and falls
    back gracefully when they cannot (kcap past the 512 candidate cap)."""
    from dmlp_tpu.engine.sharded import ShardedEngine
    from dmlp_tpu.parallel.mesh import make_mesh

    eng = ShardedEngine(EngineConfig(mode="sharded", use_pallas=True),
                        mesh=make_mesh())
    r, c = eng.mesh.devices.shape
    d = np.zeros((12800 * r, 8), np.float32)
    q = np.zeros((128 * c, 8), np.float32)
    sel, _, k = eng._plan_shard(d, q, 16, merged_width=True)
    assert sel == "extract" and k >= 16
    sel2, _, _ = eng._plan_shard(d, q, 600, merged_width=True)  # kcap > 512
    assert sel2 != "extract"


def test_contract_run_extract_path_matches_golden(tmp_path):
    """Full multi-host contract pipeline (sharded feed -> per-shard
    extraction kernel -> distributed f64 rescore -> merge) on the
    (4,2) virtual mesh, single process, golden parity."""
    import os as _os

    from dmlp_tpu.engine.sharded import ShardedEngine
    from dmlp_tpu.parallel.distributed import distributed_contract_run
    from dmlp_tpu.parallel.mesh import make_mesh

    text = generate_input_text(1024, 24, 5, -6, 6, 1, 12, 4, seed=41)
    path = tmp_path / "ex.txt"
    path.write_text(text)
    inp = parse_input_text(text)
    want = [r.checksum() for r in knn_golden(inp)]

    eng = ShardedEngine(EngineConfig(mode="sharded", select="extract",
                                     use_pallas=True), mesh=make_mesh())
    with open(_os.devnull, "w") as devnull:
        got = distributed_contract_run(str(path), eng,
                                       out=devnull, err=devnull)
    assert eng._last_select == "extract"
    assert [r.checksum() for r in got] == want


def _distinct_distance_input(n=600, nq=24, seed=31):
    """All (query, data) distances pairwise-distinct AND exact in f32, so
    device-full (no host repair) must match the golden model bit-for-bit
    regardless of tie policy: 1-D distinct integer attrs, queries offset by
    .25 (v1 + v2 = 2q is never solvable; every term is a small multiple of
    1/16, exactly representable)."""
    rng = np.random.default_rng(seed)
    vals = rng.permutation(n).astype(np.float64) + 1.0
    data = vals[:, None]
    queries = (rng.permutation(nq).astype(np.float64) + 0.25)[:, None]
    labels = rng.integers(0, 5, n).astype(np.int32)
    ks = rng.integers(1, 17, nq).astype(np.int32)
    return KNNInput(Params(n, nq, 1), labels, data, ks, queries)


def test_engine_extract_device_full_matches_golden():
    """round-3 review item 3: --device-full must run the flagship extraction
    kernel (it previously remapped to seg/topk)."""
    inp = _distinct_distance_input()
    eng = _engine()
    got = eng.run_device_full(inp)
    assert eng._last_select == "extract"
    want = knn_golden(inp)
    for g, w in zip(got, want):
        assert g.predicted_label == w.predicted_label
        assert list(g.neighbor_ids) == list(w.neighbor_ids)
        assert g.checksum() == w.checksum()


def test_sharded_device_full_extract_matches_golden():
    """Mesh device-full path honors select="extract" per shard (the merge
    re-sorts the kernel's unsorted lists before vote/report)."""
    import jax

    from dmlp_tpu.engine.sharded import RingEngine, ShardedEngine

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    inp = _distinct_distance_input(seed=32)
    want = knn_golden(inp)
    for cls, mode in ((ShardedEngine, "sharded"), (RingEngine, "ring")):
        eng = cls(EngineConfig(mode=mode, select="extract", use_pallas=True))
        got = eng.run_device_full(inp)
        assert eng._last_select == "extract", mode
        for g, w in zip(got, want):
            assert g.predicted_label == w.predicted_label, mode
            assert list(g.neighbor_ids) == list(w.neighbor_ids), mode
            assert g.checksum() == w.checksum(), mode
