"""Single-chip engine vs the float64 golden model (differential tests)."""

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.datagen import generate_input_text
from dmlp_tpu.io.grammar import KNNInput, Params, parse_input_text
from dmlp_tpu.io.report import format_results


def assert_same_results(got, want, check_dists=True):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.query_id == w.query_id
        assert g.k == w.k
        assert g.predicted_label == w.predicted_label, f"query {g.query_id}"
        assert list(g.neighbor_ids) == list(w.neighbor_ids), f"query {g.query_id}"
        assert g.checksum() == w.checksum()
        if check_dists:
            np.testing.assert_allclose(g.neighbor_dists, w.neighbor_dists,
                                       rtol=1e-12)


@pytest.mark.parametrize("seed", [11, 12])
def test_exact_mode_matches_golden(seed):
    text = generate_input_text(300, 40, 8, -10, 10, 1, 12, 5, seed=seed)
    inp = parse_input_text(text)
    eng = SingleChipEngine(EngineConfig(data_block=64, query_block=16))
    assert_same_results(eng.run(inp), knn_golden(inp))


def test_exact_mode_small_blocks_edge():
    # num_data not a multiple of data_block; num_queries not a multiple of
    # query_block — exercises padding/masking everywhere.
    text = generate_input_text(37, 9, 3, 0, 1, 1, 37, 3, seed=5)
    inp = parse_input_text(text)
    eng = SingleChipEngine(EngineConfig(data_block=16, query_block=4))
    assert_same_results(eng.run(inp), knn_golden(inp))


def test_duplicate_distance_ties():
    # Integer grid attrs => many exact distance ties; f32 and f64 agree
    # exactly, so tie-breaking is what's under test.
    rng = np.random.default_rng(0)
    data = rng.integers(0, 4, size=(64, 2)).astype(np.float64)
    queries = rng.integers(0, 4, size=(16, 2)).astype(np.float64)
    labels = rng.integers(0, 3, size=64).astype(np.int32)
    ks = rng.integers(1, 20, size=16).astype(np.int32)
    inp = KNNInput(Params(64, 16, 2), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(data_block=16, query_block=8))
    assert_same_results(eng.run(inp), knn_golden(inp))


def test_fast_mode_integer_attrs_matches_golden():
    # exact=False (no f64 rescore): with integer attrs the f32 matmul path
    # is exact, so even fast mode must reproduce the golden results.
    rng = np.random.default_rng(3)
    data = rng.integers(-8, 8, size=(50, 3)).astype(np.float64)
    queries = rng.integers(-8, 8, size=(10, 3)).astype(np.float64)
    labels = rng.integers(0, 4, size=50).astype(np.int32)
    ks = np.full(10, 7, np.int32)
    inp = KNNInput(Params(50, 10, 3), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(exact=False, data_block=16, query_block=8))
    assert_same_results(eng.run(inp), knn_golden(inp), check_dists=False)


def test_device_full_pipeline_integer_attrs():
    rng = np.random.default_rng(4)
    data = rng.integers(0, 6, size=(40, 4)).astype(np.float64)
    queries = rng.integers(0, 6, size=(12, 4)).astype(np.float64)
    labels = rng.integers(0, 5, size=40).astype(np.int32)
    ks = rng.integers(1, 9, size=12).astype(np.int32)
    inp = KNNInput(Params(40, 12, 4), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(exact=False, data_block=8, query_block=4))
    got = eng.run_device_full(inp)
    want = knn_golden(inp)
    for g, w in zip(got, want):
        assert g.predicted_label == w.predicted_label
        assert list(g.neighbor_ids) == list(w.neighbor_ids)
        assert g.checksum() == w.checksum()


def test_k_equals_num_data():
    text = generate_input_text(16, 4, 2, 0, 5, 16, 16, 2, seed=9)
    inp = parse_input_text(text)
    eng = SingleChipEngine(EngineConfig(data_block=8, query_block=4))
    assert_same_results(eng.run(inp), knn_golden(inp))


def test_k_exceeds_num_data_sentinel_padding():
    inp = KNNInput(Params(2, 1, 1),
                   np.array([1, 0], np.int32),
                   np.array([[0.0], [2.0]]),
                   np.array([5], np.int32),
                   np.array([[0.5]]))
    eng = SingleChipEngine(EngineConfig(data_block=8, query_block=8))
    got = eng.run(inp)
    assert list(got[0].neighbor_ids) == [0, 1, -1, -1, -1]
    assert_same_results(got, knn_golden(inp), check_dists=False)


def test_stdout_text_matches_golden():
    text = generate_input_text(100, 10, 4, -1, 1, 1, 8, 3, seed=21)
    inp = parse_input_text(text)
    eng = SingleChipEngine(EngineConfig())
    got = format_results(eng.run(inp))
    want = format_results(knn_golden(inp))
    assert got == want
    assert got.startswith("Query 0 checksum: ")


def test_bf16_exact_mode_matches_golden():
    """round-2 review item 7: dtype=bfloat16 + exact f64 rescore must hold
    checksum parity — the coarse on-device selection is licensed by the
    margin + boundary-tie repair. On generator-style continuous data the
    repair rarely fires (0/10000 queries at the benchmark shape on the
    chip, PR 21 smoke), so dtype="auto" resolves to bf16 on TPU in exact
    mode; this test's
    contrived ranges exercise the repair-heavy worst case."""
    text = generate_input_text(2000, 80, 16, -50, 50, 1, 32, 6, seed=3)
    inp = parse_input_text(text)
    for select in ("topk", "seg", "extract"):
        eng = SingleChipEngine(EngineConfig(dtype="bfloat16", exact=True,
                                            select=select,
                                            use_pallas=select == "extract"))
        assert_same_results(eng.run(inp), knn_golden(inp), check_dists=False)
        assert eng._last_select == select  # no silent fallback


def test_bf16_exact_duplicate_heavy_ties():
    """bf16 + duplicates: every distance collapses into a handful of
    values, so the tie-overflow repair must fire wholesale and still
    land on golden."""
    rng = np.random.default_rng(17)
    data = rng.integers(0, 3, size=(512, 3)).astype(np.float64)
    queries = rng.integers(0, 3, size=(24, 3)).astype(np.float64)
    labels = rng.integers(0, 4, size=512).astype(np.int32)
    ks = rng.integers(1, 24, size=24).astype(np.int32)
    inp = KNNInput(Params(512, 24, 3), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(dtype="bfloat16", exact=True,
                                        select="topk"))
    assert_same_results(eng.run(inp), knn_golden(inp), check_dists=False)


def test_auto_dtype_resolution(monkeypatch):
    """dtype="auto" resolves per backend: bf16 only on TPU and only in
    exact mode (fast mode's output IS the device ordering, so the dtype
    must never change behind the caller's back)."""
    import jax

    # This CI runs on the CPU backend (conftest pins JAX_PLATFORMS=cpu).
    assert EngineConfig().resolve_dtype() == "float32"
    assert EngineConfig(dtype="bfloat16").resolve_dtype() == "bfloat16"
    assert EngineConfig(dtype="float32").resolve_dtype() == "float32"

    class _FakeTpu:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [_FakeTpu()])
    assert EngineConfig().resolve_dtype() == "bfloat16"
    assert EngineConfig(exact=False).resolve_dtype() == "float32"
    assert EngineConfig(dtype="float32").resolve_dtype() == "float32"


def test_bf16_wide_k_eps_repair_matches_golden():
    """Regression (r4): bf16 attr rounding perturbs distances
    NON-monotonically, so a true neighbor can rank past the candidate
    horizon with no exact device tie — the old exact-equality hazard test
    missed it (0 repairs, wrong checksums at k ~ 1500). The eps-widened
    test (finalize.staging_eps) plus the k-scaled bf16 margin must catch
    and repair every such query."""
    rng = np.random.default_rng(30)
    n, nq, na = 4000, 30, 32
    data = rng.uniform(0, 100, (n, na))
    queries = rng.uniform(0, 100, (nq, na))
    labels = rng.integers(0, 10, n).astype(np.int32)
    ks = rng.integers(1400, 1601, nq).astype(np.int32)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(dtype="bfloat16", select="topk"))
    assert_same_results(eng.run(inp), knn_golden(inp), check_dists=False)


def test_no_auto_coarsen_guard():
    """run_device_full must not let dtype="auto" stage bf16 (its output IS
    the device ordering; no rescore licenses coarsening) while an explicit
    bfloat16 request stays honored."""
    from dmlp_tpu.engine.single import no_auto_coarsen

    eng = SingleChipEngine(EngineConfig())
    eng._staging = "bfloat16"  # simulate auto -> bf16 (TPU backend)
    with no_auto_coarsen(eng):
        assert eng._staging == "float32"
    assert eng._staging == "bfloat16"

    eng2 = SingleChipEngine(EngineConfig(dtype="bfloat16"))
    with no_auto_coarsen(eng2):
        assert eng2._staging == "bfloat16"


def test_chunk_throttle_window():
    """The staging backpressure keeps at most W fold outputs pending and
    blocks oldest-first (beyond-HBM streaming: without this, the enqueue
    loop would allocate every chunk's device buffer ahead of execution)."""
    from dmlp_tpu.engine.single import ChunkThrottle

    waited = []

    class _Fake:
        def __init__(self, i):
            self.i = i

    import jax

    orig = jax.block_until_ready
    t = ChunkThrottle(window=3)
    try:
        jax.block_until_ready = lambda x: waited.append(x.i)
        for i in range(10):
            t.tick(_Fake(i))
            assert len(t._pending) <= 3
    finally:
        jax.block_until_ready = orig
    assert waited == [0, 1, 2, 3, 4, 5, 6]  # oldest-first, window kept full


@pytest.mark.parametrize("select,n", [("sort", 600), ("topk", 600),
                                      ("extract", 900)])
def test_clustered_cancellation_repair_matches_golden(select, n):
    """Regression (r4 fuzz): clustered near-duplicate points at coordinate
    scale ~5 have true distance gaps ~1e-6 but the f32 norm-expansion's
    CANCELLATION error is ~1e-5 — candidates silently reorder past the
    margin with no exact tie, and the sort path wasn't hazard-flagged at
    all. The computation term of finalize.staging_eps plus the sort-path
    flag must catch and repair every such query."""
    rng = np.random.default_rng(5152)
    nq, na = 12, 3
    centers = rng.uniform(-5, 5, (3, na))
    data = centers[rng.integers(0, 3, n)] + rng.normal(0, 1e-3, (n, na))
    queries = centers[rng.integers(0, 3, nq)] + rng.normal(0, 1e-3, (nq, na))
    labels = rng.integers(0, 5, n).astype(np.int32)
    ks = rng.integers(1, 60, nq).astype(np.int32)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(select=select,
                                        use_pallas=select == "extract"))
    got = eng.run(inp)
    assert eng.last_repairs > 0  # the hazard must actually fire here
    assert_same_results(got, knn_golden(inp), check_dists=False)


def test_clustered_cancellation_sharded_matches_golden():
    """Same regression on the mesh engine (merged-list hazard test)."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from dmlp_tpu.engine.sharded import ShardedEngine

    rng = np.random.default_rng(5149)
    n, nq, na = 576, 11, 3
    centers = rng.uniform(-5, 5, (3, na))
    data = centers[rng.integers(0, 3, n)] + rng.normal(0, 1e-3, (n, na))
    queries = centers[rng.integers(0, 3, nq)] + rng.normal(0, 1e-3, (nq, na))
    labels = rng.integers(0, 5, n).astype(np.int32)
    ks = rng.integers(1, 48, nq).astype(np.int32)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    eng = ShardedEngine(EngineConfig(mode="sharded", use_pallas=True))
    got = eng.run(inp)
    assert eng.last_repairs > 0  # the merged-list hazard must fire here
    assert_same_results(got, knn_golden(inp), check_dists=False)


class TestMultipassExtract:
    """round-4 review item 2: all-wide-k inputs run the extraction kernel in
    floor-raised passes instead of dropping to the streaming selects."""

    def _run(self, inp):
        eng = SingleChipEngine(EngineConfig(select="extract",
                                            use_pallas=True))
        got = eng.run(inp)
        assert eng._last_select == "extract"
        assert eng.last_mp_passes >= 2
        assert_same_results(got, knn_golden(inp))
        return eng

    def test_all_wide_k_matches_golden(self):
        text = generate_input_text(3000, 8, 6, -5, 5, 1300, 1500, 4,
                                   seed=11)
        eng = self._run(parse_input_text(text))
        assert eng.last_repairs == 0  # typical data: no plateau/shortfall

    def test_k_equals_num_data_all_queries(self):
        # k legal up to num_data (generate_input.py:19) — the maximal case.
        text = generate_input_text(1600, 6, 5, -3, 3, 1600, 1600, 3, seed=5)
        self._run(parse_input_text(text))

    def test_tie_plateau_stall_repairs_exact(self):
        # Every point identical: a >512-wide tie plateau pins the floor
        # after pass 1; the stall detector must flag every query for exact
        # oracle repair (the no-progress loss mode).
        n, q, a, k = 2000, 4, 3, 1000
        lines = [f"{n} {q} {a}"]
        lines += [f"{i % 3} " + " ".join(["1.000000"] * a)
                  for i in range(n)]
        lines += [f"Q {k} " + " ".join(["2.000000"] * a) for _ in range(q)]
        inp = parse_input_text("\n".join(lines) + "\n")
        eng = self._run(inp)
        assert eng.last_repairs == q  # all stalled -> all repaired

    def test_device_full_keeps_streaming_fallback(self):
        # run_device_full has no host repair, so the multipass path (whose
        # loss modes rely on it) must not serve it.
        text = generate_input_text(2000, 8, 4, -2, 2, 900, 1000, 3, seed=3)
        inp = parse_input_text(text)
        eng = SingleChipEngine(EngineConfig(select="auto", use_pallas=True))
        got = eng.run_device_full(inp)
        assert eng._last_select != "extract"
        assert_same_results(got, knn_golden(inp), check_dists=False)

    def test_mixed_k_still_routes_hetk(self):
        # One narrow-k query keeps the router's bulk non-empty: the split
        # path (not multipass) must own mixed inputs.
        text = generate_input_text(2000, 8, 4, -2, 2, 4, 8, 3, seed=9)
        inp = parse_input_text(text)
        inp.ks[0] = 1800  # one wide outlier
        eng = SingleChipEngine(EngineConfig(select="extract",
                                            use_pallas=True))
        got = eng.run(inp)
        assert eng.last_hetk is not None
        assert getattr(eng, "_mp_hazard", None) is None
        assert_same_results(got, knn_golden(inp))


def test_auto_staging_prefers_f32_for_wide_k(monkeypatch):
    """Beyond the kernel window the bf16 kcap margin stops clearing the
    bf16 eps (pre-round, unverifiable: 100% oracle-repair rate at
    204800x1024, k=4096 on v5e), so dtype="auto" must stage f32 for
    wide-k solves; explicit dtype="bfloat16" stays honored."""
    import jax.numpy as jnp

    from dmlp_tpu.engine.single import staging_for_k

    monkeypatch.setattr(EngineConfig, "resolve_dtype",
                        lambda self: "bfloat16" if self.dtype == "auto"
                        else self.dtype)
    eng = SingleChipEngine(EngineConfig(dtype="auto"))
    assert eng._staging == "bfloat16"
    with staging_for_k(eng, 512):
        assert eng._staging == "bfloat16"  # at the cap: bf16 stays
    with staging_for_k(eng, 513):
        assert eng._staging == "float32"   # beyond: auto prefers f32
        assert eng._dtype == jnp.float32
    assert eng._staging == "bfloat16"      # restored

    # explicit bf16 is the caller's choice — never overridden
    eng2 = SingleChipEngine(EngineConfig(dtype="bfloat16"))
    with staging_for_k(eng2, 4096):
        assert eng2._staging == "bfloat16"

    # end-to-end: a wide-k run under forced-bf16 auto resolution must
    # still match golden (it stages f32 internally now)
    text = generate_input_text(1400, 4, 4, -3, 3, 700, 800, 3, seed=2)
    inp = parse_input_text(text)
    eng3 = SingleChipEngine(EngineConfig(select="extract", use_pallas=True,
                                         dtype="auto"))
    assert eng3._staging == "bfloat16"
    got = eng3.run(inp)
    assert_same_results(got, knn_golden(inp))
