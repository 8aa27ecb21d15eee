"""Randomized differential sweep of the extraction path (CPU interpret).

The fixed tests cover designed cases; this sweep hardens the flagship
select="extract" engine against shape edge cases: random sizes straddling
pad granules and duplicate-heavy grids (seed sweep), plus dedicated
k == n / single-query / 1-point cases the random seeds don't reach —
every case diffs against the float64 golden model, so any algorithmic or
padding bug is a checksum mismatch, not a tolerance judgement.
"""

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.grammar import KNNInput, Params
from tests.test_engine_single import assert_same_results


def _case(seed: int) -> KNNInput:
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 700))
    nq = int(rng.integers(1, 40))
    na = int(rng.integers(1, 9))
    dup = rng.random() < 0.4
    if dup:  # integer grid: exact f32 + massive tie groups
        data = rng.integers(0, 3, (n, na)).astype(np.float64)
        queries = rng.integers(0, 3, (nq, na)).astype(np.float64)
    else:
        data = rng.uniform(-20, 20, (n, na))
        queries = rng.uniform(-20, 20, (nq, na))
    labels = rng.integers(0, int(rng.integers(1, 6)) + 1, n).astype(np.int32)
    kmax = int(rng.integers(1, min(n, 48) + 1))
    ks = rng.integers(1, kmax + 1, nq).astype(np.int32)
    if rng.random() < 0.25:
        ks[0] = min(n, 48)  # k at (or near) the dataset size
    return KNNInput(Params(n, nq, na), labels, data, ks, queries)


@pytest.mark.parametrize("seed", range(101, 119))
def test_extract_engine_random_shapes_match_golden(seed):
    inp = _case(seed)
    eng = SingleChipEngine(EngineConfig(select="extract", use_pallas=True))
    assert_same_results(eng.run(inp), knn_golden(inp), check_dists=False)


@pytest.mark.parametrize("n,nq,kfull", [(37, 5, True), (48, 1, True),
                                        (513, 1, False), (1, 3, True)])
def test_extract_engine_kn_and_single_query_edges(n, nq, kfull):
    """The edge cases random seeds don't reach: k == n (every real point
    is a neighbor; sentinel padding must fill the rest), a single query
    row, and a 1-point dataset."""
    rng = np.random.default_rng(7 * n + nq)
    na = 4
    data = rng.uniform(-5, 5, (n, na))
    queries = rng.uniform(-5, 5, (nq, na))
    labels = rng.integers(0, 3, n).astype(np.int32)
    ks = np.full(nq, n if kfull else 48, np.int32)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(select="extract", use_pallas=True))
    assert_same_results(eng.run(inp), knn_golden(inp), check_dists=False)


@pytest.mark.parametrize("seed", [301, 302, 303])
def test_extract_engine_fast_mode_random_dup_grids(seed):
    # fast mode (no f64 rescore) on exact-in-f32 integer grids: the
    # boundary-overflow repair alone must deliver golden parity.
    rng = np.random.default_rng(seed)
    n, nq, na = int(rng.integers(300, 900)), int(rng.integers(4, 24)), 3
    data = rng.integers(0, 4, (n, na)).astype(np.float64)
    queries = rng.integers(0, 4, (nq, na)).astype(np.float64)
    labels = rng.integers(0, 4, n).astype(np.int32)
    ks = rng.integers(1, 32, nq).astype(np.int32)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(select="extract", use_pallas=True,
                                        exact=False))
    assert_same_results(eng.run(inp), knn_golden(inp), check_dists=False)


def test_extract_engine_k_beyond_kernel_cap_routes_outliers():
    """round-3 review item 4 follow-through: k in the thousands is legal input
    (generate_input.py:19 allows k up to num_data), but the extraction
    kernel caps kc at 512 (pallas_extract.supports). The heterogeneous-k
    router keeps the kernel for queries whose kcap fits and streams only
    the wide-k outliers (sharing the staged chunks) — and the merged
    results still match the float64 golden model exactly."""
    rng = np.random.default_rng(77)
    n, nq, na = 2000, 6, 4
    data = rng.uniform(-30, 30, (n, na))
    queries = rng.uniform(-30, 30, (nq, na))
    labels = rng.integers(0, 5, n).astype(np.int32)
    ks = np.array([700, 1, 640, 2000, 513, 512], np.int32)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(select="extract", use_pallas=True))
    got = eng.run(inp)
    assert eng._last_select == "extract"   # bulk stayed on the kernel
    assert eng.last_hetk == (1, 5)         # (bulk, outlier) query counts
    assert_same_results(got, knn_golden(inp), check_dists=False)


def test_extract_engine_all_huge_k_multipass():
    """When EVERY query's k exceeds the kernel's width there is no bulk to
    route — r4 dropped to the streaming select; r5 runs the kernel in
    floor-raised multi-passes (round-4 review item 2) and must land on golden
    with heterogeneous wide ks (kcap sized by the max)."""
    rng = np.random.default_rng(80)
    n, nq, na = 1200, 4, 3
    data = rng.uniform(-10, 10, (n, na))
    queries = rng.uniform(-10, 10, (nq, na))
    labels = rng.integers(0, 4, n).astype(np.int32)
    ks = np.array([600, 700, 1200, 997], np.int32)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(select="extract", use_pallas=True))
    got = eng.run(inp)
    assert eng._last_select == "extract"
    assert eng.last_hetk is None
    assert eng.last_mp_passes >= 2
    assert eng.last_precision["active"] == "bf16x3"
    assert_same_results(got, knn_golden(inp), check_dists=False)


@pytest.mark.parametrize("path", ["single", "multipass", "sharded", "ring"])
def test_split_form_paths_byte_identical_to_the_oracle(path):
    """Reals at BIGANN's coordinate scale (not bf16 values: the low
    planes work), every exact engine path on the three-pass form:
    labels, checksums and the debug distances are the float64
    oracle's, one kernel pass, floor-raised passes (k past 512) and the
    mesh engines alike."""
    from dmlp_tpu.engine.sharded import ShardedEngine
    from dmlp_tpu.io.report import format_results
    rng = np.random.default_rng({"single": 1, "multipass": 2,
                                 "sharded": 3, "ring": 4}[path])
    n, nq, na = 1500, 6, 7
    data = (rng.random((n, na), dtype=np.float32) * 255.0)
    queries = (rng.random((nq, na), dtype=np.float32) * 255.0)
    ks = (np.array([600, 700, 1500, 997, 513, 1024], np.int32)
          if path == "multipass"
          else rng.integers(1, 49, nq).astype(np.int32))
    inp = KNNInput(Params(n, nq, na),
                   rng.integers(0, 4, n).astype(np.int32),
                   data.astype(np.float64), ks, queries.astype(np.float64))
    if path in ("single", "multipass"):
        eng = SingleChipEngine(EngineConfig(select="extract",
                                            use_pallas=True))
    else:
        import jax
        from dmlp_tpu.engine.ring import RingEngine
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        cls = RingEngine if path == "ring" else ShardedEngine
        eng = cls(EngineConfig(mode=path, select="extract",
                               use_pallas=True))
    got = eng.run(inp)
    gold = knn_golden(inp)
    assert eng._last_select == "extract"
    assert eng.last_precision["active"] == "bf16x3"
    if path == "multipass":
        assert eng.last_mp_passes >= 2
    assert_same_results(got, gold)
    assert format_results(got, debug=True) == format_results(gold, debug=True)


@pytest.mark.parametrize("seed", [201, 202, 203])
def test_hetk_routing_random_mixed_k_matches_golden(seed):
    """Randomized mixed-k inputs: most queries small-k, a random few in
    the hundreds-to-n range, duplicate-heavy ~half the time. Exercises
    the split plan, the shared-chunk outlier fold, the per-segment
    tie-overflow repair, and the index merge."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(600, 2200))
    nq = int(rng.integers(3, 30))
    na = int(rng.integers(1, 7))
    if rng.random() < 0.5:
        data = rng.integers(0, 3, (n, na)).astype(np.float64)
        queries = rng.integers(0, 3, (nq, na)).astype(np.float64)
    else:
        data = rng.uniform(-20, 20, (n, na))
        queries = rng.uniform(-20, 20, (nq, na))
    labels = rng.integers(0, 5, n).astype(np.int32)
    ks = rng.integers(1, 40, nq).astype(np.int32)
    n_out = int(rng.integers(1, max(2, nq // 3)))
    out_rows = rng.choice(nq, n_out, replace=False)
    ks[out_rows] = rng.integers(520, n + 1, n_out)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(select="extract", use_pallas=True))
    got = eng.run(inp)
    assert eng.last_hetk == (nq - n_out, n_out)
    assert_same_results(got, knn_golden(inp), check_dists=False)


def test_hetk_routing_device_full_and_fast_mode():
    """The router also serves run_device_full and fast (exact=False) mode;
    integer attrs make the f32 device ordering exact, so both must equal
    golden."""
    rng = np.random.default_rng(88)
    n, nq, na = 1500, 10, 4
    data = rng.integers(-7, 8, (n, na)).astype(np.float64)
    queries = rng.integers(-7, 8, (nq, na)).astype(np.float64)
    labels = rng.integers(0, 4, n).astype(np.int32)
    ks = rng.integers(1, 30, nq).astype(np.int32)
    ks[2], ks[7] = 900, 1500
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    want = knn_golden(inp)

    eng = SingleChipEngine(EngineConfig(select="extract", use_pallas=True,
                                        exact=False))
    got = eng.run(inp)
    assert eng.last_hetk == (8, 2)
    assert_same_results(got, want, check_dists=False)

    # Device-full keeps the device's f32 tie handling (no host repair by
    # contract), so its routing check uses continuous data where ties
    # don't arise; the tie-heavy grid above already covered run()'s
    # repair across segments.
    data_c = rng.uniform(-50, 50, (n, na))
    queries_c = rng.uniform(-50, 50, (nq, na))
    inp_c = KNNInput(Params(n, nq, na), labels, data_c, ks, queries_c)
    want_c = knn_golden(inp_c)
    eng2 = SingleChipEngine(EngineConfig(select="extract", use_pallas=True))
    full = eng2.run_device_full(inp_c)
    assert eng2.last_hetk == (8, 2)
    for g, w in zip(full, want_c):
        assert g.query_id == w.query_id
        assert g.predicted_label == w.predicted_label
        assert list(g.neighbor_ids) == list(w.neighbor_ids)
        assert g.checksum() == w.checksum()


def test_sharded_extract_k_beyond_kernel_cap_routes_outliers():
    """The mesh engines route heterogeneous k too: the chunked driver
    keeps the extraction kernel for the bulk and folds the wide-k
    outliers on the SAME staged chunks (streaming mesh program), with
    golden parity on the merged results."""
    import jax
    import pytest as _pytest

    from dmlp_tpu.engine.sharded import ShardedEngine

    if len(jax.devices()) < 8:
        _pytest.skip("needs 8 devices")
    rng = np.random.default_rng(78)
    n, nq, na = 1500, 5, 3
    data = rng.uniform(-9, 9, (n, na))
    queries = rng.uniform(-9, 9, (nq, na))
    labels = rng.integers(0, 4, n).astype(np.int32)
    ks = np.array([600, 1, 1500, 520, 3], np.int32)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    eng = ShardedEngine(EngineConfig(mode="sharded", select="extract",
                                     use_pallas=True))
    got = eng.run(inp)
    assert eng._last_select == "extract"   # bulk stayed on the kernel
    assert eng.last_hetk == (2, 3)
    assert_same_results(got, knn_golden(inp), check_dists=False)


def test_ring_hetk_routing_matches_golden():
    """Ring merge strategy serves both router segments (outlier lists
    merge by ring all-reduce too); device-full stays unrouted-compatible
    via the same segment loop."""
    import jax
    import pytest as _pytest

    from dmlp_tpu.engine.ring import RingEngine

    if len(jax.devices()) < 8:
        _pytest.skip("needs 8 devices")
    rng = np.random.default_rng(81)
    n, nq, na = 1100, 9, 4
    data = rng.uniform(0, 50, (n, na))
    queries = rng.uniform(0, 50, (nq, na))
    labels = rng.integers(0, 5, n).astype(np.int32)
    ks = rng.integers(1, 20, nq).astype(np.int32)
    ks[4], ks[8] = 700, 1100
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    want = knn_golden(inp)
    eng = RingEngine(EngineConfig(mode="ring", select="extract",
                                  use_pallas=True))
    got = eng.run(inp)
    assert eng.last_hetk == (7, 2)
    assert_same_results(got, want, check_dists=False)

    full = eng.run_device_full(inp)
    assert eng.last_hetk == (7, 2)
    for g, w in zip(full, want):
        assert g.query_id == w.query_id
        assert g.checksum() == w.checksum()


def _pad_stage(data, queries, gran_rows=256, gran_q=8):
    """Pad (data, queries) to kernel granules for DIRECT extract_topk
    calls (the engines do this via plan_chunks/QUERY_TILE)."""
    import jax.numpy as jnp

    from dmlp_tpu.engine.single import round_up
    n, na = data.shape
    nq = queries.shape[0]
    npad, qpad = round_up(n, gran_rows), round_up(nq, gran_q)
    d = np.zeros((npad, na), np.float32); d[:n] = data
    q = np.zeros((qpad, na), np.float32); q[:nq] = queries
    return jnp.asarray(d), jnp.asarray(q), n, nq


def test_extract_kernel_tie_rows_straddling_block_boundary():
    """Duplicated data rows placed EXACTLY astride an in-kernel block
    boundary (tile_n=256: rows 255/256) with k=1: the extraction must
    keep the LOWEST global position, with and without block skipping —
    the strict `m < T` tie contract the engines' repair path depends
    on. Also the chunk-boundary form: the duplicate's twin arrives in a
    later carry fold and must NOT displace the lower id."""
    import jax.numpy as jnp

    from dmlp_tpu.ops.pallas_extract import extract_topk

    rng = np.random.default_rng(5)
    n, na = 512, 4
    data = rng.uniform(-50, 50, (n, na))
    data[256] = data[255]                 # dup pair astride block boundary
    queries = np.stack([data[255], data[10]])
    d, q, n_real, _nq = _pad_stage(data, queries)
    for skip in (True, False):
        od, oi, _ = extract_topk(q, d, n_real=n_real, kc=8,
                                 interpret=True, tile_n=256,
                                 block_skip=skip)
        # row 0's best is the dup distance (0.0): slot ids must include
        # 255 — and 255 must be extracted before 256 (lowest position
        # first), so with both present the MIN of the two slots is 255.
        ids0 = set(np.asarray(oi)[0].tolist())
        assert 255 in ids0 and 256 in ids0

    # chunk-boundary ties: the same row closes chunk 1 and opens chunk 2
    d1 = rng.uniform(-50, 50, (512, na))
    d2 = rng.uniform(-50, 50, (512, na))
    d2[0] = d1[511]
    q2 = np.ascontiguousarray(d1[511][None])
    dd1, qq, _, _ = _pad_stage(d1, q2)
    dd2 = jnp.asarray(d2.astype(np.float32))
    for skip in (True, False):
        od, oi, _ = extract_topk(qq, dd1, n_real=512, kc=8,
                                 interpret=True, tile_n=256,
                                 block_skip=skip)
        od, oi, _ = extract_topk(qq, dd2, od, oi, n_real=512, id_base=512,
                                 kc=8, interpret=True, tile_n=256,
                                 block_skip=skip)
        oi_np = np.asarray(oi)[0]
        srt = oi_np[np.argsort(np.asarray(od)[0], kind="stable")]
        # both tied copies are in the top-8 (dist 0), and k=1 semantics
        # (the first report slot) keep the lower global id 511
        assert {511, 512} <= set(oi_np.tolist())
        assert min(srt[0], srt[1]) == 511


def test_extract_engine_tie_heavy_dup_rows_block_boundaries_vs_golden(
        monkeypatch):
    """Engine-level tie regression for block skipping: the resolver is
    made to give a small tile_n (many in-kernel block boundaries), the
    dataset repeats whole row-groups so tie groups straddle those
    boundaries, and the full run() must still equal the float64 golden
    model exactly — block skipping cannot silently change
    lowest-global-position tie breaking."""
    from dmlp_tpu.engine.single import resolve_kcap
    from dmlp_tpu.ops import pallas_extract

    rng = np.random.default_rng(91)
    n_base, nq, na = 160, 14, 3
    base = rng.integers(0, 3, (n_base, na)).astype(np.float64)
    data = np.concatenate([base, base, base, base])      # 4 copies: deep ties
    n = data.shape[0]
    queries = rng.integers(0, 3, (nq, na)).astype(np.float64)
    labels = rng.integers(0, 4, n).astype(np.int32)
    # kmax stays small so kcap (40) fits the pinned tile_n — a wider k
    # would route to multipass, whose lists the pinned block cannot hold.
    ks = rng.integers(1, 33, nq).astype(np.int32)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)

    kc = resolve_kcap(EngineConfig(), int(ks.max()), "extract", 1 << 30,
                      staging="float32")
    pinned = {"tile_q": 32, "tile_n": 256, "ne": 2, "unroll": 1}
    assert kc <= pinned["tile_n"]          # the kernel must take the tiles
    monkeypatch.setattr(pallas_extract, "resolve_variant",
                        lambda kc, b, qb=None, a=None: dict(pinned))
    from dmlp_tpu.obs import trace as obs_trace
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        eng = SingleChipEngine(EngineConfig(select="extract",
                                            use_pallas=True))
        got = eng.run(inp)
    finally:
        obs_trace.uninstall()
    assert eng._last_select == "extract"
    # prove the pinned multi-block variant actually drove the kernel
    spans = [e for e in tracer.to_dict()["traceEvents"]
             if e.get("name") == "single.enqueue_extract"]
    assert spans and spans[0]["args"]["variant"] == pinned
    assert eng.last_variant["tile_n"] == 256
    assert_same_results(got, knn_golden(inp), check_dists=False)


@pytest.mark.parametrize("seed", [401, 402, 403, 404])
def test_extract_block_skip_output_identical_fuzz(seed):
    """Direct-kernel A/B over the fuzz distribution (duplicate-heavy
    grids included): block_skip on/off must be bit-identical in dists,
    ids, AND the running lists after a warm second fold — the skip gate
    may only elide rounds that would have inserted nothing."""
    import jax.numpy as jnp

    from dmlp_tpu.ops.pallas_extract import extract_topk

    inp = _case(seed)
    kc = 16
    d, q, n_real, _ = _pad_stage(inp.data_attrs, inp.query_attrs)
    outs = {}
    for skip in (True, False):
        od1, oi1, it1 = extract_topk(q, d, n_real=n_real, kc=kc,
                                     interpret=True, tile_n=256,
                                     block_skip=skip)
        od2, oi2, it2 = extract_topk(q, d, od1, oi1, n_real=n_real,
                                     id_base=d.shape[0], kc=kc,
                                     interpret=True, tile_n=256,
                                     block_skip=skip)
        outs[skip] = (np.asarray(od2), np.asarray(oi2),
                      int(np.asarray(it1).sum() + np.asarray(it2).sum()))
    assert np.array_equal(outs[True][0], outs[False][0])
    assert np.array_equal(outs[True][1], outs[False][1])
    # the gate can only REMOVE no-op rounds
    assert outs[True][2] <= outs[False][2]


@pytest.mark.parametrize("gate", [False, True], ids=["two_pass", "fused"])
@pytest.mark.parametrize("seed", range(601, 609))
def test_two_level_selection_fuzz(seed, gate):
    """Direct-kernel A/B over the fuzz distribution (duplicate-heavy
    grids included), with and without the fold pass (PR 47: blocks of
    256 rows fold to 2 slabs of 128): a fresh call and a warm carried
    one. The same score multiset a row always; the same (score, id)
    pairs wherever the row's last score is not tied with a score left
    out; every id reproduces its score; and a visit is wide only if it
    ran a round."""
    import jax.numpy as jnp

    from dmlp_tpu.ops.pallas_extract import extract_topk

    inp = _case(seed)
    kc = 16
    d, q, n_real, _ = _pad_stage(inp.data_attrs, inp.query_attrs)
    outs = {}
    for fold in (2, 0):
        kw = dict(kc=kc, interpret=True, tile_n=256, fold=fold,
                  mxu_gate=gate, with_wide=True)
        od, oi, it1, w1 = extract_topk(q, d, n_real=n_real, **kw)
        od, oi, it2, w2 = extract_topk(q, d, od, oi, n_real=n_real,
                                       id_base=d.shape[0], **kw)
        outs[fold] = (np.asarray(od), np.asarray(oi))
        for it, w in ((it1, w1), (it2, w2)):
            assert not (np.asarray(w) & (np.asarray(it) == 0)).any()
    (od, oi), (od0, oi0) = outs[2], outs[0]
    assert np.array_equal(np.sort(od, axis=1), np.sort(od0, axis=1))
    # ids reproduce their scores: both copies of the corpus
    rows = np.concatenate([np.asarray(d), np.asarray(d)])
    rec = ((np.asarray(q)[:, None, :] - rows[np.clip(oi, 0, None)]) ** 2
           ).sum(-1)
    assert np.allclose(np.where(oi >= 0, rec, 0),
                       np.where(oi >= 0, od, 0), rtol=1e-5, atol=1e-3)
    assert np.array_equal(oi >= 0, np.isfinite(od))
    # the same pairs where no tie straddles the list's end
    dist = ((np.asarray(q, np.float64)[:, None, :]
             - np.asarray(d, np.float64)[None, :n_real]) ** 2).sum(-1)
    for r in range(q.shape[0]):
        last = np.max(od[r])
        if np.isfinite(last) and 2 * int((dist[r] <= last * (1 + 1e-6)
                                          ).sum()) == kc:
            assert sorted(zip(od[r].tolist(), oi[r].tolist())) \
                == sorted(zip(od0[r].tolist(), oi0[r].tolist())), r


def test_extract_engine_wide_k_tuned_variant():
    """k > 64 routes to the wide-list tuned variant (ne=4; tq=128 since
    PR 47, with the fold pass); parity must hold there too."""
    rng = np.random.default_rng(79)
    n, nq, na = 1400, 9, 5
    data = rng.uniform(-15, 15, (n, na))
    queries = rng.uniform(-15, 15, (nq, na))
    labels = rng.integers(0, 6, n).astype(np.int32)
    ks = rng.integers(100, 201, nq).astype(np.int32)
    inp = KNNInput(Params(n, nq, na), labels, data, ks, queries)
    eng = SingleChipEngine(EngineConfig(select="extract", use_pallas=True))
    got = eng.run(inp)
    assert eng._last_select == "extract"
    assert_same_results(got, knn_golden(inp), check_dists=False)
