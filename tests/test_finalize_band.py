"""The float64 finalize gathers only the band its bound cannot order
(PR 48): a candidate whose device distance clears the k-th by more than
the hazard test's bound is out of the float64 top k before its row is
read (``engine.finalize.boundary_band``).

Two halves, both at the finalize layer with the device simulated in
NumPy (float32 arithmetic over the operands as the staging dtype holds
them, lists in device order):

- the band's finalize IS the whole window's: ids, labels and float64
  distances equal to the bit, over both scores, both staging bounds and
  the directed corpora of ``tests/test_precision.py`` (near-duplicates
  under magnitude cancellation, exact ties on an integer grid);
- the band is sound: with device distances pushed adversarially as far
  as the bound allows, every row of the float64 top k is inside it.
"""

import ml_dtypes
import numpy as np
import pytest

from dmlp_tpu.engine import finalize
from dmlp_tpu.engine.finalize import (band_widths, boundary_band,
                                      boundary_hazard, finalize_host,
                                      kth_column, rescore_f64)


def _stage(x: np.ndarray, staging: str) -> np.ndarray:
    """``x`` as the staging dtype holds it, in float32."""
    if staging == "bfloat16":
        return x.astype(ml_dtypes.bfloat16).astype(np.float32)
    return x.astype(np.float32)


def _corpus(kind: str, rng, n: int, nq: int, na: int):
    """(data, queries) in float64. ``uniform``: reals in [0, 255);
    ``near_dup``: half the rows within 1e-3 of one centre at coordinate
    scale 255, the queries there too (true gaps far under the float32
    cancellation error: test_precision's "cancel" corpus); ``ties``: an
    integer grid, every point several times, queries AT points (exact
    distance ties that only the id order breaks)."""
    if kind == "uniform":
        return rng.random((n, na)) * 255, rng.random((nq, na)) * 255
    if kind == "near_dup":
        center = rng.uniform(-1, 1, na) * 255
        data = np.vstack([center + rng.normal(0, 0.255, (n // 2, na)),
                          rng.uniform(-255, 255, (n - n // 2, na))])
        return data, center + rng.normal(0, 0.255, (nq, na))
    pts = rng.integers(-3, 4, (n // 6, na)).astype(np.float64)
    data = pts[rng.integers(0, len(pts), n)]
    return data, pts[rng.integers(0, len(pts), nq)].copy()


def _device_lists(data, queries, kcap, staging, score):
    """What a device hands the finalize: the ``kcap`` best rows a query
    by its float32 score over the staged operands, in device order
    (dist asc, id desc), padded with (+inf, -1) where the corpus is
    shorter than the window."""
    ds, qs = _stage(data, staging), _stage(queries, staging)
    cross = qs @ ds.T                                     # float32
    if score == "ip":
        d = -cross
    else:
        d = (np.einsum("qa,qa->q", qs, qs)[:, None]
             + np.einsum("na,na->n", ds, ds)[None, :] - 2 * cross)
    n = data.shape[0]
    ids = np.broadcast_to(np.arange(n), d.shape)
    order = np.lexsort((-ids, d), axis=1)[:, :kcap]
    dd = np.take_along_axis(d, order, axis=1)
    di = np.take_along_axis(ids, order, axis=1)
    if kcap > n:
        pad = ((0, 0), (0, kcap - n))
        dd = np.pad(dd, pad, constant_values=np.inf)
        di = np.pad(di, pad, constant_values=-1)
    return dd.astype(np.float64), di.astype(np.int64)


def _eps(last, queries, data, staging, score):
    """The engines' bound (SingleChipEngine._hazard_eps on the extract
    path): staging + what the first pass's form drops."""
    qn = np.einsum("qa,qa->q", queries, queries)
    dn_max = float(np.einsum("na,na->n", data, data).max())
    prec = "bf16x3" if staging == "float32" else "f32"
    return (finalize.staging_eps(last, qn, dn_max, staging,
                                 data.shape[1], score)
            + finalize.lowp_eps(prec, qn, dn_max, score))


def _both(data, labels, queries, ks, kcap, staging, score, eps=None):
    """(whole-window results, band results, widths, flags)."""
    dd, ids = _device_lists(data, queries, kcap, staging, score)
    lab = np.where(ids >= 0, labels[np.clip(ids, 0, None)], -1)
    whole = finalize_host(None, lab, ids, ks, queries, data, exact=True,
                          score=score)
    kth = kth_column(dd, ks)
    if eps is None:
        eps = _eps(dd[:, -1], queries, data, staging, score)
    widths = band_widths(boundary_band(dd, ids, kth, eps))
    d64 = rescore_f64(ids, queries, data, score=score, widths=widths)
    band = finalize_host(d64, lab, ids, ks, queries, data, exact=False,
                         score=score)
    return whole, band, widths, boundary_hazard(kth, dd[:, -1], eps)


def _assert_bitwise(whole, band):
    assert len(whole) == len(band)
    for a, b in zip(whole, band):
        assert (a.query_id, a.k, a.predicted_label) \
            == (b.query_id, b.k, b.predicted_label)
        assert np.array_equal(a.neighbor_ids, b.neighbor_ids)
        # the bits, not the values: -0.0 and NaN would tell
        assert a.neighbor_dists.tobytes() == b.neighbor_dists.tobytes()


# -- the band's finalize is the whole window's -------------------------------

@pytest.mark.parametrize("kind", ["uniform", "near_dup", "ties"])
@pytest.mark.parametrize("staging", ["float32", "bfloat16"])
@pytest.mark.parametrize("score", ["l2", "ip"])
def test_band_finalize_is_the_whole_windows(score, staging, kind):
    rng = np.random.default_rng(4800 + len(kind) + len(staging))
    n, nq, na, k = 3000, 24, 16, 10
    kcap = 120 if staging == "bfloat16" else 32
    data, queries = _corpus(kind, rng, n, nq, na)
    labels = rng.integers(0, 5, n).astype(np.int64)
    ks = np.full(nq, k, np.int64)
    whole, band, widths, flags = _both(data, labels, queries, ks, kcap,
                                       staging, score)
    # a flagged query's answer is replaced by the retry or the oracle;
    # the band holds for it all the same, so all are compared
    _assert_bitwise(whole, band)
    assert np.all(widths >= k) and np.all(widths <= kcap)
    if kind == "uniform" and not flags.any():
        assert widths.sum() < nq * kcap     # rows were left unread


@pytest.mark.parametrize("score", ["l2", "ip"])
def test_each_query_has_the_band_of_its_own_k(score):
    """ks 1..30 in one batch: kth is each query's own column, so a
    small k has a narrow band and a large one a wide band."""
    rng = np.random.default_rng(4811)
    n, nq, na, kcap = 4000, 30, 16, 64
    data, queries = _corpus("uniform", rng, n, nq, na)
    labels = rng.integers(0, 7, n).astype(np.int64)
    ks = np.arange(1, nq + 1, dtype=np.int64)
    whole, band, widths, _ = _both(data, labels, queries, ks, kcap,
                                   "float32", score)
    _assert_bitwise(whole, band)
    assert np.all(widths >= ks)
    assert widths[0] < widths[-1]


@pytest.mark.parametrize("score", ["l2", "ip"])
def test_short_lists_padded_with_minus_one(score):
    """A corpus shorter than the window: the tail of every list is
    (+inf, -1). No engine tests such a window (nothing can have been
    missed), but the band of one is its real rows and no padding."""
    rng = np.random.default_rng(4812)
    n, nq, na, kcap = 20, 6, 16, 32
    data, queries = _corpus("uniform", rng, n, nq, na)
    labels = rng.integers(0, 3, n).astype(np.int64)
    ks = np.array([1, 5, 10, 20, 25, 32], np.int64)   # two beyond n
    whole, band, widths, flags = _both(data, labels, queries, ks, kcap,
                                       "float32", score)
    _assert_bitwise(whole, band)
    assert not flags.any()                  # last is +inf: never a flag
    assert np.all(widths <= 2 * -(-n // 2))  # real rows, rounded up
    for r in band[-2:]:                      # k > n: padded to k
        assert (r.neighbor_ids[n:] == -1).all()


def test_one_query_whose_band_is_the_whole_window():
    """56 rows packed closer to the first query than the bound fill its
    window: its band is every slot (and the hazard test flags it); the
    other queries of the batch keep narrow bands."""
    rng = np.random.default_rng(4813)
    n, nq, na, k, kcap = 3000, 8, 16, 10, 40
    data, queries = _corpus("uniform", rng, n, nq, na)
    data[rng.choice(n, 56, replace=False)] = \
        queries[0] + rng.normal(0, 1e-5, (56, na))
    labels = rng.integers(0, 5, n).astype(np.int64)
    ks = np.full(nq, k, np.int64)
    whole, band, widths, flags = _both(data, labels, queries, ks, kcap,
                                       "float32", "l2")
    _assert_bitwise(whole, band)
    assert flags[0] and widths[0] == kcap
    assert not flags[1:].any() and np.all(widths[1:] < kcap)


@pytest.mark.parametrize("score", ["l2", "ip"])
def test_without_a_bound_every_slot_is_rescored(score):
    """No widths (a caller without device distances or without a bound):
    today's call. Widths of the whole window, and widths beyond it, give
    the same bits through the grouped path."""
    rng = np.random.default_rng(4814)
    n, nq, na, kcap = 2000, 16, 24, 48
    data, queries = _corpus("uniform", rng, n, nq, na)
    ids = rng.integers(-1, n, (nq, kcap)).astype(np.int64)
    whole = rescore_f64(ids, queries, data, score=score)
    assert np.isinf(whole[ids < 0]).all() and np.isfinite(whole).any()
    for widths in (np.full(nq, kcap), np.full(nq, kcap + 9)):
        got = rescore_f64(ids, queries, data, score=score, widths=widths)
        assert got.tobytes() == whole.tobytes()


@pytest.mark.parametrize("block", [None, 1, 5])
@pytest.mark.parametrize("score", ["l2", "ip"])
def test_a_slot_past_its_width_is_inf_and_never_read(score, block):
    """Arbitrary widths (0 among them): a slot inside its width carries
    the whole call's bits, a slot past it +inf under either score, and
    an id past the width is not even looked up (it may be garbage)."""
    rng = np.random.default_rng(4815)
    n, nq, na, kcap = 500, 37, 12, 20
    data, queries = rng.normal(0, 9, (n, na)), rng.normal(0, 9, (nq, na))
    ids = rng.integers(0, n, (nq, kcap)).astype(np.int64)
    widths = rng.integers(0, kcap + 1, nq)
    widths[:3] = (0, kcap, 1)
    whole = rescore_f64(ids, queries, data, score=score)
    inside = np.arange(kcap)[None, :] < widths[:, None]
    wild = np.where(inside, ids, 10 ** 12)
    got = rescore_f64(wild, queries, data, score=score, widths=widths,
                      block=block)
    assert got[inside].tobytes() == whole[inside].tobytes()
    assert (got[~inside] == np.inf).all()
    none = rescore_f64(ids, queries, data, score=score,
                       widths=np.zeros(nq, np.int64))
    assert (none == np.inf).all()


# -- band_widths --------------------------------------------------------------

def _mask(kcap, *bands):
    return np.array([[j in band for j in range(kcap)] for band in bands])


@pytest.mark.parametrize("kcap,band,want", [
    (120, range(21), 24),           # a step is 8 slots at 120
    (120, range(98), 104),
    (120, range(113), 120),         # never past the window
    (120, range(120), 120),
    (120, (), 0),                   # no band, nothing read
    (120, (0,), 8),
    (32, range(10), 10),            # a step is 2 at 32
    (32, range(11), 12),
    (40, range(16), 18),            # 3 at 40
    (1152, range(1000), 1008),      # 72 at 1152
    (1152, range(1009), 1080),
    (10, range(7), 7),              # a window under 16 slots: no rounding
    # not a prefix (lists are in device order, so this is no list an
    # engine makes): read up to the band's last slot, a superset
    (32, (0, 1, 2, 9), 10),
], ids=lambda v: str(v) if isinstance(v, int) else None)
def test_band_widths_round_up_to_a_step_of_the_window(kcap, band, want):
    assert band_widths(_mask(kcap, band)).tolist() == [want]


def test_band_widths_are_at_most_sixteen_a_batch_and_empty_is_empty():
    rng = np.random.default_rng(4816)
    for kcap in (32, 40, 120, 512, 1152):
        lens = rng.integers(0, kcap + 1, 2000)
        widths = band_widths(np.arange(kcap)[None, :] < lens[:, None])
        assert np.all(widths >= lens) and np.all(widths <= kcap)
        assert np.all(widths - lens < -(-kcap // finalize.BAND_STEPS))
        assert len(np.unique(widths[widths > 0])) <= finalize.BAND_STEPS
    assert band_widths(np.zeros((0, 8), bool)).shape == (0,)
    assert band_widths(np.zeros((3, 0), bool)).tolist() == [0, 0, 0]


def test_boundary_band_is_the_hazard_tests_comparison_inside_the_list():
    """``(ids >= 0) & (d <= kth + eps)``, non-strict, eps a query's own:
    the slot AT kth + eps is in, as the row AT it flags."""
    d = np.array([[1.0, 2.0, 3.0, 4.0, 5.0, np.inf],
                  [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]])
    ids = np.array([[9, 8, 7, 6, 5, -1], [1, 2, 3, 4, 5, 6]])
    kth = np.array([2.0, 3.0])
    got = boundary_band(d, ids, kth, np.array([2.0, 0.5]))
    assert got.tolist() == [[True, True, True, True, False, False],
                            [True, True, True, False, False, False]]
    assert boundary_hazard(kth, np.array([4.0, 3.5]),
                           np.array([2.0, 0.5])).all()
    # a scalar bound, and none: the k slots up to the k-th alone
    assert boundary_band(d, ids, kth, 1.0).sum(axis=1).tolist() == [3, 4]
    assert boundary_band(d, ids, kth).sum(axis=1).tolist() == [2, 3]
    # padding is never in a band, whatever its distance
    assert not boundary_band(d, ids, np.array([np.inf, 3.0]))[0, 5]


# -- soundness ----------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_the_float64_top_k_lies_inside_the_band(seed):
    """Exact arithmetic on integers, so "up to the bound" is to the
    unit: true distances on a dense integer ladder (many rows within the
    bound of the k-th, ties among them), the bound eps even, and every
    device distance off by exactly eps / 2: UP for the rows of the true
    top k (they sink in the device's order), DOWN for every other (they
    rise), which is as far as two distances may swap under a bound that
    covers two erring distances. The window holds the 64 best by device
    distance; wherever the hazard test is quiet, every row of the true
    top k (dist asc, id desc) is a slot of the band, and some of them
    sit past the device's own first k, which is what the rescore is for."""
    rng = np.random.default_rng(4820 + seed)
    nq, n, kcap = 64, 400, 64
    ks = rng.integers(1, 17, nq)
    eps = 2.0 * rng.integers(1, 6, nq)                    # even
    true = np.sort(rng.integers(0, 100, (nq, n)), axis=1).astype(np.float64)
    true = np.take_along_axis(true, rng.permuted(
        np.broadcast_to(np.arange(n), (nq, n)), axis=1), axis=1)
    ids = np.broadcast_to(np.arange(n), (nq, n))
    best = np.lexsort((-ids, true), axis=1)               # dist asc, id desc
    top = [set(best[i, :ks[i]].tolist()) for i in range(nq)]
    in_top = np.zeros((nq, n), bool)
    for i, rows in enumerate(top):
        in_top[i, list(rows)] = True
    dev = true + np.where(in_top, 1.0, -1.0) * (eps[:, None] / 2)
    order = np.lexsort((-ids, dev), axis=1)[:, :kcap]
    dd = np.take_along_axis(dev, order, axis=1)
    di = np.take_along_axis(ids, order, axis=1)
    kth = kth_column(dd, ks)
    quiet = ~boundary_hazard(kth, dd[:, -1], eps)
    band = boundary_band(dd, di, kth, eps)
    widths = band_widths(band)
    assert quiet.sum() >= nq // 3
    sunk = 0
    for i in np.nonzero(quiet)[0]:
        in_band = set(di[i, band[i]].tolist())
        assert top[i] <= in_band, (i, top[i] - in_band)
        assert top[i] <= set(di[i, :widths[i]].tolist())
        sunk += len(top[i] - set(di[i, :ks[i]].tolist()))
    assert sunk > 0                       # the device's first k was wrong
    assert widths[quiet].sum() < quiet.sum() * kcap       # and rows unread
    # one unit less of bound and a true neighbour falls out of the band:
    # the comparison is as tight as the bound
    tight = boundary_band(dd, di, kth, eps - 2.0)
    assert any(not top[i] <= set(di[i, tight[i]].tolist())
               for i in np.nonzero(quiet)[0])


# -- the engine: what it observes decides -------------------------------------

def _spans(tracer, name):
    return [e.get("args", {}) for e in tracer.events()
            if e.get("ph") == "X" and e["name"] == name]


@pytest.mark.parametrize("rows,banded", [(9216, True), (24, False)],
                         ids=["a_tested_window", "the_whole_corpus"])
def test_the_engine_cuts_its_rescore_where_it_has_a_bound(rows, banded):
    """A window the hazard test bounds is rescored by its band (the span
    says how many rows, the finalize how many bytes); a window that holds
    the whole corpus is never tested, has no bound and is rescored
    whole. Same answers as the golden model either way."""
    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.engine.single import SingleChipEngine
    from dmlp_tpu.golden.reference import knn_golden
    from dmlp_tpu.io.grammar import KNNInput, Params
    from dmlp_tpu.io.report import format_results
    from dmlp_tpu.obs import trace as obs_trace
    rng = np.random.default_rng(4830)
    na, nq, k = 8, 12, 10
    inp = KNNInput(Params(rows, nq, na),
                   rng.integers(0, 4, rows).astype(np.int32),
                   rng.random((rows, na)) * 255,
                   np.full(nq, k, np.int32), rng.random((nq, na)) * 255)
    eng = SingleChipEngine(EngineConfig(use_pallas=True))
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        got = eng.run(inp)
    finally:
        obs_trace.uninstall()
    assert format_results(got) == format_results(knn_golden(inp))
    (rs,) = _spans(tracer, "single.rescore")
    (fin,) = _spans(tracer, "single.finalize")
    slots = rs["queries"] * rs["slots"]
    assert rs["queries"] == nq and rs["bytes"] == fin["gather_bytes"] \
        == rs["rows"] * na * 8
    assert rs["band_pct"] == pytest.approx(100.0 * rs["rows"] / slots,
                                           abs=1e-3)
    if banded:
        assert nq * k <= rs["rows"] < slots
    else:
        assert rs["rows"] == slots and rs["band_pct"] == 100.0
