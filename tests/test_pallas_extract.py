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


# -- rows staged in bfloat16 reach the MXU as bfloat16 (PR 39) -----------------

def _bf16_valued(rng, shape, scale):
    """Reals in [0, scale) rounded to bfloat16, as float32: not
    integers, so a dot's float32 sum rounds."""
    x = jnp.asarray(rng.uniform(0, scale, shape), jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _two_chunks(q, d, floor, gate, precision="f32"):
    """A fresh fold of d's first 768 rows (700 real), then the rest
    carried, above ``floor``: (dists, ids) of both steps."""
    od, oi, _ = extract_topk(q, d[:768], n_real=700, kc=24, interpret=True,
                             mxu_gate=gate, precision=precision)
    od2, oi2, _ = extract_topk(q, d[768:], od, oi, n_real=500, id_base=700,
                               kc=24, interpret=True, mxu_gate=gate,
                               floor=floor, precision=precision)
    return [np.asarray(x) for x in (od, oi, od2, oi2)]


@pytest.mark.parametrize("na", [64, 128, 960])
@pytest.mark.parametrize("gate", [False, True], ids=["two_pass", "fused"])
def test_bf16_rows_in_one_pass_give_the_highest_dots_lists(gate, na):
    """Operands that ARRIVE bfloat16 take one MXU pass over the bf16
    block itself; the same values handed over as float32 take the one
    HIGHEST dot (six). bf16 x bf16 products are exact in float32 and
    both accumulate in float32, so nothing is dropped: the same ids,
    fresh and carried and above a floor, with the MXU gate on and off,
    and the same distances (to the float32 accumulation term where a
    backend sums the two dots in a different order)."""
    from dmlp_tpu.engine.finalize import EPS_CANCEL_COEF
    rng = np.random.default_rng(3900 + na + gate)
    scale = 255.0
    q32 = _bf16_valued(rng, (16, na), scale)
    d32 = _bf16_valued(rng, (1280, na), scale)
    floor = jnp.asarray(rng.uniform(0, 0.1 * na * scale ** 2 / 6, (16, 1)),
                        jnp.float32)
    want = _two_chunks(q32, d32, floor, gate)
    got = _two_chunks(q32.astype(jnp.bfloat16), d32.astype(jnp.bfloat16),
                      floor, gate)
    assert np.isfinite(want[0]).all() and np.isfinite(want[2]).any()
    norms = [float(jnp.max(jnp.sum(x * x, axis=1))) for x in (q32, d32)]
    tol = EPS_CANCEL_COEF * (na + 2) * sum(norms)
    for step in (0, 2):
        # the same candidates (a list's slots fill in the order its
        # distances compare, so a last-bit difference may permute them)
        assert np.array_equal(np.sort(got[step + 1], axis=1),
                              np.sort(want[step + 1], axis=1)), step
        assert np.allclose(np.sort(got[step], axis=1),
                           np.sort(want[step], axis=1), rtol=0,
                           atol=tol), step
    # the same lists to the bit where the backend's two dots agree
    # (chip_smoke.py's fold.bf16 phase holds the chip to that)
    if np.array_equal(got[0], want[0]):
        assert all(np.array_equal(a, b) for a, b in zip(got, want))


def _parent_dot_cross(q, d, precision):
    """ops.pallas_extract._dot_cross as PR 38 left it, verbatim."""
    from dmlp_tpu.ops.pallas_extract import split_bf16

    def contract(a, b, mxu=jax.lax.Precision.DEFAULT):
        return jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())), precision=mxu,
            preferred_element_type=jnp.float32)

    if precision == "bf16x3":
        q_hi, q_lo = split_bf16(q)
        d_hi, d_lo = split_bf16(d)
        return contract(jnp.concatenate([q_hi, q_hi, q_lo], axis=1),
                        jnp.concatenate([d_hi, d_lo, d_hi], axis=1))
    if precision == "bf16":
        return contract(q.astype(jnp.bfloat16), d.astype(jnp.bfloat16))
    return contract(q, d, jax.lax.Precision.HIGHEST)


def _untraced_fold(q, d, precision, gate):
    """One carried kernel call through the wrapper's own body, untraced
    (``__wrapped__``: no jit cache between a run and its patched twin)."""
    from dmlp_tpu.ops.pallas_extract import _extract_topk_jit
    rng = np.random.default_rng(5)
    cd = jnp.asarray(np.sort(rng.uniform(0, 9e4, (q.shape[0], 24))),
                     jnp.float32)
    ci = jnp.asarray(rng.integers(0, 512, cd.shape), jnp.int32)
    od, oi, *_ = _extract_topk_jit.__wrapped__(
        q, d, cd, ci, n_real=jnp.int32(500), id_base=jnp.int32(512), kc=24,
        interpret=True, tile_q=128, tile_n=12800, ne=2, unroll=1,
        block_skip=True, mxu_gate=gate, floor=None, precision=precision)
    return np.asarray(od), np.asarray(oi)


@pytest.mark.parametrize("precision", ["f32", "bf16x3", "bf16"])
@pytest.mark.parametrize("gate", [False, True], ids=["two_pass", "fused"])
def test_float32_operands_take_the_parents_form_to_the_bit(
        monkeypatch, gate, precision):
    """float32 staging bypasses the mechanism: on reals that are NOT
    bfloat16 values, every form's lists are the parent's ``_dot_cross``'s
    bit for bit."""
    from dmlp_tpu.ops import pallas_extract
    rng = np.random.default_rng(39)
    q = jnp.asarray(rng.uniform(0, 255, (16, 128)), jnp.float32)
    d = jnp.asarray(rng.uniform(0, 255, (512, 128)), jnp.float32)
    got = _untraced_fold(q, d, precision, gate)
    monkeypatch.setattr(pallas_extract, "_dot_cross", _parent_dot_cross)
    want = _untraced_fold(q, d, precision, gate)
    assert np.isfinite(want[0]).all()
    for a, b in zip(got, want):
        assert np.array_equal(a, b)


def _kernel_operand_dtypes(q, d, precision="f32"):
    """(query, data) dtypes of the pallas_call's operands in the
    wrapper's traced program."""
    from dmlp_tpu.ops.pallas_extract import _extract_topk_jit
    jaxpr = jax.make_jaxpr(lambda q, d: _extract_topk_jit(
        q, d, None, None, n_real=jnp.int32(200), id_base=jnp.int32(0),
        kc=24, interpret=False, tile_q=128, tile_n=12800, ne=2, unroll=1,
        block_skip=True, mxu_gate=True, floor=None,
        precision=precision))(q, d)

    def calls(jp):
        for e in jp.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from calls(sub)

    (call,) = calls(jaxpr.jaxpr)
    return tuple(v.aval.dtype for v in call.invars[1:3])


def test_only_a_bfloat16_pair_hands_the_kernel_a_bfloat16_block():
    """The test is the operands' dtypes at the boundary: both bfloat16,
    and the data block is streamed as it is (the small resident query
    block stays float32 in HBM and is cast back in the kernel); any
    other pair is converted, as before. bfloat16 rows under float32
    queries that are NOT bfloat16 values never take the one pass: their
    lists are the float32-converted HIGHEST run's to the bit, and not
    what rounding the queries would give."""
    from dmlp_tpu.ops.pallas_extract import mxu_passes
    f32, bf16 = jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.uniform(0, 255, (16, 64)), jnp.float32)
    d = _bf16_valued(rng, (256, 64), 255.0)
    assert _kernel_operand_dtypes(q.astype(bf16), d.astype(bf16)) \
        == (f32, bf16)
    assert _kernel_operand_dtypes(q, d.astype(bf16)) == (f32, f32)
    assert _kernel_operand_dtypes(q.astype(bf16), d) == (f32, f32)
    assert _kernel_operand_dtypes(q, d, "bf16x3") == (f32, f32)
    assert [mxu_passes(p, t) for p, t in (
        ("f32", "bfloat16"), ("bf16x3", bf16), ("bf16", "bfloat16"),
        ("bf16", "float32"), ("bf16x3", f32), ("f32", "float32"))] \
        == [1, 1, 1, 1, 3, 6]

    def lists(q, d):
        od, oi, _ = extract_topk(q, d, n_real=256, kc=24, interpret=True)
        return np.asarray(od), np.asarray(oi)

    mixed, old = lists(q, d.astype(bf16)), lists(q, d)
    rounded = lists(q.astype(bf16), d.astype(bf16))
    assert np.array_equal(mixed[0], old[0]) \
        and np.array_equal(mixed[1], old[1])
    assert not np.array_equal(mixed[0], rounded[0])


# -- two-level selection: fold to per-bucket minima, rounds over them (PR 47) --

def _run(q, d, carry=(None, None), **kw):
    """One kernel call at (tile_n 512, 4 slabs of 128 lanes, kc 24
    unless given): numpy (dists, ids, iters, wide)."""
    kw = {"kc": 24, "tile_n": 512, "fold": 4, "interpret": True, **kw}
    return [np.asarray(x) for x in extract_topk(q, d, *carry,
                                                with_wide=True, **kw)]


def _score_ids(od, oi):
    """A row's list as a sorted list of (score, id) pairs."""
    return [sorted(zip(r.tolist(), i.tolist())) for r, i in zip(od, oi)]


@pytest.mark.parametrize("values", ["reals", "grid"])
@pytest.mark.parametrize("score", ["l2", "ip"])
@FORMS
@pytest.mark.parametrize("gate", [False, True], ids=["two_pass", "fused"])
def test_two_level_lists_are_the_full_width_loops(gate, precision, score,
                                                  values):
    """Every form the kernel has (gated and not, the three first-pass
    forms, both scores), a fresh call over 4 blocks with sentinel rows
    and a carried one above a floor: with the fold pass (4 slabs of 128
    lanes) the lists hold the full-width loop's scores, and the same
    (score, id) pairs wherever no score is tied (reals); on a grid of
    massive ties the same score multiset, every id reproducing its
    score. Both loops ran in the two-level runs: the early blocks fall
    back, the warm ones do not."""
    rng = np.random.default_rng(470 + gate)
    if values == "reals":
        q = jnp.asarray(rng.uniform(0, 255, (16, 8)), jnp.float32)
        d = jnp.asarray(rng.uniform(0, 255, (4096, 8)), jnp.float32)
    else:
        q = jnp.asarray(rng.integers(0, 3, (16, 8)), jnp.float32)
        d = jnp.asarray(rng.integers(0, 3, (4096, 8)), jnp.float32)
    floor = jnp.asarray(np.where(rng.random((16, 1)) < 0.5, -np.inf,
                                 -30.0 if score == "ip" else 4.0),
                        jnp.float32)
    kw = dict(mxu_gate=gate, precision=precision, score=score)
    seen = {}
    for fold in (4, 0):
        a = _run(q, d[:2048], n_real=1900, fold=fold, **kw)
        b = _run(q, d[2048:], (a[0], a[1]), n_real=2048, id_base=1900,
                 floor=floor, fold=fold, **kw)
        seen[fold] = (a, b)
    for step in (0, 1):
        (od, oi, it, wide), (od0, oi0, it0, wide0) = (seen[4][step],
                                                      seen[0][step])
        assert np.array_equal(np.sort(od, axis=1), np.sort(od0, axis=1))
        if values == "reals":
            assert _score_ids(od, oi) == _score_ids(od0, oi0)
        # full width is the only loop without the pass; with it a
        # visit that ran no round is not wide
        assert np.array_equal(wide0, (it0 > 0).astype(np.int32))
        assert not (wide & (it == 0)).any()
    wide = np.concatenate([seen[4][0][3], seen[4][1][3]], axis=1)
    it = np.concatenate([seen[4][0][2], seen[4][1][2]], axis=1)
    assert wide[0, 0] == 1                   # the first block seeds
    if values == "reals":
        assert ((it > 0) & (wide == 0)).any()    # the narrow rounds ran
    # every id reproduces its score from the staged rows (where the
    # form computes it to float32: a grid's small integers, or "f32")
    od, oi = seen[4][1][:2]
    rows = np.concatenate([np.asarray(d[:1900]), np.asarray(d[2048:])])
    qq, dd = np.asarray(q, np.float64), rows[np.clip(oi, 0, None)]
    want = -(qq[:, None, :] * dd).sum(-1) if score == "ip" \
        else ((qq[:, None, :] - dd) ** 2).sum(-1)
    if precision == "f32" or values == "grid":
        assert np.allclose(np.where(oi >= 0, od, 0),
                           np.where(oi >= 0, want, 0), rtol=1e-4, atol=1e-2)


def _line(vals):
    """Rows on a line: attribute 0 holds ``vals``, the rest are 0, so a
    query at x scores (x - v)^2 exactly (small integers)."""
    d = np.zeros((len(vals), 4), np.float32)
    d[:, 0] = vals
    return jnp.asarray(d)


def _carry(rows):
    """Running lists of 8 slots from one list of scores a row, ids
    900 + slot."""
    cd = jnp.asarray(rows, jnp.float32)
    return cd, jnp.asarray(900 + np.arange(8)[None] + 0 * np.asarray(rows),
                           jnp.int32)


@pytest.mark.parametrize("gate", [False, True], ids=["two_pass", "fused"])
def test_a_bucket_that_hides_a_second_candidate_takes_the_full_width_loop(
        gate):
    """One block of 512 rows in 4 slabs of 128: positions p and p + 128
    share a bucket. Two entries under the row's threshold there: the
    fold pass sees one of them, the second-smallest test sees the
    other, the tile falls back (``wide`` 1) and both are inserted. The
    same two entries one lane apart: no bucket hides anything, the
    rounds over the folded array insert both (``wide`` 0)."""
    q = _line([0.0] * 8)
    carry = _carry([[1, 2, 3, 4, 5, 6, 400, 500]] * 8)
    for second, want_wide in ((70 + 128, 1), (71 + 128, 0)):
        vals = np.full(512, 100.0)
        vals[70], vals[second] = 3.0, 4.0          # scores 9 and 16
        od, oi, it, wide = _run(q, _line(vals), carry, n_real=512,
                                id_base=1000, kc=8, mxu_gate=gate)
        assert wide.tolist() == [[want_wide]] and it[0, 0] >= 2
        for r in range(8):
            assert sorted(zip(od[r].tolist(), oi[r].tolist())) == [
                (1.0, 900), (2.0, 901), (3.0, 902), (4.0, 903),
                (5.0, 904), (6.0, 905), (9.0, 1070), (16.0, 1000 + second)]


@pytest.mark.parametrize("gate", [False, True], ids=["two_pass", "fused"])
def test_equal_scores_keep_the_lowest_positions(gate):
    """Four entries of one score in four buckets, room for two: the
    rounds over the folded array take the lowest BLOCK POSITIONS (5 and
    130), not the lowest lanes (384 sits in lane 0, 257 in lane 1). The
    full-width loop takes one a half, 5 and 257: the order ISSUE 47
    names as the one thing that may differ, among scores tied at the
    list's last value inside one block."""
    q = _line([0.0] * 8)
    carry = _carry([[1, 2, 3, 4, 5, 6, 400, 500]] * 8)
    vals = np.full(512, 100.0)
    vals[[5, 130, 257, 384]] = 3.0                  # score 9, four times
    kept = {}
    for fold in (4, 0):
        od, oi, _it, wide = _run(q, _line(vals), carry, n_real=512,
                                 id_base=1000, kc=8, mxu_gate=gate,
                                 fold=fold)
        assert wide[0, 0] == (0 if fold else 1)
        assert np.sort(od, axis=1).tolist() == [
            [1, 2, 3, 4, 5, 6, 9, 9]] * 8
        kept[fold] = [sorted(i for i in row.tolist() if i >= 1000)
                      for row in oi]
    assert kept[4] == [[1005, 1130]] * 8
    assert kept[0] == [[1005, 1257]] * 8


@pytest.mark.parametrize("gate", [False, True], ids=["two_pass", "fused"])
def test_a_tile_whose_rows_disagree_falls_back_whole(gate):
    """Row 0 has two candidates in one bucket, row 1 one candidate of
    its own, the other rows none: one loop a tile, so the tile takes
    the full-width loop and every row's list is right."""
    qx = np.full(8, 5000.0)
    qx[0], qx[1] = 0.0, 1000.0
    warm = [[1, 2, 3, 4, 5, 6, 400, 500]] * 8
    vals = np.full(512, 3000.0)
    vals[70], vals[198] = 3.0, 4.0     # one bucket; row 0 scores 9, 16
    vals[300] = 999.0                  # row 1 scores 1
    od, oi, _it, wide = _run(_line(qx), _line(vals), _carry(warm),
                             n_real=512, id_base=1000, kc=8, mxu_gate=gate)
    assert wide.tolist() == [[1]]
    assert sorted(oi[0].tolist())[-2:] == [1070, 1198]
    assert sorted(od[0].tolist()) == [1, 2, 3, 4, 5, 6, 9, 16]
    assert sorted(od[1].tolist()) == [1, 1, 2, 3, 4, 5, 6, 400]
    assert 1300 in oi[1].tolist()
    for r in range(2, 8):
        assert sorted(od[r].tolist()) == sorted(warm[r])
    # without row 0's second entry no row hides one: the narrow rounds
    vals[198] = 3000.0
    od, oi, _it, wide = _run(_line(qx), _line(vals), _carry(warm),
                             n_real=512, id_base=1000, kc=8, mxu_gate=gate)
    assert wide.tolist() == [[0]]
    assert sorted(od[0].tolist()) == [1, 2, 3, 4, 5, 6, 9, 400]
    assert sorted(od[1].tolist()) == [1, 1, 2, 3, 4, 5, 6, 400]


def test_the_block_picks_the_fold():
    """``fold`` is a function of the dispatch shape: whole lane vectors
    a bucket, the largest divisor of the block's that leaves the folded
    array ten of them (a shorter block folds in two), whatever the list
    width; the wrapper resolves it from the tiles it runs, a caller's
    own included."""
    from dmlp_tpu.ops.pallas_extract import fold_slabs, resolve_variant
    assert [fold_slabs(tn) for tn in (12800, 10240, 6400, 2560, 1280, 768,
                                      512, 256, 128)] \
        == [10, 8, 5, 2, 2, 2, 2, 2, 0]
    for tn in range(128, 12801, 128):
        f = fold_slabs(tn)
        if f:
            assert (tn // 128) % f == 0
            assert tn // (128 * f) >= min(10, tn // 256)
    assert resolve_variant(512, 51200, 1024, 128)["fold"] == 10
    assert resolve_variant(32, 51200, 1024, 960)["fold"] == fold_slabs(6400)
    q, d = jnp.zeros((8, 4), jnp.float32), jnp.zeros((512, 4), jnp.float32)
    with pytest.raises(ValueError, match="untileable"):
        extract_topk(q, d, n_real=512, kc=8, interpret=True, tile_n=256,
                     fold=4)                   # half a block a bucket


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
