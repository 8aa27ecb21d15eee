"""Low-precision first pass: analytic bound + byte-identity fuzz.

The forms that drop products ("bf16x3": three bf16 MXU passes over split
operands, what every exact engine runs at float32 staging; "bf16": one
pass) are sound only under their bounds; two halves of that contract get
hardened here:

- the :func:`~dmlp_tpu.engine.finalize.lowp_eps` cast bound actually
  upper-bounds the bf16-vs-f32 cross-term error, fuzzed on directed
  adversarial corpora (magnitude cancellation: huge norms, tiny true
  distances — exactly where a naive relative bound would blow up);
- with the bound wired through the candidate windows, every engine
  tier under a forced bf16 first pass stays BYTE-identical to its f32
  run and to the f64 golden oracle — including duplicate-heavy tie
  grids straddling block boundaries, where a single flipped comparison
  in the lossy pass would reorder equal-distance neighbors.
"""

import numpy as np
import pytest

import ml_dtypes

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine import finalize
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.engine.sharded import ShardedEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.io.report import format_results
from dmlp_tpu.serve.engine import ResidentEngine
from tests.test_engine_single import assert_same_results


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round-trip through bfloat16 — the first-pass cast, in f64."""
    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


# -- the analytic bound -------------------------------------------------------

def test_lowp_eps_zero_for_f32_and_no_silent_int8():
    qn = np.array([1.0, 4.0])
    assert finalize.lowp_eps("f32", qn, 9.0).tolist() == [0.0, 0.0]
    # the split form's coefficient is its derivation's, to the bit
    assert finalize.LOWP_COEF["bf16x3"] == 2.0 ** -14 * (1 + 2.0 ** -16)
    assert finalize.lowp_eps("bf16x3", qn, 9.0).tolist() == [
        finalize.LOWP_COEF["bf16x3"] * 10.0,
        finalize.LOWP_COEF["bf16x3"] * 13.0]
    with pytest.raises(KeyError):
        finalize.lowp_eps("int8", qn, 9.0)


@pytest.mark.parametrize("seed", range(301, 311))
def test_lowp_eps_bounds_bf16_cross_term_error(seed):
    """Directed-rounding fuzz: |2(q·d − bf16(q)·bf16(d))| stays within
    lowp_eps on cancellation-heavy corpora. The kernel perturbs ONLY
    the cross term (norms stay f32 from exact inputs), so this is the
    whole cast error the windows must absorb."""
    rng = np.random.default_rng(seed)
    na = int(rng.integers(2, 16))
    scale = float(2.0 ** rng.integers(0, 11))     # norms up to ~2^10
    center = rng.uniform(-1, 1, na) * scale
    # data: a tight cluster on the center (distances ~1e-3 * scale,
    # cross terms ~scale^2 — maximal cancellation) plus spread rows
    n = 400
    cluster = center + rng.normal(0, 1e-3 * scale, (n // 2, na))
    spread = rng.uniform(-scale, scale, (n - n // 2, na))
    data = np.vstack([cluster, spread])
    queries = center + rng.normal(0, 1e-3 * scale, (24, na))
    cross = queries @ data.T                       # f64 exact
    cross_lowp = _bf16(queries) @ _bf16(data).T
    err = 2.0 * np.abs(cross - cross_lowp)
    qn = np.einsum("ij,ij->i", queries, queries)
    dn_max = float(np.max(np.einsum("ij,ij->i", data, data)))
    bound = finalize.lowp_eps("bf16", qn, dn_max)[:, None]
    assert np.all(err <= bound), \
        f"cast error {err.max()} exceeds lowp_eps {bound.min()}"


def _split(x: np.ndarray):
    """ops.pallas_extract.split_bf16 in NumPy: (hi, lo) as float64."""
    hi = _bf16(x)               # x holds float32 values: one rounding
    return hi, _bf16(x - hi)    # the difference is exact


def _worst_split(rng, shape, scale):
    """float32 values a hair under a bf16 rounding midpoint whose
    residual is a hair under ITS midpoint: |x - hi| and |x - hi - lo|
    both at their largest, all of one sign, so the dropped products
    add up instead of cancelling."""
    e = np.floor(np.log2(rng.uniform(0.5, 1.0, shape) * scale))
    mant = rng.integers(0, 8, shape)      # low bf16 bits: |x| near 2^e
    x = (1 + mant / 128 + 2.0 ** -8 - 2.0 ** -17 - 2.0 ** -23) * 2.0 ** e
    return x.astype(np.float32).astype(np.float64)


@pytest.mark.parametrize("scale", [1.0, 255.0])
@pytest.mark.parametrize("na", [16, 128, 960])
@pytest.mark.parametrize("corpus", ["worst_split", "cancel"])
def test_lowp_eps_bounds_the_products_bf16x3_drops(corpus, na, scale):
    """What the three passes leave out (q_lo.d_lo and the remainders'
    products), exactly: 2 |q.d - (q_hi.d_hi + q_hi.d_lo + q_lo.d_hi)| in
    float64 stays inside HALF of lowp_eps (the coefficient covers two
    erring distances), on operands built to make the residuals as large
    as rounding allows and on the magnitude-cancellation corpus."""
    rng = np.random.default_rng(1000 + na + int(scale))
    if corpus == "worst_split":
        data = _worst_split(rng, (200, na), scale)
        queries = _worst_split(rng, (16, na), scale)
    else:
        center = rng.uniform(-1, 1, na) * scale
        data = np.vstack([center + rng.normal(0, 1e-3 * scale, (100, na)),
                          rng.uniform(-scale, scale, (100, na))])
        queries = center + rng.normal(0, 1e-3 * scale, (16, na))
        data = data.astype(np.float32).astype(np.float64)
        queries = queries.astype(np.float32).astype(np.float64)
    qh, ql = _split(queries)
    dh, dl = _split(data)
    # the split itself: the half-ulp bounds LOWP_COEF's derivation uses
    for x, hi, lo in ((queries, qh, ql), (data, dh, dl)):
        ax = np.abs(x)
        assert np.all(np.abs(x - hi) <= 2.0 ** -8 * ax)
        assert np.all(np.abs(lo) <= 2.0 ** -8 * ax)
        assert np.all(np.abs(x - hi - lo) <= 2.0 ** -17 * ax)
    cross3 = qh @ dh.T + qh @ dl.T + ql @ dh.T          # exact in f64
    err = 2.0 * np.abs(queries @ data.T - cross3)
    qn = np.einsum("ij,ij->i", queries, queries)
    dn_max = float(np.max(np.einsum("ij,ij->i", data, data)))
    half = finalize.lowp_eps("bf16x3", qn, dn_max)[:, None] / 2.0
    assert np.all(err <= half), (err / half).max()
    if corpus == "worst_split":
        # the coefficient is tight: the directed operands reach 0.94 of it
        assert (err / half).max() > 0.9, (err / half).max()


@pytest.mark.parametrize("scale", [1.0, 255.0])
@pytest.mark.parametrize("na", [16, 128, 960])
@pytest.mark.parametrize("gate", [False, True], ids=["two_pass", "fused"])
def test_bf16x3_kernel_distance_inside_staging_plus_lowp_eps(gate, na,
                                                             scale):
    """The interpreted KERNEL's "bf16x3" distances against float64, on
    the adversarial corpus (tight cluster at a large norm: true
    distances ~1e-6 of the scale, cross terms of the scale's size):
    every candidate's distance within staging_eps + lowp_eps, and the
    same candidates as the one-dot "f32" form's wherever the bound
    cannot have reordered them."""
    import jax.numpy as jnp
    from dmlp_tpu.ops.pallas_extract import extract_topk
    rng = np.random.default_rng(7000 + na + int(scale) + gate)
    center = rng.uniform(0.25, 1, na) * scale
    data = np.vstack([center + rng.normal(0, 1e-3 * scale, (384, na)),
                      rng.uniform(0, scale, (128, na))])
    data = data.astype(np.float32)
    queries = (center + rng.normal(0, 1e-3 * scale, (16, na))
               ).astype(np.float32)
    kc = 16
    got = {}
    for prec in ("f32", "bf16x3"):
        od, oi, _ = extract_topk(jnp.asarray(queries), jnp.asarray(data),
                                 n_real=500, kc=kc, interpret=True,
                                 mxu_gate=gate, precision=prec)
        got[prec] = (np.asarray(od, np.float64), np.asarray(oi))
    q64, d64 = queries.astype(np.float64), data.astype(np.float64)
    qn = np.einsum("ij,ij->i", q64, q64)
    dn_max = float(np.max(np.einsum("ij,ij->i", d64[:500], d64[:500])))
    for prec, (od, oi) in got.items():
        assert (oi >= 0).all() and (oi < 500).all()
        diff = d64[oi] - q64[:, None, :]
        true = np.einsum("qka,qka->qk", diff, diff)
        eps = finalize.staging_eps(true.max(axis=1), qn, dn_max,
                                   "float32", na) \
            + finalize.lowp_eps(prec, qn, dn_max)
        assert np.all(np.abs(od - true) <= eps[:, None]), prec
    # the k nearest by float64 are in the split form's window wherever
    # the window clears its bound (what the hazard test asks)
    full = ((q64[:, None, :] - d64[None, :500]) ** 2).sum(-1)
    order = np.argsort(full, axis=1)
    od, oi = got["bf16x3"]
    eps = finalize.staging_eps(od.max(axis=1), qn, dn_max, "float32", na) \
        + finalize.lowp_eps("bf16x3", qn, dn_max)
    for qi in range(len(queries)):
        safe = full[qi, order[qi]] + eps[qi] < od[qi].max()
        want = order[qi][safe][:kc]
        assert set(want) <= set(oi[qi]), qi


def test_dot_cross_forms_are_what_they_say():
    """"f32" is ONE dot at Precision.HIGHEST on float32 operands and
    nothing else (fast mode's form, byte for byte the parent's);
    "bf16x3" is ONE bf16 x bf16 dot accumulated in float32 over a
    contraction of three times the width, the halves split in the
    kernel and stacked, at every width; "bf16" one."""
    import jax
    import jax.numpy as jnp
    from dmlp_tpu.ops.pallas_extract import PRECISIONS, _dot_cross
    assert PRECISIONS == ("f32", "bf16x3", "bf16") \
        == tuple(finalize.LOWP_COEF)

    def dots(precision, na):
        q = jnp.ones((8, na), jnp.float32)
        d = jnp.ones((256, na), jnp.float32)
        eqns = jax.make_jaxpr(
            lambda q, d: _dot_cross(q, d, precision))(q, d).jaxpr.eqns
        return ([e for e in eqns if e.primitive.name == "dot_general"],
                [e.primitive.name for e in eqns])

    for na in (64, 128, 1024):
        one, names = dots("f32", na)
        assert names == ["dot_general"]
        assert "HIGHEST" in str(one[0].params["precision"])
        assert [v.aval.dtype for v in one[0].invars] == [jnp.float32] * 2
        single, _ = dots("bf16", na)
        assert len(single) == 1
        assert [v.aval.dtype for v in single[0].invars] \
            == [jnp.bfloat16] * 2

    for na in (64, 128, 960, 1024):
        (e,), names = dots("bf16x3", na)
        assert names.count("concatenate") == 2, na
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
        assert [v.aval.shape[1] for v in e.invars] == [3 * na] * 2
        assert e.params["preferred_element_type"] == jnp.float32


# -- engine byte-identity under the forced bf16 pass --------------------------

def _case(seed: int) -> KNNInput:
    """Duplicate-biased corpora with n straddling block granules."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(120, 700))
    nq = int(rng.integers(1, 32))
    na = int(rng.integers(1, 9))
    if rng.random() < 0.5:   # integer grid: exact f32 + massive ties
        data = rng.integers(0, 3, (n, na)).astype(np.float64)
        queries = rng.integers(0, 3, (nq, na)).astype(np.float64)
    else:
        data = rng.uniform(-20, 20, (n, na))
        queries = rng.uniform(-20, 20, (nq, na))
    labels = rng.integers(0, 5, n).astype(np.int32)
    ks = rng.integers(1, min(n, 48) + 1, nq).astype(np.int32)
    return KNNInput(Params(n, nq, na), labels, data, ks, queries)


def _cfg(precision: str, **kw) -> EngineConfig:
    return EngineConfig(select="extract", use_pallas=True,
                        precision=precision, **kw)


@pytest.mark.parametrize("seed", range(211, 221))
def test_single_engine_bf16_byte_identical_to_f32_and_golden(seed):
    inp = _case(seed)
    got_b = SingleChipEngine(_cfg("bf16")).run(inp)
    got_f = SingleChipEngine(_cfg("f32")).run(inp)
    gold = knn_golden(inp)
    assert format_results(got_b) == format_results(got_f) \
        == format_results(gold)
    assert_same_results(got_b, gold)


def test_single_engine_reports_active_precision_and_inflation():
    inp = _case(404)
    eng = SingleChipEngine(_cfg("bf16"))
    eng.run(inp)
    rec = eng.last_precision
    assert rec["active"] == "bf16" and rec["configured"] == "bf16"
    assert rec["kcap_inflation"] > 0      # the window actually widened
    assert rec["mxu_passes"] == 1 == eng.last_variant["mxu_passes"]
    # "f32" in exact mode at float32 staging MEANS the three-pass form,
    # and that form widens no window
    eng_f = SingleChipEngine(_cfg("f32"))
    eng_f.run(inp)
    assert eng_f.last_precision["active"] == "bf16x3"
    assert eng_f.last_precision["configured"] == "bf16x3"
    assert eng_f.last_precision["kcap_inflation"] == 0
    assert eng_f.last_precision["mxu_passes"] == 3


def test_bf16_tie_grid_across_block_boundary():
    """All-duplicate integer grid with rows astride the block edge:
    every distance is bf16-representable, so ties are decided purely by
    id order — a first pass that perturbed comparison order would
    reorder the neighbor lists."""
    rng = np.random.default_rng(77)
    n, na = 260, 3                 # straddles the 256 block granule
    data = rng.integers(0, 2, (n, na)).astype(np.float64)
    data[128:140] = data[0]        # duplicate row group across chunks
    queries = data[[0, 5, 129, 255]].copy()
    ks = np.full(4, 48, np.int32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    inp = KNNInput(Params(n, 4, na), labels, data, ks, queries)
    got_b = SingleChipEngine(_cfg("bf16")).run(inp)
    gold = knn_golden(inp)
    assert format_results(got_b) == format_results(gold)
    assert_same_results(got_b, gold)


def test_sharded_engine_bf16_byte_identical():
    inp = _case(555)
    eng = ShardedEngine(EngineConfig(mode="sharded", select="extract",
                                     precision="bf16", data_block=64))
    got = eng.run(inp)
    gold = knn_golden(inp)
    assert format_results(got) == format_results(gold)
    assert_same_results(got, gold)
    assert eng.last_precision["active"] == "bf16"


def test_resident_engine_bf16_matches_f32_and_golden():
    rng = np.random.default_rng(9)
    n, na = 600, 5
    corpus = KNNInput(Params(n, 0, na),
                      rng.integers(0, 4, n).astype(np.int32),
                      rng.uniform(-10, 10, (n, na)),
                      np.zeros(0, np.int32), np.zeros((0, na)))
    q = rng.uniform(-10, 10, (7, na))
    ks = np.array([1, 3, 8, 17, 32, 48, 5], np.int32)
    served_b = ResidentEngine(corpus, EngineConfig(precision="bf16")) \
        .solve_batch(q, ks)
    served_f = ResidentEngine(corpus, EngineConfig(precision="f32")) \
        .solve_batch(q, ks)
    inp = KNNInput(Params(n, len(ks), na), corpus.labels,
                   corpus.data_attrs, ks, q)
    gold = knn_golden(inp)
    assert format_results(served_b) == format_results(served_f) \
        == format_results(gold)


def test_env_kill_switch_and_force(monkeypatch):
    """$DMLP_TPU_PRECISION: "f32" disarms a bf16 config; "bf16" arms a
    default config. Either way the answers stay golden."""
    inp = _case(888)
    monkeypatch.setenv("DMLP_TPU_PRECISION", "f32")
    eng = SingleChipEngine(_cfg("bf16"))
    assert format_results(eng.run(inp)) == format_results(knn_golden(inp))
    assert eng.last_precision["active"] == "bf16x3"   # the f32 FORM
    monkeypatch.setenv("DMLP_TPU_PRECISION", "bf16")
    eng2 = SingleChipEngine(_cfg("auto"))
    assert format_results(eng2.run(inp)) == format_results(knn_golden(inp))
    assert eng2.last_precision["active"] == "bf16"


@pytest.mark.parametrize("precision", ["auto", "f32", "bf16"])
def test_fast_mode_never_runs_lowp(precision):
    """A pass that drops products is only sound with the f64 rescore
    behind it — fast (non-exact) mode must pin the pass to the one
    HIGHEST dot, whatever is configured or staged."""
    cfg = _cfg(precision, exact=False)
    assert cfg.resolve_precision() == cfg.resolve_precision("float32") \
        == cfg.f32_form("float32") == "f32"
    inp = _case(889)
    eng = SingleChipEngine(cfg)
    eng.run(inp)
    assert eng.last_precision["active"] == "f32"


def test_resolve_precision_forms():
    """The form follows what the engine knows: exact mode at float32
    staging splits; operands staged in bfloat16 have no low half and
    keep the one dot; "bf16" stays the one-pass form."""
    exact = EngineConfig()
    assert exact.resolve_precision("float32") == "bf16x3"
    assert exact.resolve_precision("bfloat16") == "f32"
    assert EngineConfig(precision="f32").resolve_precision("float32") \
        == "bf16x3"
    assert EngineConfig(precision="bf16").resolve_precision("float32") \
        == EngineConfig(precision="bf16").resolve_precision("bfloat16") \
        == "bf16"
    # staging left out: the config's own (float32 on the cpu backend)
    assert EngineConfig(dtype="float32").resolve_precision() == "bf16x3"
    assert EngineConfig(dtype="bfloat16").resolve_precision() == "f32"


@pytest.fixture
def fresh_split_check():
    """split_holds asks the backend once a process: a test that swaps
    the split asks again, and leaves the real answer behind it."""
    from dmlp_tpu.ops import pallas_extract
    pallas_extract.split_holds.cache_clear()
    yield pallas_extract
    pallas_extract.split_holds.cache_clear()


def test_the_split_holds_through_the_kernel_on_this_backend(
        fresh_split_check):
    """The guard every engine asks before it names "bf16x3": the split
    run through a pallas_call leaves |x - hi - lo| <= 2^-17 |x|."""
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert fresh_split_check.split_holds() is True


@pytest.mark.parametrize("fold", ["lo_is_zero", "lo_is_short"])
def test_a_compiler_that_folds_the_split_gets_the_one_dot(
        fresh_split_check, monkeypatch, fold):
    """What XLA:TPU did to the split in a jitted prologue (f32 -> bf16
    -> f32 taken for the identity: lo = 0), and a low half that is
    there but a quarter short (the guard holds the bound, not lo != 0):
    it says no, with a warning; f32_form then names the one
    HIGHEST dot, and an exact engine runs it and answers as the
    oracle."""
    import jax.numpy as jnp
    pe = fresh_split_check
    real = pe.split_bf16

    def folded(x):
        hi, lo = real(x)
        if fold == "lo_is_zero":
            return hi, jnp.zeros_like(lo)
        return hi, (lo.astype(jnp.float32) * 0.75).astype(jnp.bfloat16)

    monkeypatch.setattr(pe, "split_bf16", folded)
    with pytest.warns(RuntimeWarning, match="does not make the bf16 split"):
        assert pe.split_holds() is False
    cfg = _cfg("auto")
    assert cfg.f32_form("float32") == cfg.resolve_precision("float32") \
        == "f32"
    assert EngineConfig(precision="bf16").resolve_precision("float32") \
        == "bf16"          # the one-pass form splits nothing
    inp = _case(405)
    eng = SingleChipEngine(cfg)
    assert format_results(eng.run(inp)) == format_results(knn_golden(inp))
    assert (eng.last_precision["active"],
            eng.last_precision["configured"]) == ("f32", "f32")
    assert eng.last_precision["mxu_passes"] == 6 \
        == eng.last_variant["mxu_passes"]


def test_only_a_run_hands_its_solve_a_form_that_drops_products(
        monkeypatch):
    """candidates() and run_device_full() report the device ordering,
    with no rescore behind it: they solve at the default, the one
    HIGHEST dot, even in an exact config. run() hands its solve
    active_precision's answer, on every rung."""
    from dmlp_tpu.engine.single import active_precision
    from dmlp_tpu.ops import pallas_fused
    from dmlp_tpu.resilience import degrade
    seen = []
    real = pallas_fused.variant_stamp

    def spy(kc, b, qb, a, precision="f32", staging="float32"):
        seen.append(precision)
        return real(kc, b, qb, a, precision, staging)

    monkeypatch.setattr(pallas_fused, "variant_stamp", spy)
    inp = _case(404)
    eng = SingleChipEngine(_cfg("auto"))
    eng.candidates(inp)
    eng.run_device_full(inp)
    assert seen == ["f32", "f32"]
    eng.run(inp)
    assert seen[2:] == ["bf16x3"] and \
        eng.last_precision["active"] == "bf16x3"
    for rung in ("lowp", "prune", "fused", "heuristic"):
        with degrade._rung_context(eng, rung):
            assert active_precision(eng) == "bf16x3", rung
    # the one-pass form gives way to the float32 FORM below its rung
    eng_b = SingleChipEngine(_cfg("bf16"))
    with degrade._rung_context(eng_b, "lowp"):
        assert active_precision(eng_b) == "bf16"
    with degrade._rung_context(eng_b, "prune"):
        assert active_precision(eng_b) == "bf16x3"
    # bfloat16 staging: nothing to split
    eng_s = SingleChipEngine(_cfg("auto", dtype="bfloat16"))
    with degrade._rung_context(eng_s, "lowp"):
        assert active_precision(eng_s) == "f32"


@pytest.mark.parametrize("engine", ["single", "sharded", "mesh"])
def test_hazard_eps_takes_the_form_that_ran(engine, monkeypatch):
    """The three hazard tests that once hard-coded lowp_eps("bf16", ...)
    under an == "bf16" test widen by the ACTIVE form's bound."""
    import importlib
    mod = importlib.import_module({
        "single": "dmlp_tpu.engine.single",
        "sharded": "dmlp_tpu.engine.sharded",
        "mesh": "dmlp_tpu.fleet.mesh_engine"}[engine])
    seen = []
    real = mod.lowp_eps

    def spy(precision, qn, dn_max, *score):
        seen.append(precision)
        return real(precision, qn, dn_max, *score)

    monkeypatch.setattr(mod, "lowp_eps", spy)
    rng = np.random.default_rng(5)
    n, na = 600, 5
    data = rng.uniform(-10, 10, (n, na))
    labels = rng.integers(0, 4, n).astype(np.int32)
    q = rng.uniform(-10, 10, (6, na))
    ks = np.array([1, 3, 8, 17, 32, 5], np.int32)
    inp = KNNInput(Params(n, len(ks), na), labels, data, ks, q)
    for precision, want in (("auto", "bf16x3"), ("bf16", "bf16")):
        del seen[:]
        if engine == "single":
            eng = SingleChipEngine(_cfg(precision))
            got = eng.run(inp)
        elif engine == "sharded":
            eng = ShardedEngine(EngineConfig(
                mode="sharded", select="extract", precision=precision,
                data_block=64))
            got = eng.run(inp)
        else:
            from dmlp_tpu.fleet.mesh_engine import MeshResidentEngine
            corpus = KNNInput(Params(n, 0, na), labels, data,
                              np.zeros(0, np.int32), np.zeros((0, na)))
            eng = MeshResidentEngine(
                corpus, EngineConfig(mode="sharded", select="extract",
                                     use_pallas=True, data_block=256,
                                     precision=precision),
                mesh_shape=(2, 1))
            assert eng._precision_plan == eng._active_prec() == want
            got = eng.solve_batch(q, ks)
        assert seen and set(seen) == {want}, (engine, precision, seen)
        assert eng.last_precision["active"] == want
        assert format_results(got) == format_results(knn_golden(inp))


def test_resident_engine_stats_name_the_form():
    """stats.engine.precision_plan / last_precision.active say which
    form every batch ran: three passes in exact mode, the one dot in
    fast mode (the benchmark's --control)."""
    rng = np.random.default_rng(12)
    n, na = 600, 5
    corpus = KNNInput(Params(n, 0, na),
                      rng.integers(0, 4, n).astype(np.int32),
                      rng.uniform(-10, 10, (n, na)),
                      np.zeros(0, np.int32), np.zeros((0, na)))
    q = rng.uniform(-10, 10, (5, na))
    ks = np.array([1, 3, 8, 17, 5], np.int32)
    for exact, want, passes in ((True, "bf16x3", 3), (False, "f32", 6)):
        eng = ResidentEngine(corpus, EngineConfig(
            select="extract", use_pallas=True, exact=exact))
        eng.solve_batch(q, ks)
        st = eng.bucket_stats()
        assert st["precision_plan"] == want
        assert st["last_precision"]["active"] == want
        assert st["last_precision"]["mxu_passes"] == passes


# -- rows staged in bfloat16: one pass, the "f32" form's own value (PR 39) ----

def test_dot_cross_on_a_bfloat16_block_is_one_default_dot():
    """A data block that arrives bfloat16 is contracted as it is, at
    the default MXU precision with float32 accumulation, against the
    float32 query block cast back (its values are bfloat16's): one
    ``convert_element_type`` and one dot, whatever the form's name."""
    import jax
    import jax.numpy as jnp
    from dmlp_tpu.ops.pallas_extract import PRECISIONS, _dot_cross
    for precision in PRECISIONS:
        for na in (64, 128, 960):
            eqns = jax.make_jaxpr(
                lambda q, d: _dot_cross(q, d, precision))(
                jnp.ones((8, na), jnp.float32),
                jnp.ones((256, na), jnp.bfloat16)).jaxpr.eqns
            assert [e.primitive.name for e in eqns] \
                == ["convert_element_type", "dot_general"], precision
            dot = eqns[1]
            assert [v.aval.dtype for v in dot.invars] \
                == [jnp.bfloat16] * 2
            assert [v.aval.shape[1] for v in dot.invars] == [na] * 2
            assert "HIGHEST" not in str(dot.params["precision"])
            assert dot.params["preferred_element_type"] == jnp.float32


def _staged_corpus(kind: str):
    """9216 rows of 16 attributes (past the switch to the extract
    path): tests/test_serve_retry.py's planted corpus (near-duplicates
    whose queries flag, a 600-row tie plateau, an integer grid that
    ties across the fold), or uniform reals in [0, 255)."""
    from tests.test_serve_retry import (CLUSTER, GRID, N, NA, planted_corpus,
                                        queries_at)
    corpus = planted_corpus()
    if kind == "tie_heavy":
        q = np.vstack([queries_at(corpus, CLUSTER, 1)[:4],
                       queries_at(corpus, GRID, 2)[:4]])
    else:
        rng = np.random.default_rng(390)
        corpus = KNNInput(
            Params(N, 0, NA), corpus.labels,
            rng.random((N, NA), dtype=np.float32).astype(np.float64) * 255,
            np.zeros(0, np.int32), np.zeros((0, NA)))
        q = rng.random((8, NA), dtype=np.float32).astype(np.float64) * 255
    return corpus, q


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kind", ["tie_heavy", "uniform"])
@pytest.mark.parametrize("engine", ["resident", "single"])
def test_staged_engines_answer_as_the_oracle_and_say_their_passes(
        engine, kind, dtype):
    """Under bfloat16 staging the kernel is handed the bf16 rows and
    spends ONE pass: the answers stay the float64 oracle's to the
    byte, flagged queries included (the device retry in a resident
    engine, the host repair in a batch one), and the variant stamp,
    the precision record, ``stats`` and the solve's span say 1; under
    float32 staging they say 3, the split form's."""
    from dmlp_tpu.obs import trace as obs_trace
    corpus, q = _staged_corpus(kind)
    ks = np.array([10, 1, 16, 10, 10, 3, 16, 10], np.int32)
    inp = KNNInput(Params(corpus.params.num_data, len(ks),
                          corpus.params.num_attrs),
                   corpus.labels, corpus.data_attrs, ks, q)
    cfg = EngineConfig(dtype=dtype, use_pallas=True)
    form, passes = ("f32", 1) if dtype == "bfloat16" else ("bf16x3", 3)
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        if engine == "resident":
            eng = ResidentEngine(corpus, cfg)
            got = eng.solve_batch(q, ks)
        else:
            eng = SingleChipEngine(cfg)
            got = eng.run(inp)
    finally:
        obs_trace.uninstall()
    gold = knn_golden(inp)
    assert format_results(got) == format_results(gold)
    assert_same_results(got, gold)
    assert eng._last_select == "extract"
    assert eng.last_precision["active"] == form
    assert eng.last_precision["mxu_passes"] == passes \
        == eng.last_variant["mxu_passes"]
    if engine == "resident":
        st = eng.bucket_stats()
        assert st["last_precision"]["mxu_passes"] == passes
        (solve,) = [e for e in tracer.events() if e.get("ph") == "X"
                    and e["name"] == "serve.solve_extract"]
        assert solve["args"]["mxu_passes"] == passes
        if kind == "tie_heavy":     # its planted queries flag
            assert st["repairs"]["device"] >= 2


# -- the inner-product score's bounds (PR 46) --------------------------------

def _stage(x: np.ndarray, staging: str) -> np.ndarray:
    """``x`` as the staging dtype holds it, in float64."""
    return _bf16(x) if staging == "bfloat16" \
        else x.astype(np.float32).astype(np.float64)


def test_ip_coefficients_are_their_derivations():
    """EPS_IP_REL is twice (2u + u^2) rounded up by (1 + 2^-8) and no
    further; ip_coef sums the cast, the accumulation and the form."""
    for staging, u in (("bfloat16", 2.0 ** -8), ("float32", 2.0 ** -24)):
        two_sided = 2 * (2 * u + u * u)
        assert two_sided <= finalize.EPS_IP_REL[staging] \
            <= two_sided * (1 + 2.0 ** -8)
    assert finalize.ip_coef("bfloat16", 200) == (
        finalize.EPS_IP_REL["bfloat16"] + finalize.EPS_CANCEL_COEF * 202)
    assert finalize.ip_coef("float32", 128, "bf16x3") == (
        finalize.EPS_IP_REL["float32"] + finalize.EPS_CANCEL_COEF * 130
        + finalize.LOWP_COEF["bf16x3"])
    # one bf16 pass casts again: the cast term, not LOWP_COEF's 2^-6
    assert finalize.ip_coef("float32", 8, "bf16") \
        - finalize.ip_coef("float32", 8) == finalize.EPS_IP_REL["bfloat16"]
    qn = np.array([4.0, 0.0])
    assert finalize.lowp_eps("f32", qn, 9.0, "ip").tolist() == [0.0, 0.0]
    assert finalize.staging_eps(None, qn, 9.0, "bfloat16", 200,
                                "ip").tolist() == [
        finalize.ip_coef("bfloat16", 200) * 6.0, 0.0]
    with pytest.raises(KeyError):
        finalize.lowp_eps("int8", qn, 9.0, "ip")


@pytest.mark.parametrize("staging", ["bfloat16", "float32"])
@pytest.mark.parametrize("na", [16, 200, 960])
@pytest.mark.parametrize("corpus", ["worst_cast", "orthogonal"])
def test_staging_eps_ip_bounds_the_cast_error(corpus, na, staging):
    """|q.x - q~.x~| in float64, q~ and x~ the operands as the staging
    dtype holds them, stays inside HALF of staging_eps' ip form (the
    coefficient covers two erring scores). ``worst_cast``: every
    component a hair under a rounding midpoint just above a power of
    two, all of one sign and the two vectors parallel, so that the
    errors add up and sum |q_a x_a| = |q||x|: the bound is reached to
    within a percent. ``orthogonal``: large norms, winners nearly
    orthogonal to the query, so the true scores (and their gaps) are
    tiny against |q||x|: a bound relative to the score would fail."""
    rng = np.random.default_rng(4600 + na)
    u = 2.0 ** -8 if staging == "bfloat16" else 2.0 ** -24
    if corpus == "worst_cast":
        e = rng.integers(-3, 4, (64, 1)).astype(np.float64)
        data = np.full((64, na), 1 + u * (1 - 2.0 ** -20)) * 2.0 ** e
        queries = np.full((8, na), 1 + u * (1 - 2.0 ** -20)) * 2.0
    else:
        queries = rng.normal(0, 300.0, (8, na))
        data = rng.normal(0, 300.0, (64, na))
        # project the query out of each row, leave 1e-4 u of it in
        for q in queries[:1]:
            data -= np.outer(data @ q, q) / (q @ q) * (1 - 1e-4 * u)
    err = np.abs(queries @ data.T - _stage(queries, staging)
                 @ _stage(data, staging).T)
    qn = np.einsum("ij,ij->i", queries, queries)
    dn_max = float(np.max(np.einsum("ij,ij->i", data, data)))
    half = finalize.staging_eps(None, qn, dn_max, staging, na, "ip") / 2
    assert np.all(err <= half[:, None]), (err / half[:, None]).max()
    if corpus == "worst_cast":
        # tight: the largest rows reach the cast term to a percent
        cast = finalize.EPS_IP_REL[staging] / 2 * np.sqrt(qn * dn_max)
        assert (err.max(axis=1) / cast).min() > 0.98
    else:
        # the true scores are nothing against the bound's scale
        assert np.abs(queries[0] @ data.T).max() < 1e-3 * half[0]


@pytest.mark.parametrize("gate", [False, True], ids=["two_pass", "fused"])
@pytest.mark.parametrize("staging,precision", [
    ("float32", "f32"), ("float32", "bf16x3"), ("float32", "bf16"),
    ("bfloat16", "f32")], ids=["f32_six", "f32_bf16x3", "f32_bf16",
                                "bf16_one"])
@pytest.mark.parametrize("na", [16, 200])
def test_ip_kernel_never_loses_a_winner_unflagged(na, staging, precision,
                                                  gate):
    """The interpreted KERNEL under ``score="ip"`` on the adversarial
    corpus (rows and queries of large norm, two hundred winners nearly
    orthogonal to their query: true scores and gaps ~1e-6 of |q||x|,
    far inside the pass's error, every other row anti-aligned): every
    candidate's device score within half the bound of its float64 score
    of the ORIGINAL values, and no row of the float64 top-k outside the
    candidate window unless the hazard test flags the query. (Here it
    flags nearly all of them, which is the point: the window cannot be
    trusted and the test says so.)"""
    import jax.numpy as jnp
    from dmlp_tpu.ops.pallas_extract import extract_topk
    rng = np.random.default_rng(4700 + na + gate)
    nq, n, k, kc = 16, 512, 4, 16
    queries = rng.normal(0, 40.0, (nq, na))
    data = -np.abs(rng.normal(0, 40.0, (n, na))) * np.sign(queries[0])
    q0 = queries[0]
    win = rng.normal(0, 40.0, (200, na))
    win -= np.outer(win @ q0, q0) / (q0 @ q0) * (1 - 1e-6)
    data[rng.choice(n - 12, 200, replace=False)] = win
    queries[1:] = q0 * rng.uniform(0.5, 2.0, (nq - 1, 1)) \
        + rng.normal(0, 1e-4, (nq - 1, na))
    sdt = ml_dtypes.bfloat16 if staging == "bfloat16" else np.float32
    od, oi, _ = extract_topk(
        jnp.asarray(queries.astype(sdt)), jnp.asarray(data.astype(sdt)),
        n_real=n - 12, kc=kc, interpret=True, mxu_gate=gate,
        precision=precision, score="ip")
    od, oi = np.asarray(od, np.float64), np.asarray(oi)
    assert (oi >= 0).all() and (oi < n - 12).all()
    true = -(queries @ data[:n - 12].T)            # ascending like od
    qn = np.einsum("ij,ij->i", queries, queries)
    dn_max = float(np.max(np.einsum("ij,ij->i", data[:n - 12],
                                    data[:n - 12])))
    eps = finalize.staging_eps(od.max(axis=1), qn, dn_max, staging, na,
                               "ip") \
        + finalize.lowp_eps(precision, qn, dn_max, "ip")
    got = np.take_along_axis(true, oi, axis=1)
    assert np.all(np.abs(od - got) <= eps[:, None] / 2), \
        (np.abs(od - got) / eps[:, None]).max()
    srt = np.sort(od, axis=1)
    flagged = finalize.boundary_hazard(srt[:, k - 1], srt[:, -1], eps)
    order = np.lexsort((-np.arange(n - 12)[None].repeat(nq, 0), true),
                       axis=1)[:, :k]
    lost = np.array([not set(order[j]) <= set(oi[j]) for j in range(nq)])
    assert not np.any(lost & ~flagged), np.nonzero(lost & ~flagged)
    assert flagged.sum() >= nq // 2     # the corpus does what it is for


# -- the cosine score's bounds (PR 49) ---------------------------------------

def _unit_adversary(na: int, u: float) -> np.ndarray:
    """A float64 vector of norm 1 (to an ulp) whose components, all but
    the last, sit a hair under a rounding midpoint just above a power
    of two of the dtype whose unit roundoff is ``u``, all of one sign:
    staging rounds every one of them DOWN by u of its value, the worst a
    cast can do, and a row and a query made of it are parallel, so that
    sum |q^_a x^_a| = |q^||x^| = 1. Two binades are mixed so that the
    squares add up to just under 1 at any width; the last component
    takes what is left (under 6 hundredths of the mass, 2 thousandths
    at the wide widths)."""
    import math
    e = math.floor(math.log2(na ** -0.5))        # 4^e <= 1/na < 4^(e+1)
    c = 1.0 + u * (1.0 - 2.0 ** -20)
    budget = 0.998 / (c * c) * 4.0 ** -e         # in units of 4^e
    n_hi = max(0, min(na - 1, int((budget - (na - 1)) // 3)))
    v = np.concatenate([np.full(n_hi, 2.0 ** (e + 1) * c),
                        np.full(na - 1 - n_hi, 2.0 ** e * c)])
    rest = 1.0 - float(np.sum(v * v))
    assert 0.0 < rest < 0.06
    return np.concatenate([v, [math.sqrt(rest)]])


def test_cosine_coefficients_are_ips_at_unit_operands():
    """Under "cosine" the scale is 1 whatever norms are handed in (0 for
    a zero query, or a corpus of zero rows), the coefficient ip's, and
    the normalisation's own rounding is added once."""
    qn = np.array([4.0e6, 0.0, 1e-30])
    for staging, na in (("bfloat16", 200), ("float32", 1536)):
        want = finalize.ip_coef(staging, na) \
            + finalize.COS_NORM_COEF * (na + 4)
        assert finalize.staging_eps(None, qn, 1.0, staging, na,
                                    "cosine").tolist() == [want, 0.0, want]
        assert finalize.staging_eps(None, qn, 0.0, staging, na,
                                    "cosine").tolist() == [0.0] * 3
    assert finalize.lowp_eps("bf16x3", qn, 1.0, "cosine").tolist() == [
        finalize.LOWP_COEF["bf16x3"], 0.0, finalize.LOWP_COEF["bf16x3"]]
    assert finalize.lowp_eps("bf16", qn, 1.0, "cosine").tolist() == [
        finalize.EPS_IP_REL["bfloat16"], 0.0,
        finalize.EPS_IP_REL["bfloat16"]]
    assert finalize.lowp_eps("f32", qn, 1.0, "cosine").tolist() == [0.0] * 3
    # (3A + 8) 2^-52, the two-sided count of the derivation, fits
    for na in (1, 8, 1536, 65536):
        assert (3 * na + 8) * 2.0 ** -52 \
            <= finalize.COS_NORM_COEF * (na + 4)
    # the cell's arithmetic: 1.16e-3 under float32 staging with the
    # split pass; under bfloat16 staging the engine runs the one-pass
    # "f32" form over the bf16 rows, which casts nothing again: 0.0168
    # (ISSUE 49 reckoned 0.033, the cast counted twice: the "bf16" form)
    f32 = finalize.ip_coef("float32", 1536, "bf16x3")
    b16 = finalize.ip_coef("bfloat16", 1536, "f32")
    twice = finalize.ip_coef("bfloat16", 1536, "bf16")
    assert 1.15e-3 < f32 < 1.17e-3 and 0.0167 < b16 < 0.0169
    assert 0.032 < twice < 0.034


@pytest.mark.parametrize("staging", ["bfloat16", "float32"])
@pytest.mark.parametrize("na", [1536, 200, 1000, 17])
def test_directed_unit_operands_reach_the_cosine_bound_and_never_pass_it(
        na, staging):
    """Rows and queries that normalise (in float64, as the engine
    stages them) to ``_unit_adversary``: every component is cast down by
    u, so the device's cosine of the staged operands is off the host's
    float64 cosine of the ORIGINAL rows by 2u + u^2 to within a percent:
    at least 0.9 of the cast term, and never more than half of
    staging_eps' cosine form (two erring scores), at the cell's width
    too."""
    from dmlp_tpu.golden.reference import cosine_of, row_norms
    u = 2.0 ** -8 if staging == "bfloat16" else 2.0 ** -24
    v = _unit_adversary(na, u)
    rng = np.random.default_rng(4900 + na)
    rows = v[None, :] * 2.0 ** rng.integers(-20, 21, (64, 1))
    queries = v[None, :] * 2.0 ** rng.integers(-20, 21, (8, 1))
    rn, qn = row_norms(rows), row_norms(queries)
    s = cosine_of(queries @ rows.T, qn[:, None], rn[None, :])
    assert np.all(np.abs(s - 1.0) < 1e-12)
    staged = _stage(queries / qn[:, None], staging) \
        @ _stage(rows / rn[:, None], staging).T
    err = np.abs(staged - s)
    half = finalize.staging_eps(None, qn * qn, 1.0, staging, na,
                                "cosine") / 2
    assert np.all(err <= half[:, None]), (err / half[:, None]).max()
    cast = finalize.EPS_IP_REL[staging] / 2
    assert (err / cast).min() >= 0.9, (err / cast).min()


@pytest.mark.parametrize("na", [3, 200, 1536])
def test_the_normalisation_term_bounds_what_normalising_first_changes(na):
    """q^ . x^ of operands normalised in float64 against the contract's
    s on the originals, norms over twelve decades: within half of
    COS_NORM_COEF * (A + 4) (the term covers two scores)."""
    from dmlp_tpu.golden.reference import cosine_of, row_norms
    rng = np.random.default_rng(4950 + na)
    rows = rng.normal(0, 1, (256, na)) * 10.0 ** rng.uniform(-6, 6, (256, 1))
    queries = rng.normal(0, 1, (32, na)) * 10.0 ** rng.uniform(-6, 6, (32, 1))
    rn, qn = row_norms(rows), row_norms(queries)
    s = cosine_of(np.einsum("qa,na->qn", queries, rows), qn[:, None],
                  rn[None, :])
    unit = np.einsum("qa,na->qn", queries / qn[:, None], rows / rn[:, None])
    assert np.abs(unit - s).max() <= finalize.COS_NORM_COEF * (na + 4) / 2
