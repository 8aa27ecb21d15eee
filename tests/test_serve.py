"""Online serving layer (dmlp_tpu.serve): padding parity, compile-once,
ingestion, gate carry-over, admission control, batching, daemon e2e.

The load-bearing contract: every bucketed/padded micro-batch response
must be BYTE-IDENTICAL to the solo unpadded solve over the same corpus
and to the float64 golden oracle — fuzzed across power-of-two bucket
boundaries (nq and k straddling 8/16/32), with gate carry-over on and
off, before and after incremental ingestion.
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.io.report import format_results
from dmlp_tpu.obs import telemetry
from dmlp_tpu.serve import client as sc
from dmlp_tpu.serve import protocol
from dmlp_tpu.serve.admission import AdmissionController
from dmlp_tpu.serve.batching import MicroBatcher, Request
from dmlp_tpu.serve.daemon import ServeDaemon
from dmlp_tpu.serve.engine import (CapacityError, RequestShapeError,
                                   ResidentEngine, k_bucket, query_bucket,
                                   shape_bucket)


def make_corpus(n=600, na=5, labels=4, seed=3) -> KNNInput:
    rng = np.random.default_rng(seed)
    return KNNInput(Params(n, 0, na),
                    rng.integers(0, labels, n).astype(np.int32),
                    rng.uniform(-10, 10, (n, na)),
                    np.zeros(0, np.int32), np.zeros((0, na)))


def solo_and_golden(corpus: KNNInput, q, ks, config=None):
    inp = KNNInput(Params(corpus.params.num_data, len(ks),
                          corpus.params.num_attrs),
                   corpus.labels, corpus.data_attrs,
                   np.asarray(ks, np.int32), np.asarray(q, np.float64))
    solo = format_results(
        SingleChipEngine(config or EngineConfig()).run(inp))
    gold = format_results(knn_golden(inp))
    assert solo == gold
    return solo


# -- buckets ------------------------------------------------------------------

def test_shape_bucket_keying():
    assert [shape_bucket(b) for b in (0, 1, 2, 3)] == [1, 1, 2, 4]
    assert shape_bucket(12800) == shape_bucket(16000) == 16384
    assert shape_bucket(16384) == 16384 and shape_bucket(16385) == 32768
    assert shape_bucket(51200) == 65536


def test_shape_buckets_are_powers_of_two():
    assert [query_bucket(v) for v in (1, 7, 8, 9, 17)] == \
        [8, 8, 8, 16, 32]
    assert query_bucket(3, granule=128) == 128
    assert [k_bucket(v) for v in (1, 2, 3, 8, 9, 17)] == \
        [1, 2, 4, 8, 16, 32]


# -- padding parity (the tentpole's byte-identity contract) -------------------

def test_padding_parity_fuzz_across_bucket_boundaries():
    """nq and k straddling powers of two: every served batch equals the
    solo solve and the golden oracle byte-for-byte."""
    corpus = make_corpus()
    eng = ResidentEngine(corpus, EngineConfig())
    rng = np.random.default_rng(21)
    for nq in (1, 7, 8, 9, 15, 16, 17):
        for kmax in (1, 7, 8, 9, 16, 17):
            q = rng.uniform(-10, 10, (nq, corpus.params.num_attrs))
            ks = rng.integers(1, kmax + 1, nq).astype(np.int32)
            got = format_results(eng.solve_batch(q, ks))
            assert got == solo_and_golden(corpus, q, ks), \
                f"parity broke at nq={nq} kmax={kmax}"


def test_compile_once_per_bucket_and_no_request_recompilation():
    corpus = make_corpus()
    eng = ResidentEngine(corpus, EngineConfig())
    eng.warmup([(8, 8), (16, 8), (8, 16)])
    c0 = eng.compile_count
    rng = np.random.default_rng(5)
    for nq, k in [(3, 5), (8, 8), (12, 8), (5, 16), (8, 13)]:
        eng.solve_batch(rng.uniform(-10, 10, (nq, 5)),
                        np.full(nq, k, np.int32))
    assert eng.compile_count == c0, \
        "a warmed-bucket request recompiled"
    # a genuinely new bucket compiles exactly once
    eng.solve_batch(rng.uniform(-10, 10, (40, 5)),
                    np.full(40, 4, np.int32))
    assert eng.compile_count == c0 + 1
    eng.solve_batch(rng.uniform(-10, 10, (33, 5)),
                    np.full(33, 3, np.int32))  # same (q64, k4) bucket
    assert eng.compile_count == c0 + 1


def test_warmup_records_cold_start_and_dedups_buckets():
    eng = ResidentEngine(make_corpus(), EngineConfig())
    per = eng.warmup([(8, 8), (7, 7), (3, 5)])   # all one (q8, k8) bucket
    assert len(per) == 1 and eng.compile_count == 1
    assert eng.cold_start_compile_ms is not None \
        and eng.cold_start_compile_ms > 0
    assert eng.bucket_stats()["cold_start_compile_ms"] == \
        eng.cold_start_compile_ms


# -- incremental ingestion ----------------------------------------------------

def test_ingest_parity_and_no_solve_recompilation():
    corpus = make_corpus(n=500)
    eng = ResidentEngine(corpus, EngineConfig(), capacity=1024)
    rng = np.random.default_rng(9)
    q = rng.uniform(-10, 10, (6, 5))
    ks = np.full(6, 9, np.int32)
    eng.solve_batch(q, ks)
    c0 = eng.compile_count
    labels_all = corpus.labels
    attrs_all = corpus.data_attrs
    for m in (1, 7, 64):                        # straddle update buckets
        newl = rng.integers(0, 4, m).astype(np.int32)
        newa = rng.uniform(-10, 10, (m, 5))
        eng.ingest(newl, newa)
        labels_all = np.concatenate([labels_all, newl])
        attrs_all = np.vstack([attrs_all, newa])
        grown = KNNInput(Params(len(labels_all), 0, 5), labels_all,
                         attrs_all, np.zeros(0, np.int32),
                         np.zeros((0, 5)))
        got = format_results(eng.solve_batch(q, ks))
        assert got == solo_and_golden(grown, q, ks), \
            f"ingest parity broke at +{m} rows"
    assert eng.compile_count == c0, "ingestion recompiled a solve"
    assert eng.n_real == 500 + 1 + 7 + 64


def _append(corpus, rng):
    return (None, rng.integers(0, 4, 40).astype(np.int32),
            rng.uniform(-10, 10, (40, corpus.params.num_attrs)))


def _overwrite(scale):
    def rows(corpus, rng):
        """Writes over the rows that hold the largest norm (and their
        neighbours) with rows ``scale`` times their size."""
        a = corpus.data_attrs
        top = int(np.einsum("na,na->n", a, a).argmax())
        at = min(max(top - 3, 0), len(a) - 8)
        return (at, rng.integers(0, 4, 8).astype(np.int32),
                a[at:at + 8] * scale)
    return rows


@pytest.mark.parametrize("path", ["stream", "extract"])
@pytest.mark.parametrize("change,relation", [
    (None, "=="), (_append, "=="),
    (_overwrite(0.25), ">"), (_overwrite(3.0), "==")],
    ids=["load_only", "append", "overwrite_smaller", "overwrite_larger"])
def test_hazard_norm_is_the_resident_one_and_never_too_small(
        monkeypatch, path, change, relation):
    """The hazard test's corpus-wide scalar comes from the resident
    engine, not from a pass a batch: after a load or an append it is
    the float64 number a pass over the live rows gives; after an
    overwrite it may be larger (eps widens, answers do not move) and
    is never smaller. Served answers equal the solo solve's."""
    from dmlp_tpu.engine import single
    corpus = make_corpus(n=700, na=5, seed=12)
    config = extract_config() if path == "extract" else EngineConfig()
    eng = ResidentEngine(corpus, config, capacity=1024)
    rng = np.random.default_rng(44)
    labels, attrs = corpus.labels, corpus.data_attrs.copy()
    if change is not None:
        at, newl, newa = change(corpus, rng)
        eng.ingest(newl, newa, start=at)
        if at is None:
            labels = np.concatenate([labels, newl])
            attrs = np.vstack([attrs, newa])
        else:
            labels = labels.copy()
            labels[at:at + len(newl)] = newl
            attrs[at:at + len(newa)] = newa
    live = KNNInput(Params(len(labels), 0, 5), labels, attrs,
                    np.zeros(0, np.int32), np.zeros((0, 5)))
    used = []
    real_eps = single.staging_eps

    def spy(last, qn, dn_max, *rest):
        used.append(dn_max)
        return real_eps(last, qn, dn_max, *rest)
    q = attrs[np.linspace(0, len(attrs) - 1, 9).astype(int)]
    ks = rng.integers(1, 9, 9).astype(np.int32)
    with monkeypatch.context() as m:        # the served solve only
        m.setattr(single, "staging_eps", spy)
        got = format_results(eng.solve_batch(q, ks))
    true_max = float(np.einsum("na,na->n", attrs, attrs).max())
    assert len(used) == 1
    assert used[0] >= true_max
    assert (used[0] == true_max) if relation == "==" \
        else (used[0] > true_max)
    assert got == solo_and_golden(live, q, ks, config)


def test_ingest_capacity_error():
    eng = ResidentEngine(make_corpus(n=500), EngineConfig(),
                         capacity=512)
    with pytest.raises(CapacityError):
        eng.ingest(np.zeros(600, np.int32), np.zeros((600, 5)))
    # a failed ingest changes nothing
    assert eng.n_real == 500


def test_request_shape_cap():
    eng = ResidentEngine(make_corpus(n=100), EngineConfig(),
                         capacity=128)
    with pytest.raises(RequestShapeError):
        eng.solve_batch(np.zeros((2, 5)), np.full(2, 500, np.int32))


def test_k_beyond_corpus_rows_pads_with_sentinels_like_golden():
    """k in (n_real, capacity] is LEGAL: the reference contract pads
    with id = -1 sentinels when fewer than k candidates exist
    (common.cpp:66), and the golden oracle does the same — a served
    response must match it byte-for-byte, not get rejected."""
    corpus = make_corpus(n=100)
    eng = ResidentEngine(corpus, EngineConfig(), capacity=128)
    rng = np.random.default_rng(8)
    q = rng.uniform(-10, 10, (3, 5))
    ks = np.array([120, 100, 101], np.int32)
    got = eng.solve_batch(q, ks)
    assert got[0].neighbor_ids[-1] == -1          # sentinel tail
    assert format_results(got) == solo_and_golden(corpus, q, ks)


# -- extract path + cross-request gate warm-up --------------------------------

def extract_config():
    return EngineConfig(select="extract", use_pallas=True,
                        data_block=12800)


def test_extract_gate_carry_ab_byte_identical_and_golden():
    """Carry on vs off over multiple batches on the resident extract
    path: identical bytes, both equal to the golden oracle."""
    corpus = make_corpus(n=20000, na=4, seed=31)
    outs = {}
    for carry in (True, False):
        eng = ResidentEngine(corpus, extract_config(), gate_carry=carry)
        texts = []
        for i in range(3):
            rng = np.random.default_rng(400 + i)
            q = rng.uniform(-10, 10, (9, 4))
            ks = rng.integers(1, 9, 9).astype(np.int32)
            texts.append(format_results(eng.solve_batch(q, ks)))
            assert eng.last_extract_impl in ("fused", "extract")
        outs[carry] = texts
    assert outs[True] == outs[False]
    rng = np.random.default_rng(402)
    q = rng.uniform(-10, 10, (9, 4))
    ks = rng.integers(1, 9, 9).astype(np.int32)
    inp = KNNInput(Params(20000, 9, 4), corpus.labels,
                   corpus.data_attrs, ks, q)
    assert outs[True][2] == format_results(knn_golden(inp))


def test_gate_carry_hot_block_ordering_gates_cold_blocks(monkeypatch):
    """Non-vacuous warm-up proof on a norm-banded corpus: the winners
    live in the LAST chunk, so natural order folds them last (cold
    blocks never gate — they fold before any tight threshold exists),
    while carry-over folds the hot chunk first and the far bands gate
    out. Results stay byte-identical either way.

    Pruning is pinned OFF here: the two-stage prune (ops.summaries)
    would skip the far bands before the MXU gate ever sees them —
    exactly the layering this test isolates the gate FROM (the pruned
    composition has its own coverage in tests/test_prune.py)."""
    monkeypatch.setenv("DMLP_TPU_PRUNE", "0")
    rng = np.random.default_rng(55)
    n, na = 38400, 4                       # 3 extract chunks of 12800
    base = rng.uniform(-1.0, 1.0, (n, na))
    attrs = base.copy()
    attrs[:12800] += 600.0                 # far band (never wins)
    attrs[12800:25600] += 300.0            # middle band (never wins)
    corpus = KNNInput(Params(n, 0, na),
                      rng.integers(0, 4, n).astype(np.int32), attrs,
                      np.zeros(0, np.int32), np.zeros((0, na)))
    q = rng.uniform(-1.0, 1.0, (8, na))    # near the 3rd band
    ks = np.full(8, 5, np.int32)
    fracs, texts = {}, {}
    for carry in (True, False):
        eng = ResidentEngine(corpus, extract_config(), gate_carry=carry)
        t = [format_results(eng.solve_batch(q + 0.01 * i, ks))
             for i in range(2)]
        texts[carry] = t[0]
        fracs[carry] = eng.last_gated_fraction
    assert texts[True] == texts[False]
    # First batch teaches the histogram; the second folds the hot
    # (winning) chunk first, so both far bands gate out entirely.
    assert fracs[True] is not None and fracs[True] > 0.5
    assert fracs[True] > (fracs[False] or 0.0)


def test_extract_ingest_into_new_chunk_stays_golden():
    corpus = make_corpus(n=12800, na=4, seed=77)
    eng = ResidentEngine(corpus, extract_config(), capacity=25600)
    rng = np.random.default_rng(6)
    q = rng.uniform(-10, 10, (5, 4))
    ks = np.full(5, 6, np.int32)
    eng.solve_batch(q, ks)
    m = 200                                 # spills into chunk 2
    newl = rng.integers(0, 4, m).astype(np.int32)
    newa = rng.uniform(-10, 10, (m, 4))
    eng.ingest(newl, newa)
    grown = KNNInput(Params(12800 + m, 0, 4),
                     np.concatenate([corpus.labels, newl]),
                     np.vstack([corpus.data_attrs, newa]),
                     np.zeros(0, np.int32), np.zeros((0, 4)))
    got = format_results(eng.solve_batch(q, ks))
    assert got == solo_and_golden(grown, q, ks, extract_config())


# -- the one-program resident fold ---------------------------------------------

#: what changes between micro-batches, in the order the journey makes
#: them: (step, hand-set winner histogram, hand-set survivor mask,
#: ingest (start row, rows), the fold order the engine must then
#: schedule)
FOLD_STEPS = [
    ("natural", [0, 0, 0], None, None, [0, 1]),
    ("hot_first", [1, 5, 3], None, None, [1, 0]),
    ("survivors", [1, 5, 3], [False, True, True], None, [1]),
    ("restaged_chunk", [0, 0, 0], None, (100, 64), [0, 1]),
    ("appended_rows", [0, 0, 0], None, (None, 8000), [0, 1, 2]),
    ("hot_first_partial_last", [1, 5, 3], None, None, [1, 2, 0]),
]
FOLD_BUCKETS = {"q128": 5, "q1024": 1000}


def _reference_fold(eng, q_dev, order, kc, prec):
    """Today's fold as PR 27 ran it: the resolved kernel once a chunk
    from Python, first call with no carry, the gate's pair (visits
    that extracted nothing, visits that extracted at full width)
    counted eagerly."""
    from dmlp_tpu.ops import pallas_fused
    cr = eng._ex_chunk_rows
    kern, _ = pallas_fused.resolve_topk_kernel(
        q_dev.shape[0], cr, eng.num_attrs, kc, rung=eng._degrade_rung)
    od = oi = None
    gated = wide = tiles = 0
    for c in order:
        od, oi, its, wd = kern(q_dev, eng._chunks[c], od, oi,
                               n_real=min(eng.n_real - c * cr, cr),
                               id_base=c * cr, kc=kc,
                               interpret=eng._interpret, precision=prec,
                               with_wide=True)
        gated += int(np.count_nonzero(np.asarray(its) == 0))
        wide += int(np.asarray(wd).sum())
        tiles += its.size
    return np.array(od), np.array(oi), [gated, wide], tiles


@pytest.fixture(scope="module")
def fold_journey():
    """One engine, two warm buckets, FOLD_STEPS in order; a micro-batch
    a bucket a step. Records what the engine's one program was given
    and gave, what the chunk-by-chunk reference gives for the same
    order on the same chunks, and the compile counters."""
    from dmlp_tpu.serve import engine as se
    calls = []

    class Recording(ResidentEngine):
        def _fold_resident(self, q_dev, order, impl, kc, prec):
            out = super()._fold_resident(q_dev, order, impl, kc, prec)
            calls.append((q_dev, list(order), kc, prec, out))
            return out

    corpus = make_corpus(n=20000, na=4, seed=91)   # 12800 + 7200 + 0 rows
    eng = Recording(corpus, extract_config())
    assert eng._ex_nchunks == 3 and eng._ex_chunk_rows == 12800
    eng.warmup([(nq, 6) for nq in FOLD_BUCKETS.values()])
    calls.clear()
    warm = (eng.compile_count, se._fold_stack._cache_size())
    rng = np.random.default_rng(92)
    seen = {}
    for step, hits, survivors, ingest, _want in FOLD_STEPS:
        if ingest is not None:
            start, m = ingest
            eng.ingest(rng.integers(0, 4, m).astype(np.int32),
                       rng.uniform(-10, 10, (m, 4)), start=start)
        # (an instance attribute over the method; popped to restore it)
        eng.__dict__.pop("_prune_survivors", None)
        if survivors is not None:
            eng._prune_survivors = (
                lambda inp, entry, q_dev, keep=np.asarray(survivors): (
                    keep, {"blocks_total": 3,
                           "blocks_pruned": int((~keep).sum())}))
        for bucket, nq in FOLD_BUCKETS.items():
            eng._block_hits[:] = hits
            eng.solve_batch(rng.uniform(-10, 10, (nq, 4)),
                            rng.integers(1, 7, nq).astype(np.int32))
            q_dev, order, kc, prec, (od, oi, gated, tiles) = calls.pop()
            assert not calls
            seen[step, bucket] = {
                "order": order, "n_real": eng.n_real,
                # copies: a view would keep the device array alive
                "got": (np.array(od), np.array(oi),
                        np.asarray(gated).tolist(), tiles),
                "want": _reference_fold(eng, q_dev, order, kc, prec),
                "counters": (eng.compile_count,
                             se._fold_stack._cache_size())}
    return {"warm": warm, "steps": seen}


@pytest.mark.parametrize("bucket", sorted(FOLD_BUCKETS))
@pytest.mark.parametrize("step,want_order",
                         [(s[0], s[4]) for s in FOLD_STEPS])
def test_one_program_fold_equals_the_chunk_loop_bit_for_bit(
        fold_journey, step, want_order, bucket):
    rec = fold_journey["steps"][step, bucket]
    assert rec["order"] == want_order
    (od, oi, gated, tiles), (rod, roi, rgated, rtiles) = \
        rec["got"], rec["want"]
    assert od.dtype == rod.dtype == np.float32 and od.shape == rod.shape
    # bit for bit: the running lists themselves, not just the answers
    assert od.tobytes() == rod.tobytes()
    assert oi.tobytes() == roi.tobytes()
    # the gate gauges: as many tiles gated and as many at full width,
    # of as many visited
    assert (gated, tiles) == (rgated, rtiles)
    assert 0 <= gated[1] <= tiles - gated[0]


@pytest.mark.parametrize("step", [s[0] for s in FOLD_STEPS])
def test_fold_schedule_and_ingest_never_recompile(fold_journey, step):
    """A new order, fewer survivors, a restaged chunk, more rows: the
    same executable (bucket builds and the program's jit cache)."""
    for bucket in FOLD_BUCKETS:
        assert fold_journey["steps"][step, bucket]["counters"] \
            == fold_journey["warm"]


def test_appended_rows_reached_the_fold(fold_journey):
    steps = fold_journey["steps"]
    assert steps["restaged_chunk", "q128"]["n_real"] == 20000
    assert steps["appended_rows", "q128"]["n_real"] == 28000


def test_wide_k_sweeps_the_resident_stack_and_stays_golden():
    """k past the kernel's window over SEVERAL resident chunks: pass 1
    is the one-program fold, the later passes sweep the same stack as
    one array, and nothing else of the corpus's size is on the device."""
    import jax
    corpus = make_corpus(n=20000, na=4, seed=93)
    size = 3 * 12800 * 4 * 4                 # one copy of the stack
    before = {id(a) for a in jax.live_arrays() if a.nbytes >= size}
    eng = ResidentEngine(corpus, extract_config())
    eng.warmup([(2, 600)])
    cc = eng.compile_count
    assert eng.bucket_stats()["paths"]["q128k1024"] == "multipass"
    rng = np.random.default_rng(94)
    q = rng.uniform(-10, 10, (2, 4))
    ks = np.asarray([520, 600], np.int32)
    got = format_results(eng.solve_batch(q, ks))
    assert got == solo_and_golden(corpus, q, ks, extract_config())
    assert eng.last_mp_passes > 1 and eng.compile_count == cc
    assert not hasattr(eng, "_mp_full")
    assert eng._chunks.nbytes == size
    mine = {id(a) for a in jax.live_arrays() if a.nbytes >= size} - before
    assert mine <= {id(eng._chunks), id(eng._d_attrs)}


# -- admission control --------------------------------------------------------

def test_admission_memory_budget_sheds_before_solve():
    eng = ResidentEngine(make_corpus(), EngineConfig())
    adm = AdmissionController(eng, budget_bytes=1)   # everything over
    d = adm.decide(4, 4, queued_queries=0)
    assert d["verdict"] == "reject" and d["reason"] == "memory"
    adm2 = AdmissionController(eng, budget_bytes=1 << 40)
    assert adm2.decide(4, 4, 0)["verdict"] == "accept"
    assert adm2.headroom_bytes() < (1 << 40)   # model priced in


def test_admission_prices_the_coalesced_batch_not_the_lone_request():
    """64 small admits must not OOM as one coalesced micro-batch: the
    memory check prices min(queued + nq, batch cap) at the queue's
    running kmax, so the budget that admits a lone request refuses the
    same request once the queue it would join is deep."""
    eng = ResidentEngine(make_corpus(), EngineConfig())
    lone = AdmissionController(eng, batch_queries_cap=512)
    lone_need = lone.batch_bytes(8, 4)
    coalesced_need = lone.batch_bytes(512, 4)
    assert coalesced_need > lone_need
    budget = lone._resident_model_bytes() + lone_need + 1
    adm = AdmissionController(eng, budget_bytes=budget,
                              batch_queries_cap=512)
    assert adm.decide(8, 4, queued_queries=0)["verdict"] == "accept"
    d = adm.decide(8, 4, queued_queries=504, queued_kmax=4)
    assert d["verdict"] == "reject" and d["reason"] == "memory"


def test_warmup_honors_k_above_corpus_rows():
    """An explicit warm bucket with n_real < k <= capacity must warm
    THAT k-bucket (k > n_real is a served shape), so the first real
    wide-k request finds it compiled."""
    eng = ResidentEngine(make_corpus(n=100), EngineConfig(),
                         capacity=1024)
    eng.warmup([(4, 512)])
    c0 = eng.compile_count
    rng = np.random.default_rng(3)
    eng.solve_batch(rng.uniform(-10, 10, (4, 5)),
                    np.full(4, 400, np.int32))   # same (q8, k512) bucket
    assert eng.compile_count == c0, \
        "warm-up silently warmed a smaller k-bucket"


def test_admission_rejects_shapes_queue_and_draining():
    eng = ResidentEngine(make_corpus(), EngineConfig())
    adm = AdmissionController(eng, max_queue_queries=10,
                              max_request_queries=8, max_k=16)
    assert adm.decide(9, 4, 0)["reason"] == "shape"
    assert adm.decide(2, 17, 0)["reason"] == "k_too_large"
    assert adm.decide(4, 4, 8)["reason"] == "queue_full"
    adm.draining = True
    assert adm.decide(1, 1, 0)["reason"] == "draining"


def test_admission_injected_squeeze_sheds_without_ladder(monkeypatch):
    from dmlp_tpu.resilience import inject as rs_inject
    from dmlp_tpu.resilience import stats as rs_stats
    rs_stats.reset()
    eng = ResidentEngine(make_corpus(), EngineConfig())
    adm = AdmissionController(eng)
    sched = rs_inject.FaultSchedule.from_dict(
        {"schema": 1, "seed": 0, "faults": [
            {"site": "serve.admit", "kind": "oom", "times": 1}]})
    rs_inject.install(sched)
    try:
        d = adm.decide(2, 2, 0)
        assert d["verdict"] == "reject" \
            and d["reason"] == "injected_squeeze"
        assert adm.decide(2, 2, 0)["verdict"] == "accept"  # once only
    finally:
        rs_inject.uninstall()
    assert rs_stats.snapshot().get("degradations") == []
    assert telemetry.registry().counter("serve.rejected").value(
        label="injected_squeeze") >= 1


# -- micro-batching -----------------------------------------------------------

def test_batcher_coalesces_and_slices_per_request():
    corpus = make_corpus()
    eng = ResidentEngine(corpus, EngineConfig())
    adm = AdmissionController(eng)
    b = MicroBatcher(eng, adm, max_batch_queries=64, tick_s=0.02)
    rng = np.random.default_rng(13)
    reqs = []
    for i in range(5):
        nq = int(rng.integers(1, 7))
        reqs.append(Request(
            kind="query", req_id=str(i),
            query_attrs=rng.uniform(-10, 10, (nq, 5)),
            ks=rng.integers(1, 9, nq).astype(np.int32)))
    b.start()
    try:
        for r in reqs:
            assert b.submit(r)["verdict"] == "accept"
        for r in reqs:
            assert r.done.wait(timeout=120)
    finally:
        b.stop(drain=True)
    assert b.batches < len(reqs), "nothing coalesced"
    for r in reqs:
        assert r.error is None
        got = format_results(r.results)
        assert got == solo_and_golden(corpus, r.query_attrs, r.ks), \
            f"sliced-out request {r.req_id} differs from solo solve"


def test_batcher_drain_finishes_queued_work():
    eng = ResidentEngine(make_corpus(), EngineConfig())
    b = MicroBatcher(eng, AdmissionController(eng), tick_s=0.0)
    rng = np.random.default_rng(2)
    reqs = [Request(kind="query", req_id=str(i),
                    query_attrs=rng.uniform(-10, 10, (2, 5)),
                    ks=np.full(2, 3, np.int32)) for i in range(4)]
    for r in reqs:
        b.submit(r)
    b.start()
    b.stop(drain=True)
    assert all(r.done.is_set() and r.error is None for r in reqs)


def test_batcher_serve_solve_injection_site():
    """The ``serve.solve`` straggler site: a delay fault slows the
    consumer WITHOUT changing answers (the slo_smoke capacity lever);
    a transient fault fails the whole batch visibly and the batcher
    survives it."""
    import time as _time

    from dmlp_tpu.resilience import inject
    from dmlp_tpu.resilience.inject import FaultSchedule

    corpus = make_corpus()
    eng = ResidentEngine(corpus, EngineConfig())
    b = MicroBatcher(eng, AdmissionController(eng), tick_s=0.0)
    rng = np.random.default_rng(7)

    def mkreq(i: int) -> Request:
        return Request(kind="query", req_id=f"inj{i}",
                       query_attrs=rng.uniform(-10, 10, (2, 5)),
                       ks=np.full(2, 3, np.int32))

    b.start()
    try:
        inject.install(FaultSchedule.from_dict(
            {"schema": 1, "seed": 1, "faults": [
                {"site": "serve.solve", "kind": "delay", "ms": 120,
                 "times": 10, "prob": 1.0}]}))
        r = mkreq(0)
        t0 = _time.perf_counter()
        assert b.submit(r)["verdict"] == "accept"
        assert r.done.wait(timeout=120)
        assert r.error is None
        assert _time.perf_counter() - t0 >= 0.12, \
            "delay fault did not slow the batch"
        assert format_results(r.results) == solo_and_golden(
            corpus, r.query_attrs, r.ks), \
            "delay fault perturbed the answers"

        inject.install(FaultSchedule.from_dict(
            {"schema": 1, "seed": 1, "faults": [
                {"site": "serve.solve", "kind": "transient",
                 "times": 1, "prob": 1.0}]}))
        errs0 = telemetry.registry().counter(
            "serve.batch_errors").value()
        r2 = mkreq(1)
        assert b.submit(r2)["verdict"] == "accept"
        assert r2.done.wait(timeout=120)
        assert r2.error is not None \
            and "Injected" in r2.error
        assert telemetry.registry().counter(
            "serve.batch_errors").value() == errs0 + 1

        r3 = mkreq(2)        # the schedule is spent: service resumes
        assert b.submit(r3)["verdict"] == "accept"
        assert r3.done.wait(timeout=120)
        assert r3.error is None
        assert format_results(r3.results) == solo_and_golden(
            corpus, r3.query_attrs, r3.ks)
    finally:
        b.stop(drain=True)
        inject.uninstall()


# -- protocol -----------------------------------------------------------------

def test_protocol_parse_and_errors():
    req = protocol.parse_request(
        json.dumps({"op": "query", "id": "a", "k": 3,
                    "queries": [[1, 2], [3, 4]]}), 2)
    assert req.kind == "query" and req.nq == 2 \
        and list(req.ks) == [3, 3]
    ctl = protocol.parse_request('{"op": "stats"}', 2)
    assert isinstance(ctl, dict)
    for bad in ('{"op": "query"}',
                '{"op": "query", "queries": [[1]]}',        # na mismatch
                '{"op": "query", "k": 0, "queries": [[1, 2]]}',
                '{"op": "query", "ks": [1], "queries": [[1, 2], [3, 4]]}',
                '{"op": "ingest", "rows": [[1, 2]]}',
                'not json', '[1]', '{"op": "wat"}'):
        with pytest.raises(protocol.ProtocolError):
            protocol.parse_request(bad, 2)


# -- daemon end to end (in-process, real sockets) -----------------------------

def test_daemon_end_to_end_replay_ingest_stats_drain():
    corpus = make_corpus(n=800, seed=41)
    d = ServeDaemon(corpus, EngineConfig(), port=0,
                    warm_buckets=[(8, 8), (16, 8)])
    d.start()
    try:
        header = {"serve_trace_schema": 1,
                  "corpus": {"num_attrs": 5, "min_attr": -10,
                             "max_attr": 10}}
        reqs = [{"nq": 1 + (i % 4), "k": 1 + (i % 6), "seed": 800 + i}
                for i in range(8)]
        res = sc.replay(d.port, header, reqs, connections=3)
        assert all(r["ok"] for r in res)
        golden = sc.golden_reference(corpus, header, reqs)
        assert sc.contract_text([r["checksums"] for r in res]) == \
            sc.contract_text(golden)
        cli = sc.ServeClient(d.port)
        st = cli.stats()["stats"]
        assert st["requests_completed"] >= 8
        assert st["engine"]["compile_count"] == d.engine.compile_count
        # wire ingestion + grown-corpus parity
        rng = np.random.default_rng(1)
        newa = rng.uniform(-10, 10, (3, 5))
        r = cli.ingest([0, 1, 2], newa)
        assert r["ok"] and r["corpus_rows"] == 803
        grown = KNNInput(
            Params(803, 0, 5),
            np.concatenate([corpus.labels,
                            np.array([0, 1, 2], np.int32)]),
            np.vstack([corpus.data_attrs, newa]),
            np.zeros(0, np.int32), np.zeros((0, 5)))
        res2 = sc.replay(d.port, header, reqs[:3], connections=2)
        assert [r["checksums"] for r in res2] == \
            sc.golden_reference(grown, header, reqs[:3])
        # malformed line leaves the connection usable
        bad = cli.call({"op": "query"})
        assert not bad["ok"] and "queries" in bad["error"]
        assert cli.stats()["ok"]
        # in-band drain: a request already queued when the drain
        # lands must still get its response before shutdown
        # (the drain waits for handler threads to write).
        late = sc.ServeClient(d.port)
        assert cli.drain()["draining"]
        cli.close()
        t = threading.Thread(target=d.run_until_drained, daemon=True)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive(), "drain hung"
        assert d._inflight == 0
        late.close()
    finally:
        if not d._drain_event.is_set():
            d.close()


def test_daemon_rejections_surface_as_protocol_errors():
    corpus = make_corpus(n=300)
    d = ServeDaemon(corpus, EngineConfig(), port=0, max_k=4,
                    warm_buckets=[(1, 1)])
    d.start()
    try:
        cli = sc.ServeClient(d.port)
        r = cli.query(np.zeros((1, 5)), k=99)
        assert not r["ok"] and "k_too_large" in r["error"]
        r = cli.query(np.zeros((1, 5)), k=2)
        assert r["ok"]
        cli.close()
    finally:
        d.close()


def test_daemon_serve_record_loads_as_runrecord(tmp_path):
    rec = tmp_path / "SERVE_TEST.jsonl"
    corpus = make_corpus(n=300)
    d = ServeDaemon(corpus, EngineConfig(), port=0,
                    record_path=str(rec), warm_buckets=[(1, 1)])
    d.start()
    try:
        cli = sc.ServeClient(d.port)
        assert cli.query(np.zeros((2, 5)), k=3)["ok"]
        cli.close()
    finally:
        d.drain()
    from dmlp_tpu.obs.run import RunRecord
    back = RunRecord.load(str(rec))
    assert back.kind == "serve"
    assert "cold_start_compile_ms" in back.metrics
    assert "requests_per_sec" in back.metrics
    assert (back.tool, back.device) == ("dmlp_tpu.serve", "cpu")


# -- concurrent serving: parallel query + ingest + drain ----------------------


def test_concurrent_query_ingest_drain_parity():
    """Parallel query, ingest, and drain connections against ONE
    daemon: every served request's checksums must equal the solo
    solve/golden oracle (today's other daemon tests serialize their
    requests). Ingested rows sit FAR outside the query envelope, so
    the original-corpus oracle is exact under any interleaving — the
    batcher's one consumer thread serializes corpus mutation against
    solves, and this test is the proof."""
    corpus = make_corpus(n=800, seed=17)
    header = {"serve_trace_schema": 1,
              "corpus": {"num_attrs": 5, "min_attr": -10,
                         "max_attr": 10}}
    wave1 = [{"nq": 1 + (w * 5 + i) % 6, "k": 1 + (w + i) % 6,
              "seed": 9000 + w * 100 + i}
             for w in range(3) for i in range(6)]
    wave2 = [{"nq": 2, "k": 3, "seed": 9900 + i} for i in range(6)]
    golden1 = sc.golden_reference(corpus, header, wave1)
    golden2 = sc.golden_reference(corpus, header, wave2)
    d = ServeDaemon(corpus, EngineConfig(), port=0, tick_s=0.001,
                    warm_buckets=[(8, 8), (16, 8)])
    d.start()
    errors, results = [], {}
    try:
        # -- wave 1: 3 query workers + 1 ingest worker, fully parallel
        def query_worker(w):
            try:
                cli = sc.ServeClient(d.port)
                try:
                    for i in range(6):
                        idx = w * 6 + i
                        req = wave1[idx]
                        r = cli.query(
                            sc.materialize_queries(req, header),
                            ks=[int(v) for v in
                                sc.request_ks(req)],
                            req_id=str(idx))
                        results[idx] = r
                finally:
                    cli.close()
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(f"worker {w}: {e}")

        def ingest_worker():
            try:
                rng = np.random.default_rng(3)
                cli = sc.ServeClient(d.port)
                try:
                    for _ in range(4):
                        rows = 1e6 + rng.uniform(0, 1, (3, 5))
                        r = cli.ingest([0, 1, 2], rows)
                        if not r.get("ok"):
                            errors.append(f"ingest: {r}")
                finally:
                    cli.close()
            except Exception as e:  # pragma: no cover
                errors.append(f"ingest: {e}")

        threads = [threading.Thread(target=query_worker, args=(w,),
                                    daemon=True) for w in range(3)]
        threads.append(threading.Thread(target=ingest_worker,
                                        daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), "wave 1 hung"
        assert not errors, errors
        for idx, want in enumerate(golden1):
            r = results[idx]
            assert r.get("ok"), f"request {idx} failed: {r}"
            assert r["checksums"] == want, \
                f"request {idx} diverged from the solo solve"
        assert d.engine.n_real == 800 + 4 * 3

        # -- wave 2: more queries RACING an in-band drain; every
        # response is either correct or an explicit draining rejection,
        # and queued work still completes (the drain contract)
        out2 = {}

        def late_worker(i):
            try:
                cli = sc.ServeClient(d.port)
                try:
                    req = wave2[i]
                    out2[i] = cli.query(
                        sc.materialize_queries(req, header),
                        ks=[int(v) for v in sc.request_ks(req)],
                        req_id=f"late{i}")
                finally:
                    cli.close()
            except (ConnectionError, OSError):
                # A connection the daemon never ACCEPTED can be reset
                # by the drain — a legal shed, distinct from losing an
                # admitted request's response (which the drain must
                # never do, asserted below).
                out2[i] = {"ok": False, "error": "rejected: draining "
                                                 "(connection reset)"}
            except Exception as e:  # pragma: no cover
                errors.append(f"late {i}: {e}")

        drainer = sc.ServeClient(d.port)
        late = [threading.Thread(target=late_worker, args=(i,),
                                 daemon=True) for i in range(6)]
        for t in late:
            t.start()
        assert drainer.drain()["draining"]
        drainer.close()
        runner = threading.Thread(target=d.run_until_drained,
                                  daemon=True)
        runner.start()
        for t in late:
            t.join(timeout=300)
        runner.join(timeout=300)
        assert not runner.is_alive(), "drain hung under load"
        assert not errors, errors
        served = 0
        for i, r in sorted(out2.items()):
            if r.get("ok"):
                served += 1
                assert r["checksums"] == golden2[i], \
                    f"late request {i} diverged during drain"
            else:
                assert "draining" in r.get("error", ""), r
        assert d._inflight == 0
        # the drain waited for every accepted request's response
        assert served + sum(1 for r in out2.values()
                            if not r.get("ok")) == len(wave2)
    finally:
        if not d._drain_event.is_set():
            d.close()


# -- telemetry drain hook (the PR 9 SIGTERM clean-drain satellite) ------------

def test_sigterm_drain_hook_skips_flight_dump(tmp_path):
    sess = telemetry.start(path=str(tmp_path / "t.prom"),
                           handle_signals=False)
    try:
        fired = []
        sess.set_sigterm_drain(lambda: fired.append(1))
        sess._on_sigterm(15, None)
        assert fired == [1]
        assert not sess.flight.dumped, \
            "drain-hook SIGTERM must not dump a flight artifact"
        events = [e["name"] for e in sess.flight.events()]
        assert "sigterm_drain" in events
    finally:
        sess.set_sigterm_drain(None)
        sess.close()


# -- serve metric names pass the R6 static contract ---------------------------

def test_serve_metric_names_pass_r6():
    import os

    from dmlp_tpu.check.analyzer import analyze_paths
    pkg = os.path.join(os.path.dirname(__file__), "..", "dmlp_tpu",
                       "serve")
    findings = [f for f in analyze_paths([os.path.abspath(pkg)])
                if f.rule.startswith("R6")]
    assert findings == [], [str(f) for f in findings]


# -- memwatch serve model -----------------------------------------------------

def test_serve_memwatch_model_terms_hand_computed():
    from dmlp_tpu.obs import memwatch
    m = memwatch.resident_bytes_model(
        "serve", capacity_rows=1024, na=8, staging="float32",
        qpad=16, kcap=24, extract_chunks=2, chunk_rows=512)
    t = m["terms"]
    assert t["resident_corpus"] == 1024 * 8 * 4
    assert t["labels_ids"] == 1024 * 8
    assert t["extract_chunks"] == 2 * 512 * 8 * 4
    assert t["query_blocks"] == 16 * 8 * 4
    assert t["topk_carries"] == 2 * 16 * 24 * 12
    assert m["total_bytes"] == sum(t.values())
    eng = ResidentEngine(make_corpus(), EngineConfig())
    live = memwatch.model_for_engine(
        eng, eng._batch_input(np.zeros((4, 5)), np.full(4, 3, np.int32)))
    assert live["kind"] == "serve" \
        and live["terms"]["resident_corpus"] > 0
