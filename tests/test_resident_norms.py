"""The resident fold reads, it does not derive (PR 41), in interpret
mode on CPU: the kernel wrapper's stack form (a resident (nchunks, B, A)
stack, a chunk index in the grid's scalar prefetch, the rows' norms
handed in) against its block form (``stack[c]``, norms computed a
call), and the norms the two resident engines keep beside their stacks
through staging, ingest and the consistency repair's re-ingest.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.fleet.mesh_engine import MeshResidentEngine
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.ops.pallas_extract import extract_topk, row_norms
from dmlp_tpu.ops.pallas_fused import fused_topk, variant_stamp
from dmlp_tpu.serve.engine import ResidentEngine, _variant_args

CHUNKS, ROWS = 3, 512


def _stack(rng, na, dtype):
    """A seeded (CHUNKS, ROWS, na) stack of ``dtype`` and its staged
    norms, laid out as ``_update_chunk`` lays them out."""
    stack = jnp.asarray(rng.uniform(-4, 4, (CHUNKS, ROWS, na)), dtype)
    return stack, row_norms(stack)[:, None, :]


@pytest.mark.parametrize("na", [128, 1024])
@pytest.mark.parametrize("nq", [16, 1024])
@pytest.mark.parametrize("kc", [32, 120, 512])
@pytest.mark.parametrize("carried", [False, True], ids=["fresh", "carried"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_stack_form_with_staged_norms_is_the_block_form_to_the_bit(
        dtype, carried, kc, nq, na):
    """``extract_topk(q, stack, chunk=c, d_norms=norms)`` returns what
    ``extract_topk(q, stack[c])`` returns, every list slot and every
    iteration count, at a traced index and an eager one."""
    rng = np.random.default_rng(41 + kc + nq + na)
    stack, norms = _stack(rng, na, dtype)
    q = jnp.asarray(rng.uniform(-4, 4, (nq, na)), dtype)
    c, n_real, id_base = 2, ROWS - 37, 5 * ROWS
    precision = "bf16x3" if dtype == jnp.float32 else "f32"
    kw = dict(n_real=n_real, id_base=id_base, kc=kc, interpret=True,
              mxu_gate=True, precision=precision)
    carry = (None, None)
    if carried:
        od, oi, _ = extract_topk(q, stack[0], n_real=ROWS, id_base=0,
                                 **{k: kw[k] for k in (
                                     "kc", "interpret", "mxu_gate",
                                     "precision")})
        carry = (od, oi)
    want = extract_topk(q, stack[c], *carry, **kw)
    got = extract_topk(q, stack, *carry, chunk=c, d_norms=norms, **kw)
    # (arrays as arguments, as the fold has them: closed over, XLA
    # would fold the query norms as constants, in another order)
    traced = jax.jit(lambda q, stack, norms, carry, i: extract_topk(
        q, stack, *carry, chunk=i, d_norms=norms, **kw))(
            q, stack, norms, carry, jnp.int32(c))
    for w, g, t in zip(want, got, traced):
        assert np.asarray(w).tobytes() == np.asarray(g).tobytes()
        assert np.asarray(w).tobytes() == np.asarray(t).tobytes()


@pytest.mark.parametrize("norms", ["staged", "computed"])
@pytest.mark.parametrize("form", ["block", "stack"])
def test_either_data_form_takes_norms_or_computes_them(form, norms):
    """The four ways in are one kernel call: a block or a stack, norms
    given or computed from the chunk; a query panel of another dtype
    than the rows converts the chunk alone."""
    rng = np.random.default_rng(7)
    stack, staged = _stack(rng, 128, jnp.bfloat16)
    q = jnp.asarray(rng.uniform(-4, 4, (16, 128)), jnp.float32)
    kw = dict(n_real=ROWS, id_base=ROWS, kc=32, interpret=True)
    want = extract_topk(q, stack[1], **kw)
    data = dict(block=(stack[1], {}), stack=(stack, {"chunk": 1}))[form]
    given = {} if norms == "computed" else {
        "d_norms": staged[1, 0] if form == "block" else staged}
    got = extract_topk(q, data[0], **data[1], **given, **kw)
    for w, g in zip(want, got):
        assert np.asarray(w).tobytes() == np.asarray(g).tobytes()


def test_a_stack_without_an_index_and_a_block_with_one_are_refused():
    stack, _ = _stack(np.random.default_rng(0), 128, jnp.float32)
    q = stack[0, :16]
    with pytest.raises(ValueError, match="takes a chunk index"):
        extract_topk(q, stack, n_real=ROWS, kc=32, interpret=True)
    with pytest.raises(ValueError, match="takes a chunk index"):
        extract_topk(q, stack[0], n_real=ROWS, kc=32, interpret=True,
                     chunk=0)


def test_the_batch_engines_kernel_still_computes_its_norms():
    """``fused_topk`` (a chunk staged a call, nothing resident) keeps
    the block form, and what it says of itself names the norms
    "computed"; a resident engine's stamp says "staged"."""
    rng = np.random.default_rng(3)
    d = jnp.asarray(rng.integers(0, 50, (ROWS, 128)), jnp.float32)
    q = d[:16]
    od, oi, _ = fused_topk(q, d, n_real=ROWS, kc=32, interpret=True)
    assert np.array_equal(np.sort(np.asarray(od), 1)[:, 0], np.zeros(16))
    stamp = variant_stamp(32, ROWS, 16, 128)
    assert _variant_args(stamp)["norms"] == "computed"
    assert _variant_args({**stamp, "norms": "staged"})["norms"] == "staged"
    assert _variant_args(None) == {}


# -- the engines keep rows and norms in step ----------------------------------

NA = 24


def _corpus(n: int, seed: int) -> KNNInput:
    rng = np.random.default_rng(seed)
    return KNNInput(Params(n, 0, NA),
                    rng.integers(0, 6, n).astype(np.int32),
                    rng.uniform(-9, 9, (n, NA)),
                    np.zeros(0, np.int32), np.zeros((0, NA)))


def _single(staging: str):
    eng = ResidentEngine(
        _corpus(30000, 1),
        EngineConfig(use_pallas=True, select="extract", dtype=staging,
                     data_block=12800), capacity=51200)
    eng.solve_batch(eng._host_attrs[:3], np.full(3, 4, np.int32))
    return eng, eng._ex_nchunks, eng._ex_chunk_rows


def _mesh(staging: str):
    eng = MeshResidentEngine(
        _corpus(60000, 2),
        EngineConfig(mode="sharded", use_pallas=True, select="extract",
                     dtype=staging, data_block=12800),
        mesh_shape=(4, 1), capacity=102400)
    return eng, eng._nchunks, eng._chunk_rows


def _assert_norms_are_the_stacks(eng):
    """Every chunk's resident norms are ``sum(f32(stack) ** 2, -1)`` of
    the STAGED rows (to a float32 sum's reordering: the whole stack in
    one program here, a chunk a program in the engine); under bfloat16
    staging that is NOT the host rows' norm, which a bf16 rounding of
    every attribute moves a thousand times further."""
    stack = np.asarray(eng._chunks.astype(jnp.float32))
    norms = np.asarray(eng._norms)
    assert norms.dtype == np.float32
    assert norms.shape == stack.shape[:1] + (1,) + stack.shape[1:2]
    np.testing.assert_allclose(norms[:, 0], (stack ** 2).sum(-1),
                               rtol=1e-6, atol=0)
    assert norms.any()
    if eng._chunks.dtype == jnp.bfloat16:
        host = np.einsum("na,na->n", eng._host_attrs[:1000],
                         eng._host_attrs[:1000])
        rel = np.abs(norms.reshape(-1)[:1000] / host - 1)
        assert 1e-5 < rel.max() < 1e-2


@pytest.mark.parametrize("staging", ["float32", "bfloat16"])
@pytest.mark.parametrize("build", [_single, _mesh], ids=["one_chip", "mesh"])
def test_ingest_and_the_repair_re_ingest_keep_norms_and_rows_in_step(
        build, staging):
    eng, nchunks, cr = build(staging)
    assert eng.bucket_stats()["norm_restages"] == nchunks
    _assert_norms_are_the_stacks(eng)
    rng = np.random.default_rng(11)

    def touched(lo, hi):
        """Chunks an ingest of rows [lo, hi) restages: on one chip the
        chunks the range crosses; on the mesh chunk ``t`` holds every
        shard's ``t``-th piece, so the pieces' indices."""
        if isinstance(eng, ResidentEngine):
            return len(range(lo // cr, -(-hi // cr)))
        return len({(g % eng._shard_rows) // cr for g in range(lo, hi)})

    # an overwrite inside one chunk
    before = eng.norm_restages
    eng.ingest(rng.integers(0, 6, 50).astype(np.int32),
               rng.uniform(-9, 9, (50, NA)), start=100)
    assert eng.norm_restages - before == 1
    _assert_norms_are_the_stacks(eng)
    # an append that crosses into the next chunk
    n0 = eng.n_real
    m = 2 * cr - (n0 % cr) - 5 if isinstance(eng, ResidentEngine) else 700
    before = eng.norm_restages
    eng.ingest(rng.integers(0, 6, m).astype(np.int32),
               rng.uniform(-9, 9, (m, NA)))
    assert eng.norm_restages - before == touched(n0, n0 + m)
    _assert_norms_are_the_stacks(eng)
    # the consistency repair's re-ingest: a replica's own rows, fetched
    # by the ``corpus`` op's source and written back at their ids
    labels, rows = eng.corpus_slice(n0 - 20, 60)
    sig, before = eng.corpus_state()["checksum"], eng.norm_restages
    eng.ingest(labels, rows, start=n0 - 20)
    assert eng.corpus_state()["checksum"] == sig
    assert eng.norm_restages - before == touched(n0 - 20, n0 + 40)
    _assert_norms_are_the_stacks(eng)
    assert eng.bucket_stats()["norm_restages"] == eng.norm_restages
    # and the answers are the oracle's over the grown corpus
    from dmlp_tpu.golden.reference import knn_golden
    from dmlp_tpu.io.report import format_results
    q = rng.uniform(-9, 9, (5, NA))
    ks = np.full(5, 7, np.int32)
    grown = KNNInput(Params(eng.n_real, 5, NA),
                     eng._host_labels[:eng.n_real].copy(),
                     eng._host_attrs[:eng.n_real].copy(), ks, q)
    assert format_results(eng.solve_batch(q, ks)) \
        == format_results(knn_golden(grown))
