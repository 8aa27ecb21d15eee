"""Sharded/ring engines on the virtual 8-device CPU mesh vs the golden model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.sharded import RingEngine, ShardedEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.datagen import generate_input_text
from dmlp_tpu.io.grammar import KNNInput, Params, parse_input_text
from dmlp_tpu.parallel.mesh import balanced_dims, make_mesh

from test_engine_single import assert_same_results


def needs_devices(n):
    return pytest.mark.skipif(len(jax.devices()) < n,
                              reason=f"needs {n} devices")


def test_balanced_dims():
    assert balanced_dims(8) == (4, 2)
    assert balanced_dims(24) == (6, 4)
    assert balanced_dims(1) == (1, 1)
    assert balanced_dims(7) == (7, 1)


@needs_devices(8)
@pytest.mark.parametrize("shape", [(4, 2), (2, 4), (8, 1), (1, 8)])
def test_sharded_matches_golden(shape):
    text = generate_input_text(230, 33, 6, -5, 5, 1, 11, 4, seed=17)
    inp = parse_input_text(text)
    eng = ShardedEngine(EngineConfig(mode="sharded", data_block=16),
                        mesh=make_mesh(shape))
    assert_same_results(eng.run(inp), knn_golden(inp))


@needs_devices(8)
def test_ring_matches_golden_and_allgather():
    text = generate_input_text(150, 21, 5, -2, 2, 1, 9, 3, seed=23)
    inp = parse_input_text(text)
    ring = RingEngine(EngineConfig(mode="ring", data_block=8),
                      mesh=make_mesh((4, 2)))
    got = ring.run(inp)
    assert_same_results(got, knn_golden(inp))
    ag = ShardedEngine(EngineConfig(mode="sharded", data_block=8),
                       mesh=make_mesh((4, 2)))
    assert_same_results(got, ag.run(inp))


@needs_devices(8)
def test_sharded_tiny_uneven_input():
    # num_data < number of data shards exercises all-sentinel shards.
    text = generate_input_text(3, 5, 2, 0, 1, 1, 3, 2, seed=4)
    inp = parse_input_text(text)
    for cls, mode in ((ShardedEngine, "sharded"), (RingEngine, "ring")):
        eng = cls(EngineConfig(mode=mode), mesh=make_mesh((4, 2)))
        assert_same_results(eng.run(inp), knn_golden(inp))


@needs_devices(8)
def test_sharded_ties_integer_attrs_fast_mode():
    rng = np.random.default_rng(7)
    data = rng.integers(0, 4, size=(64, 3)).astype(np.float64)
    queries = rng.integers(0, 4, size=(16, 3)).astype(np.float64)
    labels = rng.integers(0, 3, size=64).astype(np.int32)
    ks = rng.integers(1, 20, size=16).astype(np.int32)
    inp = KNNInput(Params(64, 16, 3), labels, data, ks, queries)
    for cls in (ShardedEngine, RingEngine):
        eng = cls(EngineConfig(mode="sharded" if cls is ShardedEngine else "ring",
                               exact=False, data_block=8),
                  mesh=make_mesh((4, 2)))
        assert_same_results(eng.run(inp), knn_golden(inp), check_dists=False)


def test_sharded_single_device_mesh():
    text = generate_input_text(40, 6, 3, 0, 1, 1, 5, 2, seed=6)
    inp = parse_input_text(text)
    eng = ShardedEngine(EngineConfig(mode="sharded"),
                        mesh=make_mesh((1, 1), devices=jax.devices()[:1]))
    assert_same_results(eng.run(inp), knn_golden(inp))


def test_sharded_device_full_matches_golden():
    """round-1 review missing item 5: device-side vote + report for the mesh
    engines, on the 8-virtual-device mesh, integer attrs (f32-safe)."""
    rng = np.random.default_rng(11)
    data = rng.integers(0, 7, size=(96, 4)).astype(np.float64)
    queries = rng.integers(0, 7, size=(24, 4)).astype(np.float64)
    labels = rng.integers(0, 5, size=96).astype(np.int32)
    ks = rng.integers(1, 9, size=24).astype(np.int32)
    inp = KNNInput(Params(96, 24, 4), labels, data, ks, queries)
    want = knn_golden(inp)
    for cls, mode in ((ShardedEngine, "sharded"), (RingEngine, "ring")):
        eng = cls(EngineConfig(mode=mode, exact=False, data_block=8,
                               query_block=8))
        got = eng.run_device_full(inp)
        for g, w in zip(got, want):
            assert g.predicted_label == w.predicted_label, mode
            assert list(g.neighbor_ids) == list(w.neighbor_ids), mode
            assert g.checksum() == w.checksum(), mode


@needs_devices(8)
def test_sharded_chunked_extract_multichunk_matches_golden():
    """round-3 review item 1: the pipelined chunked mesh driver — per-shard
    rows split across multiple staged chunks with carry folding, merged
    across the data axis — must match the golden model exactly. The
    data_block=12800 hint forces 2 chunks per shard (shard_rows 25600,
    chunk_rows 12800 at the extract granule), so the non-fresh carry
    branch of the fold program is really exercised."""
    text = generate_input_text(30000, 17, 5, -8, 8, 1, 13, 4, seed=29)
    inp = parse_input_text(text)
    for cls, mode in ((ShardedEngine, "sharded"), (RingEngine, "ring")):
        eng = cls(EngineConfig(mode=mode, select="extract", use_pallas=True,
                               data_block=12800),
                  mesh=make_mesh((2, 4)))
        got = eng.run(inp)
        assert eng._last_select == "extract", mode
        assert_same_results(got, knn_golden(inp))


@needs_devices(8)
def test_sharded_chunked_extract_overshoot_shard_boundary():
    """plan_chunks can overshoot (nchunks * chunk_rows > shard_rows):
    n=120000, r=2 -> shard_rows 64000, data_block=25600 -> 3 chunks of
    25600 = 76800 staged rows per shard. The last chunk's tail crosses
    into the next shard's id range; an uncapped fold would stage those
    rows TWICE and the merge would report duplicate neighbor ids. Exact
    golden parity proves the cap (both host- and device-side) holds."""
    text = generate_input_text(120000, 9, 3, -6, 6, 1, 11, 3, seed=33)
    inp = parse_input_text(text)
    eng = ShardedEngine(EngineConfig(mode="sharded", select="extract",
                                     use_pallas=True, data_block=25600),
                        mesh=make_mesh((2, 4)))
    got = eng.run(inp)
    assert eng._last_select == "extract"
    # The overshoot plan must really have been exercised.
    from dmlp_tpu.engine.single import plan_chunks
    shard_rows, nchunks, chunk_rows = plan_chunks(60000, 12800, 25600)
    assert nchunks * chunk_rows > shard_rows
    assert_same_results(got, knn_golden(inp))


@needs_devices(8)
def test_sharded_device_full_stages_swapped_dtype(monkeypatch):
    """ADVICE r4 (medium): no_auto_coarsen swaps engine._staging to
    float32 for device-full runs, but the mesh staging sites used to
    re-resolve dtype="auto" via the config — which returns bfloat16 on
    TPU — silently staging bf16 under a float32 ordering contract. CPU
    can't hit the TPU branch of resolve_dtype, so simulate it: force
    resolve_dtype to "bfloat16" and assert staging follows the ENGINE's
    swapped state, not the config."""
    import ml_dtypes
    from dmlp_tpu.engine.single import no_auto_coarsen

    monkeypatch.setattr(EngineConfig, "resolve_dtype",
                        lambda self: "bfloat16" if self.dtype == "auto"
                        else self.dtype)
    text = generate_input_text(64, 6, 3, -2, 2, 1, 4, 2, seed=7)
    inp = parse_input_text(text)
    eng = ShardedEngine(EngineConfig(mode="sharded", dtype="auto"),
                        mesh=make_mesh((4, 2)))
    assert eng._staging == "bfloat16"
    assert eng._np_dtype() == ml_dtypes.bfloat16
    d_attrs, _, _, q_attrs = eng._shard_inputs(inp, 8)
    assert d_attrs.dtype == jnp.bfloat16 and q_attrs.dtype == jnp.bfloat16
    with no_auto_coarsen(eng):
        assert eng._staging == "float32"
        assert eng._np_dtype() == np.float32
        d_attrs, _, _, q_attrs = eng._shard_inputs(inp, 8)
        assert d_attrs.dtype == jnp.float32, \
            "device-full staging must follow the swapped engine state"
        assert q_attrs.dtype == jnp.float32
    # Swap restored after the context.
    assert eng._staging == "bfloat16"
