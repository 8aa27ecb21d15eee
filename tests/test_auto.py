"""Compiler-sharded engine (GSPMD): parity fuzz, composition, contract.

The AutoShardedEngine expresses the chunked distance -> top-k solve as
one pure jit with pinned NamedShardings and a with_sharding_constraint
merge point — XLA's GSPMD partitioner picks the collective schedule the
hand-rolled engines (shard_map + explicit allgather/ring merge) spell
out by hand. Everything here pins the contract that makes that swap
safe:

- byte-identity to the single-chip engine and the f64 golden model on
  duplicate-heavy tie grids and k boundaries, across mesh shapes
  (including the degenerate 1x1 mesh);
- composition with the prune/precision axes resolved OUTSIDE the jit;
- the honest no-model stance (no analytic comms claim, memory model
  priced at the allgather worst case);
- the construction-time mesh-axis contract and the loud multi-host
  NotImplementedError;
- the ``auto/`` RunRecord family landing in the perf ledger gated;
- the persistent compile cache making a relaunched daemon's cold start
  strictly cheaper with a flat bucket compile count.
"""

import os

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.auto import AutoShardedEngine
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.io.report import format_results
from dmlp_tpu.obs import memwatch
from dmlp_tpu.obs.comms import engine_comms
from dmlp_tpu.parallel.mesh import make_mesh
from tests.test_engine_single import assert_same_results


def _case(seed: int, kmax: int = 48) -> KNNInput:
    """Duplicate-biased corpora straddling block granules (the
    test_precision generator, with k pushed to the cap boundary)."""
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
    ks = rng.integers(1, min(n, kmax) + 1, nq).astype(np.int32)
    return KNNInput(Params(n, nq, na), labels, data, ks, queries)


def _auto(mesh_shape=(4, 2), **kw) -> AutoShardedEngine:
    return AutoShardedEngine(EngineConfig(mode="auto", **kw),
                             mesh=make_mesh(mesh_shape))


# -- byte-identity fuzz -------------------------------------------------------

@pytest.mark.parametrize("seed", range(611, 619))
def test_auto_byte_identical_to_single_and_golden(seed):
    inp = _case(seed)
    got = _auto().run(inp)
    solo = SingleChipEngine(EngineConfig()).run(inp)
    gold = knn_golden(inp)
    assert format_results(got) == format_results(solo) \
        == format_results(gold)
    assert_same_results(got, gold)


@pytest.mark.parametrize("shape", [(1, 1), (2, 4), (8, 1), (1, 8)])
def test_auto_mesh_shapes_byte_identical(shape):
    """Every mesh factorization — including the degenerate 1x1 and the
    all-data / all-query extremes — resolves to the same bytes: GSPMD
    owns the schedule, never the answer."""
    inp = _case(733)
    devices = None
    if shape == (1, 1):
        devices = jax.devices()[:1]
    eng = AutoShardedEngine(EngineConfig(mode="auto"),
                            mesh=make_mesh(shape, devices=devices))
    assert format_results(eng.run(inp)) == format_results(knn_golden(inp))


def test_auto_k_boundary_tie_grid():
    """k == 1, k == n, and a duplicate group astride the shard edge:
    the merged candidate lists must keep the composite (dist asc, id
    desc) order the repair pipeline assumes."""
    rng = np.random.default_rng(91)
    n, na = 264, 3
    data = rng.integers(0, 2, (n, na)).astype(np.float64)
    data[128:144] = data[0]        # duplicate row group across shards
    queries = data[[0, 5, 130, 263]].copy()
    ks = np.array([1, n, 48, 7], np.int32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    inp = KNNInput(Params(n, 4, na), labels, data, ks, queries)
    got = _auto().run(inp)
    gold = knn_golden(inp)
    assert format_results(got) == format_results(gold)
    assert_same_results(got, gold)


def test_auto_chunked_data_block_byte_identical():
    inp = _case(645)
    eng = _auto(data_block=64)
    assert format_results(eng.run(inp)) == format_results(knn_golden(inp))


# -- composition: config axes resolved OUTSIDE the jit ------------------------

def test_auto_bf16_first_pass_byte_identical(monkeypatch):
    monkeypatch.delenv("DMLP_TPU_PRECISION", raising=False)
    inp = _case(821)
    eng_b = _auto(precision="bf16")
    eng_f = _auto(precision="f32")
    gold = knn_golden(inp)
    assert format_results(eng_b.run(inp)) == format_results(eng_f.run(inp)) \
        == format_results(gold)
    assert eng_b.last_precision["active"] == "bf16"
    assert eng_f.last_precision["active"] == "f32"


def test_auto_prune_composition_skips_blocks_and_stays_golden(monkeypatch):
    """Clustered corpus with a far band: prune on must skip blocks
    (host scan bytes drop), prune off must scan dense — both arms
    byte-identical to golden."""
    rng = np.random.default_rng(55)
    n, nq, na = 4096, 6, 3
    data = rng.uniform(0, 1, (n, na))
    data[3584:] += 500.0           # far band: whole blocks prunable
    inp = KNNInput(Params(n, nq, na),
                   rng.integers(0, 4, n).astype(np.int32), data,
                   rng.integers(1, 6, nq).astype(np.int32),
                   rng.uniform(0, 1, (nq, na)))
    gold = format_results(knn_golden(inp))
    pruned_arm = {}
    for prune in ("1", "0"):
        monkeypatch.setenv("DMLP_TPU_PRUNE", prune)
        eng = AutoShardedEngine(
            EngineConfig(mode="auto", data_block=512),
            mesh=make_mesh((4, 1), devices=jax.devices()[:4]))
        assert format_results(eng.run(inp)) == gold, prune
        pruned_arm[prune] = dict(eng.last_prune)
    assert pruned_arm["0"]["blocks_pruned"] == 0
    assert pruned_arm["1"]["blocks_pruned"] > 0
    assert pruned_arm["1"]["scanned_bytes"] < pruned_arm["0"]["dense_bytes"]


def test_auto_fast_mode_no_repair_paths_still_match_slow_k_order():
    """Fast (non-exact) mode routes the device-full epilogue; the
    report bytes must still match golden (device ordering is exact on
    these integer grids)."""
    rng = np.random.default_rng(71)
    n, nq, na = 300, 5, 4
    inp = KNNInput(Params(n, nq, na),
                   rng.integers(0, 4, n).astype(np.int32),
                   rng.integers(0, 3, (n, na)).astype(np.float64),
                   rng.integers(1, 12, nq).astype(np.int32),
                   rng.integers(0, 3, (nq, na)).astype(np.float64))
    got = _auto(exact=False).run(inp)
    gold = knn_golden(inp)
    assert format_results(got) == format_results(gold)


# -- the honest no-model stance ----------------------------------------------

def test_auto_reports_no_analytic_comms():
    assert engine_comms("gspmd", (4, 2), 8, 5) == []
    eng = _auto()
    eng.run(_case(733))
    assert eng.last_comms == []


def test_auto_memory_model_prices_allgather_worst_case():
    """The admission model must not under-budget a compiler-chosen
    schedule: gspmd merge buffers are priced at the allgather worst
    case (>= the ring model, == the allgather model)."""
    kw = dict(mesh_shape=(4, 2), shard_rows=256, na=8, monolithic=True,
              qloc=64, kcap=32)
    auto_m = memwatch.fleet_engine_model(merge="gspmd", **kw)
    ag_m = memwatch.fleet_engine_model(merge="allgather", **kw)
    ring_m = memwatch.fleet_engine_model(merge="ring", **kw)
    assert auto_m["total_bytes"] == ag_m["total_bytes"]
    assert auto_m["total_bytes"] >= ring_m["total_bytes"]


# -- construction + multi-host contract ---------------------------------------

def test_auto_rejects_mesh_without_named_axes():
    devs = np.array(jax.devices()[:2]).reshape(2, 1)
    with pytest.raises(ValueError, match="must declare axes"):
        AutoShardedEngine(EngineConfig(mode="auto"),
                          mesh=Mesh(devs, ("rows", "cols")))


def test_auto_multi_host_contract_fails_loudly():
    eng = _auto()
    with pytest.raises(NotImplementedError, match="multi-host"):
        eng.solve_global(None, None, None, None, 5)
    with pytest.raises(NotImplementedError, match="multi-host"):
        eng.solve_local_shards(None, None, None, None, 5)


def test_fleet_mesh_engine_accepts_auto_merge():
    from dmlp_tpu.fleet.mesh_engine import MeshResidentEngine
    rng = np.random.default_rng(17)
    n, na = 600, 5
    corpus = KNNInput(Params(n, 0, na),
                      rng.integers(0, 4, n).astype(np.int32),
                      rng.uniform(0, 50, (n, na)),
                      np.zeros(0, np.int32), np.zeros((0, na)))
    q = rng.uniform(0, 50, (7, na))
    ks = np.array([1, 3, 8, 12, 5, 2, 7], np.int32)
    eng = MeshResidentEngine(corpus, EngineConfig(),
                             mesh_shape=(4, 1), merge="auto")
    got = [r.checksum() for r in eng.solve_batch(q, ks)]
    inp = KNNInput(Params(n, len(ks), na), corpus.labels,
                   corpus.data_attrs, ks, q)
    want = [r.checksum() for r in knn_golden(inp)]
    assert got == want
    assert eng.bucket_stats()["merge"] == "gspmd"
    with pytest.raises(ValueError):
        MeshResidentEngine(corpus, EngineConfig(), merge="bogus")


# -- the differential harness under --mode auto --------------------------------

def test_harness_auto_mode_record_loads_as_runrecord(tmp_path, monkeypatch):
    """``python -m dmlp_tpu.bench N --mode auto --metrics FILE``: the
    compiler-sharded engine against the cached golden oracle through
    the real CLI, and the config's RunRecord reads back."""
    import io

    from dmlp_tpu.bench import configs as bench_configs
    from dmlp_tpu.bench.harness import run_config
    from dmlp_tpu.obs.run import RunRecord
    monkeypatch.setitem(
        bench_configs.BENCH_CONFIGS, 1,
        bench_configs.BenchConfig(1, 200, 20, 4, 0.0, 10.0, 1, 8, 4, 7,
                                  "tiny.in"))
    rec = tmp_path / "AUTO_r99.jsonl"
    buf = io.StringIO()
    res = run_config(1, base_dir=str(tmp_path), mode="auto", out=buf,
                     record_path=str(rec))
    assert res["checksums_match"], buf.getvalue()
    back = RunRecord.load(str(rec))
    assert (back.kind, back.tool) == ("bench", "dmlp_tpu.bench")
    assert back.round == 99        # from the file's _r99 suffix
    assert back.metrics["checksums_match"] is True
    assert back.metrics["engine_ms"] is not None


# -- persistent compile cache: a relaunch is served from it, compile count flat

def test_warm_compile_cache_relaunch_hits_and_count_flat(
        tmp_path, monkeypatch):
    """Two serve daemons, same corpus + warm buckets, same
    ``--compile-cache`` dir: the first writes every program it compiles
    to the cache (misses, no hit), the second is served every one of
    them from it (hits, no miss) with an unchanged bucket compile count
    — the executables are reused, not rebuilt. Counts, which repeat
    exactly; the two cold-start wall times (~400 ms each on CPU) are
    not compared. Subprocesses, not threads: jax's in-process jit cache
    would mask the persistent layer."""
    from dmlp_tpu.fleet import harness as fh
    from dmlp_tpu.serve import client as sc
    # The test places the cache itself; an ambient placement would win
    # over --compile-cache and could hand the "cold" arm a warm cache.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    header = {"serve_trace_schema": 1,
              "corpus": dict(num_data=200, num_queries=4, num_attrs=4,
                             min_attr=0.0, max_attr=50.0, min_k=1,
                             max_k=8, num_labels=5, seed=42)}
    corpus_path = tmp_path / "corpus.in"
    corpus_path.write_text(sc.corpus_text(header))
    ccdir = tmp_path / "compile_cache"
    out = str(tmp_path)
    caches, counts = [], []
    for gen in ("cold", "warm"):
        fp = fh.spawn_replica(str(corpus_path), out, f"cc_{gen}",
                              "8x8", batch_cap=8,
                              compile_cache=str(ccdir))
        try:
            fh.await_replica(fp)
            caches.append(fp.ready["compile_cache"])
            counts.append(fp.ready["compile_count"])
            cli = sc.ServeClient(fp.ready["port"])
            cli.drain()
            cli.close()
            assert fp.proc.wait(timeout=120) == 0
        finally:
            fh.kill_all([fp])
    assert os.path.isdir(str(ccdir)) and os.listdir(str(ccdir)), \
        "the persistent cache directory stayed empty"
    assert counts[1] == counts[0]
    cold, warm = caches
    assert cold["dir"] == warm["dir"] == str(ccdir)
    assert cold["hits"] == 0 and cold["misses"] > 0, cold
    assert warm["misses"] == 0 and warm["hits"] == cold["misses"], \
        f"warm relaunch was not served from the cache: {cold} -> {warm}"
    assert warm["requests"] == cold["requests"]


def test_compile_cache_env_beats_flag_and_default_is_checkout(
        monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR, placed from outside, wins over
    --compile-cache; with neither, the fixed <checkout>/.jax_cache."""
    from dmlp_tpu.utils import compile_cache as cc
    flag_dir = tmp_path / "flagged"
    env_dir = tmp_path / "from_env"
    monkeypatch.setenv(cc.JAX_ENV_VAR, str(env_dir))
    assert cc.resolve_cache_dir(str(flag_dir)) == str(env_dir)
    assert cc.resolve_cache_dir(None) == str(env_dir)
    monkeypatch.delenv(cc.JAX_ENV_VAR)
    assert cc.resolve_cache_dir(str(flag_dir)) == str(flag_dir)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.resolve_cache_dir(None) == os.path.join(repo, ".jax_cache")
