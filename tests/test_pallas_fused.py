"""Fused distance→top-k megakernel (ops.pallas_fused) vs the two-pass
pipeline: BIT-IDENTITY is the contract.

The fused kernel's MXU tile gate may only elide blocks whose extraction
would have inserted nothing, so every output — dists, ids, the running
carry lists after warm folds — must equal the ungated kernel bit for
bit over the PR 3 tie-semantics fuzz corpus (duplicate rows astride
fused block boundaries included), with block skipping on AND off, in
interpret mode on CPU. Engine level: a DMLP_TPU_FUSED=1 run must be
byte-identical to a DMLP_TPU_FUSED=0 run and to the float64 golden
model, across the single-chip extract paths and the sharded mesh fold.
"""

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.grammar import KNNInput, Params
from tests.test_engine_single import assert_same_results
from tests.test_extract_fuzz import _case, _pad_stage


def _kernel_outputs(q, d, n_real, kc, *, mxu_gate, block_skip):
    """One fresh dispatch + one warm carry fold over shifted rows (the
    regime the gate actually optimizes) — returns every output."""
    from dmlp_tpu.ops.pallas_extract import extract_topk

    od1, oi1, it1 = extract_topk(q, d, n_real=n_real, kc=kc,
                                 interpret=True, tile_n=256,
                                 block_skip=block_skip, mxu_gate=mxu_gate)
    od2, oi2, it2 = extract_topk(q, d + 3.0, od1, oi1, n_real=n_real,
                                 id_base=n_real, kc=kc, interpret=True,
                                 tile_n=256, block_skip=block_skip,
                                 mxu_gate=mxu_gate)
    return [np.asarray(x) for x in (od1, oi1, od2, oi2)], \
        [np.asarray(x) for x in (it1, it2)]


@pytest.mark.parametrize("seed", [501, 502, 503, 504, 505, 506])
def test_fused_vs_two_pass_bit_identical_fuzz(seed):
    """Fuzz corpus (duplicate-heavy integer grids included), skip
    on/off x gate on/off: all four kernel configurations produce
    IDENTICAL dists/ids/carries — the gate and the skip are pure
    elisions."""
    inp = _case(seed)
    d, q, n_real, _ = _pad_stage(inp.data_attrs, inp.query_attrs)
    kc = 16
    outs = {}
    for gate in (False, True):
        for skip in (True, False):
            outs[(gate, skip)], _ = _kernel_outputs(
                q, d, n_real, kc, mxu_gate=gate, block_skip=skip)
    ref = outs[(False, True)]
    for key, got in outs.items():
        for a, b in zip(ref, got):
            assert np.array_equal(a, b), (seed, key)


def test_fused_tie_rows_astride_fused_block_boundary():
    """Duplicated rows exactly astride the fused kernel's in-kernel
    block boundary (tile_n=256: rows 255/256) and astride the carry
    fold: the MXU gate must not disturb the lowest-global-position tie
    contract. k=1 semantics checked through the composite sort."""
    import jax.numpy as jnp

    from dmlp_tpu.ops.pallas_extract import extract_topk

    rng = np.random.default_rng(29)
    na = 4
    # continuous values: only the DELIBERATE twins can tie at dist 0
    base = rng.uniform(-20, 20, (512, na))
    base[256] = base[255]                  # twins astride the boundary
    q2 = base[255][None, :]
    dd, qq, _, _ = _pad_stage(base, q2)
    for gate in (True, False):
        od, oi, _ = extract_topk(qq, dd, n_real=512, kc=8,
                                 interpret=True, tile_n=256,
                                 mxu_gate=gate)
        oi_np = np.asarray(oi)[0]
        srt = oi_np[np.argsort(np.asarray(od)[0], kind="stable")]
        assert {255, 256} <= set(oi_np.tolist())
        assert min(srt[0], srt[1]) == 255

    # chunk/carry form: the twin arrives in a LATER fold with higher
    # global ids — it must tie into the list without displacing id 255
    d1, d2 = base[:256], base[256:]
    dd1, qq, _, _ = _pad_stage(d1, q2)
    dd2 = jnp.asarray(np.asarray(_pad_stage(d2, q2)[0]))
    for gate in (True, False):
        od, oi, _ = extract_topk(qq, dd1, n_real=256, kc=8,
                                 interpret=True, tile_n=256,
                                 mxu_gate=gate)
        od, oi, _ = extract_topk(qq, dd2, od, oi, n_real=256,
                                 id_base=256, kc=8, interpret=True,
                                 tile_n=256, mxu_gate=gate)
        oi_np = np.asarray(oi)[0]
        srt = oi_np[np.argsort(np.asarray(od)[0], kind="stable")]
        assert {255, 256} <= set(oi_np.tolist())
        assert min(srt[0], srt[1]) == 255


def test_mxu_gate_skips_hopeless_blocks_outright():
    """The gate's whole point: a warm fold whose every candidate is
    provably worse than the current k-th best must cost ZERO loop
    iterations even with the r6 block-skip prefilter DISABLED — the
    norm bound gates the while-loop (and, on hardware, the matmul)
    before the prefilter ever runs. Outputs stay bit-identical."""
    import jax.numpy as jnp

    from dmlp_tpu.ops.pallas_extract import extract_topk

    rng = np.random.default_rng(3)
    n, nq, a, kc = 512, 8, 6, 16
    d = jnp.asarray(rng.uniform(0, 10, (n, a)), jnp.float32)
    q = jnp.asarray(rng.uniform(0, 10, (nq, a)), jnp.float32)
    d_far = d + 1000.0                    # norm gap >> any current best
    res = {}
    for gate in (True, False):
        od1, oi1, _ = extract_topk(q, d, n_real=n, kc=kc, interpret=True,
                                   block_skip=False, mxu_gate=gate)
        od2, oi2, it2 = extract_topk(q, d_far, od1, oi1, n_real=n,
                                     id_base=n, kc=kc, interpret=True,
                                     block_skip=False, mxu_gate=gate)
        res[gate] = (np.asarray(od2), np.asarray(oi2),
                     int(np.asarray(it2).sum()))
    assert np.array_equal(res[True][0], res[False][0])
    assert np.array_equal(res[True][1], res[False][1])
    assert res[True][2] == 0              # gated: zero loop iterations
    assert res[False][2] > 0              # ungated pays full discovery


# -- selection / kill switch -------------------------------------------------

def test_resolve_topk_kernel_prefers_fused_and_honors_kill_switch(
        monkeypatch):
    from dmlp_tpu.ops import pallas_fused
    from dmlp_tpu.ops.pallas_extract import extract_topk

    kern, impl = pallas_fused.resolve_topk_kernel(128, 12800, 8, 32)
    assert impl == "fused" and kern is pallas_fused.fused_topk

    monkeypatch.setenv("DMLP_TPU_FUSED", "0")
    kern, impl = pallas_fused.resolve_topk_kernel(128, 12800, 8, 32)
    assert impl == "extract" and kern is extract_topk

    monkeypatch.delenv("DMLP_TPU_FUSED")
    kern, impl = pallas_fused.resolve_topk_kernel(128, 12800, 8, 32)
    assert impl == "fused"


def test_resolve_topk_kernel_degrade_rung_pins_two_pass():
    """Any rung below "fused" (the resilience ladder's first step-down)
    must dispatch the two-pass kernel even with the switch on."""
    from dmlp_tpu.ops import pallas_fused

    _, impl = pallas_fused.resolve_topk_kernel(128, 12800, 8, 32,
                                               rung="heuristic")
    assert impl == "extract"


def test_resolve_topk_kernel_unsupported_shape_falls_through():
    from dmlp_tpu.ops import pallas_fused

    # kc beyond the kernel cap: neither kernel tiles it
    kern, impl = pallas_fused.resolve_topk_kernel(128, 12800, 8, 4096)
    assert kern is None and impl is None


# -- engine level ------------------------------------------------------------

def _engine_case(seed=41, n=900, nq=12, na=4):
    rng = np.random.default_rng(seed)
    return KNNInput(Params(n, nq, na),
                    rng.integers(0, 5, n).astype(np.int32),
                    rng.uniform(-20, 20, (n, na)),
                    rng.integers(1, 28, nq).astype(np.int32),
                    rng.uniform(-20, 20, (nq, na)))


def test_engine_fused_on_off_byte_identical_and_golden(monkeypatch):
    from dmlp_tpu.io.report import format_results

    inp = _engine_case()
    results = {}
    for fused in ("1", "0"):
        monkeypatch.setenv("DMLP_TPU_FUSED", fused)
        eng = SingleChipEngine(EngineConfig(select="extract",
                                            use_pallas=True))
        results[fused] = (format_results(eng.run(inp)),
                          eng.last_extract_impl)
    assert results["1"][0] == results["0"][0]          # byte identical
    assert results["1"][1] == "fused"
    assert results["0"][1] == "extract"
    monkeypatch.delenv("DMLP_TPU_FUSED")
    assert_same_results(
        SingleChipEngine(EngineConfig(select="extract",
                                      use_pallas=True)).run(inp),
        knn_golden(inp), check_dists=False)


def test_engine_multipass_fused_on_off_byte_identical(monkeypatch):
    """The multipass extract path (floor-masked resident passes) under
    the fused kernel: same bytes as two-pass, and the engine reports
    the impl it dispatched."""
    from dmlp_tpu.io.report import format_results

    rng = np.random.default_rng(17)
    n, nq, na = 600, 6, 3
    inp = KNNInput(Params(n, nq, na),
                   rng.integers(0, 4, n).astype(np.int32),
                   rng.uniform(-10, 10, (n, na)),
                   np.full(nq, 500, np.int32),    # wide k: multipass
                   rng.uniform(-10, 10, (nq, na)))
    outs = {}
    for fused in ("1", "0"):
        monkeypatch.setenv("DMLP_TPU_FUSED", fused)
        eng = SingleChipEngine(EngineConfig(select="extract",
                                            use_pallas=True))
        outs[fused] = format_results(eng.run(inp))
    assert outs["1"] == outs["0"]


def test_sharded_engine_fused_on_off_byte_identical(monkeypatch):
    """The mesh chunk-fold path bakes the fused/two-pass choice into its
    compiled-program cache key: flipping the switch recompiles the
    other program and the outputs stay byte-identical."""
    from dmlp_tpu.engine.sharded import ShardedEngine
    from dmlp_tpu.io.report import format_results

    inp = _engine_case(seed=43, n=1200, nq=16, na=4)
    outs = {}
    for fused in ("1", "0"):
        monkeypatch.setenv("DMLP_TPU_FUSED", fused)
        eng = ShardedEngine(EngineConfig(select="extract",
                                         use_pallas=True))
        outs[fused] = (format_results(eng.run(inp)),
                       eng.last_extract_impl)
    assert outs["1"][0] == outs["0"][0]
    assert outs["1"][1] == "fused" and outs["0"][1] == "extract"
    assert_same_results(
        ShardedEngine(EngineConfig(select="extract",
                                   use_pallas=True)).run(inp),
        knn_golden(inp), check_dists=False)


def test_fused_rung_degrades_to_two_pass_on_oom(monkeypatch, tmp_path):
    """Resilience integration: a fused-path OOM steps the ladder down
    to the two-pass kernel (one rung, not a crash), the degrade
    event lands in the resilience stats block, and the output is
    byte-identical to the unfaulted run."""
    import json

    from dmlp_tpu.resilience import inject, stats
    from dmlp_tpu.io.report import format_results

    inp = _engine_case(seed=47)
    golden = format_results(
        SingleChipEngine(EngineConfig(select="extract",
                                      use_pallas=True)).run(inp))

    sched = {"schema": 1, "seed": 5, "faults": [
        {"site": "single.stage_put", "kind": "oom", "times": 3}]}
    p = tmp_path / "faults.json"
    p.write_text(json.dumps(sched))
    monkeypatch.setenv("DMLP_TPU_FAULTS", str(p))
    stats.reset()
    inject.install_from_env()
    try:
        eng = SingleChipEngine(EngineConfig(select="extract",
                                            use_pallas=True))
        got = format_results(eng.run(inp))
    finally:
        inject.uninstall()
        monkeypatch.delenv("DMLP_TPU_FAULTS")
    assert got == golden
    assert eng.last_degrade_rung == "heuristic"
    assert eng.last_extract_impl == "extract"
    snap = stats.snapshot()["degradations"]
    assert "lowp->prune" in snap and "prune->fused" in snap \
        and "fused->heuristic" in snap


# -- analytic cost model -----------------------------------------------------

def test_fused_cost_model_shows_hbm_traffic_elimination():
    """The acceptance number: on the parity dispatch shape the fused
    dispatch's HBM bytes drop by exactly the (nq, nd) f32 distance
    write+read the two-pass pipeline pays — ~2x hot-path traffic."""
    from dmlp_tpu.obs.kernel_cost import (fused_topk_cost,
                                          two_pass_equivalent_cost)

    qb, b, a, kc = 10240, 204800, 64, 40   # parity dispatch shape
    fused = fused_topk_cost(qb, b, a, kc)
    two = two_pass_equivalent_cost(qb, b, a, kc)
    dist_rt = 2.0 * 4.0 * qb * b           # f32 write + re-read
    assert two["bytes_accessed"] - fused["bytes_accessed"] \
        == pytest.approx(dist_rt)
    assert fused["hbm_bytes_saved_vs_two_pass"] == pytest.approx(dist_rt)
    assert fused["hbm_traffic_reduction_x"] >= 1.9
    assert fused["extraction_term"] == "modeled_lower_bound"
    meas = fused_topk_cost(qb, b, a, kc, iters_total=1000)
    assert meas["extraction_term"] == "measured"
    assert meas["flops"] > fused["flops"]


def test_fused_dispatch_resolves_analytic_model():
    """obs.counters must resolve fused_topk through the analytic table
    (pallas_call has no XLA cost analysis) — the R106 runtime half."""
    import jax.numpy as jnp

    from dmlp_tpu.obs.kernel_cost import analytic_cost
    from dmlp_tpu.ops.pallas_fused import fused_topk

    q = jnp.zeros((16, 8), jnp.float32)
    d = jnp.zeros((256, 8), jnp.float32)
    out = analytic_cost(fused_topk, (q, d), {"kc": 16})
    assert out is not None and out["bytes_accessed"] > 0
    assert out["hbm_traffic_reduction_x"] > 1.0
