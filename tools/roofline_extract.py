#!/usr/bin/env python
"""Roofline the on-chip extract solve (round-4 review item 8; ISSUE 3 r6).

Targets EXACTLY the number bench.py records as device_solve_ms_extract:
bench.stage_extract_inputs' f32-staged arrays,
kc = round_up(kmax + 8, 8), the fused kernel PLUS the label-gather +
composite-sort epilogue, timed by bench.time_fenced_solve_ms (the
dependent-readback fence). Floors:

1. MXU: the bare norm+matmul distance computation at the same shape and
   precision (HIGHEST), same fence — the kernel must do this matmul work.
2. HBM: the kernel's block sweep re-reads the dataset once per query
   tile: (Qpad/tq) * Npad * A * 4 bytes over the chip's HBM bandwidth.

Methodology (r6):

- The extraction term is MEASURED, not modeled: the kernel's own
  per-tile ``iters`` output is read back and folded into the counters
  block via obs.kernel_cost.extract_topk_cost(iters_total=...) — the
  RunRecord's flops are no longer a deterministic lower bound
  (ROADMAP item closed).
- The kernel is timed BOTH with the r6 threshold-gated block skipping
  (default) and with ``block_skip=False`` (the r5 kernel), interleaved
  in the same run, so the record carries an honest
  before/after kernel-only ms and %-of-roof pair for the optimization.
- The variant that ran resolves through the measured autotuner cache
  (dmlp_tpu.tune) when an entry exists — the record names it either way.

Writes one schema-1 RunRecord (obs.run). On a host with no TPU,
``--emit-unavailable`` writes an explicit-marker RunRecord (device,
why, and an interpret-mode parity + measured-iters demonstration at a
small shape) instead of failing silently — never a missing artifact.

``--fused`` (ISSUE 8) adds the fused distance→top-k megakernel arm
(ops.pallas_fused: the MXU tile gate + fused tune-cache namespace):
the fused kernel is timed INTERLEAVED with the ungated kernel in the
same run (kernel-only, dispatch-corrected), and the
RunRecord's counters block carries obs.kernel_cost.fused_topk_cost —
including the analytic HBM write+read the fusion eliminates vs the
materialize-then-reread two-pass pipeline
(``hbm_bytes_saved_vs_two_pass`` / ``hbm_traffic_reduction_x``). On a
no-TPU host, ``--fused --emit-unavailable`` writes the honest
fused-roofline-unavailable record: an interpret-mode proof that the
gate is a pure elision (fused vs ungated bit-identity, gate-zeroed
iters on a hopeless warm block) plus the on-hardware recipe.

Usage (DEFAULT env, real chip): python tools/roofline_extract.py
    [--out ROOFLINE_r06.json] [--n 204800 --q 10240 --a 64 --k 32]
    [--fused --out ROOFLINE_FUSED_r08.json]
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

HBM_GBPS = {"tpu v5 lite": 819.0, "v5e": 819.0}


def emit_unavailable(args, dev) -> int:
    """The honest no-TPU artifact: an explicit roofline_unavailable
    RunRecord carrying (1) why, (2) what the last real-chip measurement
    said, (3) an interpret-mode demonstration that the r6 block-skip
    kernel is output-identical and actually skips warm no-improve blocks
    (iters 0 vs 1), so the record is evidence, not just an excuse."""
    import numpy as np

    import jax.numpy as jnp
    from dmlp_tpu.obs.kernel_cost import extract_topk_cost
    from dmlp_tpu.obs.run import RunRecord
    from dmlp_tpu.ops.pallas_extract import extract_topk, resolve_variant

    n, nq, a, kc = 1024, 16, 8, 16
    rng = np.random.default_rng(0)
    d = jnp.asarray(rng.uniform(0, 100, (n, a)), jnp.float32)
    q = jnp.asarray(rng.uniform(0, 100, (nq, a)), jnp.float32)
    # Chunk 1 fresh, chunk 2 the same rows shifted FAR away: no chunk-2
    # candidate can beat any row's k-th best, so the skip gate must zero
    # chunk 2's loop count while the r5 kernel pays one full extraction
    # round per tile discovering the same nothing.
    d_far = d + 1000.0
    runs = {}
    for skip in (True, False):
        od1, oi1, it1 = extract_topk(q, d, n_real=n, kc=kc,
                                     interpret=True, block_skip=skip)
        od2, oi2, it2 = extract_topk(q, d_far, od1, oi1, n_real=n,
                                     id_base=n, kc=kc, interpret=True,
                                     block_skip=skip)
        runs[skip] = (np.asarray(od2), np.asarray(oi2),
                      int(np.asarray(it1).sum()),
                      int(np.asarray(it2).sum()))
    parity = (np.array_equal(runs[True][0], runs[False][0])
              and np.array_equal(runs[True][1], runs[False][1]))
    iters_total = runs[True][2] + runs[True][3]

    why = (
        f"no TPU reachable from this container (backend={dev.platform}); "
        "the before/after kernel-only timing needs the real chip. "
        "The r6 kernel gates the extraction loop per block (threshold "
        "prefilter) and resolves variants through the measured tuner "
        "cache — re-measure with `python -m dmlp_tpu.tune` + "
        "`python tools/roofline_extract.py` on hardware.")
    rec = RunRecord(
        kind="roofline", tool="tools/roofline_extract",
        config={"device": dev.platform, "shape": [args.n, args.q, args.a],
                "k": args.k, "requested_reps": args.reps},
        metrics={
            "roofline_unavailable": why,
            "before_after_unavailable": "kernel-only ms requires TPU",
            "cpu_interpret_check": {
                "shape": [n, nq, a], "kc": kc,
                "variant": resolve_variant(kc, n, nq, a),
                "block_skip_parity": bool(parity),
                "iters_chunk2_with_skip": runs[True][3],
                "iters_chunk2_without_skip": runs[False][3],
            },
        },
        counters=extract_topk_cost(nq, n, a, kc, iters_total=iters_total))
    rec.write(args.out)
    print(rec.to_json())
    return 0 if parity else 1


def emit_fused_unavailable(args, dev) -> int:
    """The honest no-TPU artifact for the fused megakernel: an explicit
    fused-roofline-unavailable RunRecord carrying (1) why, (2) an
    interpret-mode proof that the MXU tile gate is a PURE ELISION —
    fused vs ungated kernel bit-identical on fresh + warm folds, and a
    provably-hopeless warm block costs the fused kernel ZERO loop
    iterations even with the r6 block-skip prefilter off — and (3) the
    analytic HBM-traffic elimination vs the two-pass pipeline at the
    parity dispatch shape, so the ~2x claim is a checked number
    in the ledger while the ms win awaits hardware."""
    import numpy as np

    import jax.numpy as jnp
    from dmlp_tpu.obs.kernel_cost import (fused_topk_cost,
                                          two_pass_equivalent_cost)
    from dmlp_tpu.obs.run import RunRecord
    from dmlp_tpu.ops.pallas_extract import extract_topk
    from dmlp_tpu.ops.pallas_fused import resolve_variant

    n, nq, a, kc = 1024, 16, 8, 16
    rng = np.random.default_rng(0)
    d = jnp.asarray(rng.uniform(0, 100, (n, a)), jnp.float32)
    q = jnp.asarray(rng.uniform(0, 100, (nq, a)), jnp.float32)
    d_far = d + 1000.0   # warm fold no candidate of which can insert
    runs = {}
    for gate in (True, False):
        od1, oi1, it1 = extract_topk(q, d, n_real=n, kc=kc,
                                     interpret=True, block_skip=False,
                                     mxu_gate=gate)
        od2, oi2, it2 = extract_topk(q, d_far, od1, oi1, n_real=n,
                                     id_base=n, kc=kc, interpret=True,
                                     block_skip=False, mxu_gate=gate)
        runs[gate] = (np.asarray(od2), np.asarray(oi2),
                      int(np.asarray(it1).sum()),
                      int(np.asarray(it2).sum()))
    parity = (np.array_equal(runs[True][0], runs[False][0])
              and np.array_equal(runs[True][1], runs[False][1]))
    gate_elides = runs[True][3] == 0 and runs[False][3] > 0
    iters_total = runs[True][2] + runs[True][3]

    # The acceptance number at the parity dispatch shape: what the
    # fusion eliminates vs the materialize-then-reread two-pass pipeline.
    qb, b = args.q, args.n
    fused = fused_topk_cost(qb, b, args.a, kc)
    two = two_pass_equivalent_cost(qb, b, args.a, kc)

    why = (
        f"no TPU reachable from this container (backend={dev.platform}); "
        "the fused-vs-two-pass kernel-only ms needs the real chip. "
        "On hardware: `python -m dmlp_tpu.tune --kernel both` (sweep the "
        "fused namespace), then `python tools/roofline_extract.py --fused "
        "--reps 3` (interleaved fused/ungated arms), then "
        "`python -m dmlp_tpu.report` + `make perf-gate` to fold the "
        "round into the trajectory. Expected: the MXU gate converts the "
        "extraction term's warm no-improve blocks from one VPU "
        "prefilter pass each into NOTHING — the matmul tile itself is "
        "skipped.")
    rec = RunRecord(
        kind="roofline", tool="tools/roofline_extract_fused",
        config={"device": dev.platform, "shape": [args.n, args.q, args.a],
                "k": args.k, "requested_reps": args.reps, "fused": True},
        metrics={
            "roofline_unavailable": why,
            "fused_vs_two_pass_ms_unavailable":
                "kernel-only ms requires TPU",
            "hbm_bytes_saved_vs_two_pass":
                fused["hbm_bytes_saved_vs_two_pass"],
            "hbm_traffic_reduction_x": fused["hbm_traffic_reduction_x"],
            "hbm_bytes_fused": fused["bytes_accessed"],
            "hbm_bytes_two_pass_equiv": two["bytes_accessed"],
            "cpu_interpret_check": {
                "shape": [n, nq, a], "kc": kc,
                "variant": resolve_variant(kc, n, nq, a),
                "fused_vs_ungated_parity": bool(parity),
                "gate_zeroes_hopeless_block_iters": bool(gate_elides),
                "iters_warm_block_gated": runs[True][3],
                "iters_warm_block_ungated": runs[False][3],
            },
        },
        counters=fused_topk_cost(nq, n, a, kc, iters_total=iters_total))
    rec.write(args.out)
    print(rec.to_json())
    return 0 if parity and gate_elides else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="ROOFLINE_r06.json")
    ap.add_argument("--n", type=int, default=204800)
    ap.add_argument("--q", type=int, default=10240)
    ap.add_argument("--a", type=int, default=64)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--emit-unavailable", action="store_true",
                    help="on a non-TPU host, write the explicit "
                         "roofline-unavailable RunRecord (exit 0) "
                         "instead of failing")
    ap.add_argument("--fused", action="store_true",
                    help="add the fused-megakernel arm (ops.pallas_fused"
                         "): interleaved fused vs ungated kernel-only "
                         "timing + the analytic HBM-traffic elimination "
                         "(ISSUE 8); with --emit-unavailable, writes "
                         "the fused parity-proof marker record")
    args = ap.parse_args()
    if args.fused and args.out == "ROOFLINE_r06.json":
        args.out = "ROOFLINE_FUSED_r08.json"

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        if args.emit_unavailable:
            return emit_fused_unavailable(args, dev) if args.fused \
                else emit_unavailable(args, dev)
        print(f"FATAL: roofline needs the real chip, got {dev.platform} "
              "(--emit-unavailable writes the explicit marker record)")
        return 1

    from bench import stage_extract_inputs, time_fenced_solve_ms
    from dmlp_tpu.engine.single import _extract_finalize, round_up
    from dmlp_tpu.io.grammar import KNNInput, Params
    from dmlp_tpu.ops.pallas_distance import _tile
    from dmlp_tpu.ops.pallas_extract import (BLOCK_ROWS, _resolve_variant,
                                             extract_topk)

    n, q, a = args.n, args.q, args.a
    rng = np.random.default_rng(0)
    inp = KNNInput(Params(n, q, a),
                   rng.integers(0, 10, n).astype(np.int32),
                   rng.uniform(0, 100, (n, a)),
                   np.full(q, args.k, np.int32),
                   rng.uniform(0, 100, (q, a)))
    kc = round_up(args.k + 8, 8)          # bench's device-solve width
    qd, dd, lab, npad, qpad = stage_extract_inputs(inp)

    trivial = jax.jit(lambda q_, d_: q_ + 1.0)

    # --- measured: bench-identical solve (kernel + sort epilogue) -------
    def solve_fn(q_, d_):
        od, oi, _ = extract_topk(q_, d_, n_real=n, kc=kc)
        return _extract_finalize(od, oi, lab, k=kc).dists

    # --- kernel only (no epilogue): isolates the sort term --------------
    def kernel_fn(q_, d_):
        od, _, _ = extract_topk(q_, d_, n_real=n, kc=kc)
        return od

    # --- the r5 kernel (block skipping off): the "before" of the A/B ----
    def kernel_noskip_fn(q_, d_):
        od, _, _ = extract_topk(q_, d_, n_real=n, kc=kc, block_skip=False)
        return od

    # --- the fused megakernel (MXU tile gate + fused tune namespace) ----
    def kernel_fused_fn(q_, d_):
        from dmlp_tpu.ops.pallas_fused import fused_topk
        od, _, _ = fused_topk(q_, d_, n_real=n, kc=kc)
        return od

    # --- MXU floor: bare fused distance matmul, same precision/fence ----
    @jax.jit
    def dist_only(q_, d_):
        cross = jax.lax.dot_general(
            q_, d_, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        qn = jnp.sum(q_ * q_, -1, keepdims=True)
        dn = jnp.sum(d_ * d_, -1)[None, :]
        # reduce to (Q, 1): keeps the (Q, N) matrix out of HBM (the
        # kernel never writes it) but XLA cannot elide the matmul
        return jnp.min(jnp.maximum(qn + dn - 2.0 * cross, 0.0), axis=1,
                       keepdims=True)

    # The per-dispatch overhead does NOT amortize across chained reps,
    # so the measurements are INTERLEAVED round-robin and medianed —
    # they share machine conditions, making the subtraction-based
    # decomposition meaningful.
    fns = {"dispatch": trivial, "solve": solve_fn, "kernel": kernel_fn,
           "kernel_noskip": kernel_noskip_fn, "mxu": dist_only}
    if args.fused:
        fns["kernel_fused"] = kernel_fused_fn
    rounds = {k: [] for k in fns}
    for r in range(5):
        for name in (list(fns) if r % 2 == 0 else list(fns)[::-1]):
            rounds[name].append(
                time_fenced_solve_ms(fns[name], qd, dd, args.reps))
    med = {k: float(np.median(v)) for k, v in rounds.items()}
    dispatch_ms = med["dispatch"]
    solve_ms, kernel_ms, mxu_ms = med["solve"], med["kernel"], med["mxu"]
    noskip_ms = med["kernel_noskip"]

    # --- HBM floor (actual resolved tiles; kernel streams f32) ----------
    v = _resolve_variant(kc, npad, qpad, a)
    tq = _tile(qpad, v["tile_q"], 8)
    tn = _tile(npad, v.get("tile_n", BLOCK_ROWS), 128 * v["ne"])
    sweep_bytes = (qpad // tq) * npad * a * 4 + (npad // tn) * qpad * a * 4
    bw = next((g for k_, g in HBM_GBPS.items()
               if k_ in dev.device_kind.lower()), 819.0)
    hbm_floor_ms = sweep_bytes / (bw * 1e9) * 1e3

    # --- extraction-iteration diagnostics (measured, both kernels) ------
    _, _, iters = extract_topk(qd, dd, n_real=n, kc=kc)
    total_iters = int(np.asarray(iters).sum())
    _, _, iters_ns = extract_topk(qd, dd, n_real=n, kc=kc,
                                  block_skip=False)
    total_iters_noskip = int(np.asarray(iters_ns).sum())

    flops = 2.0 * npad * qpad * a
    # Single-dispatch chains (kernel, mxu, dispatch) are directly
    # comparable after subtracting the measured per-dispatch overhead.
    # The solve-vs-kernel difference (the sort epilogue's second
    # dispatch) sits BELOW the noise — consecutive enqueues pipeline —
    # so the epilogue is reported raw, not as a corrected term.
    kernel_c = kernel_ms - dispatch_ms
    noskip_c = noskip_ms - dispatch_ms
    mxu_c = max(mxu_ms - dispatch_ms, 1e-6)
    floor = max(mxu_c, hbm_floor_ms)
    rec = {
        "device": dev.device_kind, "shape": [n, q, a],
        "k": args.k, "kc": kc, "variant": v,
        "tiles": {"tq": tq, "tn": tn},
        "dispatch_overhead_ms": round(dispatch_ms, 2),
        "raw_ms": {"solve_with_epilogue": round(solve_ms, 2),
                   "kernel_only": round(kernel_ms, 2),
                   "kernel_only_noskip": round(noskip_ms, 2),
                   "mxu_matmul": round(mxu_ms, 2)},
        # before = the r5 kernel (block_skip off), after = r6 (skip on);
        # interleaved medians, dispatch-corrected.
        "corrected": {
            "kernel_ms_before": round(noskip_c, 2),
            "kernel_ms": round(kernel_c, 2),
            "block_skip_speedup": round(noskip_c / max(kernel_c, 1e-6), 3),
            "mxu_floor_ms": round(mxu_c, 2),
            "extraction_term_ms_before": round(noskip_c - mxu_c, 2),
            "extraction_term_ms": round(kernel_c - mxu_c, 2),
            "pct_of_roof_before": round(
                100.0 * floor / max(noskip_c, 1e-6), 1),
            "pct_of_roof": round(100.0 * floor / max(kernel_c, 1e-6), 1),
        },
        "mxu_achieved_tflops_f32_highest": round(
            flops / (mxu_c * 1e-3) / 1e12, 1),
        "hbm_floor_ms": round(hbm_floor_ms, 2),
        "hbm_bw_gbps_assumed": bw,
        "sweep_gb": round(sweep_bytes / 1e9, 2),
        "extract_iters_total": total_iters,
        "extract_iters_total_noskip": total_iters_noskip,
    }
    if args.fused:
        from dmlp_tpu.obs.kernel_cost import fused_topk_cost
        from dmlp_tpu.ops.pallas_fused import fused_topk
        fused_c = med["kernel_fused"] - dispatch_ms
        _, _, it_f = fused_topk(qd, dd, n_real=n, kc=kc)
        fc = fused_topk_cost(qpad, npad, a, kc,
                             iters_total=int(np.asarray(it_f).sum()))
        rec["fused"] = {
            "kernel_ms_fused": round(fused_c, 2),
            "fused_vs_ungated_speedup": round(
                kernel_c / max(fused_c, 1e-6), 3),
            "pct_of_roof_fused": round(100.0 * floor / max(fused_c, 1e-6),
                                       1),
            "extract_iters_total_fused": int(np.asarray(it_f).sum()),
            "hbm_bytes_saved_vs_two_pass":
                fc["hbm_bytes_saved_vs_two_pass"],
            "hbm_traffic_reduction_x": fc["hbm_traffic_reduction_x"],
        }
    rec["verdict"] = (
        f"binding floor = {'MXU' if mxu_c > hbm_floor_ms else 'HBM'} "
        f"({floor:.1f} ms, dispatch-corrected) at HIGHEST-precision f32 "
        f"matmul ({rec['mxu_achieved_tflops_f32_highest']} TFLOP/s); "
        f"block-skip kernel {rec['corrected']['kernel_ms']} ms "
        f"({rec['corrected']['pct_of_roof']}% of roof) vs "
        f"{rec['corrected']['kernel_ms_before']} ms "
        f"({rec['corrected']['pct_of_roof_before']}%) without = "
        f"{rec['corrected']['block_skip_speedup']}x on the kernel; "
        f"measured extraction term "
        f"{rec['corrected']['extraction_term_ms']} ms over {total_iters} "
        f"iters ({total_iters_noskip} without skip); sort epilogue is "
        f"below the noise (raw solve "
        f"{rec['raw_ms']['solve_with_epilogue']} vs kernel "
        f"{rec['raw_ms']['kernel_only']} ms); each dispatch adds "
        f"~{rec['dispatch_overhead_ms']} ms wall time")

    # One schema-1 RunRecord (obs.run); the counters block carries the
    # kernel cost model WITH the measured extraction term folded in
    # (obs.kernel_cost, iters_total) — measured, not a lower bound.
    from dmlp_tpu.obs.kernel_cost import extract_topk_cost
    from dmlp_tpu.obs.run import RunRecord
    record = RunRecord(
        kind="roofline", tool="tools/roofline_extract",
        config={"device": dev.device_kind, "shape": [n, q, a],
                "k": args.k, "kc": kc, "reps": args.reps},
        metrics=rec,
        counters=extract_topk_cost(qpad, npad, a, kc,
                                   iters_total=total_iters))
    record.write(args.out)
    print(record.to_json())
    return 0


if __name__ == "__main__":
    sys.exit(main())
