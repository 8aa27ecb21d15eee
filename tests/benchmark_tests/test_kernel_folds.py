"""``kernel_ms.*`` / ``kernel_roofline.*`` count a micro-batch's kernel
calls from the window's own spans (PR 45), on hand-made traces with
hand-computed answers: the ``chunks`` of ``serve.solve_extract``
(``scheduled`` of ``fleet.solve_resident`` on a mesh), not the daemon's
count of chunks that hold rows, which is right only while nothing is
pruned."""

import pytest

from benchmark import kernel_cost
from benchmark.readers import (kernel_ms, kernel_ms_mesh, kernel_roofline,
                               kernel_roofline_mesh)
from benchmark.run import Context

KERNEL = 'custom_call_target="tpu_custom_call"'
MS = 1e6
E, F = "serve.solve_extract", "fleet.solve_resident"
SHAPE = {"nq": 1024, "n": 4_000_000, "na": 128, "kc": 32, "itemsize": 4,
         "dispatches": 4}                    # four chunks hold rows


def ev(start_ms, dur_ms, plane=0):
    return {"plane": f"/device:TPU:{plane}", "line": "XLA Ops",
            "name": f"%dmlp_topk_fused.5 = custom-call(), {KERNEL}",
            "start_ns": start_ms * MS, "dur_ns": dur_ms * MS}


def ctx_of(spans, events):
    ctx = Context()
    ctx.window_pc = (0.0, 100.0)
    ctx.spans = [{"name": n, "t0": t, "t1": t + 0.001, "args": a}
                 for n, t, a in spans]
    ctx.trace = {"events": events, "sync_ns": 0.0,
                 "window_ns": [0.0, 10_000 * MS]}
    ctx.scan_shape = dict(SHAPE)
    ctx.peaks = {"flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
    return ctx


#: two micro-batches: one folds all four chunks, one has two pruned
FULL = [ev(1000 + 10 * i, 10) for i in range(4)]
PRUNED = [ev(2000 + 10 * i, 10) for i in range(2)]


def test_a_pruned_batch_is_a_batch():
    """6 events of 10 ms are TWO batches (4 + 2 folds, the spans say),
    30 ms a batch; over the 4 chunks that hold rows they would be 1.5
    batches of 40 ms."""
    ctx = ctx_of([(E, 1.0, {"chunks": 4}), (E, 2.0, {"chunks": 2})],
                 FULL + PRUNED)
    assert kernel_ms.folds_per_batch(ctx, E, "chunks") == 3.0
    assert kernel_ms.read(ctx, KERNEL) == pytest.approx(30.0)
    # the share is of the rows the folds visited: 3 of 4 chunks' rows
    cost = kernel_cost.topk_scan_cost(1024, 3_000_000, 128, 32, 4, 3)
    want = kernel_cost.roofline(cost, ctx.peaks, 0.030)["pct"]
    assert kernel_roofline.read(ctx, KERNEL) == pytest.approx(want)
    whole = kernel_cost.roofline(kernel_cost.topk_scan_cost(**SHAPE),
                                 ctx.peaks, 0.030)["pct"]
    assert want == pytest.approx(whole * 0.75, rel=1e-3)


def test_nothing_pruned_reads_as_the_count_of_chunks_did():
    ctx = ctx_of([(E, 1.0, {"chunks": 4}), (E, 2.0, {"chunks": 4})],
                 FULL + [ev(2000 + 10 * i, 10) for i in range(4)])
    assert kernel_ms.read(ctx, KERNEL) == pytest.approx(40.0)
    want = kernel_cost.roofline(kernel_cost.topk_scan_cost(**SHAPE),
                                ctx.peaks, 0.040)["pct"]
    assert kernel_roofline.read(ctx, KERNEL) == want      # to the bit


@pytest.mark.parametrize("spans", [
    [], [(E, 1.0, {})], [(E, 1.0, {"chunks": 0})],
    [("serve.micro_batch", 1.0, {"chunks": 4})],
    [(E, 500.0, {"chunks": 4})],                # outside the window
], ids=["no_span", "no_arg", "no_fold", "another_span", "outside"])
def test_a_window_whose_spans_name_no_fold_reads_nothing(spans):
    ctx = ctx_of(spans, FULL)
    assert kernel_ms.read(ctx, KERNEL) is None
    assert kernel_roofline.read(ctx, KERNEL) is None


def test_the_mesh_counts_what_its_fold_scheduled():
    """Two chips, each 4 + 2 events of 10 ms: two batches a chip, 30 ms
    a batch and chip; each chip charged its half of three chunks' rows."""
    events = [dict(e, plane=f"/device:TPU:{p}") for p in (0, 1)
              for e in FULL + PRUNED]
    ctx = ctx_of([(F, 1.0, {"scheduled": 4, "chunks": 4}),
                  (F, 2.0, {"scheduled": 2, "chunks": 2})], events)
    assert kernel_ms_mesh.read(ctx, KERNEL, [2, 1]) == pytest.approx(30.0)
    cost = kernel_cost.topk_scan_cost(1024, 1_500_000, 128, 32, 4, 3)
    want = kernel_cost.roofline(cost, ctx.peaks, 0.030)["pct"]
    assert kernel_roofline_mesh.read(ctx, KERNEL, [2, 1]) \
        == pytest.approx(want)
    # a one-chip daemon's spans are not the mesh's
    ctx = ctx_of([(E, 1.0, {"chunks": 4})], events)
    assert kernel_ms_mesh.read(ctx, KERNEL, [2, 1]) is None
