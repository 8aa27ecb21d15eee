"""The top-k kernel's share of its roofline, in percent: the least time
the chip could take for one micro-batch's scan (``kernel_cost``, peaks
from ``peaks.json``) over the kernel time the trace shows for it. The
scan is of the rows the batch's folds visit: ``scan_shape`` is the whole
corpus in ``dispatches`` chunks, a batch that folded fewer (the spans'
``chunks``: ``kernel_ms``) is charged that share of the rows, so a
pruned chunk is work spared and never a share over 100%."""

from benchmark import kernel_cost
from benchmark.readers import kernel_ms


def scanned(shape, folds: float):
    """``scan_shape`` cut to the ``folds`` chunks a batch visited."""
    return {**shape, "n": shape["n"] * folds / shape["dispatches"],
            "dispatches": folds}


def read(ctx, pattern: str, span: str = "serve.solve_extract",
         arg: str = "chunks"):
    s = kernel_ms.per_batch_seconds(ctx, pattern, span, arg)
    if s is None or ctx.scan_shape is None:
        return None
    cost = kernel_cost.topk_scan_cost(**scanned(
        ctx.scan_shape, kernel_ms.folds_per_batch(ctx, span, arg)))
    roof = kernel_cost.roofline(cost, ctx.peaks, s)
    ctx.notes["kernel_roofline_bound"] = roof["bound"]
    return roof["pct"]
