"""The top-k kernel's share of its roofline, in percent: the least time
the chip could take for one micro-batch's scan (``kernel_cost``, peaks
from ``peaks.json``) over the kernel time the trace shows for it."""

from benchmark import kernel_cost
from benchmark.readers import kernel_ms


def read(ctx, pattern: str):
    s = kernel_ms.per_batch_seconds(ctx, pattern)
    if s is None or ctx.scan_shape is None:
        return None
    cost = kernel_cost.topk_scan_cost(**ctx.scan_shape)
    roof = kernel_cost.roofline(cost, ctx.peaks, s)
    ctx.notes["kernel_roofline_bound"] = roof["bound"]
    return roof["pct"]
