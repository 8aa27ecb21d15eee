"""The top-k kernel's share of its roofline over a micro-batch that
takes several passes, in percent. The work is ONE scan of the corpus
(``kernel_cost.topk_scan_cost`` at the bucket's whole candidate width,
one dispatch a resident chunk), counted once a batch whatever the
number of passes the program takes to fill that width; the time is the
kernel time of all of them (``kernel_ms_by_span``). So the share reads
the same work whatever implements it: three sweeps of the corpus read
about a third of what one would, and nothing can read over 100%."""

from benchmark import kernel_cost
from benchmark.readers import kernel_ms_by_span


def read(ctx, pattern: str, span: str, cycle: str = "serve.cycle"):
    got = kernel_ms_by_span.whole_batches(ctx, pattern, span, cycle)
    if not got or ctx.scan_shape is None:
        return None
    seconds = sum(b["seconds"] for b in got) / len(got)
    cost = kernel_cost.topk_scan_cost(
        **{**ctx.scan_shape, "dispatches": got[0]["chunks"]})
    roof = kernel_cost.roofline(cost, ctx.peaks, seconds)
    ctx.notes["kernel_roofline_by_span_bound"] = roof["bound"]
    return roof["pct"]
