"""The top-k kernel's share of ONE chip's roofline on a mesh, in
percent: the least time a chip could take for its share of one
micro-batch's scan (``kernel_cost_mesh``: N / R rows for nq / C queries;
peaks from ``peaks.json`` are one chip's) over the kernel time the trace
shows for one chip and one micro-batch (``kernel_ms_mesh``); a batch
that scheduled fewer chunks than hold rows is charged that share of the
rows (``kernel_roofline.scanned``)."""

from benchmark import kernel_cost, kernel_cost_mesh
from benchmark.readers import kernel_ms, kernel_ms_mesh, kernel_roofline


def read(ctx, pattern: str, mesh):
    s = kernel_ms_mesh.per_batch_seconds(ctx, pattern, mesh)
    if s is None or ctx.scan_shape is None:
        return None
    folds = kernel_ms.folds_per_batch(ctx, kernel_ms_mesh.SPAN,
                                      kernel_ms_mesh.ARG)
    cost = kernel_cost_mesh.topk_scan_cost_per_chip(
        mesh=mesh, **kernel_roofline.scanned(ctx.scan_shape, folds))
    roof = kernel_cost.roofline(cost, ctx.peaks, s)
    ctx.notes["kernel_roofline_mesh_bound"] = roof["bound"]
    return roof["pct"]
