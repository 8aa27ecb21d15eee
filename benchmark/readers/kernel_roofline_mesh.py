"""The top-k kernel's share of ONE chip's roofline on a mesh, in
percent: the least time a chip could take for its share of one
micro-batch's scan (``kernel_cost_mesh``: N / R rows for nq / C queries;
peaks from ``peaks.json`` are one chip's) over the kernel time the trace
shows for one chip and one micro-batch (``kernel_ms_mesh``)."""

from benchmark import kernel_cost, kernel_cost_mesh
from benchmark.readers import kernel_ms_mesh


def read(ctx, pattern: str, mesh):
    s = kernel_ms_mesh.per_batch_seconds(ctx, pattern, mesh)
    if s is None or ctx.scan_shape is None:
        return None
    cost = kernel_cost_mesh.topk_scan_cost_per_chip(mesh=mesh,
                                                    **ctx.scan_shape)
    roof = kernel_cost.roofline(cost, ctx.peaks, s)
    ctx.notes["kernel_roofline_mesh_bound"] = roof["bound"]
    return roof["pct"]
