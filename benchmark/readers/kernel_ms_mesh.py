"""Device time of the top-k kernel per micro-batch and per chip on a
mesh, in ms. Plane by plane: the summed duration of that chip's kernel
events over the micro-batches they make up on THAT chip (its events /
folds a batch — one kernel call per scheduled chunk of its shard, as the
window's own ``fleet.solve_resident`` spans say: ``kernel_ms``); then
the mean over the chips. ``mesh`` is the cell's (data, query)
shape: a trace whose kernel events lie on another number of chips than
the mesh has is not read (the corpus was not spread as the cell says).
"""

from benchmark import trace_reduce
from benchmark.readers import kernel_ms

SPAN, ARG = "fleet.solve_resident", "scheduled"


def per_plane_seconds(ctx, pattern: str, mesh):
    """{plane: seconds of kernel time a micro-batch} or None."""
    folds = kernel_ms.folds_per_batch(ctx, SPAN, ARG)
    if ctx.trace is None or not folds:
        return None
    by_plane = {}
    for ev in trace_reduce.kernel_events(ctx.trace, pattern):
        by_plane.setdefault(ev["plane"], []).append(ev["dur_ns"])
    if len(by_plane) != int(mesh[0]) * int(mesh[1]):
        return None
    out = {}
    for plane, durs in by_plane.items():
        batches = len(durs) / folds
        if batches < 1:
            return None
        out[plane] = sum(durs) / 1e9 / batches
    return out


def per_batch_seconds(ctx, pattern: str, mesh):
    per = per_plane_seconds(ctx, pattern, mesh)
    return None if per is None else sum(per.values()) / len(per)


def read(ctx, pattern: str, mesh):
    s = per_batch_seconds(ctx, pattern, mesh)
    return None if s is None else s * 1e3
