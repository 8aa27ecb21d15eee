"""Device time of one named program per run of it and per chip, in ms.

A jitted program runs on a chip as one event of the line ``XLA
Modules`` (named ``jit_<function>(<fingerprint>)``); the operations it
is made of are the events of the line ``XLA Ops`` inside that interval
(a chip runs one program at a time). Plane by plane: the summed
duration of those operations over the runs of the program wholly inside
the traced window; then the mean over the chips that ran it. For the
mesh merge (``jit_dmlp_mesh_merge``: one run a micro-batch) that is the
all-gather and the re-select fusions, without the time the program
spent queued."""

import bisect
import re

from benchmark import trace_reduce

MODULES_LINE = re.compile(r"^XLA Modules")


def read(ctx, module: str):
    if ctx.trace is None:
        return None
    rx = re.compile(module)
    lo, hi = ctx.trace["window_ns"]
    runs = {}
    for ev in ctx.trace["events"]:
        if (trace_reduce.DEVICE_PLANE.match(ev["plane"])
                and MODULES_LINE.match(ev["line"])
                and rx.search(ev["name"]) and ev["start_ns"] >= lo
                and ev["start_ns"] + ev["dur_ns"] <= hi):
            runs.setdefault(ev["plane"], []).append(
                (ev["start_ns"], ev["start_ns"] + ev["dur_ns"]))
    if not runs:
        return None
    ops = trace_reduce.device_ops(ctx.trace)
    per_plane = []
    for plane, spans in runs.items():
        spans.sort()
        starts = [a for a, _ in spans]
        total = 0.0
        for op in ops.get(plane, ()):
            i = bisect.bisect_right(starts, op["start_ns"]) - 1
            if i >= 0 and op["start_ns"] + op["dur_ns"] <= spans[i][1]:
                total += op["dur_ns"]
        per_plane.append(total / len(spans))
    return sum(per_plane) / len(per_plane) / 1e6
