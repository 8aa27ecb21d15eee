"""How unevenly the chips of a mesh were busy in the traced window, in
percent: (busiest - least busy) / mean of the per-chip busy seconds
(``trace_reduce.busy``'s ``per_device_s``). 0 says every shard did the
same work; a single chip has no skew to read."""

from benchmark import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    per = trace_reduce.busy(ctx.trace).get("per_device_s") or {}
    mean = sum(per.values()) / len(per) if per else 0.0
    if len(per) < 2 or mean <= 0:
        return None
    return 100.0 * (max(per.values()) - min(per.values())) / mean
