"""Device time of the top-k kernel per micro-batch, in ms: the summed
duration of the trace's kernel events over the number of micro-batches
they make up (events / dispatches a batch — one dispatch per resident
chunk)."""

from benchmark import trace_reduce


def per_batch_seconds(ctx, pattern: str):
    if ctx.trace is None or not ctx.kernel_dispatches:
        return None
    evs = trace_reduce.kernel_events(ctx.trace, pattern)
    batches = len(evs) / ctx.kernel_dispatches
    if batches < 1:
        return None
    return sum(e["dur_ns"] for e in evs) / 1e9 / batches


def read(ctx, pattern: str):
    s = per_batch_seconds(ctx, pattern)
    return None if s is None else s * 1e3
