"""Device time of the top-k kernel per micro-batch, in ms: the summed
duration of the trace's kernel events over the number of micro-batches
they make up: events over the folds a batch made, one kernel call a
resident chunk it folded. The folds are what the window's own spans say
ran (the mean of ``arg`` over the ``span`` spans: ``chunks`` of
``serve.solve_extract``), not the daemon's count of chunks that hold
rows: the two part the day a batch prunes a chunk."""

from benchmark import trace_reduce


def folds_per_batch(ctx, span: str, arg: str):
    """Mean kernel calls a micro-batch over the window's ``span``s."""
    vals = [s["args"][arg] for s in ctx.window_spans(span)
            if isinstance(s["args"].get(arg), int) and s["args"][arg] > 0]
    return sum(vals) / len(vals) if vals else None


def per_batch_seconds(ctx, pattern: str, span: str = "serve.solve_extract",
                      arg: str = "chunks"):
    folds = folds_per_batch(ctx, span, arg)
    if ctx.trace is None or not folds:
        return None
    evs = trace_reduce.kernel_events(ctx.trace, pattern)
    batches = len(evs) / folds
    if batches < 1:
        return None
    return sum(e["dur_ns"] for e in evs) / 1e9 / batches


def read(ctx, pattern: str, span: str = "serve.solve_extract",
         arg: str = "chunks"):
    s = per_batch_seconds(ctx, pattern, span, arg)
    return None if s is None else s * 1e3
