"""``span_arg`` over the named spans that ended BEFORE the window: what
set-up said of itself (``serve.stage_chunks``'s ``pad_bytes``).
``span_arg`` reads the window's spans, and staging is over by then.
Spans that do not carry the argument are left out; a program whose
set-up never carries it (one older than the argument) gives nothing to
read."""

from benchmark.readers import stat_of


def read(ctx, name: str, arg: str, stat: str = "median",
         scale: float = 1.0):
    start = ctx.window_pc[0]
    vals = [s["args"][arg] for s in ctx.spans
            if s["name"] == name and s["t1"] <= start
            and isinstance(s["args"].get(arg), (int, float))]
    one = stat_of(vals, stat)
    return None if one is None else one * scale
