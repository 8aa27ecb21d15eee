"""A statistic of one numeric argument over the named spans inside the
window, times ``scale``: ``single.hazard``'s ``clear_min``,
``single.finalize``'s ``gather_bytes``. Spans that do not carry the
argument are left out; a program whose spans never carry it (one older
than the argument) gives nothing to read."""

from benchmark.readers import stat_of


def read(ctx, name: str, arg: str, stat: str = "median",
         scale: float = 1.0):
    vals = [s["args"][arg] for s in ctx.window_spans(name)
            if isinstance(s["args"].get(arg), (int, float))]
    one = stat_of(vals, stat)
    return None if one is None else one * scale
