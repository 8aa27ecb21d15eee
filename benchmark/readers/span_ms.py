"""A statistic of the durations of the named spans inside the window,
in ms; several names are summed statistic by statistic (a layer whose
time is split over two spans). Spans come from the program's tracer
(``obs/trace.py``) and from the harness's own ``bench.*`` spans."""

from benchmark.readers import stat_of


def read(ctx, names, stat: str = "median"):
    total = 0.0
    for name in names:
        vals = [(s["t1"] - s["t0"]) * 1e3 for s in ctx.window_spans(name)]
        one = stat_of(vals, stat)
        if one is None:
            return None
        total += one
    return total
