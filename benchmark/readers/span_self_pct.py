"""A span's self time, in percent: over the window's ``parent`` spans,
the part of their duration that none of the named ``children`` spans
covers (summed self time over summed duration). The children's
intervals are clipped to the parent and merged, so that overlapping or
nested children are not counted twice. 0 says the children tile the
parent; a large share says time passes inside the parent where no finer
span looks."""

from benchmark import trace_reduce


def read(ctx, parent: str, children):
    parents = ctx.window_spans(parent)
    total = sum(p["t1"] - p["t0"] for p in parents)
    if not parents or total <= 0:
        return None
    kids = [(s["t0"], s["t1"]) for name in children
            for s in ctx.window_spans(name)]
    covered = 0.0
    for p in parents:
        inside = [(max(a, p["t0"]), min(b, p["t1"])) for a, b in kids
                  if b > p["t0"] and a < p["t1"]]
        covered += sum(b - a for a, b in trace_reduce.union(inside))
    return 100.0 * (total - covered) / total
