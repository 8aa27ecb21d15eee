"""Seconds the named spans cover over the WHOLE run, set-up included
(``ctx.spans``, not the window's): the length of the union of their
intervals, so that a span nested in another of the list, or two that
overlap, are counted once. ``minus`` names spans whose intervals are
taken out again (warm-up without the staging that runs inside it), so
that two metrics of one layer do not count the same seconds."""

from benchmark import trace_reduce


def _covered(ctx, names) -> float:
    wanted = set(names)
    return sum(b - a for a, b in trace_reduce.union(
        (s["t0"], s["t1"]) for s in ctx.spans if s["name"] in wanted))


def read(ctx, names, minus=()):
    wanted = set(names)
    if not any(s["name"] in wanted for s in ctx.spans):
        return None
    # |A \ B| = |A u B| - |B|
    return _covered(ctx, wanted | set(minus)) - _covered(ctx, minus)
