"""Sum of one numeric argument over the named spans in the window, as a
percentage of the queries the window's micro-batches (or solves) held:
``single.finalize``'s ``repairs`` over the queries finalized."""


def read(ctx, name: str, arg: str, per: str):
    spans = ctx.window_spans(name)
    base = sum(s["args"].get("queries", 0) for s in ctx.window_spans(per))
    if not spans or not base:
        return None
    return 100.0 * sum(s["args"].get(arg, 0) for s in spans) / base
