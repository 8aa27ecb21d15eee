"""Device time of the top-k kernel per micro-batch, in ms, with the
batches counted from the program's spans and not from a counter of the
daemon: a micro-batch is one ``span`` (``serve.solve_multipass``: the
enqueue of every pass and the fence that waits for them) that lies
wholly inside the traced window, and its kernel time is the summed
duration of the trace's kernel events inside it. The span says how many
kernel calls it made (``chunks`` folds of pass 1 and one whole-stack
sweep for each further pass, ``chunks + passes - 1``): a span that does
not hold exactly that many events (the window's edge cut it, or the
program is one whose span does not cover the device's work, or carries
no ``chunks``) is left out. The mean over the whole batches; ``None``
where the window holds none."""

from benchmark import trace_reduce

#: what the trace's clock and the host's may differ by once aligned on
#: ``bench.clock_sync``; batches lie hundreds of milliseconds apart
SLACK_NS = 2e6


def whole_batches(ctx, pattern: str, span: str):
    """[{"seconds", "chunks", "passes"}], one a whole micro-batch."""
    if ctx.trace is None or "sync_pc" not in ctx.notes:
        return []
    spans = [s for s in ctx.spans if s["name"] == span
             and all(isinstance(s["args"].get(k), int)
                     for k in ("chunks", "passes"))]
    lo, hi = ctx.trace["window_ns"]
    evs = trace_reduce.kernel_events(ctx.trace, pattern)
    out = []
    for s, c in zip(spans, trace_reduce.spans_on_trace_clock(
            spans, ctx.notes["sync_pc"], ctx.trace["sync_ns"])):
        a, b = c["start_ns"] - SLACK_NS, c["end_ns"] + SLACK_NS
        if a < lo or b > hi:
            continue
        inside = [e["dur_ns"] for e in evs if e["start_ns"] >= a
                  and e["start_ns"] + e["dur_ns"] <= b]
        chunks, passes = s["args"]["chunks"], s["args"]["passes"]
        if len(inside) == chunks + passes - 1:
            out.append({"seconds": sum(inside) / 1e9, "chunks": chunks,
                        "passes": passes})
    return out


def read(ctx, pattern: str, span: str):
    got = whole_batches(ctx, pattern, span)
    if not got:
        return None
    return 1e3 * sum(b["seconds"] for b in got) / len(got)
