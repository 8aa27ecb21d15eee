"""Device time of the top-k kernel per micro-batch, in ms, where a
micro-batch takes several passes, with the batches counted from the
program's spans and not from a counter of the daemon.

The batcher thread's timeline is cut into ``cycle`` spans
(``serve.cycle``: one a delivered micro-batch, none overlapping), and a
cycle says which batch it began (``begun``) and which it delivered
(``batch``). ``span`` (``serve.solve_multipass``) says, for the batch
whose serial it carries (``batch``), how many kernel calls it made:
``chunks`` folds of pass 1 and one whole-stack sweep for each further
pass, ``chunks + passes - 1``. That span itself is NOT the interval:
since the batcher keeps two batches in the engine it runs from one
batch's enqueues to the end of its fence, over the next batch's
enqueues and whatever the device ran of them. A cycle that lies wholly
inside the traced window and holds exactly as many kernel events as the
batch it began made (the batch it delivered, where it began none) is
one micro-batch's kernel time: the device takes the batches in order
and each makes the same calls, so as many events in a row as one batch
makes are one of each. A cycle that holds another number (the window's
edge cut it, the device ran behind and a batch's events straddle the
cut, the program's spans carry no ``chunks``) is left out. The mean
over those cycles; ``None`` where the window holds none."""

from benchmark import trace_reduce


def whole_batches(ctx, pattern: str, span: str, cycle: str = "serve.cycle"):
    """[{"seconds", "chunks", "passes"}], one a whole micro-batch."""
    if ctx.trace is None or "sync_pc" not in ctx.notes:
        return []
    plans = {s["args"]["batch"]: (s["args"]["chunks"], s["args"]["passes"])
             for s in ctx.spans if s["name"] == span
             and all(isinstance(s["args"].get(k), int)
                     for k in ("batch", "chunks", "passes"))}
    cycles = [s for s in ctx.spans if s["name"] == cycle]
    lo, hi = ctx.trace["window_ns"]
    evs = trace_reduce.kernel_events(ctx.trace, pattern)
    out = []
    for s, c in zip(cycles, trace_reduce.spans_on_trace_clock(
            cycles, ctx.notes["sync_pc"], ctx.trace["sync_ns"])):
        plan = plans.get(s["args"].get("begun") or s["args"].get("batch"))
        a, b = c["start_ns"], c["end_ns"]
        if plan is None or a < lo or b > hi:
            continue
        inside = [e["dur_ns"] for e in evs if e["start_ns"] >= a
                  and e["start_ns"] + e["dur_ns"] <= b]
        chunks, passes = plan
        if len(inside) == chunks + passes - 1:
            out.append({"seconds": sum(inside) / 1e9, "chunks": chunks,
                        "passes": passes})
    return out


def read(ctx, pattern: str, span: str, cycle: str = "serve.cycle"):
    got = whole_batches(ctx, pattern, span, cycle)
    if not got:
        return None
    return 1e3 * sum(b["seconds"] for b in got) / len(got)
