"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window, averaged over the chips)."""

from benchmark import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    return trace_reduce.idle_pct(ctx.trace)
