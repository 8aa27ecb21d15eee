"""How late the load generator itself ran: a statistic of (sent - due)
over the window's requests, in ms. A starved generator reads as a fast
server, so this stands beside the tails."""

from benchmark.readers import stat_of


def read(ctx, stat: str = "p95"):
    return stat_of([r["lag_ms"] for r in ctx.requests], stat)
