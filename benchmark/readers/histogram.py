"""Mean of a telemetry-registry histogram over the window: the
difference of its (count, sum) between the window's two edges."""


def read(ctx, name: str):
    before, after = ctx.registry_before.get(name), ctx.registry_after.get(
        name)
    if not after:
        return None
    count = after["count"] - (before or {"count": 0})["count"]
    total = after["sum"] - (before or {"sum": 0.0})["sum"]
    return total / count if count else None
