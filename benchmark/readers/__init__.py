"""Per-layer metric readers: one small module each, ``read(ctx, **args)``
returning a number, or None when it finds nothing to read (the harness
then leaves the metric out of the line)."""

import statistics
from typing import Optional, Sequence


def stat_of(values: Sequence[float], stat: str) -> Optional[float]:
    vals = sorted(values)
    if not vals:
        return None
    if stat == "median":
        return statistics.median(vals)
    if stat == "mean":
        return statistics.fmean(vals)
    if stat.startswith("p"):
        return percentile(vals, float(stat[1:]))
    raise ValueError(f"unknown statistic {stat!r}")


def percentile(sorted_vals: Sequence[float], pct: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    rank = max(1, -(-len(sorted_vals) * pct // 100))
    return sorted_vals[int(rank) - 1]
