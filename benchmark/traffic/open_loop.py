"""Traffic kind ``open_loop``: independent users, arrivals on a schedule.

Requests are due at Poisson-like instants at ``rate_per_s`` whether or
not earlier ones have returned. Every seed gets the SAME multiset of
inter-arrival gaps (the exponential distribution's quantiles) and of
request sizes (``sizes`` with ``weights``, by largest remainder), in
another order, so two seeds offer the same work. Every
``debug_every``-th request asks for neighbours and distances too, which
the output check reads.
"""

from typing import Any, Dict, List

import numpy as np


def _sizes(params: Dict[str, Any], n: int) -> List[int]:
    sizes = [int(s) for s in params["sizes"]]
    w = np.asarray(params.get("weights") or [1.0] * len(sizes), float)
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return [s for s, c in zip(sizes, counts) for _ in range(c)]


def plan(params: Dict[str, Any], seed: int, seconds: float
         ) -> Dict[str, Any]:
    rate = float(params["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed), 11])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)     # unit exponential
    u = np.cumsum(rng.permutation(gaps))
    u *= (n / u[-1]) * (1.0 - 0.5 / n)              # last due inside window
    due = u / rate
    sizes = rng.permutation(np.asarray(_sizes(params, n)))
    every = int(params.get("debug_every", 0))
    return {
        "mode": "open",
        "clients": int(params["senders"]),
        "sizes": [int(s) for s in sizes],
        "debug": [bool(every) and i % every == every - 1
                  for i in range(n)],
        "due_s": [float(t) for t in due],
    }
