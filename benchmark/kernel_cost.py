"""Operations and bytes the top-k scan needs, from its shapes alone.

The benchmark's own count (the program's ``obs/kernel_cost.py`` counts
per tile and can move with a refactor; this one cannot). One micro-batch
of ``nq`` padded queries scans ``n`` corpus rows of ``na`` attributes in
``dispatches`` kernel calls (one per resident chunk), each carrying the
running (nq, kc) distance and id lists in and out:

- operations: the cross-term matmul, 2 * nq * n * na (XLA's convention
  for a dot; norms, the expansion and the selection are not counted, so
  the share is of the matmul the algorithm cannot avoid);
- bytes: every corpus row once and, per dispatch, the query panel and
  the two running lists read and written.
"""

from __future__ import annotations

from typing import Dict


def topk_scan_cost(nq: int, n: int, na: int, kc: int, itemsize: int,
                   dispatches: int) -> Dict[str, float]:
    flops = 2.0 * nq * n * na
    lists = 2 * nq * kc * (4 + 4)          # f32 dists + i32 ids, in and out
    byts = (float(n) * na * itemsize
            + dispatches * (float(nq) * na * itemsize + lists))
    return {"flops": flops, "bytes": byts}


def roofline(cost: Dict[str, float], peaks: Dict[str, float],
             seconds: float) -> Dict[str, float]:
    """Share of the roofline: least time the chip could take (the larger
    of operations over peak FLOP/s and bytes over peak bytes/s) over the
    time taken, in percent, and which of the two bounds it."""
    t_flops = cost["flops"] / peaks["flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"pct": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "t_flops_s": t_flops, "t_bytes_s": t_bytes}
