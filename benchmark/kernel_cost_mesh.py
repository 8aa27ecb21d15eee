"""One chip's share of a top-k scan that a mesh runs, from its shapes.

``kernel_cost.topk_scan_cost`` counts the scan of the WHOLE corpus; over
one chip's peak and one chip's kernel time that reads R x C times the
true share on an R x C mesh. Here the same count for what ONE chip of a
("data" R, "query" C) mesh does in one micro-batch: it scans its N / R
rows for its nq / C queries in ``dispatches`` kernel calls (one per
resident chunk of its shard), each carrying its (nq / C, kc) running
lists in and out.

- operations: 2 * (nq / C) * (N / R) * na (the cross-term matmul alone,
  as ``kernel_cost`` counts it);
- bytes: the shard's rows once and, per dispatch, the chip's query
  panel and its two running lists read and written.

The all-gather merge is not the kernel's and is not counted here.
"""

from __future__ import annotations

from typing import Dict, Sequence

from benchmark import kernel_cost


def topk_scan_cost_per_chip(nq: int, n: int, na: int, kc: int,
                            itemsize: int, dispatches: int,
                            mesh: Sequence[int]) -> Dict[str, float]:
    r, c = (int(x) for x in mesh)
    if r < 1 or c < 1:
        raise ValueError(f"illegal mesh {list(mesh)}")
    # ceil: the fullest chip's share, where rows or queries do not divide
    return kernel_cost.topk_scan_cost(
        nq=-(-nq // c), n=-(-n // r), na=na, kc=kc, itemsize=itemsize,
        dispatches=dispatches)
