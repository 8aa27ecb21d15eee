"""Device-mesh construction — the declarative replacement for the MPI grid.

The reference hand-rolls a 2D process grid: ``MPI_Dims_create`` →
``MPI_Cart_create`` → ``MPI_Cart_sub`` row/col communicators
(engine.cpp:40-57). On TPU the same topology is one
``jax.sharding.Mesh((R, C), ("data", "query"))``: rows shard the dataset,
columns shard the queries, and per-axis collectives replace the
sub-communicators. The ICI/DCN hierarchy (the reference's
intra-node/inter-node split, run_bench.sh -N 2) comes for free from device
order within the mesh.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

DATA_AXIS = "data"    # shards the dataset (the reference's grid rows)
QUERY_AXIS = "query"  # shards the queries (the reference's grid columns)


def balanced_dims(n: int) -> Tuple[int, int]:
    """Near-square factorization R x C = n with R >= C.

    The analog of ``MPI_Dims_create(size, 2, dims)`` (engine.cpp:41): the
    data axis gets the larger factor (datasets are usually bigger than query
    batches).
    """
    c = int(n ** 0.5)
    while c > 1 and n % c != 0:
        c -= 1
    return n // c, c


def make_mesh(shape: Optional[Tuple[int, int]] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the 2D ("data", "query") mesh.

    ``shape=None`` auto-factorizes over all available devices (or the
    ``devices`` given). An explicit R x C shape takes the first R*C of
    them (``--mesh 2,1`` on a four-chip host); one that needs more
    devices than there are is an error.
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = balanced_dims(len(devices))
    r, c = shape
    if r * c > len(devices):
        raise ValueError(f"mesh shape {shape} needs {r * c} devices, "
                         f"have {len(devices)}")
    import numpy as np
    return Mesh(np.asarray(devices[:r * c]).reshape(r, c),
                (DATA_AXIS, QUERY_AXIS))
