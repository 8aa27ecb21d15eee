"""The jax spellings this tree is written against, in one place.

The installed jax (0.9.0) has ``jax.shard_map(check_vma=)``,
``jax.lax.axis_size`` and ``pltpu.CompilerParams``; every call in the
tree goes through these functions, so a later rename is one edit here.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis_name) -> int:
    return jax.lax.axis_size(axis_name)


def host_memory_kind() -> str:
    """The host-DRAM memory kind this backend addresses: "pinned_host"
    on TPU runtimes; XLA:CPU may expose only "unpinned_host". The
    offload paths place host-resident leaves with this kind instead of
    hard-coding the TPU spelling."""
    kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    if "pinned_host" in kinds:
        return "pinned_host"
    for k in sorted(kinds):
        if "host" in k:
            return k
    return "pinned_host"


def tpu_compiler_params(**kwargs):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)
