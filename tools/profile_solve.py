"""Decompose the device KNN solve into per-op timings on the real chip.

Round-2 review weak #1: the fenced device-solve number (1616 ms)
contradicts the "transfer-bound" narrative. This script times each op of the "seg" selection
step in isolation (matmul, fused pallas dist+segmin, segment top_k, segment
gather, candidate merge top_k) at the exact benchmark shape, so the dominant
cost is measured, not guessed. Output: one JSON object to stdout; commit as
PROFILE_r03.json.

Every timing is fenced by a dependent scalar readback.
"""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def fence(x) -> float:
    return float(jnp.ravel(x)[0])


def timeit(fn, *args, repeats=3):
    out = fn(*args)
    fence(out[0] if isinstance(out, (tuple, list)) else out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    fence(out[0] if isinstance(out, (tuple, list)) else out)
    return (time.perf_counter() - t0) / repeats * 1e3


def main() -> int:
    n, nq, a, k = 204800, 10240, 64, 40
    dblock = 51200
    nseg = dblock // 128
    s = min(nseg, k + 16)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.uniform(0, 100, (nq, a)), jnp.float32)
    d = jnp.asarray(rng.uniform(0, 100, (dblock, a)), jnp.float32)
    lab = jnp.asarray(rng.integers(0, 10, dblock, dtype=np.int32))
    ids = jnp.arange(dblock, dtype=jnp.int32)
    fence(jnp.sum(d))

    out = {"shape": {"n": n, "nq": nq, "a": a, "k": k, "dblock": dblock,
                     "nseg": nseg, "s": s}}

    # 1. Raw cross-term matmul (the MXU floor).
    mm = jax.jit(lambda q, d: q @ d.T)
    out["matmul_ms"] = timeit(mm, q, d)

    # 2. Fused pallas dist+segmin (one pass over the tile).
    from dmlp_tpu.ops.pallas_distance import (fused_dist_segmin,
                                              pallas_interpret)
    native = not pallas_interpret()
    out["pallas_native"] = native
    fd = functools.partial(fused_dist_segmin, interpret=not native)
    out["fused_dist_segmin_ms"] = timeit(fd, q, d, ids)

    # 3. XLA dist tile alone (unfused norm expansion) for comparison.
    from dmlp_tpu.ops.distance import masked_pairwise_sq_l2
    dist_xla = jax.jit(lambda q, d, i: masked_pairwise_sq_l2(q, d, i))
    out["xla_dist_tile_ms"] = timeit(dist_xla, q, d, ids)

    tile, segmin = fd(q, d, ids)
    fence(tile)

    # 4. Segment-min reduce from a resident tile (XLA second pass).
    segred = jax.jit(
        lambda t: t.reshape(nq, nseg, 128).min(axis=-1))
    out["segmin_reduce_ms"] = timeit(segred, tile)

    # 5. top_k over segment minima -> segment indices.
    seg_topk = jax.jit(lambda sm: jax.lax.top_k(-sm, s))
    out["seg_topk_ms"] = timeit(lambda sm: seg_topk(sm)[0], segmin)

    _, seg_idx = seg_topk(segmin)
    fence(seg_idx)

    # 6. Segment gather (take_along_axis on (nq, nseg, 128)).
    gat = jax.jit(lambda t, si: jnp.take_along_axis(
        t.reshape(nq, nseg, 128), si[:, :, None], axis=1
    ).reshape(nq, s * 128))
    out["seg_gather_ms"] = timeit(gat, tile, seg_idx)

    cand = gat(tile, seg_idx)
    fence(cand)

    # 6b. Label/id gather from (nseg, 128) by (nq, s).
    lgat = jax.jit(
        lambda l, si: l.reshape(nseg, 128)[si].reshape(nq, s * 128))
    out["label_gather_ms"] = timeit(lgat, lab, seg_idx)

    # 7. Candidate merge top_k over (nq, s*128 + k).
    carry = jnp.zeros((nq, k), jnp.float32)
    mtk = jax.jit(lambda c, cd: jax.lax.top_k(
        -jnp.concatenate([c, cd], axis=-1), k))
    out["merge_topk_ms"] = timeit(lambda c, cd: mtk(c, cd)[0], carry, cand)

    # 7b. Straight full top_k over the whole tile (the "topk" select cost).
    ftk = jax.jit(lambda t: jax.lax.top_k(-t, k))
    out["full_tile_topk_ms"] = timeit(lambda t: ftk(t)[0], tile)

    # 7c. approx_max_k over the tile (recall-configurable alternative).
    atk = jax.jit(lambda t: jax.lax.approx_max_k(-t, k,
                                                 recall_target=0.99))
    out["approx_topk_ms"] = timeit(lambda t: atk(t)[0], tile)

    # 8. The whole seg step end-to-end at one chunk, then the full 4-chunk
    #    streaming solve (what bench.py's device_solve measures).
    from dmlp_tpu.ops.topk import init_topk, make_block_step, streaming_topk
    step = make_block_step("seg", k, native, jnp.float32)
    stepj = jax.jit(lambda c, q, da, dl, di: step(c, q, da, dl, di))
    init = init_topk(nq, k)
    out["seg_step_ms"] = timeit(
        lambda c, q, da, dl, di: stepj(c, q, da, dl, di).dists,
        init, q, d, lab, ids)

    dfull = jnp.asarray(rng.uniform(0, 100, (n, a)), jnp.float32)
    labf = jnp.asarray(rng.integers(0, 10, n, dtype=np.int32))
    idsf = jnp.arange(n, dtype=jnp.int32)
    fence(jnp.sum(dfull))
    solve = jax.jit(functools.partial(
        streaming_topk, k=k, data_block=dblock, select="seg",
        use_pallas=native))
    out["streaming_solve_seg_ms"] = timeit(
        lambda q, d, l, i: solve(q, d, l, i).dists, q, dfull, labf, idsf)

    solve_topk = jax.jit(functools.partial(
        streaming_topk, k=k, data_block=dblock, select="topk"))
    out["streaming_solve_topk_ms"] = timeit(
        lambda q, d, l, i: solve_topk(q, d, l, i).dists, q, dfull, labf, idsf)

    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
