"""Amortized per-op timings: each op runs R times inside ONE jitted
lax.fori_loop with a carry-dependent input perturbation (defeats CSE), so
per-dispatch overhead, which swamps single-dispatch timings, divides
out. This is the measurement that decides
where the seg-select solve's per-chunk ~78 ms actually goes."""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

R = 10


def amortized(make_body, *args, repeats=R):
    """Time one jit'd fori_loop of `repeats` body iterations; return ms/iter.

    make_body(eps, *args) -> scalar-reducible array; eps is a carry-derived
    scalar (0.0 in practice) folded into an input so iterations chain.
    """
    @jax.jit
    def loop(*a):
        def body(_, c):
            return make_body(c * 1e-30, *a)
        return jax.lax.fori_loop(0, repeats, body, jnp.float32(0.0))

    float(loop(*args))  # compile + warm
    t0 = time.perf_counter()
    out = float(loop(*args))
    return (time.perf_counter() - t0) / repeats * 1e3


def main() -> int:
    nq, a, k = 10240, 64, 40
    dblock = 51200
    nseg = dblock // 128
    s = min(nseg, k + 16)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.uniform(0, 100, (nq, a)), jnp.float32)
    d = jnp.asarray(rng.uniform(0, 100, (dblock, a)), jnp.float32)
    lab = jnp.asarray(rng.integers(0, 10, dblock, dtype=np.int32))
    ids = jnp.arange(dblock, dtype=jnp.int32)
    tile = jnp.abs(jnp.asarray(
        rng.standard_normal((nq, dblock)), jnp.float32)) * 100
    segmin = tile.reshape(nq, nseg, 128).min(axis=-1)
    seg_idx = jax.lax.top_k(-segmin, s)[1]
    cand = jnp.take_along_axis(
        tile.reshape(nq, nseg, 128), seg_idx[:, :, None], axis=1
    ).reshape(nq, s * 128)
    carry = jnp.zeros((nq, k), jnp.float32)
    float(jnp.sum(cand))

    out = {"shape": {"nq": nq, "a": a, "k": k, "dblock": dblock, "s": s},
           "repeats": R}

    out["matmul"] = amortized(
        lambda e, q, d: jnp.sum((q + e) @ d.T), q, d)

    from dmlp_tpu.ops.distance import masked_pairwise_sq_l2
    out["xla_dist_tile"] = amortized(
        lambda e, q, d, i: jnp.sum(masked_pairwise_sq_l2(q + e, d, i)),
        q, d, ids)

    from dmlp_tpu.ops.pallas_distance import (fused_dist_segmin,
                                              pallas_interpret)
    native = not pallas_interpret()
    out["pallas_native"] = native

    def fused(e, q, d, i):
        t, sm = fused_dist_segmin(q + e, d, i, interpret=not native)
        return t[0, 0] + sm[0, 0]
    out["pallas_fused_dist_segmin"] = amortized(fused, q, d, ids)

    def fused_sum(e, q, d, i):
        t, sm = fused_dist_segmin(q + e, d, i, interpret=not native)
        return jnp.sum(sm)
    out["pallas_fused_dist_segmin_smsum"] = amortized(fused_sum, q, d, ids)

    out["segmin_reduce_from_tile"] = amortized(
        lambda e, t: jnp.sum((t + e).reshape(nq, nseg, 128).min(axis=-1)),
        tile)

    out["seg_topk_400_to_56"] = amortized(
        lambda e, sm: jnp.sum(jax.lax.top_k(-(sm + e), s)[0]), segmin)

    out["seg_gather"] = amortized(
        lambda e, t, si: jnp.sum(jnp.take_along_axis(
            (t + e).reshape(nq, nseg, 128), si[:, :, None], axis=1)),
        tile, seg_idx)

    out["label_gather"] = amortized(
        lambda e, l, si: jnp.sum(
            l.reshape(nseg, 128)[
                jnp.minimum(si, nseg - 1 + e.astype(jnp.int32))
            ].astype(jnp.float32)),
        lab, seg_idx)

    out["merge_topk_7208_to_40"] = amortized(
        lambda e, c, cd: jnp.sum(jax.lax.top_k(
            -jnp.concatenate([c, cd + e], axis=-1), k)[0]),
        carry, cand)

    out["full_tile_topk"] = amortized(
        lambda e, t: jnp.sum(jax.lax.top_k(-(t + e), k)[0]), tile)

    # Whole seg step (one chunk) amortized, pallas on and off.
    from dmlp_tpu.ops.topk import TopK, init_topk, make_block_step
    init = init_topk(nq, k)
    for use_pallas, name in ((native, "seg_step_pallas"),
                             (False, "seg_step_xla")):
        step = make_block_step("seg", k, use_pallas, jnp.float32)
        out[name] = amortized(
            lambda e, c0, q, d, l, i, _step=step: jnp.sum(
                _step(TopK(c0.dists + e, c0.labels, c0.ids),
                      q, d, l, i).dists),
            init, q, d, lab, ids)

    step_t = make_block_step("topk", k, False, jnp.float32)
    out["topk_step"] = amortized(
        lambda e, c0, q, d, l, i: jnp.sum(
            step_t(TopK(c0.dists + e, c0.labels, c0.ids),
                   q, d, l, i).dists),
        init, q, d, lab, ids)

    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
