"""Benchmark alternative implementations of the seg-step's two hot ops
(segment gather, candidate merge top-k) and the one-big-chunk layout, to
pick the round-3 solve design empirically. Amortized in-jit loops as in
profile_amortized.py."""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

R = 10


def amortized(make_body, *args, repeats=R):
    @jax.jit
    def loop(*a):
        def body(_, c):
            return make_body(c * 1e-30, *a)
        return jax.lax.fori_loop(0, repeats, body, jnp.float32(0.0))

    float(loop(*args))
    t0 = time.perf_counter()
    float(loop(*args))
    return (time.perf_counter() - t0) / repeats * 1e3


def main() -> int:
    nq, a, k = 10240, 64, 40
    out = {}

    for dblock in (51200, 204800):
        nseg = dblock // 128
        s = min(nseg, k + 16)
        # The gather variants materialize a perturbed copy of the tile, so
        # the big block needs 2x tile bytes on device: halve the query
        # rows there (per-query costs are linear in nq; the variant
        # RANKING this tool exists for is unchanged) and generate on
        # device — a host f64 standard_normal of (10240, 204800) is
        # 16.8 GB of host RAM for no reason.
        nq_b = nq // 2 if dblock >= 204800 else nq
        tile = jnp.abs(jax.random.normal(
            jax.random.PRNGKey(0), (nq_b, dblock), jnp.float32)) * 100
        segmin = tile.reshape(nq_b, nseg, 128).min(axis=-1)
        seg_idx = jax.lax.top_k(-segmin, s)[1]
        cand = jnp.take_along_axis(
            tile.reshape(nq_b, nseg, 128), seg_idx[:, :, None], axis=1
        ).reshape(nq_b, s * 128)
        carry = jnp.zeros((nq_b, k), jnp.float32)
        float(jnp.sum(cand))
        tag = f"b{dblock}_q{nq_b}"  # nq in the key: cross-round
        # artifacts must not be conflated when the workload halves

        # seg_topk at this nseg
        out[f"{tag}/seg_topk_{nseg}_to_{s}"] = amortized(
            lambda e, sm: jnp.sum(jax.lax.top_k(-(sm + e), s)[0]), segmin)

        # gather variants
        out[f"{tag}/gather_take_along"] = amortized(
            lambda e, t, si: jnp.sum(jnp.take_along_axis(
                (t + e).reshape(nq_b, nseg, 128), si[:, :, None], axis=1)),
            tile, seg_idx)

        def gather_onehot(e, t, si):
            oh = (si[:, :, None] == jnp.arange(nseg)[None, None, :]
                  ).astype(jnp.float32)          # (nq_b, s, nseg)
            g = jax.lax.dot_general(
                oh, (t + e).reshape(nq_b, nseg, 128),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # (nq_b, s, 128)
            return jnp.sum(g)
        out[f"{tag}/gather_onehot_matmul"] = amortized(
            gather_onehot, tile, seg_idx)

        # merge variants (carry + cand -> top k)
        out[f"{tag}/merge_direct"] = amortized(
            lambda e, c, cd: jnp.sum(jax.lax.top_k(
                -jnp.concatenate([c, cd + e], axis=-1), k)[0]),
            carry, cand)

        def merge_2stage(e, c, cd):
            c3 = (cd + e).reshape(nq_b, s, 128)
            t1 = jax.lax.top_k(-c3, k)[0]            # (nq, s, k)
            allc = jnp.concatenate([c, -t1.reshape(nq_b, s * k)], axis=-1)
            return jnp.sum(jax.lax.top_k(-allc, k)[0])
        out[f"{tag}/merge_2stage"] = amortized(merge_2stage, carry, cand)

        def merge_sortseg(e, c, cd):
            c3 = jax.lax.sort((cd + e).reshape(nq_b, s, 128), dimension=-1)
            t1 = c3[:, :, :k]
            allc = jnp.concatenate([c, t1.reshape(nq_b, s * k)], axis=-1)
            return jnp.sum(jax.lax.top_k(-allc, k)[0])
        out[f"{tag}/merge_sortseg"] = amortized(merge_sortseg, carry, cand)

    # End-to-end seg solve, one big chunk vs 4 chunks, via streaming_topk.
    from dmlp_tpu.ops.pallas_distance import pallas_interpret
    from dmlp_tpu.ops.topk import streaming_topk
    native = not pallas_interpret()
    rng = np.random.default_rng(0)
    n = 204800
    q = jnp.asarray(rng.uniform(0, 100, (nq, a)), jnp.float32)
    d = jnp.asarray(rng.uniform(0, 100, (n, a)), jnp.float32)
    lab = jnp.asarray(rng.integers(0, 10, n, dtype=np.int32))
    ids = jnp.arange(n, dtype=jnp.int32)
    float(jnp.sum(d))
    import functools
    for db in (51200, 102400, 204800):
        # One-big-chunk at full Q needs ~2x the 8.4 GB live tile in HBM
        # (tile + selection temps) — compile OOM on a 16 GB chip; halve
        # the query rows there (linear in Q, ranking unchanged).
        qe = q[: nq // 2] if db >= 204800 else q
        fn = jax.jit(functools.partial(
            streaming_topk, k=k, data_block=db, select="seg",
            use_pallas=native))
        out[f"solve_seg_dblock{db}_q{qe.shape[0]}"] = amortized(
            lambda e, qq, d, l, i, _fn=fn: jnp.sum(
                _fn(qq + e, d, l, i).dists),
            qe, d, lab, ids, repeats=3)

    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
