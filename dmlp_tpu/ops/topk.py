"""Exact-tie-break k-selection and blockwise merge.

The correctness contract is order-sensitive (checksums, survey §4). The
MEASURED oracle-binary comparator (r5: the binaries ran in-container and
were fuzzed on tie-adversarial inputs, golden.reference docstring /
tools/fuzz_vs_binaries.py) breaks both selection and report ties to the
**larger id**, label-free. ``jax.lax.top_k`` breaks ties by lowest index,
so it cannot express this; instead selection is a multi-operand
``jax.lax.sort`` over the composite key

    (distance asc, id desc)

— a strict total order (see dmlp_tpu.golden.reference).
Totality is what makes blockwise selection exact: top-k of a union equals
top-k of concatenated per-block top-k's, so the same primitive implements the
local select (engine.cpp:249-256), the root merge (engine.cpp:300-307), the
sharded all-gather merge, and the ring running merge.
"""

from __future__ import annotations

from typing import NamedTuple

import functools

import jax
import jax.numpy as jnp


def streaming_fallback(use_pallas: bool) -> str:
    """The array-ids selection strategy used wherever the extraction
    kernel cannot run (it needs affine per-block ids): the fused seg
    producer with Pallas, plain lax.top_k without. Single definition —
    config.resolve_streaming_select and streaming_topk both use it."""
    return "seg" if use_pallas else "topk"


class TopK(NamedTuple):
    """Per-query candidate lists, sorted by the selection order.

    Shapes are (..., k). Padding entries carry dist=+inf, label=-1, id=-1.
    """

    dists: jax.Array   # float
    labels: jax.Array  # int32
    ids: jax.Array     # int32


def select_topk(dists: jax.Array, labels: jax.Array, ids: jax.Array,
                k: int) -> TopK:
    """Select the k best (dist asc, id desc) along the last axis — the
    MEASURED oracle-binary comparator (label-free; golden.reference
    docstring / tools/fuzz_vs_binaries.py), identical to the report order.

    ``labels``/``ids`` broadcast against ``dists`` (e.g. (N,) vs (Q, N)).
    If k exceeds the axis size, results are padded with (+inf, -1, -1).
    """
    labels = jnp.broadcast_to(labels, dists.shape)
    ids = jnp.broadcast_to(ids, dists.shape)
    n = dists.shape[-1]
    if k > n:
        pad = k - n
        shape = dists.shape[:-1] + (pad,)
        dists = jnp.concatenate(
            [dists, jnp.full(shape, jnp.inf, dists.dtype)], axis=-1)
        labels = jnp.concatenate(
            [labels, jnp.full(shape, -1, labels.dtype)], axis=-1)
        ids = jnp.concatenate([ids, jnp.full(shape, -1, ids.dtype)], axis=-1)
    # Ascending lexicographic sort on (dist, -id): exactly the selection
    # total order; labels ride as payload. num_keys=2 (was 3 when
    # selection was label-aware) keeps everything int32/f32 (no x64).
    sd, _, sl, si = jax.lax.sort(
        (dists, -ids, labels, ids), num_keys=2, dimension=-1)
    return TopK(sd[..., :k], sl[..., :k], si[..., :k])


def merge_topk(a: TopK, b: TopK, k: int) -> TopK:
    """Merge two candidate lists into the k best — the root-merge analog
    (engine.cpp:289-308), also the ring engine's running-reduction step."""
    return select_topk(
        jnp.concatenate([a.dists, b.dists], axis=-1),
        jnp.concatenate([a.labels, b.labels], axis=-1),
        jnp.concatenate([a.ids, b.ids], axis=-1),
        k)


def streaming_topk(query_attrs: jax.Array, data_attrs: jax.Array,
                   data_labels: jax.Array, data_ids: jax.Array, k: int,
                   data_block: int, accum_dtype=jnp.float32,
                   select: str = "sort", use_pallas: bool = False) -> TopK:
    """Top-k nearest data points per query, streaming over data blocks.

    Computes (Qb x data_block) distance tiles one block at a time and folds
    each into a running top-k, so peak memory is O(Qb * (data_block + k))
    instead of O(Qb * N) — the blockwise-partial-reduce shape the reference
    implements across ranks (survey §5.7), here as a ``lax.scan`` on one chip
    (and reused per-shard by the distributed engines).

    ``data_attrs`` must be padded to a multiple of ``data_block`` with
    sentinel rows (id = -1); real N may be smaller.

    ``select`` picks the per-step merge: "sort" is the strict total order
    (reference tie semantics on device); "topk" is a ``lax.top_k`` partial
    reduce — ~4x faster on TPU, exact by distance, but distance ties keep
    the lowest *position* instead of the reference's larger-id
    preference. That matters only when a tie group straddles the candidate
    boundary k: the kept candidates may then exclude the preferred ones, a
    loss no downstream rescore can undo. Engines detect that hazard on host
    (dmlp_tpu.engine.finalize.boundary_overflow) and recompute affected
    queries exactly, so either path yields golden parity.
    """
    n = data_attrs.shape[0]
    assert n % data_block == 0, "pad data to a multiple of data_block first"
    nblocks = n // data_block
    qb = query_attrs.shape[0]

    if select == "extract":
        # The extraction kernel needs affine ids; this generic streaming
        # fold gets arbitrary id arrays, so apply the shared array-ids
        # fallback policy (config.resolve_streaming_select delegates to
        # the same function — one definition, no drift).
        select = streaming_fallback(use_pallas)

    blocks = (data_attrs.reshape(nblocks, data_block, -1),
              data_labels.reshape(nblocks, data_block),
              data_ids.reshape(nblocks, data_block))

    init = init_topk(qb, k, accum_dtype)
    if select == "seg" and (data_block % 128 != 0 or data_block < 256):
        select = "topk"  # seg needs whole 128-lane segments to pay off
    step = make_block_step(select, k, use_pallas, accum_dtype)

    out, _ = jax.lax.scan(
        lambda carry, blk: (step(carry, query_attrs, *blk), None),
        init, blocks)
    return out


@functools.partial(jax.jit, static_argnames=("qb", "k", "accum_dtype"))
def init_topk(qb: int, k: int, accum_dtype=jnp.float32) -> TopK:
    """Empty running top-k carry: all slots (+inf, -1, -1).

    Jitted (all-static args, one cached constant program per shape) so
    the eager chunk drivers can build carries under the sanitizer's
    transfer guard — eager ``jnp.full`` materializes its fill value via
    an implicit host->device transfer, which ``--sanitize`` disallows.
    """
    return TopK(
        jnp.full((qb, k), jnp.inf, accum_dtype),
        jnp.full((qb, k), -1, jnp.int32),
        jnp.full((qb, k), -1, jnp.int32))


def make_block_step(select: str, k: int, use_pallas: bool = False,
                    accum_dtype=jnp.float32):
    """One running-top-k fold step: (carry, queries, block) -> carry.

    Shared by the in-jit ``lax.scan`` (streaming_topk) and the pipelined
    per-chunk driver (engine.single), which dispatches one step per data
    chunk so host->device chunk transfers overlap the previous chunk's
    compute — the TPU-native replacement for the reference's synchronous
    Scatterv-then-compute phasing (engine.cpp:62-131 then :233-257).
    """
    from dmlp_tpu.ops.distance import masked_pairwise_sq_l2

    def step_sort(carry: TopK, query_attrs, battrs, blabels, bids):
        tile = masked_pairwise_sq_l2(query_attrs, battrs, bids, accum_dtype)
        cand = TopK(tile,
                    jnp.broadcast_to(blabels[None, :], tile.shape),
                    jnp.broadcast_to(bids[None, :], tile.shape))
        return merge_topk(carry, cand, k)

    def merge_cand(carry_, cand_d, cand_l, cand_i):
        """top_k over carry + candidate columns -> (Qb, k) TopK."""
        alld = jnp.concatenate([carry_.dists, cand_d], axis=-1)
        negd, idx = jax.lax.top_k(-alld, k)
        from_carry = idx < k
        cidx = jnp.minimum(idx, k - 1)
        bidx = jnp.maximum(idx - k, 0)
        labels_ = jnp.where(
            from_carry, jnp.take_along_axis(carry_.labels, cidx, axis=-1),
            jnp.take_along_axis(cand_l, bidx, axis=-1))
        ids_ = jnp.where(
            from_carry, jnp.take_along_axis(carry_.ids, cidx, axis=-1),
            jnp.take_along_axis(cand_i, bidx, axis=-1))
        return TopK(-negd, labels_, ids_)

    def step_seg(carry: TopK, query_attrs, battrs, blabels, bids):
        """Segment-min threshold selection (select="seg").

        Exact tile top-k with ~B/128 of the sort work: reduce the tile to
        per-128-column segment minima, pick the S = k+16 smallest-min
        segments (every true tile-top-k point lives in a segment whose min
        is <= the k-th smallest segment min T — if one didn't, >= k segments
        with min < its distance would each contribute a closer point), and
        run the real top_k on just the gathered S*128 candidates. When the
        S-th selected min still ties T (more eligible segments may exist
        beyond S — duplicate-heavy data), a lax.cond falls back to the full
        top_k for that step, so the result is always the exact per-tile
        top-k by distance.
        """
        from dmlp_tpu.ops.pallas_distance import (fused_dist_segmin,
                                                  pallas_interpret,
                                                  supports)
        if use_pallas and supports(query_attrs.shape[0], battrs.shape[0],
                                   battrs.shape[1]):
            tile, segmin = fused_dist_segmin(
                query_attrs, battrs, bids,
                interpret=pallas_interpret())
        else:
            tile = masked_pairwise_sq_l2(query_attrs, battrs, bids,
                                         accum_dtype)
            segmin = None
        qb_, bcols = tile.shape
        nseg = bcols // 128
        s = min(nseg, k + 16)

        if segmin is None:
            segmin = tile.reshape(qb_, nseg, 128).min(axis=-1)
        neg_sel, seg_idx = jax.lax.top_k(-segmin, s)      # (Qb, S)
        sel_min = -neg_sel                                 # asc by segment min
        t = sel_min[:, min(k, s) - 1]
        hazard = (s < nseg) & jnp.any(
            jnp.isfinite(sel_min[:, -1]) & (sel_min[:, -1] <= t))

        def full(args):
            carry_, tile_, blabels_, bids_, _ = args
            return merge_cand(carry_, tile_,
                              jnp.broadcast_to(blabels_[None, :], tile_.shape),
                              jnp.broadcast_to(bids_[None, :], tile_.shape))

        def seg(args):
            carry_, tile_, blabels_, bids_, seg_idx_ = args
            # Gather whole 128-lane segments along the segment axis —
            # contiguous lanes, ~4x faster on TPU than a flat-index gather.
            # (A one-hot matmul gather measured ~8 ms faster at r3 but needs
            # a clamped tile copy + materialized one-hot at HIGHEST
            # precision — +12 GB peak HBM at the big-chunk shape — so the
            # plain gather wins overall.)
            t3 = tile_.reshape(qb_, nseg, 128)
            cand_d = jnp.take_along_axis(
                t3, seg_idx_[:, :, None], axis=1).reshape(qb_, s * 128)
            cand_l = blabels_.reshape(nseg, 128)[seg_idx_].reshape(
                qb_, s * 128)
            cand_i = bids_.reshape(nseg, 128)[seg_idx_].reshape(qb_, s * 128)
            return merge_cand(carry_, cand_d, cand_l, cand_i)

        if s == nseg:
            return full((carry, tile, blabels, bids, seg_idx))
        return jax.lax.cond(hazard, full, seg,
                            (carry, tile, blabels, bids, seg_idx))

    def step_topk(carry: TopK, query_attrs, battrs, blabels, bids):
        tile = masked_pairwise_sq_l2(query_attrs, battrs, bids, accum_dtype)
        alld = jnp.concatenate([carry.dists, tile], axis=-1)
        negd, idx = jax.lax.top_k(-alld, k)
        # Entry idx < k came from the carry, else from the block — gather
        # metadata from whichever side without materializing (Qb, B) labels.
        from_carry = idx < k
        cidx = jnp.minimum(idx, k - 1)
        bidx = jnp.maximum(idx - k, 0)
        new_labels = jnp.where(
            from_carry, jnp.take_along_axis(carry.labels, cidx, axis=-1),
            blabels[bidx])
        new_ids = jnp.where(
            from_carry, jnp.take_along_axis(carry.ids, cidx, axis=-1),
            bids[bidx])
        return TopK(-negd, new_labels, new_ids)

    if select not in ("sort", "topk", "seg"):
        raise ValueError(f"unknown select {select!r}")
    return {"sort": step_sort, "topk": step_topk, "seg": step_seg}[select]
