"""Fenced measured sweep over the extract/fused kernel variant spaces.

For each requested (kernel, shape, kc) the sweep enumerates every
variant the kernel can actually tile — ``tile_q`` x ``tile_n`` x ``ne``
x ``unroll``, gated by ``ops.pallas_extract.variant_supports`` so the
sweep can never persist a variant the hot path would reject — times each
with a dependent-readback fence (:func:`_fenced_ms`), and records
the winner in the variant cache (:mod:`dmlp_tpu.tune.cache`) under that
kernel's namespace. The fused megakernel (ops.pallas_fused) shares the
tile space but sweeps separately: its MXU gate turns warm no-improve
blocks into one VPU bound pass, which shifts the block-size trade-off
the winner encodes.

Two honesty rules:

- compile + the eager perturbation chain are warmed OUT of the timed
  region (the r2 mismeasurement: the chain's tiny kernels compile on
  first use);
- a variant that fails to compile (Mosaic tiling edge) is skipped and
  counted, never silently dropped — the summary names how much of the
  space was actually measured.

The sweep also probes kc padding: timing the winner at kc+8 records
whether a wider running list would be cheaper per candidate
(``kc_pad_probe_ms`` in the cache entry, informational — engines keep
the semantic kc that resolve_kcap derived from the workload's k).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["variant_space", "sweep_extract", "sweep_prune_score",
           "smoke_space"]

_TQ_CHOICES = (32, 64, 128, 256)
_NE_CHOICES = (2, 4, 8)
_UNROLL_CHOICES = (1, 2)


def variant_space(qb: int, b: int, a: int, kc: int,
                  tile_n_targets: Optional[Sequence[int]] = None
                  ) -> List[Dict]:
    """Every variant the kernel can tile at this dispatch shape.

    ``tile_n`` candidates default to whole-block fractions of the
    kernel's default block (full, half, quarter), snapped per-ne to the
    128*ne lane granule; degenerate/duplicate resolutions collapse."""
    from dmlp_tpu.ops.pallas_extract import BLOCK_ROWS, variant_supports

    targets = tuple(tile_n_targets or
                    (BLOCK_ROWS, BLOCK_ROWS // 2, BLOCK_ROWS // 4))
    out: List[Dict] = []
    seen = set()
    for ne in _NE_CHOICES:
        gran = 128 * ne
        for tn_t in targets:
            tn = max(gran, tn_t - tn_t % gran)
            for tq in _TQ_CHOICES:
                for unroll in _UNROLL_CHOICES:
                    v = {"tile_q": tq, "tile_n": tn, "ne": ne,
                         "unroll": unroll}
                    key = (tq, tn, ne, unroll)
                    if key in seen:
                        continue
                    seen.add(key)
                    if variant_supports(qb, b, a, kc, v):
                        out.append(v)
    return out


def smoke_space(qb: int, b: int, a: int, kc: int) -> List[Dict]:
    """A ~4-variant slice of the space for the CPU CI smoke: one per ne
    choice plus an unroll=2 point — enough to exercise the measure ->
    pick -> persist -> reload pipeline without minutes of interpret-mode
    emulation."""
    space = variant_space(qb, b, a, kc)
    picked: List[Dict] = []
    for ne in _NE_CHOICES:
        for v in space:
            if v["ne"] == ne and v["unroll"] == 1:
                picked.append(v)
                break
    for v in space:
        if v["unroll"] == 2:
            picked.append(v)
            break
    return picked[:4]


def _fenced_ms(fn, q, d, reps: int) -> float:
    """Compile + fence, warm the
    perturbation chain, then time ``reps`` chained dispatches bounded by
    a dependent scalar readback."""
    r = fn(q, d)
    _ = float(r[0, 0])
    r = fn(q + 0.0 * r[0, 0], d)
    _ = float(r[0, 0])
    t0 = time.perf_counter()
    for _i in range(reps):
        r = fn(q + 0.0 * r[0, 0], d)
    _ = float(r[0, 0])
    return (time.perf_counter() - t0) / reps * 1e3


def time_variant_ms(q, d, n_real: int, kc: int, v: Dict, reps: int,
                    interpret: bool, warm_folds: int = 1,
                    kernel: str = "extract",
                    precision: str = "f32") -> float:
    """Fenced time of one kernel variant at the staged arrays: one FRESH
    dispatch plus ``warm_folds`` carry folds over the same block. The
    engines' hot path is a chunk chain — one cold fold, then warm folds
    where the running lists gate most blocks out (the block skip's —
    and for ``kernel="fused"``, the MXU gate's — whole win) — so
    ranking variants on the cold dispatch alone would pick winners at
    an operating point the chain mostly doesn't run; the 1-cold +
    1-warm chain weights both regimes. Raises whatever the compile
    raises — the sweep catches and skips."""
    from dmlp_tpu.ops.pallas_extract import extract_topk

    b = d.shape[0]
    kw = dict(kc=kc, interpret=interpret, tile_q=v["tile_q"],
              tile_n=v["tile_n"], ne=v["ne"], unroll=v["unroll"],
              mxu_gate=kernel == "fused", precision=precision)

    def fn(q_, d_):
        od, oi, _it = extract_topk(q_, d_, n_real=n_real, **kw)
        for w in range(1, warm_folds + 1):
            od, oi, _it = extract_topk(q_, d_, od, oi, n_real=n_real,
                                       id_base=w * b, **kw)
        return od
    return _fenced_ms(fn, q, d, reps)


def sweep_extract(n: int, nq: int, a: int, kcs: Sequence[int],
                  reps: int = 3, seed: int = 0,
                  space_fn=variant_space, out=None,
                  kernel: str = "extract", precision: str = "f32",
                  ) -> Tuple[List[Dict], List[Dict]]:
    """Measure the variant space at BOTH dispatch shapes the engines use
    for an (n, nq, a) workload and return (winners, detail rows).

    Two timed ``b`` points per kc (deduped when they coincide):

    - the CHUNKED shape (plan_chunks on the extract granule) — what
      engine.single._solve_extract dispatches per staged chunk;
    - the WHOLE padded dataset — what the multipass resident passes
      dispatch. Without this point a b=npad dispatch would resolve in
      a bucket the sweep never keyed and silently fall back to the
      heuristic.

    Queries pad to whole query tiles. ``kernel`` ("extract" | "fused")
    selects which kernel the variants drive; winners persist under that
    kernel's cache namespace. ``precision`` ("f32" | "bf16x3" | "bf16")
    selects the first-pass form the variants are timed WITH — the MXU
    pass count (six, three, one) moves which tile shapes win,
    so winners carry the precision and persist under that key axis of
    the cache (schema 3). ``winners`` is a list of
    {"kernel", "kc", "b", "qb", "variant", "precision", "measured_ms",
    "swept", "skipped_compile", "kc_pad_probe_ms"?} records — one per
    (kc, b point) that measured at least one variant.
    """
    import numpy as np

    import jax.numpy as jnp
    from dmlp_tpu.engine.single import plan_chunks, round_up
    from dmlp_tpu.ops.pallas_distance import pallas_interpret
    from dmlp_tpu.ops.pallas_extract import QUERY_TILE, BLOCK_ROWS

    log = (lambda *_: None) if out is None else \
        (lambda *a_: print(*a_, file=out, flush=True))
    npad, _nchunks, chunk_rows = plan_chunks(n, BLOCK_ROWS, None)
    qpad = round_up(max(nq, 1), QUERY_TILE)
    interpret = pallas_interpret()
    b_points = sorted({chunk_rows, npad})

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.uniform(0.0, 100.0, (qpad, a)), jnp.float32)

    winners: List[Dict] = []
    rows: List[Dict] = []
    for b in b_points:
        d = jnp.asarray(rng.uniform(0.0, 100.0, (b, a)), jnp.float32)
        n_real = min(n, b)
        float(jnp.sum(d))  # fence staging
        for kc in kcs:
            space = space_fn(qpad, b, a, kc)
            best: Optional[Dict] = None
            best_ms = float("inf")
            skipped = 0
            for v in space:
                try:
                    ms = time_variant_ms(q, d, n_real, kc, v, reps,
                                         interpret, kernel=kernel,
                                         precision=precision)
                except Exception as e:  # Mosaic tiling edge: skip, count
                    skipped += 1
                    rows.append({"kernel": kernel, "kc": kc, "b": b,
                                 "variant": v, "error": str(e)[:200]})
                    continue
                rows.append({"kernel": kernel, "kc": kc, "b": b,
                             "variant": v, "ms": round(ms, 3)})
                log(f"  {kernel} b={b} kc={kc} {v} -> {ms:.2f} ms")
                if ms < best_ms:
                    best, best_ms = v, ms
            if best is None:
                log(f"  {kernel} b={b} kc={kc}: no variant measured "
                    f"({skipped} compile-skipped of {len(space)})")
                continue
            entry = {"kernel": kernel, "kc": kc, "b": b, "qb": qpad,
                     "variant": best, "precision": precision,
                     "measured_ms": best_ms,
                     "swept": len(space) - skipped,
                     "skipped_compile": skipped}
            # kc-padding probe: the winner at kc+8 — informational only.
            try:
                entry["kc_pad_probe_ms"] = round(
                    time_variant_ms(q, d, n_real, kc + 8, best, reps,
                                    interpret, kernel=kernel,
                                    precision=precision), 3)
            except Exception:
                pass
            winners.append(entry)
            log(f"  {kernel} b={b} kc={kc}: winner {best} at "
                f"{best_ms:.2f} ms "
                f"({entry['swept']} measured, {skipped} skipped)")
    return winners, rows


#: host block-chunk candidates for the prune_score sweep — the slab
#: width block_bounds/piece_bounds vectorize over; bounded above so the
#: (Q, chunk, P, A) f64 temp stays tens of MB at bench-scale q counts
_CHUNK_CHOICES = (32, 64, 128, 256, 512)


def sweep_prune_score(n: int, nq: int, a: int, reps: int = 3,
                      seed: int = 0, out=None,
                      chunks: Sequence[int] = _CHUNK_CHOICES,
                      ) -> Tuple[List[Dict], List[Dict]]:
    """Measured sweep of the HOST block-scoring chunk (the
    ``prune_score`` tune-cache namespace ops.summaries.
    resolve_score_variant reads): time prune_mask's bound computation —
    block_bounds plus, with the split format, piece_bounds at the
    halved chunk — per block-chunk candidate over summaries built at
    the engines' extract-granule block layout, and return (winners,
    rows) in the sweep_extract record shape.

    The chunk trades f64 slab temp size against numpy dispatch count:
    too small and the per-chunk einsum overhead dominates, too large
    and the (Q, chunk, A) temp falls out of cache. Winners key at the
    EXACT lookup point resolve_score_variant uses — kc=8 (a fixed
    namespace tag, not a candidate width) and b=n_blocks — with
    ``variant = {"tile_q": chunk, "ne": 1, "unroll": 1}``. Host f64
    scoring has no low-precision first pass, so winners always carry
    precision "f32" (the only key the resolver looks under).
    """
    import numpy as np

    from dmlp_tpu.ops.pallas_extract import BLOCK_ROWS
    from dmlp_tpu.ops.summaries import (PIECES, block_bounds,
                                        build_summaries, piece_bounds)

    log = (lambda *_: None) if out is None else \
        (lambda *a_: print(*a_, file=out, flush=True))
    rng = np.random.default_rng(seed)
    attrs = rng.uniform(0.0, 100.0, (n, a)).astype(np.float32)
    ranges = [(i, min(i + BLOCK_ROWS, n))
              for i in range(0, n, BLOCK_ROWS)]
    summ = build_summaries(attrs, ranges)
    q = rng.uniform(0.0, 100.0, (nq, a))

    def _time_chunk(chunk: int) -> float:
        def run():
            block_bounds(q, summ, block_chunk=chunk)
            if summ.pcounts is not None:
                piece_bounds(q, summ,
                             block_chunk=max(1, chunk // PIECES))
        run()  # warm allocator / page-fault the summary arrays
        t0 = time.perf_counter()
        for _ in range(reps):
            run()
        return (time.perf_counter() - t0) / reps * 1e3

    winners: List[Dict] = []
    rows: List[Dict] = []
    best, best_ms = None, float("inf")
    for chunk in sorted(set(int(c) for c in chunks)):
        v = {"tile_q": chunk, "ne": 1, "unroll": 1}
        ms = _time_chunk(chunk)
        rows.append({"kernel": "prune_score", "kc": 8,
                     "b": summ.n_blocks, "variant": v,
                     "ms": round(ms, 3)})
        log(f"  prune_score blocks={summ.n_blocks} chunk={chunk} "
            f"-> {ms:.2f} ms")
        if ms < best_ms:
            best, best_ms = v, ms
    if best is not None:
        winners.append({"kernel": "prune_score", "kc": 8,
                        "b": summ.n_blocks, "qb": nq, "variant": best,
                        "precision": "f32", "measured_ms": best_ms,
                        "swept": len(rows), "skipped_compile": 0})
        log(f"  prune_score blocks={summ.n_blocks}: winner {best} at "
            f"{best_ms:.2f} ms ({len(rows)} measured)")
    return winners, rows
