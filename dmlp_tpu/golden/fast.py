"""Fast exact golden oracle — BLAS coarse pass + difference-form refinement.

The strict oracle (golden.reference.knn_golden) computes every distance in
the difference form and lexsorts full rows: exact, but O(Q*N*A) elementwise
f64 plus O(Q * N log N) sorting — hours at benchmark scale. This module
produces *identical results* orders of magnitude faster:

1. coarse distances via f64 dgemm (|q|^2 + |d|^2 - 2 q.d);
2. top-(kmax + margin) candidates per query by coarse value (argpartition);
3. exact difference-form rescore of just the candidates;
4. per-query safety check: the exact k-th distance must clear the coarse
   selection boundary by more than the norm+matmul error bound, else that
   query falls back to the strict full-row path.

The fallback makes the result exact regardless of the bound's tightness —
the bound only decides how often the slow path runs (measure-zero for
continuous data, possible for adversarial duplicates).

Under ``score="ip"`` (golden.reference has the contract) the coarse value
is the dgemm's -q.x alone and the rescore the same product by einsum over
the gathered rows; the safety check stands with the dot's own bound
(A products of magnitude at most |q||x|, no cancellation). Under
``score="cosine"`` both are golden.reference.cosine_of on that product
and the norms (``inp.data_norms`` where the corpus' holder keeps them),
and the bound is the dot's at unit operands.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from dmlp_tpu.engine.finalize import finalize_host
from dmlp_tpu.golden.reference import cosine_of, finalize_query, row_norms
from dmlp_tpu.io.grammar import KNNInput
from dmlp_tpu.io.report import QueryResult


def _strict_row(inp: KNNInput, qi: int, data: np.ndarray,
                labels: np.ndarray, ids: np.ndarray,
                score: str = "l2",
                dnorm: Optional[np.ndarray] = None) -> QueryResult:
    """Exact full-row solve for one query (the knn_golden inner loop)."""
    if score in ("ip", "cosine"):
        drow = np.einsum("na,a->n", data, inp.query_attrs[qi])
        if score == "cosine":
            drow = cosine_of(drow, row_norms(
                inp.query_attrs[qi:qi + 1]), dnorm)
        drow = -drow
    else:
        diff = data - inp.query_attrs[qi][None, :]
        drow = np.einsum("na,na->n", diff, diff)
    return finalize_query(drow, labels, ids, int(inp.ks[qi]), qi, score)


def knn_golden_fast(inp: KNNInput, margin: int = 64,
                    query_block: int = 1024,
                    stats: Optional[dict] = None,
                    score: str = "l2") -> List[QueryResult]:
    """Same results as knn_golden(inp, score=score) (float64),
    benchmark-scale fast.

    ``stats``, if given, receives {"fallbacks": <count of queries routed
    to the strict full-row path>} so the safety valve's cost is observable.
    """
    nd, nq = inp.params.num_data, inp.params.num_queries
    # (no copy of a float64 corpus: a served repair holds 10^7 rows)
    data = inp.data_attrs.astype(np.float64, copy=False)
    labels = inp.labels.astype(np.int64)
    ids = np.arange(nd, dtype=np.int64)
    product = score in ("ip", "cosine")
    dnorm = dn = None
    if score == "cosine":
        dnorm = inp.data_norms if inp.data_norms is not None \
            else row_norms(data)
    else:
        dn = np.einsum("na,na->n", data, data)
    kmax = int(inp.ks.max()) if nq else 1
    kcand = min(nd, kmax + margin)
    # Error bound of the norm+matmul form relative to the difference form:
    # cancellation of terms of magnitude ~(|q|^2 + |d|^2). A couple of
    # hundred ulps is far beyond the real accumulation error for A ~ 10^2.
    eps = np.finfo(np.float64).eps

    results: List[QueryResult] = [None] * nq  # type: ignore[list-item]
    fallbacks = 0
    for q0 in range(0, nq, query_block):
        q1 = min(q0 + query_block, nq)
        q = inp.query_attrs[q0:q1].astype(np.float64)
        qn = np.einsum("qa,qa->q", q, q)
        if score == "cosine":
            qnorm = row_norms(q)
        # In-place epilogue on the dgemm output: the broadcast expression
        # form allocates ~4 (Qb, N) f64 temporaries, which measured ~10x
        # the dgemm itself at benchmark scale (page faults on fresh GBs).
        coarse = q @ data.T
        if product:
            if score == "cosine":
                # a screen (cosine_of decides, below): divided in place,
                # no further (Qb, N) temporary; a zero norm's dots are 0
                np.divide(coarse, dnorm[None, :], out=coarse,
                          where=(dnorm > 0)[None, :])
                np.divide(coarse, qnorm[:, None], out=coarse,
                          where=(qnorm > 0)[:, None])
            np.negative(coarse, out=coarse)
        else:
            coarse *= -2.0
            coarse += qn[:, None]
            coarse += dn[None, :]

        if kcand < nd:
            cand = np.argpartition(coarse, kcand - 1, axis=1)[:, :kcand]
        else:
            cand = np.broadcast_to(ids[None, :], (q1 - q0, nd))
        # Exact difference-form rescore of the candidates only.
        if product:
            exact = np.einsum("qka,qa->qk", data[cand], q)
            if score == "cosine":
                exact = cosine_of(exact, qnorm[:, None], dnorm[cand])
            exact = -exact
        else:
            diff = data[cand] - q[:, None, :]
            exact = np.einsum("qka,qka->qk", diff, diff)

        ks_blk = inp.ks[q0:q1].astype(np.int64)
        if kcand < nd:
            coarse_cand = np.take_along_axis(coarse, cand, axis=1)
            # The bound must cover the points the coarse pass EXCLUDED
            # (their coarse value could be understated by up to the
            # rounding error of the norm+matmul form), and an excluded
            # point's |d|^2 can exceed every candidate's — so it uses the
            # global max norm, not dn[cand] (ADVICE r1: the candidate-norm
            # bound did not strictly prove exactness for adversarial
            # large-norm excluded points).
            if score == "cosine":
                # unit operands, A products a dot: the worst case grows
                # with the width, the 256 does not
                err_q = np.full(q1 - q0, (256.0 + 2.0 * data.shape[1])
                                * eps * 3.0)
            else:
                err_q = 256.0 * eps * (qn + (dn.max() if nd else 0.0) + 1.0)
            # Safety (vectorized): the k-th exact distance must clear the
            # coarse selection boundary by the error bound, else that
            # query's candidates may be wrong -> strict full-row fallback.
            kth_exact = np.take_along_axis(
                np.sort(exact, axis=1),
                np.minimum(ks_blk, kcand)[:, None] - 1, axis=1)[:, 0]
            boundary = coarse_cand.max(axis=1)
            ok = kth_exact < boundary - err_q
        else:
            ok = np.ones(q1 - q0, bool)

        # Batched finalize over the whole query block (round-3 review item 6:
        # the per-query Python finalize loop dominated oracle time at
        # benchmark scale — 182 s on harness config 4). finalize_host is
        # the engines' own vectorized implementation of the identical
        # contract; oracle honesty is anchored by the strict per-query
        # fallback below and the fast-vs-strict differential tests
        # (tests/test_golden_fast.py), which diff this path against
        # knn_golden's independent per-query code.
        cand_l, cand_i, exact_f = labels[cand], cand, exact
        if kcand < int(ks_blk.max(initial=0)):
            # k may legally exceed num_data (sentinel padding); widen the
            # candidate lists so finalize_host can pad with (-1, +inf).
            padw = int(ks_blk.max()) - kcand
            shape = (q1 - q0, padw)
            exact_f = np.concatenate([exact, np.full(shape, np.inf)], axis=1)
            cand_l = np.concatenate(
                [cand_l, np.full(shape, -1, np.int64)], axis=1)
            cand_i = np.concatenate(
                [cand_i, np.full(shape, -1, np.int64)], axis=1)
        blk = finalize_host(exact_f, cand_l, cand_i, ks_blk,
                            inp.query_attrs, inp.data_attrs, exact=False,
                            query_ids=np.arange(q0, q1, dtype=np.int64),
                            score=score)
        results[q0:q1] = blk
        for row in np.nonzero(~ok)[0]:
            results[q0 + row] = _strict_row(inp, q0 + row, data, labels,
                                            ids, score, dnorm)
            fallbacks += 1
    if stats is not None:
        stats["fallbacks"] = fallbacks
    return results
