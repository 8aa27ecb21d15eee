"""Graceful-degradation ladder for the extract/solve path.

On device memory exhaustion (simulated RESOURCE_EXHAUSTED from the
fault injector, or a real XLA OOM — resilience.retry.classify treats
them identically) the single-chip solve steps DOWN a ladder instead of
crashing, and every rung preserves the contract checksums exactly:

1. ``lowp``       — the normal path: the bound-based pruned two-stage
                    solve COMPOSED with the low-precision first pass
                    (``config.precision``/``$DMLP_TPU_PRECISION``
                    resolving to "bf16"): one MXU pass per tile
                    instead of the float32 form's three (six where
                    it is one ``HIGHEST`` dot: measured, PR 36),
                    candidate windows and every prune/gate threshold
                    widened by the analytic
                    ``engine.finalize.lowp_eps`` bound.
                    With precision resolving to "f32" (the default and
                    the ``DMLP_TPU_PRECISION=f32`` kill switch) this
                    rung is exactly the pruned solve — the kill switch
                    pins the precision without consuming a ladder
                    step. An OOM steps down to the float32 form (a
                    bf16-inflated candidate window is the first
                    allocation to give back); that form, three bf16
                    passes over split operands in exact mode at
                    float32 staging ("bf16x3"), is what every rung
                    below runs too.
2. ``prune``      — the bound-based pruned two-stage solve
                    (ops.summaries) over the fused megakernel at f32 —
                    only survivor blocks are staged/folded. The
                    ``DMLP_TPU_PRUNE=0`` kill switch pins this rung to
                    the dense fused solve without consuming a ladder
                    step.
3. ``fused``      — the dense scan on the fused distance→top-k
                    streaming megakernel (ops.pallas_fused) where its
                    supports() holds, two-pass extraction otherwise.
                    The ``DMLP_TPU_FUSED=0`` kill switch (mirroring
                    ``DMLP_TPU_RESILIENCE``) pins this rung to the
                    two-pass kernel without consuming a ladder step.
4. ``heuristic``  — the two-pass extraction kernel (the MXU gate off),
                    at the tiles ops.pallas_extract.resolve_variant
                    gives every dispatch: the fused kernel's per-block
                    gate state is what a fused-path OOM gives back.
5. ``streaming``  — the chunked multipass streaming fold
                    (engine.single._solve_pipelined): no running-list
                    kernel state, the live tile shrinks to one
                    (query_block x chunk) slab. Squared L2 alone: an
                    engine that ranks by inner product or by cosine
                    skips it.
6. ``host``       — the float64 golden solve on the host
                    (golden.fast.knn_golden_fast): zero device memory;
                    it IS the oracle the contract diffs against, so
                    byte-identity is by construction.

Each step records a ``resilience.degrade`` trace event and a stats
degradation entry, so run records and the chaos harness can see recovery
happen (and measure what it cost).
"""

from __future__ import annotations

import contextlib
from typing import Callable, List

from dmlp_tpu.config import score_of
from dmlp_tpu.resilience import stats
from dmlp_tpu.resilience.retry import classify, resilience_enabled

RUNGS = ("lowp", "prune", "fused", "heuristic", "streaming", "host")


@contextlib.contextmanager
def _rung_context(engine, rung: str):
    """Configure the engine for one rung. ``_degrade_rung`` is consulted
    by engine.single._solve/_solve_segments (``streaming`` skips every
    extract-kernel path; the top ``lowp``/``prune`` rungs may run the
    bound-based scan pruning, and only ``lowp`` may run the one-pass
    bf16 form; every rung runs the three-pass one) and by
    ops.pallas_fused.resolve_topk_kernel (the ``lowp``/
    ``prune``/``fused`` rungs may dispatch the fused megakernel)."""
    prev = getattr(engine, "_degrade_rung", "fused")
    engine._degrade_rung = rung
    # Live rung gauge: numeric ladder position (0 = lowp ... 5 = host)
    # so a scrape mid-incident sees WHERE the solve currently sits.
    from dmlp_tpu.obs import telemetry
    telemetry.registry().gauge("resilience.degrade_rung").set(
        RUNGS.index(rung))
    try:
        yield
    finally:
        engine._degrade_rung = prev


def _host_fallback(inp, score: str = "l2") -> List:
    """The last rung: the float64 host oracle (exact by construction),
    under the engine's score."""
    from dmlp_tpu.golden.fast import knn_golden_fast
    from dmlp_tpu.obs.trace import span as obs_span
    with obs_span("resilience.host_fallback",
                  nq=inp.params.num_queries, n=inp.params.num_data):
        return knn_golden_fast(inp, score=score)


def steps_down(e: BaseException) -> bool:
    """Whether the ladder answers ``e`` with a step down (an OOM-class
    failure, with the ladder enabled) or lets it propagate."""
    return resilience_enabled() and classify(e) == "oom"


def note_step(rung: str, nxt: str, e: BaseException) -> None:
    """Record one step down: the stats entry and the trace instant
    (which also lands in the flight recorder when a telemetry session
    is active: obs.trace's instant observer)."""
    stats.record_degradation(rung, nxt)
    from dmlp_tpu.obs import trace as obs_trace
    obs_trace.instant("resilience.degrade", frm=rung, to=nxt,
                      error=str(e)[:200])


@contextlib.contextmanager
def top_rung(engine):
    """One HALF of a solve on the ladder's top rung: a caller that cuts
    its solve in two (serve.engine.ResidentEngine) runs each half under
    this and, where ``steps_down`` says so, re-runs the solve whole with
    ``run_ladder(..., first=1)``."""
    engine.last_degrade_rung = RUNGS[0]
    with _rung_context(engine, RUNGS[0]):
        yield


def run_ladder(engine, inp, solve: Callable, first: int = 0):
    """Run ``solve(inp)`` (normally ``engine._run``), stepping down the
    ladder on each OOM-class failure; the last rung needs no device
    memory at all. Non-OOM errors propagate unchanged — the ladder
    trades capacity, it does not paper over bugs. ``first`` is the rung
    to enter at (a caller whose top-rung attempt already failed).

    ``DMLP_TPU_RESILIENCE=0`` disables the LADDER (no step-downs), not
    the top rung's feature set: the solve still runs at RUNGS[0], so
    the low-precision first pass and the pruned two-stage solve keep
    their own kill switches (``DMLP_TPU_PRECISION``/``DMLP_TPU_PRUNE``)
    instead of silently riding the resilience one — the chaos overhead
    A/B's resilience-off arm must differ from the on arm by the
    wrappers only."""
    if not resilience_enabled():
        with top_rung(engine):
            return solve(inp)
    # The streaming fold ranks by squared L2 alone: an engine under
    # another score (config.EngineConfig.score) steps from the kernel
    # rungs straight to the host oracle, which has its form.
    score = score_of(engine)
    rungs = [r for r in RUNGS[first:] if r != "streaming" or score == "l2"]
    for i, rung in enumerate(rungs):
        try:
            engine.last_degrade_rung = rung
            if rung == "host":
                return _host_fallback(inp, score)
            with _rung_context(engine, rung):
                return solve(inp)
        except Exception as e:
            if classify(e) != "oom" or i + 1 >= len(rungs):
                raise
            note_step(rung, rungs[i + 1], e)
    raise AssertionError("unreachable: the host rung returns or raises")
