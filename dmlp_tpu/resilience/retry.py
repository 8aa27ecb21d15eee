"""Bounded retry with exponential backoff, deterministic jitter, and
transient-vs-fatal error classification.

The repo's operations are unusually retry-friendly: staging, solve
dispatch, and readback are all pure functions of host arrays already in
memory, so re-running them cannot change answers — the chaos harness
proves that end to end. This module supplies the one retry loop every
wrapped site shares:

- **classification** (:func:`classify`): three-way. ``transient``
  (injected transients, connection/timeout errors, jax runtime errors
  carrying the UNAVAILABLE / DEADLINE_EXCEEDED / ABORTED markers) is
  retried here; ``oom`` (simulated or real HBM RESOURCE_EXHAUSTED) is
  NOT — retrying the same allocation is futile, the degradation ladder
  (resilience.degrade) owns that recovery; everything else is ``fatal``
  and propagates immediately — including a kernel that fails to
  compile, whose VMEM overflow also reads RESOURCE_EXHAUSTED.
- **deterministic jitter**: the backoff delay's jitter fraction is a
  hash of (policy seed, site, attempt) — full de-thundering across
  sites, bit-reproducible across runs (a chaos run's timing profile is
  part of its replayability).
- **injectable clock/sleep**: tests pass ``sleep=`` and never wait.

``$DMLP_TPU_RESILIENCE=0`` disables the layer wholesale (wrappers become
direct calls) — the off arm of the chaos harness's zero-fault overhead
A/B.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
import time
from typing import Callable, Optional

from dmlp_tpu.resilience import stats
from dmlp_tpu.resilience.inject import (InjectedTransientError,
                                        SimulatedResourceExhausted)

#: substrings of runtime-error text classified transient (the PJRT /
#: gRPC status names a flaky dispatch or readback surfaces as)
TRANSIENT_MARKERS = ("DEADLINE_EXCEEDED", "UNAVAILABLE", "ABORTED",
                     "injected transient")

#: substrings classified as out-of-memory (ladder recovery, not retry)
OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")

#: substrings of a kernel COMPILE failure. A kernel that does not
#: compile is a bug, not a capacity event: stepping down the ladder
#: would end on the host oracle with exit 0 and no device work. The
#: kernel running out of VMEM is a property of its tiling, decided at
#: compile time, and says RESOURCE_EXHAUSTED too — so these are tested
#: before OOM_MARKERS.
KERNEL_COMPILE_MARKERS = ("Mosaic failed to compile", "vmem", "VMEM")


def resilience_enabled() -> bool:
    """The layer-wide kill switch ($DMLP_TPU_RESILIENCE=0 disables) —
    checked per call so the chaos overhead A/B can flip it per run."""
    return os.environ.get("DMLP_TPU_RESILIENCE", "1") != "0"


def classify(exc: BaseException) -> str:
    """"transient" | "oom" | "fatal" for an exception."""
    if isinstance(exc, SimulatedResourceExhausted):
        return "oom"
    if isinstance(exc, (InjectedTransientError, ConnectionError,
                        TimeoutError, InterruptedError, OperationTimeout)):
        return "transient"
    msg = str(exc)
    if type(exc).__name__ == "MosaicError" \
            or any(m in msg for m in KERNEL_COMPILE_MARKERS):
        return "fatal"
    if any(m in msg for m in OOM_MARKERS):
        return "oom"
    if any(m in msg for m in TRANSIENT_MARKERS):
        return "transient"
    return "fatal"


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff: attempt n (0-based) sleeps
    ``min(base_ms * multiplier**n, cap_ms) * (1 + jitter * h)`` where
    ``h`` is the deterministic per-(seed, site, attempt) hash fraction."""

    attempts: int = 3
    base_ms: float = 25.0
    cap_ms: float = 2000.0
    multiplier: float = 2.0
    jitter: float = 0.25
    seed: int = 0


DEFAULT_POLICY = RetryPolicy()


def backoff_ms(policy: RetryPolicy, site: str, attempt: int) -> float:
    raw = min(policy.base_ms * policy.multiplier ** attempt, policy.cap_ms)
    digest = hashlib.sha256(
        f"{policy.seed}:{site}:{attempt}".encode()).digest()
    frac = int.from_bytes(digest[:8], "big") / 2 ** 64
    return raw * (1.0 + policy.jitter * frac)


def call_with_retry(op: Callable, site: str,
                    policy: Optional[RetryPolicy] = None,
                    classify_fn: Callable = classify,
                    sleep: Callable = time.sleep):
    """Run ``op()`` with bounded transient retries; fatal and oom
    errors propagate immediately (oom belongs to the degradation
    ladder). Every retry records a ``resilience.retry`` span and bumps
    the stats counters — recovery is never silent."""
    if not resilience_enabled():
        return op()
    policy = policy or DEFAULT_POLICY
    attempt = 0
    while True:
        try:
            return op()
        except Exception as e:
            clc = classify_fn(e)
            if clc != "transient" or attempt + 1 >= policy.attempts:
                # Post-mortem evidence BEFORE the raise unwinds: a
                # fatal-classified (or retries-exhausted) fault dumps
                # the flight recorder while the last spans/events are
                # still in the ring (no-op without a telemetry session;
                # oom propagates to the ladder, which is recovery, not
                # death — event only, no dump).
                from dmlp_tpu.obs import telemetry
                telemetry.flight_fault(
                    site=site, classification=clc,
                    error=type(e).__name__,
                    dump=clc == "fatal" or (clc == "transient"
                                            and attempt + 1
                                            >= policy.attempts))
                raise
            delay = backoff_ms(policy, site, attempt)
            stats.record_retry(site)
            from dmlp_tpu.obs.trace import span as obs_span
            with obs_span("resilience.retry", site=site,
                          attempt=attempt + 1,
                          backoff_ms=round(delay, 2),
                          error=type(e).__name__):
                sleep(delay / 1e3)
            attempt += 1


class OperationTimeout(RuntimeError):
    """An operation exceeded its deadline (see call_with_timeout)."""


def call_with_timeout(op: Callable, timeout_s: float, site: str = "",
                      clock: Callable = time.monotonic):
    """Run ``op`` on a worker thread and join with a deadline; raises
    :class:`OperationTimeout` (classified transient) when the deadline
    passes. NOTE: Python cannot kill the worker — a genuinely hung
    ``op`` leaks its (daemon) thread, so this guards *operations whose
    hang modes eventually resolve* (slow readbacks, stalled I/O); hung
    *processes* are the supervision loop's job (resilience.supervise),
    which can actually kill them."""
    result: list = []
    error: list = []

    def _worker():
        try:
            result.append(op())
        except BaseException as e:  # check: no-retry — relayed to caller
            error.append(e)

    t = threading.Thread(target=_worker, daemon=True,
                         name=f"resilience-timeout:{site}")
    t0 = clock()
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        stats.record_timeout(site)
        raise OperationTimeout(
            f"operation at {site or '<unnamed>'} exceeded "
            f"{timeout_s:.3g}s (waited {clock() - t0:.3g}s; worker "
            "thread abandoned)")
    if error:
        raise error[0]
    return result[0]
