"""Lightweight span tracer exporting Chrome-trace / Perfetto JSON.

One process-wide :class:`Tracer` (installed with :func:`install`) collects
complete-duration events (``ph: "X"``) from ``with span("name"):`` blocks
scattered through the engines, the CLI, and the train loop. When no tracer
is installed every hook degenerates to a module-global read returning a
shared no-op span (``NULL_SPAN``): no object is built and no clock is
read. What stays on without a sink is the host-sync bracket at the end of
this module (``device_wait``: two ``perf_counter`` and two
``thread_time`` reads a wait) and the micro-batcher's account of its
cycle (serve/batching.py); the calls they make a cycle are counted by
tests/test_batcher_cycle.py.

While a sink is installed every span also says how long its thread was
on a core: ``cpu_ms`` (``time.thread_time`` across the block) and
``offcpu_ms`` (the duration less that: the thread wanted to run and did
not — the interpreter lock, the scheduler, a blocking fault, a sleep).
The thread's CPU clock is the host kernel's: where it advances in ticks
(10 ms on the chip's sealed host) one span's ``cpu_ms`` is a multiple of
the tick and only the mean over many spans of a name means anything.
A span stitched from clock reads taken on TWO threads
(:func:`complete_at` without ``cpu_s``) carries neither.

Device work is asynchronous under JAX, so a span that brackets only the
*enqueue* of a dispatch would lie about where time goes. Spans therefore
support explicit device fencing: ``sp.fence(arrays)`` makes the span's
closing edge call ``jax.block_until_ready`` on those arrays, so the
recorded duration covers the device work the block launched. Callers that
already synchronize (``jax.device_get``, host readbacks) need no fence.

Export is the Chrome trace-event JSON format — loadable directly in
https://ui.perfetto.dev or chrome://tracing: ``ts``/``dur`` are
microseconds from the tracer's epoch, nested ``X`` events on one thread
render as a flame stack. On a real TPU the tracer can additionally mirror
every span into ``jax.profiler`` annotations (``annotate=True``) so the
same span names appear inside an XLA profiler capture
(``jax.profiler.start_trace`` / ``--profile``).

This module must stay import-light (no jax import at module level): the
CLI imports it unconditionally.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

_clock = time.perf_counter
#: the calling thread's CPU time, user and system (CLOCK_THREAD_CPUTIME_ID)
_cpu = time.thread_time

# -- telemetry bridge ---------------------------------------------------------
# When a telemetry session (obs.telemetry) is active it registers
# observers here; every completed span / instant is forwarded (span
# latency histograms + flight-recorder events) WHETHER OR NOT a Tracer
# is installed — `span()` hands out a minimal timing span when only the
# observer wants it. Both slots None (the default) keeps the
# uninstrumented fast path at one module-global read.
_span_observer = None
_instant_observer = None


def set_telemetry_observer(span_cb, instant_cb) -> None:
    """Install/clear the telemetry forwarding callbacks.
    ``span_cb(name, dur_ms, args)``; ``instant_cb(name, args)``."""
    global _span_observer, _instant_observer
    _span_observer = span_cb
    _instant_observer = instant_cb


class _TelemetrySpan:
    """Minimal timing span used when telemetry observes but no Tracer
    is installed: measures wall duration (honoring device fences, like
    the real Span) and forwards one observation — no event storage."""

    __slots__ = ("name", "args", "_t0", "_c0", "_fences")

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name = name
        self.args = dict(args) if args else {}
        self._t0 = self._c0 = 0.0
        self._fences: list = []

    def set(self, **kwargs) -> None:
        self.args.update(kwargs)

    def fence(self, value) -> None:
        self._fences.append(value)

    def __enter__(self) -> "_TelemetrySpan":
        self._t0 = _clock()
        self._c0 = _cpu()
        return self

    def __exit__(self, *exc) -> bool:
        if self._fences:
            try:
                import jax
                jax.block_until_ready(self._fences)
            except Exception:
                pass
            self._fences = []
        cb = _span_observer
        if cb is not None:
            cpu_s = _cpu() - self._c0
            dur_ms = (_clock() - self._t0) * 1e3
            cb(self.name, dur_ms, _with_cpu(self.args, dur_ms, cpu_s))
        return False


class _NullSpan:
    """Shared no-op span: the uninstrumented fast path. Stateless, so one
    singleton serves every (possibly nested, possibly concurrent) site."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kwargs) -> None:
        pass

    def fence(self, value) -> None:
        pass


NULL_SPAN = _NullSpan()


def _annotation(name: str):
    """``name`` entered as a ``jax.profiler.TraceAnnotation`` (None
    where the profiler cannot be had): how a span shows inside an XLA
    profiler capture."""
    try:
        from jax.profiler import TraceAnnotation
        annot = TraceAnnotation(name)
        annot.__enter__()
        return annot
    except Exception:
        return None


def _close_annotation(annot, exc) -> None:
    if annot is not None:
        try:
            annot.__exit__(*exc)
        except Exception:
            pass


def _with_cpu(args: Dict[str, Any], dur_ms: float,
              cpu_s: Optional[float]) -> Dict[str, Any]:
    """``args`` with the span's CPU account, where its two ends were
    read on one thread (``cpu_s``: that thread's CPU seconds between
    them)."""
    if cpu_s is not None:
        args["cpu_ms"] = cpu_s * 1e3
        args["offcpu_ms"] = dur_ms - cpu_s * 1e3
    return args


class Span:
    """One traced region. Use as a context manager; ``set()`` attaches
    args (rendered in the Perfetto detail pane), ``fence()`` registers
    device values to ``block_until_ready`` before the closing timestamp."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_c0", "_fences",
                 "_annot")

    def __init__(self, tracer: "Tracer", name: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.args = dict(args) if args else {}
        self._t0 = self._c0 = 0.0
        self._fences: list = []
        self._annot = None

    def set(self, **kwargs) -> None:
        self.args.update(kwargs)

    def fence(self, value) -> None:
        self._fences.append(value)

    def __enter__(self) -> "Span":
        if self._tracer._annotate:
            self._annot = _annotation(self.name)
        self._t0 = _clock()
        self._c0 = _cpu()
        return self

    def __exit__(self, *exc) -> bool:
        if self._fences:
            try:
                import jax
                jax.block_until_ready(self._fences)
            except Exception:
                pass  # fencing is best-effort; the span still records
            self._fences = []
        cpu_s = _cpu() - self._c0
        t1 = _clock()
        _close_annotation(self._annot, exc)
        self._tracer._complete(self.name, self._t0, t1, self.args, cpu_s)
        return False


class Tracer:
    """Thread-safe collector of Chrome-trace events.

    ``annotate=True`` mirrors spans into ``jax.profiler.TraceAnnotation``
    so they show up inside an XLA profiler capture on real TPUs;
    ``profile_dir`` additionally brackets the tracer's lifetime with
    ``jax.profiler.start_trace``/``stop_trace`` (the heavyweight on-device
    capture — span JSON stays available either way).
    """

    #: the tracer's clock domain: per-process ``time.perf_counter`` is
    #: monotonic but has a process-private epoch — timestamps from two
    #: "monotonic" traces are NOT comparable until a merge aligns them
    #: on a shared sync event (tools/merge_traces.py then stamps the
    #: merged doc "synced"). Exported in trace metadata so downstream
    #: skew analysis can refuse mixed clock domains instead of
    #: producing nonsense numbers.
    clock_source = "monotonic"

    def __init__(self, annotate: bool = False,
                 profile_dir: Optional[str] = None):
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._epoch = _clock()
        self._pid = os.getpid()
        self._tids: Dict[int, int] = {}
        self._annotate = annotate
        self._profile_dir = profile_dir
        self._profiling = False
        if profile_dir:
            try:
                import jax
                jax.profiler.start_trace(profile_dir)
                self._profiling = True
            except Exception:
                self._profiling = False

    # -- recording -----------------------------------------------------------
    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    def instant(self, name: str, ts: float = None, **args) -> None:
        """A zero-duration marker (``ph: "i"``). ``ts`` (epoch-relative
        us) lets a caller that already stamped a clock read reuse it —
        the dist tracer's clock-sync marker must carry EXACTLY the
        timestamp the merge aligns on, not a second read µs later."""
        if ts is None:
            ts = (_clock() - self._epoch) * 1e6
        self._append({"name": name, "ph": "i", "ts": ts, "s": "t",
                      "pid": self._pid, "tid": self._tid(),
                      **({"args": args} if args else {})})

    def sync_instant(self, name: str, **args) -> None:
        """A clock-sync marker pairing one perf_counter read with one
        wall-clock read taken back-to-back. Unbarriered fleet processes
        have no shared event to align on (unlike the dist collective
        barrier), but they do share the host's wall clock — the merge
        recovers per-process offsets from the (ts, unix_ms) pair, so
        the two reads must bracket nothing in between."""
        t = _clock()
        unix_ms = time.time() * 1e3
        self.instant(name, ts=(t - self._epoch) * 1e6,
                     unix_ms=unix_ms, **args)

    def _complete(self, name: str, t0: float, t1: float,
                  args: Dict[str, Any],
                  cpu_s: Optional[float] = None) -> None:
        dur_ms = max((t1 - t0) * 1e3, 0.0)
        _with_cpu(args, dur_ms, cpu_s)
        ev = {"name": name, "ph": "X",
              "ts": (t0 - self._epoch) * 1e6,
              "dur": max((t1 - t0) * 1e6, 0.0),
              "pid": self._pid, "tid": self._tid()}
        if args:
            ev["args"] = args
        self._append(ev)
        cb = _span_observer
        if cb is not None:
            cb(name, dur_ms, args)

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            return self._tids.setdefault(ident, len(self._tids))

    def _append(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    def events(self) -> List[dict]:
        """Thread-safe snapshot of the recorded events — the obs.hlo
        trace-reconcile leg reads collective span byte args from it."""
        with self._lock:
            return list(self._events)

    # -- export --------------------------------------------------------------
    def to_dict(self, process_name: str = "dmlp_tpu") -> dict:
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "args": {"name": process_name}}]
        with self._lock:
            events = meta + list(self._events)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "clock": {"source": self.clock_source}}

    def write(self, path: str, process_name: str = "dmlp_tpu") -> None:
        if self._profiling:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._profiling = False
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(process_name), f)
        os.replace(tmp, path)


# -- process-wide hook -------------------------------------------------------
_active: Optional[Tracer] = None


def install(tracer: Tracer) -> Tracer:
    """Make ``tracer`` the process-wide collector hooks report to."""
    global _active
    _active = tracer
    return tracer


def uninstall() -> None:
    global _active
    _active = None


def active() -> Optional[Tracer]:
    return _active


def span(name: str, **args):
    """Instrumentation hook: a Span on the installed tracer, a minimal
    timing span when only a telemetry session observes, or the shared
    no-op span when both are off (the common case; near-zero cost)."""
    t = _active
    if t is not None:
        return t.span(name, **args)
    if _span_observer is not None:
        return _TelemetrySpan(name, args)
    return NULL_SPAN


def instant(name: str, **args) -> None:
    t = _active
    if t is not None:
        t.instant(name, **args)
    cb = _instant_observer
    if cb is not None:
        cb(name, args)


def sinks_active() -> bool:
    """True when completed spans go anywhere (Tracer or telemetry
    observer). Request-phase instrumentation that must be zero-cost
    when untraced gates its clock reads on this."""
    return _active is not None or _span_observer is not None


def thread_cpu() -> Optional[float]:
    """The calling thread's CPU time while a sink is installed, None
    otherwise (no system call on the untraced path): stamped beside a
    ``perf_counter`` read that is taken anyway, at each end of a clock
    pair whose two ends lie on one thread (``complete_at``'s
    ``cpu_s``)."""
    return _cpu() if sinks_active() else None


def complete_at(name: str, t0: float, t1: float,
                cpu_s: Optional[float] = None, **args) -> None:
    """Record a span from caller-measured ``perf_counter`` endpoints.

    The ``with span():`` form can only bracket one thread's stack
    frame; request phases (queue wait, scheduled-fire latency) start
    on one thread and end on another, so the producer stamps ``t0``,
    the consumer stamps ``t1``, and this records the interval as a
    regular complete event — same tracer + observer fan-out as Span
    exit, no-op when no sink is installed. A caller whose two ends lie
    on ONE thread stamps ``time.thread_time`` beside each and passes
    the difference as ``cpu_s``: the span then carries ``cpu_ms`` and
    ``offcpu_ms`` as a ``with`` span does; a pair that crosses threads
    has no thread's CPU time to give and carries neither."""
    t = _active
    if t is not None:
        t._complete(name, t0, t1, args, cpu_s)
        return
    cb = _span_observer
    if cb is not None:
        dur_ms = max((t1 - t0) * 1e3, 0.0)
        cb(name, dur_ms, _with_cpu(args, dur_ms, cpu_s))


# -- host-sync bracket ---------------------------------------------------------
# Where a thread blocks on the device (the ``# check: allow-host-sync``
# seams of the served path) it says so through ``device_wait``: two clock
# reads, two reads of the thread's CPU time and a per-thread tally, always
# on. While a sink is installed the bracket's own reads are also a span:
# the seam's own where it names one (``single.fetch``, ``serve.mp_fetch``,
# ``fleet.fetch``, ``fleet.merge_drain``), ``serve.wait.device``
# elsewhere (``prune_score``, ``gate``, ``merge``, ``retry``); either
# carries ``site``, and a cycle's wait spans sum to its ``device_wait_ms``
# by construction. The micro-batcher reads its own thread's tally once a
# cycle (serve/batching.py).

class WaitTally:
    """One thread's device waits since its last :meth:`take`: seconds
    in all and by ``site``, and the CPU seconds the thread spent inside
    them (a host sync that spins shows there). Only its own thread
    touches it."""

    __slots__ = ("seconds", "cpu_seconds", "by_site")

    def __init__(self):
        self.seconds = self.cpu_seconds = 0.0
        self.by_site: Dict[str, float] = {}

    def take(self):
        """(seconds, CPU seconds, {site: seconds}) waited since the
        last take."""
        out = self.seconds, self.cpu_seconds, self.by_site
        self.seconds, self.cpu_seconds, self.by_site = 0.0, 0.0, {}
        return out


_wait_local = threading.local()


def wait_tally() -> WaitTally:
    """The calling thread's tally (made on first use)."""
    try:
        return _wait_local.tally
    except AttributeError:
        tally = _wait_local.tally = WaitTally()
        return tally


class device_wait:  # noqa: N801 (used as ``with device_wait(site):``)
    """Bracket one host sync: ``site`` names the seam (``prune_score``,
    ``fetch``, ``mp_fetch``, ``gate``, ``merge_drain``, ``merge``,
    ``retry``).
    Retries and injected faults of the call inside stay inside; an
    exception still counts the wait. With a sink the bracket is a span
    from its own two clock reads: ``name`` (the seam's own span, with
    its ``args``) or ``serve.wait.device``, with ``site``, the
    micro-batch (``batch``) it belongs to, and the CPU account every
    one-thread span carries."""

    __slots__ = ("site", "name", "args", "_t0", "_c0", "_annot")

    def __init__(self, site: str, batch: Optional[int] = None,
                 name: str = "serve.wait.device", **args):
        self.site = site
        self.name = name
        self.args = args
        if batch is not None:
            args["batch"] = batch
        self._t0 = self._c0 = 0.0
        self._annot = None

    def __enter__(self) -> "device_wait":
        t = _active
        if t is not None and t._annotate:
            self._annot = _annotation(self.name)
        # the CPU reads lie OUTSIDE the clock reads: what the cycle takes
        # off its own CPU time covers all of what it takes off its wall
        self._c0 = _cpu()
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = _clock()
        cpu_s = _cpu() - self._c0
        tally = wait_tally()
        tally.seconds += t1 - self._t0
        tally.cpu_seconds += cpu_s
        tally.by_site[self.site] = \
            tally.by_site.get(self.site, 0.0) + (t1 - self._t0)
        _close_annotation(self._annot, exc)
        if sinks_active():
            complete_at(self.name, self._t0, t1, cpu_s, site=self.site,
                        **self.args)
        return False
