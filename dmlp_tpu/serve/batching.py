"""Continuous micro-batching: coalesce whatever is queued each tick.

Requests arrive one at a time (tiny nq each); the MXU wants full
tiles. The :class:`MicroBatcher` drains the admission queue each tick
into ONE padded micro-batch — variable (nq, k) requests concatenate,
the combined shape buckets to the engine's power-of-two jit-cache
buckets, and per-request results slice back out bit-identically (the
solo solve over the same corpus produces the same bytes; both equal
the golden oracle by the finalize/repair contract).

A micro-batch is solved in two halves (``engine.begin_batch``: what
only enqueues device work; ``engine.finish_batch``: the fence and the
host's float64 finalize; one pair for both resident engines,
``serve.engine.ResidentServingCore``'s), and the batcher runs the first
half of batch N + 1 BEFORE the second half of batch N whenever a
batch's worth of queries is already waiting: the device folds N + 1
while the host finalizes N, and the host finalizes while the device
folds. At most two batches are alive; a light load (less than a batch
queued while one is in flight) runs them one after the other, as a
serial batcher would, so small requests keep collecting company until
their fold can start.

The batcher accounts for its own time. Its thread's timeline is cut at
the end of every delivery into **cycles**, one a micro-batch, and each
cycle is split exactly three ways: the thread's waits for requests
(``queue_wait``), its waits on the device (``device_wait``: the
engines' host syncs, through ``obs.trace.device_wait``) and the rest
(``own``). ``own`` is split again by the kernel's account of the thread
(:func:`_cpu_now`, one reading a cycle): on a core (``own_cpu``) and
off it (``own_offcpu``). Always on: histograms ``serve.cycle_ms{,.own,
.device_wait,.queue_wait,.own_cpu,.own_offcpu,.wait_cpu}``, the thread's
CPU totals and a ring of slow cycles (``stats.batcher``); with a sink,
span ``serve.cycle`` and, around each wait, ``serve.wait.queue`` /
``serve.wait.device`` (or the seam's own span, with its ``site``).

Single consumer thread: the engine (and its ingest path) is driven by
exactly one thread, so resident-buffer updates never race a solve.
Requests complete through a per-request event; connection handlers
block on it and write the response.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dmlp_tpu.config import score_of
from dmlp_tpu.obs import telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.obs.trace import span as obs_span
from dmlp_tpu.resilience import inject as rs_inject
from dmlp_tpu.serve.admission import ACCEPT, AdmissionController
from dmlp_tpu.serve.engine import ResidentServingCore

try:
    import resource
    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):    # not Linux: thread_time alone
    _RUSAGE_THREAD = None

#: what the cycle account reads of ``getrusage(RUSAGE_THREAD)``. The
#: user / system split is tick-grained everywhere: exact over a window,
#: a MEAN's worth over cycles, never a median's.
_RUSAGE_FIELDS = (("user_s", "ru_utime"), ("sys_s", "ru_stime"),
                  ("minflt", "ru_minflt"), ("majflt", "ru_majflt"),
                  ("nvcsw", "ru_nvcsw"), ("nivcsw", "ru_nivcsw"))


def _usable_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:               # not Linux
        return os.cpu_count() or 1


def _cpu_now() -> Dict[str, float]:
    """The kernel's account of the calling thread and of the process,
    running totals: ``thread_s`` (``time.thread_time``: CPU seconds,
    user and system), ``process_s`` (every thread's) and, on Linux, the
    thread's rusage: the user / system split, minor and major page
    faults, voluntary and involuntary context switches. Three system
    calls. All of it is as fine as the host's kernel keeps it: a
    sandboxed kernel may advance the CPU clocks in 10 ms ticks and
    count no fault or switch at all (the chip's host does both), so
    read a cycle's CPU parts as MEANS over a window."""
    out = {"thread_s": time.thread_time(),
           "process_s": time.process_time()}
    if _RUSAGE_THREAD is not None:
        ru = resource.getrusage(_RUSAGE_THREAD)
        for key, field in _RUSAGE_FIELDS:
            out[key] = getattr(ru, field)
    return out

#: default batcher tick: how long a lone request waits for company
TICK_S = 0.002

#: the ring of slow cycles (``stats.batcher.slow_cycles``): a cycle is
#: kept, whole, when it is longer than SLOW_FACTOR x the running median
#: of ``serve.cycle_ms`` and at least SLOW_MIN_OVER_MS over it; none of
#: a daemon's first SLOW_WARM_CYCLES (the median is not yet one); the
#: ring holds the newest SLOW_RING
SLOW_FACTOR = 3.0
SLOW_MIN_OVER_MS = 50.0
SLOW_WARM_CYCLES = 32
SLOW_RING = 16


@dataclasses.dataclass
class Request:
    """One admitted unit of work. ``kind`` is "query" | "ingest" |
    "corpus"; non-query requests execute standalone between
    micro-batches, with none in flight (the one batcher thread
    serializes them against solves — a ``corpus`` read can therefore
    never observe a torn ingest, and both halves of a micro-batch see
    the corpus its requests were admitted against)."""

    kind: str
    req_id: str = ""
    rid: str = ""                                 # trace request id ("" =
    #                                               untraced; never invented
    #                                               server-side)
    query_attrs: Optional[np.ndarray] = None      # (nq, na) float64
    ks: Optional[np.ndarray] = None               # (nq,) int32
    labels: Optional[np.ndarray] = None           # ingest: (m,) int32
    attrs: Optional[np.ndarray] = None            # ingest: (m, na) f64
    start: Optional[int] = None                   # ingest row-write /
    #                                               corpus read offset
    count: Optional[int] = None                   # corpus read length
    debug: bool = False                           # echo neighbors/dists
    parsed_native: bool = False                   # query_attrs came from
    #                                               the native scanner
    t_enqueue: float = dataclasses.field(default_factory=time.monotonic)
    # Same instant in the tracer's clock domain: request-phase spans
    # (queue/coalesce/...) are cross-thread intervals stitched from
    # perf_counter reads via trace.complete_at.
    t_enqueue_pc: float = dataclasses.field(
        default_factory=time.perf_counter)
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    results: Optional[List] = None                # QueryResults (local ids)
    error: Optional[str] = None
    latency_ms: Optional[float] = None
    batch: Optional[int] = None                   # serial of the micro-
    #                                               batch it rode
    respond_pc: Optional[Tuple[float, float, Optional[float]]] = None
    #                                               perf_counter pair around
    #                                               query_response, and the
    #                                               handler's CPU seconds in it
    corpus_rows: Optional[int] = None             # ingest outcome
    payload: Optional[Dict[str, Any]] = None      # corpus outcome

    @property
    def nq(self) -> int:
        return 0 if self.ks is None else len(self.ks)

    def complete(self, results=None, error=None, corpus_rows=None) -> None:
        self.results = results
        self.error = error
        self.corpus_rows = corpus_rows
        self.latency_ms = (time.monotonic() - self.t_enqueue) * 1e3
        self.done.set()


@dataclasses.dataclass
class _Flight:
    """A micro-batch between its two halves: begun (its device work is
    enqueued), not finished. With the pipeline engaged it is begun in
    one cycle of the batcher and finished in the next, so the spans
    that run from its begin to its finish (``serve.micro_batch``,
    ``serve.solve_multipass``) cross the other batch's second half;
    every other span of it lies inside one cycle. Those two are clock
    pairs with another batch's work between their ends, and carry no
    ``cpu_ms`` / ``offcpu_ms``: the spans inside them do."""

    requests: List[Request]
    total: int           # queries
    qpad: int
    wake_pc: float       # perf_counter: the consumer took it off the queue
    t0: float            # perf_counter: its first half started
    #: the engine's record of it (begin_batch's return): ``batch`` is the
    #: serial every span of the batch carries, ``rids`` its trace rids
    pending: Any


class MicroBatcher:
    """The admission queue + the one batch-execution thread, which
    keeps up to two micro-batches in the engine: it begins the next one
    (when the queue already holds a batch's worth of queries) before it
    finishes and delivers the one in flight. ``ingest`` and ``corpus``
    requests run with nothing in flight.

    **A cycle** is one delivered micro-batch's piece of the thread's
    timeline: from the end of the previous ``_finish`` (delivery
    included) to the end of this one. Pipelined that is ``_collect`` +
    ``_begin(N + 1)`` + ``_finish(N)``; in the serial order ``_collect``
    (blocking: the idle wait and the tick) + ``_begin(N)`` + an empty
    ``_collect`` + ``_finish(N)``. No two cycles overlap and together
    they tile the thread while batches flow (an ``ingest`` / ``corpus``
    request, or a batch whose first half failed, lies in the next
    batch's cycle). ``cycle = own + device_wait + queue_wait``, exactly:
    ``queue_wait`` is the thread blocked in ``_cond.wait``,
    ``device_wait`` its host syncs (the thread's ``WaitTally``), ``own``
    the rest (Python and NumPy work, waits for the interpreter lock,
    collector pauses). ``gc_ms`` beside them is the collector's pause
    time, any thread's, that ended inside the cycle.

    ``own = own_cpu + own_offcpu``, exactly: ``own_cpu`` is the thread's
    CPU time over the cycle less what it burnt inside its device waits
    (``wait_cpu``: a host sync that spins) and its queue waits;
    ``own_offcpu`` the rest of ``own``: the thread wanted to run and did
    not (the interpreter lock, the scheduler, a blocking fault). Beside
    them, from the thread's rusage where the platform has it:
    ``own_sys_ms`` (system CPU over the whole cycle), ``minflt``,
    ``majflt``, ``nvcsw``, ``nivcsw``; and ``cores_busy``, the whole
    process's CPU time over the cycle's wall time."""

    def __init__(self, engine: ResidentServingCore,
                 admission: AdmissionController,
                 max_batch_queries: int = 1024,
                 tick_s: float = TICK_S):
        self.engine = engine
        self.admission = admission
        self.max_batch_queries = max_batch_queries
        self.tick_s = tick_s
        self._queue: deque = deque()
        self._queued_queries = 0
        self._queued_kmax = 0     # max k among queued query requests
        self._cond = threading.Condition()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self.batches = 0
        # Serial of the last micro-batch begun: every span of one batch
        # carries its own as ``batch``. Consumer-thread-private.
        self._serial = 0
        # The cycle in hand (consumer-thread-private): where it started
        # (perf_counter), what the thread has waited for requests in it,
        # the batch begun in it (0: none), the collector's total at its
        # start. `cycles` and the ring are read by stats handlers.
        self._cycle_t0 = 0.0
        self._queue_wait_s = 0.0
        self._queue_cpu_s = 0.0   # thread CPU inside those waits
        self._begun = 0
        self._gc_s = 0.0
        # _cpu_now() at the thread's start and at the last cycle's end
        # (each a fresh dict, swapped in whole: stats handlers read
        # their difference as stats.batcher.cpu)
        self._cpu_first: Dict[str, float] = {}
        self._cpu_last: Dict[str, float] = {}
        self.cycles = 0
        self._slow: deque = deque(maxlen=SLOW_RING)

    # -- producer side ---------------------------------------------------------

    def submit(self, req: Request) -> Dict[str, Any]:
        """Admission decision + enqueue; returns the decision dict.
        Rejected requests complete immediately with the reason.

        Two-phase admission: the request-local half (shape caps + the
        ``serve.admit`` injection hook, which may SLEEP for a straggler
        fault) runs before the queue lock; only the queue-state half —
        pure reads + arithmetic — runs under it, keeping decision +
        enqueue atomic without a blocking call under the lock (check
        rule R703 enforces this split statically)."""
        if req.kind == "query":
            kmax = int(req.ks.max()) if req.nq else 0
            a0 = time.perf_counter() if obs_trace.sinks_active() else 0.0
            pre = self.admission.precheck(req.nq, kmax)
            with self._cond:
                decision = self.admission.decide_queued(
                    req.nq, kmax, self._queued_queries,
                    queued_kmax=self._queued_kmax, prechecked=pre)
                if decision["verdict"] == ACCEPT:
                    self._queue.append(req)
                    self._queued_queries += req.nq
                    self._queued_kmax = max(self._queued_kmax, kmax)
                    telemetry.registry().gauge("serve.queue_depth").set(
                        self._queued_queries)
                    self._cond.notify()
            if a0:
                # Runs on the handler thread CONCURRENTLY with the
                # queue wait, so it is reported for attribution but
                # excluded from the phase sum the merge reconciles
                # (see tools/merge_traces.py --fleet).
                args = {"rid": req.rid} if req.rid else {}
                obs_trace.complete_at("serve.phase.admission", a0,
                                      time.perf_counter(),
                                      verdict=decision["verdict"], **args)
            if decision["verdict"] != ACCEPT:
                req.complete(error=f"rejected: {decision['reason']}")
            return decision
        # Ingest + corpus reads ride the same queue (serialized against
        # solves) but skip the per-query admission gates; capacity
        # errors surface at execution.
        with self._cond:
            if self.admission.draining:
                req.complete(error="rejected: draining")
                return {"verdict": "reject", "reason": "draining"}
            self._queue.append(req)
            self._cond.notify()
        return {"verdict": ACCEPT, "reason": "ok"}

    # -- consumer side ---------------------------------------------------------

    def start(self) -> None:
        # The whole check-then-spawn is one critical section: two
        # concurrent start() calls (or start() racing stop()) must not
        # each observe `_thread is None` and spawn TWO consumer loops —
        # the single-consumer invariant is what lets the engine run
        # lock-free. `_stop` is likewise guarded state (the consumer
        # reads it under the lock in _collect/_run_loop).
        with self._cond:
            if self._thread is not None:
                return
            self._stop = False
            t = self._thread = threading.Thread(
                target=self._run_loop, name="serve-batcher",
                daemon=True)
            # started inside the guard so a racing stop() can never
            # grab an un-started handle (join would raise); the new
            # consumer just blocks on the lock until we release
            t.start()

    def stop(self, drain: bool = True, timeout: float = 60.0) -> None:
        """Stop the batcher thread. ``drain=True`` finishes everything
        already queued first (the SIGTERM path); ``drain=False`` fails
        queued requests with a shutdown error. A micro-batch in flight
        is finished and delivered either way: its device work is
        already enqueued."""
        with self._cond:
            self._stop = True
            if not drain:
                while self._queue:
                    self._queue.popleft().complete(error="shutdown")
                self._queued_queries = 0
                self._queued_kmax = 0   # the queue is empty: a stale
                #            kmax would over-price the next admission
                #            if this batcher is ever restarted
            self._cond.notify_all()
            t = self._thread
            self._thread = None     # handle handoff under the lock;
            #                         the join itself must NOT hold it
            #                         (the consumer needs the lock to
            #                         finish draining — check R703)
        if t is not None:
            t.join(timeout=timeout)

    def _collect(self, block: bool) -> Tuple[List[Request], float]:
        """Drain the queue up to the batch cap — the 'coalesce whatever
        is queued each tick' core — and say when (perf_counter: the
        queue-wait / coalesce-wait boundary of the phase spans).
        ``block``: wait for work, and let a lone request wait one tick
        for company before solving solo. With a batch in flight the
        caller does not block, and takes query requests only if what is
        queued fills a batch: requests that could still take company
        (the test the tick makes) wait for it through the finish of the
        batch in flight, as they would have waited through its whole
        solve; taken now they would be committed to a batch a device
        pass before their fold can start. An empty return sends the
        caller to finish that batch. The time blocked in ``_cond.wait``
        (for work; for company) is the cycle's ``queue_wait``."""
        waits: List[Tuple[float, float, float]] = []
        taken = self._take(block, waits)
        # outside the queue lock: the cycle's tally, and the spans
        for w0, w1, cpu_s in waits:
            self._queue_wait_s += w1 - w0
            self._queue_cpu_s += cpu_s
            obs_trace.complete_at("serve.wait.queue", w0, w1, cpu_s)
        return taken

    def _take(self, block: bool, waits: List[Tuple[float, float, float]]
              ) -> Tuple[List[Request], float]:
        """``waits``: (start, end, thread CPU seconds) of each block in
        ``_cond.wait``."""
        clock, cpu = time.perf_counter, time.thread_time
        with self._cond:
            if block and not self._queue and not self._stop:
                c0, w0 = cpu(), clock()    # CPU reads outside the clock's
                while not self._queue and not self._stop:
                    self._cond.wait(timeout=0.1)
                waits.append((w0, clock(), cpu() - c0))
            if not self._queue or (
                    not block and self._queue[0].kind == "query"
                    and self._queued_queries < self.max_batch_queries):
                return [], 0.0
            tick = block and not self._stop and self.tick_s > 0 \
                and self._queued_queries < self.max_batch_queries
            c0 = cpu() if tick else 0.0
            wake_pc = clock()
            if tick:
                self._cond.wait(timeout=self.tick_s)
                waits.append((wake_pc, clock(), cpu() - c0))
            batch: List[Request] = []
            total = 0
            while self._queue:
                head = self._queue[0]
                if head.kind != "query":
                    if batch:
                        break          # solve what we have first
                    self._queue.popleft()
                    return [head], wake_pc   # ingest/corpus: standalone
                if batch and total + head.nq > self.max_batch_queries:
                    break
                self._queue.popleft()
                batch.append(head)
                total += head.nq
            self._queued_queries -= total
            if self._queued_queries == 0:
                self._queued_kmax = 0   # conservative: only reset when
                #                         nothing queued remains
            telemetry.registry().gauge("serve.queue_depth").set(
                self._queued_queries)
            return batch, wake_pc

    def _run_loop(self) -> None:
        flight: Optional[_Flight] = None
        self._cycle_t0 = time.perf_counter()
        self._cpu_first = self._cpu_last = _cpu_now()
        self._gc_s = telemetry.gc_pauses().total_s
        obs_trace.wait_tally().take()
        while True:
            batch, wake_pc = self._collect(block=flight is None)
            if batch and batch[0].kind == "query":
                # Batch N + 1's fold goes onto the device's queue BEFORE
                # batch N is read back and finalized.
                begun = self._begin(batch, wake_pc)
                if flight is not None:
                    self._finish(flight)
                flight = begun
                continue
            if flight is not None:
                # Nothing to begin behind it (the queue is empty or
                # holds less than a batch, or an ingest / corpus request
                # heads it and must see no batch in flight).
                self._finish(flight)
                flight = None
            if not batch:
                with self._cond:
                    if self._stop and not self._queue:
                        return
            elif batch[0].kind == "ingest":
                self._execute_ingest(batch[0])
            else:
                self._execute_corpus(batch[0])

    def _phase(self, name: str, t0: float, t1: float, rid: str,
               **args) -> None:
        """One request-phase span through the complete_at seam (tracer
        AND the PR 9 telemetry observer, so ``serve.phase.*.ms``
        histograms stay live); rid-tagged when the request carried
        one. A no-op when no sink is installed. These pairs start on a
        handler thread (``serve.phase.queue``) or tile the batch's
        interval per request, so none carries ``cpu_ms`` /
        ``offcpu_ms``: no one thread's CPU time is theirs."""
        if rid:
            args["rid"] = rid
        obs_trace.complete_at(name, t0, max(t0, t1), **args)

    def _execute_ingest(self, req: Request) -> None:
        e0 = 0.0
        if obs_trace.sinks_active():
            # Queued until it runs: the batch that was in flight when
            # it reached the head of the queue is finished first.
            e0 = time.perf_counter()
            self._phase("serve.phase.queue", req.t_enqueue_pc, e0,
                        req.rid, kind="ingest")
        try:
            # The fleet chaos harness's dropped-ingest site: a
            # transient fault here fails THIS replica's ingest before
            # any state is touched — the router reports the divergence
            # and the consistency repairer must re-deliver the rows.
            rs_inject.fire("serve.ingest", rows=int(len(req.labels)),
                           start=-1 if req.start is None
                           else int(req.start))
            rows = self.engine.ingest(req.labels, req.attrs,
                                      start=req.start)
            req.complete(corpus_rows=rows)
        except Exception as e:  # check: no-retry — surfaced to the client
            req.complete(error=f"{type(e).__name__}: {e}")
        if e0:
            self._phase("serve.phase.ingest", e0, time.perf_counter(),
                        req.rid, ok=req.error is None)

    def _execute_corpus(self, req: Request) -> None:
        """Serve one ``corpus`` read on the batcher thread: the rows
        and the signature are one snapshot (no ingest can interleave)."""
        e0 = 0.0
        if obs_trace.sinks_active():
            e0 = time.perf_counter()
            self._phase("serve.phase.queue", req.t_enqueue_pc, e0,
                        req.rid, kind="corpus")
        try:
            state = self.engine.corpus_state()
            labels, attrs = self.engine.corpus_slice(req.start or 0,
                                                     req.count or 0)
            req.payload = {
                "start": max(0, min(int(req.start or 0), state["rows"])),
                "labels": [int(v) for v in labels],
                "rows": [[float(x) for x in row] for row in attrs],
                "corpus_rows": state["rows"],
                "checksum": state["checksum"],
                "epoch": state["epoch"],
            }
            req.complete()
        except Exception as e:  # check: no-retry — surfaced to the client
            req.complete(error=f"{type(e).__name__}: {e}")
        if e0:
            self._phase("serve.phase.corpus", e0, time.perf_counter(),
                        req.rid, ok=req.error is None)

    @staticmethod
    def _fail(batch: List[Request], e: Exception) -> None:
        """A batch fails visibly, alone; the daemon survives."""
        telemetry.registry().counter("serve.batch_errors").inc()
        msg = f"{type(e).__name__}: {e}"
        for r in batch:
            r.complete(error=msg)

    def _begin(self, batch: List[Request],
               wake_pc: float) -> Optional[_Flight]:
        """Assemble a micro-batch and run its first half: its device
        work is enqueued when this returns (None: it failed, and its
        requests are answered with the error)."""
        self._serial += 1
        serial = self._begun = self._serial
        total = sum(r.nq for r in batch)
        with obs_span("serve.batch_assemble", batch=serial,
                      requests=len(batch), queries=total):
            q = np.concatenate([r.query_attrs for r in batch])
            ks = np.concatenate([r.ks for r in batch])
            qpad, _ = self.engine.bucket_shape(
                total, int(ks.max()) if total else 1)
        rids = ",".join(r.rid for r in batch if r.rid) \
            if obs_trace.sinks_active() else ""
        t0 = time.perf_counter()
        try:
            # The chaos harness's straggler-solve site: a delay fault
            # here slows THIS replica's serialized batch execution (the
            # single consumer sleeps while the core stays idle), which
            # is how tools/slo_smoke.py emulates accelerator-bound
            # service times on a CPU-only container; a transient fault
            # fails the whole batch visibly (serve.batch_errors).
            rs_inject.fire("serve.solve", requests=len(batch),
                           queries=total)
            pending = self.engine.begin_batch(q, ks, batch=serial,
                                              rids=rids or None)
        except Exception as e:  # check: no-retry — batch fails visibly
            self._fail(batch, e)
            return None
        return _Flight(batch, total, qpad, wake_pc, t0, pending)

    def _finish(self, f: _Flight) -> None:
        """The second half of a micro-batch, its delivery, and the end
        of the batcher's cycle (:meth:`_end_cycle`).
        ``serve.micro_batch`` runs from the start of its first half to
        the end of its second: it CROSSES batches (two of them overlap
        in time when the batch was begun behind another:
        ``overlapped``), as ``serve.solve_multipass`` does; the cycle
        and every other span of the batcher thread do not."""
        self._finish_batch(f)
        self._end_cycle(f)

    def _finish_batch(self, f: _Flight) -> None:
        results, error = None, None
        try:
            results = self.engine.finish_batch(f.pending)
        except Exception as e:  # check: no-retry — batch fails visibly
            error = e
        t1 = time.perf_counter()
        obs_trace.complete_at(
            "serve.micro_batch", f.t0, t1, requests=len(f.requests),
            queries=f.total, qpad=f.qpad, batch=f.pending.batch,
            overlapped=int(f.pending.overlapped),
            **({"rids": f.pending.rids} if f.pending.rids else {}))
        if error is not None:
            self._fail(f.requests, error)
            return
        with obs_span("serve.batch_deliver", batch=f.pending.batch,
                      requests=len(f.requests), queries=f.total):
            self._deliver(f, results, t1)

    def _end_cycle(self, f: _Flight) -> None:
        """Close the cycle that delivered ``f`` and open the next: the
        always-on histograms, the slow-cycle ring, the collector's
        noted pauses into their counters, and (with a sink) the
        ``serve.cycle`` span. Everything after the one clock read and
        the one reading of the thread's CPU account beside it is the
        next cycle's ``own``."""
        t1 = time.perf_counter()
        cpu = _cpu_now()
        t0, self._cycle_t0 = self._cycle_t0, t1
        last, self._cpu_last = self._cpu_last, cpu
        wait_s, wait_cpu_s, sites = obs_trace.wait_tally().take()
        queue_s, self._queue_wait_s = self._queue_wait_s, 0.0
        queue_cpu_s, self._queue_cpu_s = self._queue_cpu_s, 0.0
        begun, self._begun = self._begun, 0
        gcp = telemetry.gc_pauses()
        gc_s, self._gc_s = gcp.total_s - self._gc_s, gcp.total_s
        gcp.drain()
        cycle_ms = (t1 - t0) * 1e3
        device_ms, queue_ms, gc_ms = wait_s * 1e3, queue_s * 1e3, gc_s * 1e3
        own_ms = cycle_ms - device_ms - queue_ms
        spent = {k: v - last[k] for k, v in cpu.items()}
        own_cpu_ms = (spent["thread_s"] - wait_cpu_s - queue_cpu_s) * 1e3
        account = {"own_cpu_ms": own_cpu_ms,
                   "own_offcpu_ms": own_ms - own_cpu_ms,
                   "wait_cpu_ms": wait_cpu_s * 1e3,
                   "cores_busy": spent["process_s"] / max(t1 - t0, 1e-9)}
        if "sys_s" in spent:
            account["own_sys_ms"] = spent["sys_s"] * 1e3
            account.update((k, int(spent[k])) for k in (
                "minflt", "majflt", "nvcsw", "nivcsw"))
        reg = telemetry.registry()
        h_cycle = reg.histogram("serve.cycle_ms", unit="ms")
        h_cycle.observe(cycle_ms)
        reg.histogram("serve.cycle_ms.own", unit="ms").observe(own_ms)
        reg.histogram("serve.cycle_ms.device_wait",
                      unit="ms").observe(device_ms)
        reg.histogram("serve.cycle_ms.queue_wait",
                      unit="ms").observe(queue_ms)
        reg.histogram("serve.cycle_ms.own_cpu",
                      unit="ms").observe(own_cpu_ms)
        reg.histogram("serve.cycle_ms.own_offcpu",
                      unit="ms").observe(account["own_offcpu_ms"])
        reg.histogram("serve.cycle_ms.wait_cpu",
                      unit="ms").observe(account["wait_cpu_ms"])
        pend = f.pending
        overlapped = int(pend.overlapped)
        # A slow cycle is at least SLOW_MIN_OVER_MS long: the median is
        # not asked for under that.
        slow = None
        if self.cycles >= SLOW_WARM_CYCLES and cycle_ms >= SLOW_MIN_OVER_MS:
            median = h_cycle.quantile(0.5)
            if cycle_ms > SLOW_FACTOR * median \
                    and cycle_ms - median >= SLOW_MIN_OVER_MS:
                slow = {
                    "unix_time": round(time.time(), 3),
                    "batch": pend.batch,
                    "path": pend.path,
                    "queries": f.total, "requests": len(f.requests),
                    "overlapped": overlapped,
                    "cycle_ms": round(cycle_ms, 3),
                    "own_ms": round(own_ms, 3),
                    "device_wait_ms": round(device_ms, 3),
                    "queue_wait_ms": round(queue_ms, 3),
                    "gc_ms": round(gc_ms, 3),
                    "device_wait_sites_ms": {
                        k: round(v * 1e3, 3) for k, v in sites.items()},
                    "median_ms": round(median, 3),
                    **{k: round(v, 3) for k, v in account.items()}}
        if slow is not None:
            with self._cond:
                slow["queue_depth"] = self._queued_queries
                self._slow.append(slow)
        self.cycles += 1    # one writer; stats handlers load one int
        if slow is not None:
            telemetry.flight_event("serve.slow_cycle", **slow)
            tracer = obs_trace.active()
            if tracer is not None:
                tracer.instant("serve.slow_cycle", **slow)
        if obs_trace.sinks_active():
            obs_trace.complete_at(
                "serve.cycle", t0, t1, batch=pend.batch, begun=begun,
                queries=f.total, requests=len(f.requests),
                overlapped=overlapped, own_ms=own_ms,
                device_wait_ms=device_ms, queue_wait_ms=queue_ms,
                gc_ms=gc_ms, **account)

    def cycle_stats(self) -> Dict[str, Any]:
        """``stats.batcher``: cycles closed, the ring of slow ones
        (newest last), each whole: when, which batch on which path, its
        three parts and ``gc_ms``, the device wait by site, the CPU
        account, the queue's depth when it ended; and ``cpu``: the
        batcher thread's totals since it started, as of its last cycle
        (``_cpu_now``'s fields), beside the cores the process may run
        on."""
        with self._cond:
            slow = [dict(c) for c in self._slow]
        first, last = self._cpu_first, self._cpu_last
        return {"cycles": self.cycles, "slow_cycles": slow,
                "cpu": {**{k: round(v - first[k], 6)
                           for k, v in last.items()},
                        "cores": _usable_cores()}}

    def _deliver(self, f: _Flight, results: List, t1: float) -> None:
        """A solved micro-batch back to its requests: the batch's
        counters and always-on timings, then per request the slice of
        results, the completion and its phase decomposition — each
        phase one clock pair that feeds a registry histogram (every
        daemon; ``stats`` reports them as ``phases_ms``) and, while a
        sink is installed, the ``serve.phase.*`` span."""
        reg = telemetry.registry()
        batch, serial, t0 = f.requests, f.pending.batch, f.t0
        with self._cond:
            # handler threads read `batches` through daemon.stats()
            # while this consumer increments it — guard the write so
            # the field has one discipline (reads are single int loads)
            self.batches += 1
        # labelled by what the corpus is ranked by, so that a scrape of
        # a fleet holding both kinds tells them apart (readers sum)
        reg.counter("serve.batches").inc(label=score_of(self.engine))
        # The pipeline's own count: batches begun while another was in
        # flight (stats.engine.overlap).
        reg.counter("serve.batches_overlapped").inc(
            int(f.pending.overlapped))
        reg.histogram("serve.batch_queries").observe(f.total)
        # The engine's own parts of the batch it finished last
        # (engine.last_phase_ms: the dispatch, a mesh engine's
        # cross-shard merge, the readback, the hazard pass, the float64
        # finalize), whichever this engine reports.
        parts = getattr(self.engine, "last_phase_ms", None) or {}
        for part, hist in (
                ("dispatch",
                 reg.histogram("serve.batch_ms.dispatch", unit="ms")),
                ("merge", reg.histogram("serve.batch_ms.merge", unit="ms")),
                ("fetch", reg.histogram("serve.batch_ms.fetch", unit="ms")),
                ("hazard",
                 reg.histogram("serve.batch_ms.hazard", unit="ms")),
                ("finalize",
                 reg.histogram("serve.batch_ms.finalize", unit="ms"))):
            if part in parts:
                hist.observe(parts[part])
        h_queue = reg.histogram("serve.phase_ms.queue", unit="ms")
        h_coalesce = reg.histogram("serve.phase_ms.coalesce", unit="ms")
        h_solve = reg.histogram("serve.phase_ms.solve", unit="ms")
        h_finalize = reg.histogram("serve.phase_ms.finalize", unit="ms")
        off = 0
        for r in batch:
            sub = results[off:off + r.nq]
            # Re-anchor query ids to the request (byte-identical to the
            # solo solve of the same request over the same corpus).
            local = [dataclasses.replace(qr, query_id=qr.query_id - off)
                     for qr in sub]
            off += r.nq
            r.batch = serial
            r.complete(results=local)
            reg.counter("serve.requests_completed").inc()
            reg.counter("serve.queries_completed").inc(r.nq)
            reg.histogram("serve.request_latency_ms", unit="ms").observe(
                (time.monotonic() - r.t_enqueue) * 1e3,
                exemplar=r.rid or None)
            # Per-request phase decomposition. queue ends when the
            # consumer took the batch off the queue (clamped: a request
            # that arrived during the coalesce tick has zero queue
            # wait); coalesce runs to solve start; the full batch solve
            # interval — from the start of its first half to the end of
            # its second, the other batch's second half in between
            # included — is attributed to EVERY coalesced request
            # (documented overlap — the phases of one rid tile its wall
            # time, they do not sum across rids); finalize is this
            # request's delivery, from the solve's end to here.
            q1 = min(max(f.wake_pc, r.t_enqueue_pc), t0)
            t2 = time.perf_counter()
            h_queue.observe((q1 - r.t_enqueue_pc) * 1e3)
            h_coalesce.observe((t0 - q1) * 1e3)
            h_solve.observe((t1 - t0) * 1e3)
            h_finalize.observe((t2 - t1) * 1e3)
            self._phase("serve.phase.queue", r.t_enqueue_pc, q1,
                        r.rid, batch=serial)
            self._phase("serve.phase.coalesce", q1, t0, r.rid,
                        requests=len(batch), batch=serial)
            self._phase("serve.phase.solve", t0, t1, r.rid,
                        queries=f.total, qpad=f.qpad, batch=serial)
            self._phase("serve.phase.finalize", t1, t2, r.rid,
                        batch=serial)
