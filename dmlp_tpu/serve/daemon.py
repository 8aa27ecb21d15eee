"""The serving daemon: parse once, stage once, compile once, serve.

``python -m dmlp_tpu.serve --corpus FILE`` builds a
:class:`~dmlp_tpu.serve.engine.ResidentEngine` over the corpus file's
data section, warms the shape buckets derived from its query section
(plus ``--warm-buckets``), and serves the line-JSON protocol
(:mod:`dmlp_tpu.serve.protocol`) on a localhost TCP port. Telemetry is
the PR 9 substrate unchanged: ``--telemetry-port`` is the live
OpenMetrics scrape surface, per-request latency lands in the
registry's log-bucket histograms, and ``--record`` appends serve
RunRecords (kind "serve").

Shutdown contract (the graceful-drain satellite): SIGTERM (or an
in-band ``drain`` op) stops admission ("draining" rejections), lets
the batcher finish every in-flight and queued micro-batch, appends the
final RunRecord, flushes the final telemetry snapshot, and exits 0 —
an orderly drain leaves NO flight-recorder dump (crashes still do).
"""

from __future__ import annotations

import json
import os
import socketserver
import threading
import time
from typing import Any, Dict, List, Optional, Tuple, Union

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.io import native
from dmlp_tpu.io.grammar import KNNInput
from dmlp_tpu.obs import telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve import protocol
from dmlp_tpu.serve.admission import AdmissionController
from dmlp_tpu.serve.batching import MicroBatcher, Request
from dmlp_tpu.serve.engine import ResidentEngine


#: The always-on phase timings the ``stats`` op reports as
#: ``phases_ms``: where a request's time went ("request": fed once per
#: query request, by the handler thread and the batcher) and where a
#: micro-batch's went ("batch": fed once per micro-batch from the
#: engine's ``last_phase_ms``). Registered, with these literal names,
#: at the sites that time them (serve/daemon.py, serve/batching.py).
#: NB ``request.finalize`` is the per-request DELIVERY after the solve
#: (the ``serve.phase.finalize`` span); ``batch.finalize`` is the
#: engine's float64 finalize. "cycle": the batcher thread's timeline
#: cut a delivered micro-batch, ``cycle = own + device_wait +
#: queue_wait`` and ``own = own_cpu + own_offcpu`` by the kernel's
#: account of the thread, ``wait_cpu`` the CPU it burnt inside its device
#: waits (serve/batching.py; fed once per micro-batch).
PHASE_HISTOGRAMS = {
    "request": (("read", "serve.phase_ms.read"),
                ("parse", "serve.phase_ms.parse"),
                ("queue", "serve.phase_ms.queue"),
                ("coalesce", "serve.phase_ms.coalesce"),
                ("solve", "serve.phase_ms.solve"),
                ("finalize", "serve.phase_ms.finalize"),
                ("respond", "serve.phase_ms.respond"),
                ("write", "serve.phase_ms.write")),
    "batch": (("dispatch", "serve.batch_ms.dispatch"),
              ("merge", "serve.batch_ms.merge"),
              ("fetch", "serve.batch_ms.fetch"),
              ("hazard", "serve.batch_ms.hazard"),
              ("finalize", "serve.batch_ms.finalize")),
    "cycle": (("cycle", "serve.cycle_ms"),
              ("own", "serve.cycle_ms.own"),
              ("device_wait", "serve.cycle_ms.device_wait"),
              ("queue_wait", "serve.cycle_ms.queue_wait"),
              ("own_cpu", "serve.cycle_ms.own_cpu"),
              ("own_offcpu", "serve.cycle_ms.own_offcpu"),
              ("wait_cpu", "serve.cycle_ms.wait_cpu")),
}


def _cpu_between(c0: Optional[float], c1: Optional[float]
                 ) -> Optional[float]:
    """The ``cpu_s`` of a one-thread phase span (``serve.phase.read`` /
    ``parse`` / ``respond`` / ``write``) from ``obs.trace.thread_cpu``
    at its two ends: None, and the span without ``cpu_ms`` /
    ``offcpu_ms``, unless a sink was installed at both."""
    return None if c0 is None or c1 is None else c1 - c0


def default_warm_buckets(corpus: KNNInput) -> List[Tuple[int, int]]:
    """Warm-up shapes: the corpus file's own query section is the
    operator's declaration of expected traffic — bucket every (count,
    k) it contains, plus the smallest bucket as the floor."""
    out = [(1, 1)]
    nq = corpus.params.num_queries
    if nq:
        out.append((nq, int(corpus.ks.max())))
        out.append((1, int(corpus.ks.min())))
    return out


class _Handler(socketserver.StreamRequestHandler):
    """One connection: requests answered strictly in line order."""

    def handle(self):  # noqa: D102 (socketserver API)
        daemon: ServeDaemon = self.server.daemon
        # The handler reads the socket itself, never through rfile
        # (whose buffer would keep bytes from the reader). Bounded
        # read: the reader never holds more than the cap + 1, so an
        # oversized request line cannot balloon the daemon's memory
        # before rejection. A cap-exceeding read has lost line framing
        # — reject and drop the connection. The read is timed from the
        # request's first byte: an idle connection's wait is not in it.
        reader = protocol.LineReader(self.connection)
        while True:
            raw = reader.readline()
            if not raw:
                break
            t_read, c_read = time.perf_counter(), obs_trace.thread_cpu()
            t_first, pieces = reader.t_first, reader.pieces
            if len(raw) > protocol.MAX_LINE_BYTES:
                self.wfile.write(protocol.encode(
                    {"ok": False,
                     "error": "request line exceeds the size cap"}))
                break
            # The line goes on as the bytes it came as: the bulk of a
            # query line never becomes a str (protocol.parse_request).
            # In-flight accounting brackets the RESPONSE WRITE, not
            # just the solve: drain() waits for it, so a drained
            # request's response actually reaches the client before
            # the process exits (handler threads are daemonized).
            daemon._track_inflight(+1)
            try:
                req = None
                try:
                    resp, req = daemon.serve_line(raw, t_read, c_read)
                except protocol.ProtocolError as e:
                    resp = {"ok": False, "error": str(e)}
                except Exception as e:  # check: no-retry — the
                    # connection survives a bad request; solve-path
                    # crashes are already surfaced per-request by the
                    # batcher
                    resp = {"ok": False,
                            "error": f"{type(e).__name__}: {e}"}
                if resp is None:    # a blank line: nothing is due
                    continue
                rid = resp.get("rid", "")
                if req is not None and req.kind == "query":
                    telemetry.registry().histogram(
                        "serve.phase_ms.read", unit="ms").observe(
                            (t_read - t_first) * 1e3)
                    telemetry.registry().histogram(
                        "serve.read_pieces", unit="calls").observe(pieces)
                obs_trace.complete_at(
                    "serve.phase.read", t_first, t_read,
                    _cpu_between(reader.c_first, c_read), bytes=len(raw),
                    pieces=pieces,
                    **({"rid": rid} if rid else {}), **_batch_arg(req))
                w0, c0 = time.perf_counter(), obs_trace.thread_cpu()
                # encoded and written a piece at a time: each write
                # hands the interpreter lock over (protocol.encode_pieces)
                nbytes = writes = 0
                for piece in protocol.encode_pieces(resp):
                    self.wfile.write(piece)
                    nbytes += len(piece)
                    writes += 1
                self.wfile.flush()
                w1, c1 = time.perf_counter(), obs_trace.thread_cpu()
                if req is not None and req.kind == "query":
                    telemetry.registry().histogram(
                        "serve.phase_ms.write", unit="ms").observe(
                            (w1 - w0) * 1e3)
                    daemon.record_respond(req, nbytes)
                obs_trace.complete_at(
                    "serve.phase.write", w0, w1, _cpu_between(c0, c1),
                    bytes=nbytes, pieces=writes,
                    **({"rid": rid} if rid else {}),
                    **_batch_arg(req))
            finally:
                daemon._track_inflight(-1)
            if resp.get("draining"):
                break


def _batch_arg(req: Optional[Request]) -> Dict[str, int]:
    """The ``batch`` span arg of a request that rode a micro-batch."""
    if req is None or req.batch is None:
        return {}
    return {"batch": req.batch}


class _Server(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True


class ServeDaemon:
    """Lifecycle owner: engine + admission + batcher + TCP server +
    telemetry session + drain choreography."""

    def __init__(self, corpus: KNNInput, config: EngineConfig = None,
                 port: int = 0, capacity: Optional[int] = None,
                 gate_carry: bool = True,
                 budget_bytes: Optional[int] = None,
                 max_batch_queries: int = 1024,
                 max_queue_queries: int = 4096,
                 max_k: Optional[int] = None,
                 tick_s: float = 0.002,
                 telemetry_path: Optional[str] = None,
                 telemetry_port: Optional[int] = None,
                 record_path: Optional[str] = None,
                 snapshot_every_s: float = 0.0,
                 warm_buckets: Optional[List[Tuple[int, int]]] = None,
                 mesh_shape: Optional[Tuple[int, int]] = None,
                 mesh_merge: str = "allgather",
                 trace_path: Optional[str] = None,
                 objectives: Optional[List[Any]] = None):
        self.corpus = corpus
        self.record_path = record_path
        self.snapshot_every_s = snapshot_every_s
        # Request tracing opt-in: install a process-wide Tracer and
        # stamp the clock-sync marker the fleet merge aligns this
        # process's spans on. Written at drain/close.
        self.trace_path = trace_path
        self._tracer = None
        if trace_path:
            self._tracer = obs_trace.install(obs_trace.Tracer())
            self._tracer.sync_instant("fleet.clock_sync")
        self.session = None
        if telemetry_path or telemetry_port is not None:
            # handle_signals stays ON (the session owns the handler);
            # the daemon registers the clean-drain hook so an orderly
            # SIGTERM drains instead of dumping a flight artifact.
            self.session = telemetry.start(path=telemetry_path,
                                           port=telemetry_port)
        # The registry is process-global but stats()/snapshot_record()
        # divide by THIS daemon's uptime: zero the serve.* counters so
        # a second daemon lifetime in one process (tests, in-process
        # embedding) doesn't inherit the first one's counts and report
        # an inflated requests_per_sec.
        telemetry.registry().reset(prefix="serve")
        # The request scanner's library (g++ when it is stale, then
        # dlopen) comes up beside the staging below, not under the
        # window's first request and not after set-up; start() joins.
        self._native_loader = threading.Thread(
            target=native.native_available, name="serve-native-load",
            daemon=True)
        self._native_loader.start()
        if mesh_shape is not None:
            # Mesh-resident replica: the corpus held sharded-resident
            # across the mesh (dmlp_tpu.fleet) — same batcher/admission
            # surface, so everything below is engine-agnostic. Lazy
            # import: the fleet package layers on serve, not vice versa.
            from dmlp_tpu.fleet.mesh_engine import MeshResidentEngine
            self.engine = MeshResidentEngine(
                corpus, config or EngineConfig(mode="sharded"),
                mesh_shape=mesh_shape, capacity=capacity,
                merge=mesh_merge, gate_carry=gate_carry)
        else:
            self.engine = ResidentEngine(corpus,
                                         config or EngineConfig(),
                                         capacity=capacity,
                                         gate_carry=gate_carry)
        self.admission = AdmissionController(
            self.engine, budget_bytes=budget_bytes,
            max_queue_queries=max_queue_queries,
            max_request_queries=max_batch_queries, max_k=max_k,
            batch_queries_cap=max_batch_queries)
        self.batcher = MicroBatcher(self.engine, self.admission,
                                    max_batch_queries=max_batch_queries,
                                    tick_s=tick_s)
        # SLO objective plumb-through: string specs ("serve.request_
        # latency_ms p99 < 50 over 1m") or Objective instances. The
        # evaluator binds windowed rings onto the registry histograms;
        # it is constructed AFTER the serve.* reset above so the bound
        # histogram is the one this lifetime observes into. Ticked by
        # run_until_drained(); in-process embeddings tick it directly.
        self.slo = None
        if objectives:
            from dmlp_tpu.obs import slo as obs_slo
            objs = [obs_slo.parse_objective(o) if isinstance(o, str)
                    else o for o in objectives]
            self.slo = obs_slo.SLOEvaluator(objs, telemetry.registry())
        self._warm = (warm_buckets if warm_buckets is not None
                      else default_warm_buckets(corpus))
        self._drain_event = threading.Event()
        self._inflight = 0
        self._inflight_cond = threading.Condition()
        self._server = _Server(("127.0.0.1", port), _Handler)
        self._server.daemon = self
        self.port = self._server.server_address[1]
        self._server_thread: Optional[threading.Thread] = None
        self._t_ready: Optional[float] = None
        self.warmup_ms: Dict[str, float] = {}
        self._sigterm_prev = None
        self._sigterm_handler = None
        self._gc_hooked = False
        if self.session is not None:
            self.session.set_sigterm_drain(self._drain_event.set)
        else:
            import signal
            import weakref
            # The handler must hold the drain event WEAKLY: a strong
            # closure over self registered in the signal module would
            # pin this daemon's engine — resident device buffers
            # included — for the process lifetime (several daemons per
            # process tear down in arbitrary order, so prev-handler
            # restoration alone cannot unpin), silently inflating the
            # live-array watermark every later admission decision reads.
            ev_ref = weakref.ref(self._drain_event)

            def _on_sigterm(signum, frame, _ev_ref=ev_ref):
                ev = _ev_ref()
                if ev is not None:
                    ev.set()
            try:
                self._sigterm_prev = signal.signal(signal.SIGTERM,
                                                   _on_sigterm)
                self._sigterm_handler = _on_sigterm
            except ValueError:
                pass    # not the main thread (tests): drain op only

    # -- startup ---------------------------------------------------------------

    def start(self) -> None:
        """Warm the buckets, then open for traffic."""
        self.warmup_ms = self.engine.warmup(self._warm)
        self._native_loader.join()
        # the collector's pauses, for as long as this daemon serves
        # (drain() / close() take the hook off again)
        telemetry.gc_pauses().install()
        self._gc_hooked = True
        self.batcher.start()
        self._server_thread = threading.Thread(
            target=self._server.serve_forever, name="serve-accept",
            daemon=True)
        self._server_thread.start()
        self._t_ready = time.monotonic()

    def write_ready_file(self, path: str) -> None:
        from dmlp_tpu.obs.run import device_stamp
        from dmlp_tpu.utils import compile_cache
        stats = self.engine.bucket_stats()
        doc = {
            "port": self.port, "pid": os.getpid(),
            # where this daemon runs and which path its warm-up solves
            # took — what a launcher that holds no device records
            "device": device_stamp(self.engine),
            "compile_cache": compile_cache.stats(),
            "parser": self.corpus.parser,
            "paths": stats["paths"],
            "cold_start_compile_ms": self.engine.cold_start_compile_ms,
            "compile_count": self.engine.compile_count,
            "buckets": stats["buckets"],
            # per-bucket compiled-stream fingerprints at readiness: the
            # smoke re-reads this map at drain — a flat compile_count
            # with a CHANGED fingerprint would mean a recompile landed
            # on a different program (obs.hlo schedule identity).
            "hlo_schedule": stats.get("hlo_schedule", {}),
            "warmup_ms": self.warmup_ms,
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)

    # -- request plumbing ------------------------------------------------------

    def _track_inflight(self, delta: int) -> None:
        with self._inflight_cond:
            self._inflight += delta
            if self._inflight <= 0:
                self._inflight_cond.notify_all()

    def _wait_inflight_drained(self, timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        with self._inflight_cond:
            while self._inflight > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return      # give up, don't wedge the drain
                self._inflight_cond.wait(timeout=left)

    def handle_line(self, line: Union[str, bytes]) -> Dict[str, Any]:
        resp, req = self.serve_line(line)
        if req is not None and req.kind == "query":
            self.record_respond(req)
        return resp

    @staticmethod
    def record_respond(req: Request, nbytes: Optional[int] = None) -> None:
        """The ``serve.phase.respond`` span of a query request, from the
        clock pair serve_line kept: recorded by whoever holds the
        response next, once it knows what the span should say of the
        work: ``k`` (the request's largest: ``query_response`` folds k
        positions into the checksums and, on a ``debug`` request, makes
        k ids and k distances a query Python numbers) and, where the
        response went onto a socket, the ``bytes`` it encoded to."""
        if not obs_trace.sinks_active():
            return
        r0, r1, cpu_s = req.respond_pc
        obs_trace.complete_at(
            "serve.phase.respond", r0, r1, cpu_s, queries=req.nq,
            k=int(req.ks.max()) if req.nq else 0,
            **({} if nbytes is None else {"bytes": nbytes}),
            **({"rid": req.rid} if req.rid else {}), **_batch_arg(req))

    def serve_line(self, line: Union[str, bytes],
                   t_read: Optional[float] = None,
                   c_read: Optional[float] = None
                   ) -> Tuple[Optional[Dict[str, Any]], Optional[Request]]:
        """One request line to its response, and the Request it made
        (None for the control ops; both None for a blank line). A line
        given as the ``bytes`` it arrived as has its query matrix
        decoded natively where the library is loaded
        (``protocol.parse_request``). ``t_read`` is the perf_counter at
        which the line had been read: the start of the request's parse
        phase (now, when the caller read no socket), ``c_read`` the
        thread's CPU time then (``obs.trace.thread_cpu``). A query
        request's ``parse`` and ``respond`` phases are timed here, each
        one clock pair feeding its always-on histogram and — with a
        sink installed — its ``serve.phase.*`` span (``respond``'s is
        recorded by the caller: :meth:`record_respond`)."""
        if t_read is None:
            t_read, c_read = time.perf_counter(), obs_trace.thread_cpu()
        obj = protocol.parse_request(line, self.corpus.params.num_attrs)
        if obj is None:
            return None, None
        if isinstance(obj, dict):                 # control ops
            if obj.get("op") == "stats":
                return {"ok": True, "stats": self.stats()}, None
            self._drain_event.set()               # "drain"
            return {"ok": True, "draining": True}, None
        req: Request = obj
        if req.kind != "query":
            self.batcher.submit(req)
            req.done.wait()
            if req.kind == "ingest":
                return protocol.ingest_response(req), req
            return protocol.corpus_response(req), req
        reg = telemetry.registry()
        rid = {"rid": req.rid} if req.rid else {}
        t_parsed, c_parsed = time.perf_counter(), obs_trace.thread_cpu()
        reg.histogram("serve.phase_ms.parse", unit="ms").observe(
            (t_parsed - t_read) * 1e3)
        reg.counter("serve.parse_requests").inc(
            label="native" if req.parsed_native else "fallback")
        self.batcher.submit(req)
        req.done.wait()
        # recorded now, not when it ended: only now is the micro-batch
        # the request rode known
        obs_trace.complete_at(
            "serve.phase.parse", t_read, t_parsed,
            _cpu_between(c_read, c_parsed), queries=req.nq,
            native_queries=req.nq if req.parsed_native else 0,
            bytes=len(line), **rid, **_batch_arg(req))
        r0, c0 = time.perf_counter(), obs_trace.thread_cpu()
        resp = protocol.query_response(req)
        r1, c1 = time.perf_counter(), obs_trace.thread_cpu()
        reg.histogram("serve.phase_ms.respond", unit="ms").observe(
            (r1 - r0) * 1e3)
        # the span: record_respond
        req.respond_pc = (r0, r1, _cpu_between(c0, c1))
        return resp, req

    def stats(self) -> Dict[str, Any]:
        telemetry.gc_pauses().drain()   # runtime.gc_* up to date
        reg = telemetry.registry()
        eng = self.engine
        elapsed = (time.monotonic() - self._t_ready) \
            if self._t_ready else 0.0
        done = reg.counter("serve.requests_completed").total()
        parsed = reg.counter("serve.parse_requests")
        from dmlp_tpu.obs.run import device_stamp
        from dmlp_tpu.utils import compile_cache
        out = {
            "protocol": protocol.PROTOCOL_VERSION,
            "device": device_stamp(eng),
            "compile_cache": compile_cache.stats(),
            "engine": eng.bucket_stats(),
            # The fleet prober's consistency probe: rows + rolling
            # checksum + ingest epoch, comparable across replicas
            # whatever their resident layouts.
            "corpus": eng.corpus_state(),
            "admission": self.admission.snapshot(),
            "requests_completed": done,
            "queries_completed":
                reg.counter("serve.queries_completed").total(),
            "batches": self.batcher.batches,
            # the batcher thread's own account of its time: cycles
            # closed and the ring of slow ones (phases_ms.cycle has the
            # histograms)
            "batcher": self.batcher.cycle_stats(),
            # which decode this daemon's query lines took (fallback:
            # json.loads of the whole line) and the converter the
            # scanner's library was built with (None: no library)
            "parse": {
                "native_requests": int(parsed.value("native")),
                "fallback_requests": int(parsed.value("fallback")),
                "converter": native.float_converter()},
            "uptime_s": round(elapsed, 3),
            "requests_per_sec": round(done / elapsed, 3) if elapsed
            else None,
        }
        h = reg.get("serve.request_latency_ms")
        if h is not None and h.count:
            out["request_latency_ms"] = {
                "p50": round(h.quantile(0.5), 3),
                "p95": round(h.quantile(0.95), 3),
                "p99": round(h.quantile(0.99), 3),
                "count": h.count,
            }
        phases = {
            # (the quantiles are a log bucket's, 12% apart; the mean is
            # exact, and the one reading of a tick-grained CPU part)
            group: {key: {"count": h.count,
                          "p50": round(h.quantile(0.5), 3),
                          "p95": round(h.quantile(0.95), 3),
                          "mean": round(h.sum / h.count, 3)}
                    for key, h in ((k, reg.get(n)) for k, n in names)
                    if h is not None and h.count}
            for group, names in PHASE_HISTOGRAMS.items()}
        if any(phases.values()):
            out["phases_ms"] = phases
        if self.slo is not None:
            try:
                out["slo"] = self.slo.snapshot()
            except Exception:  # check: no-retry
                pass
        return out

    # -- run records -----------------------------------------------------------

    def snapshot_record(self):
        """The serving state as a RunRecord (kind "serve")."""
        from dmlp_tpu.obs.run import RunRecord, current_device
        reg = telemetry.registry()
        eng = self.engine
        elapsed = (time.monotonic() - self._t_ready) \
            if self._t_ready else 0.0
        done = reg.counter("serve.requests_completed").total()
        metrics: Dict[str, Any] = {
            "cold_start_compile_ms": eng.cold_start_compile_ms,
            "compile_count": eng.compile_count,
            "warm_buckets": len(eng.bucket_stats()["buckets"]),
            "admitted_total": reg.counter("serve.admitted").total(),
            "rejected_total": reg.counter("serve.rejected").total(),
            "batches_total": reg.counter("serve.batches").total(),
        }
        if elapsed and done:
            metrics["requests_per_sec"] = round(done / elapsed, 3)
            metrics["queries_per_sec"] = round(
                reg.counter("serve.queries_completed").total() / elapsed,
                3)
        h = reg.get("serve.request_latency_ms")
        if h is not None and h.count:
            metrics["request_latency_p50_ms"] = round(h.quantile(0.5), 3)
            metrics["request_latency_p95_ms"] = round(h.quantile(0.95), 3)
            metrics["request_latency_p99_ms"] = round(h.quantile(0.99), 3)
            metrics["request_count"] = h.count
        if eng.last_gated_fraction is not None:
            metrics["gate_gated_fraction"] = round(
                eng.last_gated_fraction, 6)
        stats = eng.bucket_stats()
        return RunRecord(
            kind="serve", tool="dmlp_tpu.serve",
            config={"corpus_rows": eng.n_real,
                    "capacity_rows": eng.capacity_rows,
                    "num_attrs": eng.num_attrs,
                    "gate_carry": eng.gate_carry,
                    "mode": ("mesh_resident" if hasattr(eng, "mesh")
                             else "resident"),
                    "buckets": stats["buckets"],
                    # per-bucket compiled-stream HLO fingerprints
                    # (obs.hlo; the schedule-identity side of the
                    # compile-once contract) — {} where the engine has
                    # no AOT stream handle to introspect
                    "hlo_schedule": stats.get("hlo_schedule", {})},
            metrics=metrics, device=current_device())

    def _append_record(self) -> None:
        if self.record_path:
            try:
                self.snapshot_record().append_jsonl(self.record_path)
            except Exception:  # check: no-retry — records never kill
                pass           # the drain

    # -- run / drain -----------------------------------------------------------

    def run_until_drained(self) -> None:
        """Block until a drain is requested (SIGTERM or the in-band
        op), then drain and shut down cleanly."""
        next_snap = (time.monotonic() + self.snapshot_every_s
                     if self.snapshot_every_s else None)
        while not self._drain_event.wait(timeout=0.2):
            if self.slo is not None:
                try:
                    self.slo.tick()
                except Exception:  # check: no-retry — SLO evaluation
                    pass           # never takes down the serve loop
            if next_snap is not None and time.monotonic() >= next_snap:
                self._append_record()
                next_snap = time.monotonic() + self.snapshot_every_s
        self.drain()

    def _restore_sigterm(self) -> None:
        """Undo the SIGTERM hook — only when it is still OURS (another
        daemon may have registered over us; clobbering its handler
        would break that daemon's drain)."""
        if self._sigterm_handler is None:
            return
        import signal
        try:
            if signal.getsignal(signal.SIGTERM) is self._sigterm_handler:
                signal.signal(signal.SIGTERM,
                              self._sigterm_prev or signal.SIG_DFL)
        except ValueError:
            pass
        self._sigterm_handler = None
        self._sigterm_prev = None

    def drain(self) -> None:
        """The orderly shutdown: shed new work, finish queued work,
        flush records + final telemetry snapshot, close. No flight
        dump — this is not a crash."""
        self.admission.draining = True
        self._server.shutdown()
        self.batcher.stop(drain=True)
        # The batcher completed every queued request; now wait for the
        # daemonized connection handlers to WRITE those responses — a
        # drain that exits mid-write loses the response on the floor.
        self._wait_inflight_drained()
        self._unhook_gc()
        self._append_record()
        self._write_trace()
        if self.session is not None:
            self.session.set_sigterm_drain(None)
            self.session.close()     # writes the final snapshot
        self._restore_sigterm()
        self._server.server_close()

    def _unhook_gc(self) -> None:
        """Take this daemon's collector hook off (start() put it on);
        what it noted last goes to the counters and the trace first."""
        if self._gc_hooked:
            self._gc_hooked = False
            telemetry.gc_pauses().remove()

    def _write_trace(self) -> None:
        if self._tracer is None:
            return
        try:
            self._tracer.write(self.trace_path,
                               process_name=f"serve:{self.port}")
        except Exception:  # check: no-retry — traces never kill a drain
            pass
        if obs_trace.active() is self._tracer:
            obs_trace.uninstall()
        self._tracer = None

    def close(self) -> None:
        """Abrupt teardown for tests (no drain semantics)."""
        self._drain_event.set()
        self.admission.draining = True
        self._server.shutdown()
        self.batcher.stop(drain=False)
        self._unhook_gc()
        self._write_trace()
        if self.session is not None:
            self.session.set_sigterm_drain(None)
            self.session.close()
        self._restore_sigterm()
        self._server.server_close()
