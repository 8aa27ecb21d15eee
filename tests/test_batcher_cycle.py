"""The batcher's own account of its time (PR 35).

The batcher thread's timeline is cut at the end of every delivery into
cycles, one a micro-batch, each split exactly into the thread's own
work, its waits on the device and its waits for requests; a ring keeps
the slow ones; one ``gc.callbacks`` hook notes the collector's pauses
without taking a lock; the handler times a request's line from its
first byte. Held here by structure (what tiles what, which part a
delay lands in), with injected delays large against the CPU's noise.

PR 51: ``own`` is split again by the kernel's account of the thread
(``own = own_cpu + own_offcpu``; ``wait_cpu``; the thread's rusage), one
reading a cycle, always on: a sleep lands off the core, a spin on it,
the interpreter lock held by another thread off it, a spin inside a
host sync in ``wait_cpu``; and the calls it makes a cycle are counted.
"""

from __future__ import annotations

import gc
import json
import socket
import sys
import threading
import time

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine import single
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.resilience import inject
from dmlp_tpu.resilience.inject import FaultSchedule
from dmlp_tpu.serve import batching
from dmlp_tpu.serve.admission import AdmissionController
from dmlp_tpu.serve.batching import MicroBatcher, Request
from dmlp_tpu.serve.daemon import PHASE_HISTOGRAMS, ServeDaemon
from dmlp_tpu.serve.engine import ResidentEngine

NA = 4
NQ = 6          # queries a request, and the batch cap: a request a batch
WAIT = 300
PARTS = ("own_ms", "device_wait_ms", "queue_wait_ms")
#: the cycle's CPU account (PR 51): on every ``serve.cycle`` span and in
#: every slow-cycle record, the rusage half where the platform has it
CPU_PARTS = ("own_cpu_ms", "own_offcpu_ms", "wait_cpu_ms", "cores_busy")
RUSAGE_PARTS = ("own_sys_ms", "minflt", "majflt", "nvcsw", "nivcsw")
CPU_TOTALS = {"thread_s", "process_s", "cores"} | (
    {"user_s", "sys_s", "minflt", "majflt", "nvcsw", "nivcsw"}
    if batching._RUSAGE_THREAD is not None else set())
#: the second half of a micro-batch on the one-chip engine
SECOND_HALF = ["single.fetch", "single.hazard", "single.finalize",
               "serve.after_batch", "serve.batch_deliver"]


def corpus_of(n: int, seed: int = 3) -> KNNInput:
    rng = np.random.default_rng(seed)
    return KNNInput(Params(n, 0, NA),
                    rng.integers(0, 5, n).astype(np.int32),
                    rng.uniform(-10, 10, (n, NA)),
                    np.zeros(0, np.int32), np.zeros((0, NA)))


def stream_engine() -> ResidentEngine:
    return ResidentEngine(corpus_of(600), EngineConfig())


def extract_engine() -> ResidentEngine:
    return ResidentEngine(corpus_of(14000), EngineConfig(
        select="extract", use_pallas=True, data_block=12800))


def request(i: int, rng) -> Request:
    return Request(kind="query", req_id=f"r{i}",
                   query_attrs=rng.uniform(-10, 10, (NQ, NA)),
                   ks=np.full(NQ, 4, np.int32))


def batcher_for(eng) -> MicroBatcher:
    return MicroBatcher(eng, AdmissionController(eng),
                        max_batch_queries=NQ, tick_s=0.0)


def serve_one(b: MicroBatcher, r: Request) -> Request:
    assert b.submit(r)["verdict"] == "accept"
    assert r.done.wait(timeout=WAIT) and r.error is None, r.error
    return r


def closed(b: MicroBatcher, n: int):
    """``stats.batcher`` once the batcher has closed its n-th cycle (a
    request is answered inside the cycle, a moment before its end)."""
    deadline = time.monotonic() + 30
    while b.cycles < n and time.monotonic() < deadline:
        time.sleep(0.001)
    return b.cycle_stats()


def spin(seconds: float) -> None:
    """Burn ``seconds`` of the calling thread's CPU time."""
    c0 = time.thread_time()
    while time.thread_time() - c0 < seconds:
        pass


def delays(site: str, ms: int, times: int = 1) -> FaultSchedule:
    return FaultSchedule.from_dict({"schema": 1, "seed": 1, "faults": [
        {"site": site, "kind": "delay", "ms": ms, "times": times,
         "prob": 1.0}]})


def spans_of(tracer):
    return [e for e in tracer.events() if e.get("ph") == "X"]


def named(spans, name, **args):
    return [e for e in spans if e["name"] == name
            and all(e["args"].get(k) == v for k, v in args.items())]


def end(e) -> float:
    return e["ts"] + e["dur"]


# -- the split, and the tiling, in both orders --------------------------------

@pytest.fixture(scope="module")
def flows():
    """The extract engine under a tracer, twice: four requests queued
    before the batcher starts (the pipeline engages: batches 2..4 are
    begun behind another), and four served one after the other with
    the queue empty 60 ms in between (the serial order)."""
    out = {}
    rng = np.random.default_rng(11)
    for order in ("pipelined", "serial"):
        eng = extract_engine()
        eng.warmup([(NQ, 4)])
        b = batcher_for(eng)
        reqs = [request(i, rng) for i in range(4)]
        tracer = obs_trace.install(obs_trace.Tracer())
        try:
            if order == "pipelined":
                for r in reqs:
                    assert b.submit(r)["verdict"] == "accept"
                b.start()
                for r in reqs:
                    assert r.done.wait(timeout=WAIT) and r.error is None
            else:
                b.start()
                for r in reqs:
                    serve_one(b, r)
                    time.sleep(0.06)
            b.stop(drain=True)
        finally:
            obs_trace.uninstall()
        out[order] = {"spans": spans_of(tracer), "cycles": b.cycles}
    return out


@pytest.mark.parametrize("order", ["pipelined", "serial"])
def test_a_cycle_is_split_exactly_three_ways(flows, order):
    cycles = named(flows[order]["spans"], "serve.cycle")
    assert len(cycles) == flows[order]["cycles"] == 4
    for c in cycles:
        a = c["args"]
        assert abs(c["dur"] / 1e3 - sum(a[p] for p in PARTS)) < 1e-3
        assert all(a[p] >= 0 for p in PARTS) and a["gc_ms"] >= 0
        assert (a["queries"], a["requests"]) == (NQ, 1)


@pytest.mark.parametrize("order", ["pipelined", "serial"])
def test_own_is_split_exactly_on_and_off_the_core(flows, order):
    """``own_ms = own_cpu_ms + own_offcpu_ms`` to the float's rounding,
    on every cycle; the thread's CPU inside its waits is not in it."""
    for c in named(flows[order]["spans"], "serve.cycle"):
        a = c["args"]
        assert set(CPU_PARTS) <= set(a)
        assert abs(a["own_ms"] - a["own_cpu_ms"] - a["own_offcpu_ms"]) \
            < 1e-9
        assert a["own_cpu_ms"] > 0 and a["wait_cpu_ms"] >= 0
        # the waits' CPU lies inside the waits' wall time
        assert a["wait_cpu_ms"] <= a["device_wait_ms"] + 0.01
        assert 0 < a["cores_busy"] <= batching._usable_cores() + 0.5
        if batching._RUSAGE_THREAD is not None:
            assert all(a[k] >= 0 for k in RUSAGE_PARTS)
            assert all(isinstance(a[k], int) for k in RUSAGE_PARTS[1:])
        # a cycle's one-thread spans cannot have been on a core longer
        # than the cycle's thread was, in its own stretch and its waits
        inner = [w for w in flows[order]["spans"]
                 if w["name"] in ("single.finalize", "serve.batch_deliver")
                 and w["tid"] == c["tid"] and c["ts"] <= w["ts"]
                 and end(w) <= end(c) + 1e-3]
        assert inner and sum(w["args"]["cpu_ms"] for w in inner) \
            <= a["own_cpu_ms"] + a["wait_cpu_ms"] + 1e-6


@pytest.mark.parametrize("order", ["pipelined", "serial"])
def test_cycles_tile_the_batcher_thread(flows, order):
    """One cycle a batch, in the order of delivery, on one thread; the
    next starts where the last ended (to the microsecond's rounding)."""
    cycles = named(flows[order]["spans"], "serve.cycle")
    assert [c["args"]["batch"] for c in cycles] == [1, 2, 3, 4]
    assert len({c["tid"] for c in cycles}) == 1
    for a, b in zip(cycles, cycles[1:]):
        assert abs(b["ts"] - end(a)) < 1e-3, (end(a), b["ts"])


def test_the_pipelined_order_begins_the_next_batch_inside_the_cycle(flows):
    cycles = named(flows["pipelined"]["spans"], "serve.cycle")
    assert [c["args"]["overlapped"] for c in cycles] == [0, 1, 1, 1]
    # the first cycle began batch 1 and, behind it, batch 2; the last
    # found nothing to begin
    assert [c["args"]["begun"] for c in cycles] == [2, 3, 4, 0]
    for c in cycles[:-1]:
        (stage,) = named(flows["pipelined"]["spans"], "serve.solve_stage",
                         batch=c["args"]["begun"])
        assert c["ts"] <= stage["ts"] and end(stage) <= end(c)


def test_the_serial_order_waits_for_requests_inside_the_cycle(flows):
    cycles = named(flows["serial"]["spans"], "serve.cycle")
    assert [c["args"]["overlapped"] for c in cycles] == [0, 0, 0, 0]
    assert [c["args"]["begun"] for c in cycles] == [1, 2, 3, 4]
    # the queue stood empty 60 ms before each of the later requests
    for c in cycles[1:]:
        assert c["args"]["queue_wait_ms"] >= 55
        waits = [w for w in named(flows["serial"]["spans"],
                                  "serve.wait.queue")
                 if c["ts"] <= w["ts"] and end(w) <= end(c) + 1e-3]
        assert waits and abs(sum(w["dur"] for w in waits) / 1e3
                             - c["args"]["queue_wait_ms"]) < 1e-3


@pytest.mark.parametrize("order", ["pipelined", "serial"])
def test_device_waits_are_spans_with_their_site_and_sum_to_the_part(
        flows, order):
    """Every host sync lies under a span that names its ``site``: the
    seam's own where it has one around exactly the wait
    (``single.fetch``), ``serve.wait.device`` elsewhere; never both."""
    spans = flows[order]["spans"]
    for c in named(spans, "serve.cycle"):
        waits = [w for w in spans if "site" in w.get("args", ())
                 and w["tid"] == c["tid"] and c["ts"] <= w["ts"]
                 and end(w) <= end(c) + 1e-3]
        by_site = {w["args"]["site"]: w["name"] for w in waits}
        assert by_site.items() >= {"fetch": "single.fetch",
                                   "gate": "serve.wait.device"}.items()
        waits.sort(key=lambda w: w["ts"])       # one span a wait
        assert all(end(a) <= b["ts"] for a, b in zip(waits, waits[1:]))
        assert all(w["args"]["batch"] in (c["args"]["batch"],
                                          c["args"]["begun"])
                   for w in waits)
        # each span IS the bracket's two clock reads (PR 51): the sum
        # equals the part to the float's rounding
        assert abs(sum(w["dur"] for w in waits) / 1e3
                   - c["args"]["device_wait_ms"]) < 1e-6


def test_the_epilogue_ends_with_the_first_half(flows):
    """``serve.solve_epilogue`` of batch N ends before any span of batch
    N - 1's second half starts: it no longer crosses batches."""
    spans = flows["pipelined"]["spans"]
    for n in (2, 3, 4):
        (epi,) = named(spans, "serve.solve_epilogue", batch=n)
        (stage,) = named(spans, "serve.solve_stage", batch=n)
        assert end(stage) <= epi["ts"]
        older = [e for name in SECOND_HALF
                 for e in named(spans, name, batch=n - 1)]
        assert len(older) == len(SECOND_HALF)
        assert end(epi) <= min(e["ts"] for e in older)
        # ... while the span that DOES cross still holds them
        (mb,) = named(spans, "serve.micro_batch", batch=n)
        assert mb["ts"] <= min(e["ts"] for e in older) \
            and max(end(e) for e in older) <= end(mb)


# -- the multipass driver's first half ------------------------------------------

def multipass_engine() -> ResidentEngine:
    """k = 300 over 900 rows: the k512 bucket's two passes."""
    return ResidentEngine(corpus_of(900, seed=41), EngineConfig(
        select="extract", use_pallas=True, dtype="float32"))


def solve_traced(eng, k: int = 300):
    rng = np.random.default_rng(7)
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        eng.solve_batch(rng.uniform(-10, 10, (NQ, NA)),
                        np.full(NQ, k, np.int32))
    finally:
        obs_trace.uninstall()
    return spans_of(tracer)


@pytest.fixture(scope="module")
def multipass_spans():
    eng = multipass_engine()
    eng.warmup([(NQ, 300)])
    spans = solve_traced(eng)
    assert eng.last_mp_passes == 2
    return spans


def test_the_multipass_first_half_is_tiled_in_the_order_it_enqueues(
        multipass_spans):
    """Staged, pass 1 dispatched, and only then the floor chain's
    scalars put (the device has its work first), the later passes, the
    merge, the epilogue: one span each, none inside another, all inside
    the span that crosses batches."""
    spans = multipass_spans
    chain = [e for e in spans if e["name"] in (
        "serve.solve_stage", "serve.mp_pass", "serve.mp_norms",
        "serve.mp_merge", "serve.solve_epilogue")]
    chain.sort(key=lambda e: e["ts"])
    assert [(e["name"], e.get("args", {}).get("pass")) for e in chain] == [
        ("serve.solve_stage", None), ("serve.mp_pass", 1),
        ("serve.mp_norms", None), ("serve.mp_pass", 2),
        ("serve.mp_merge", None), ("serve.solve_epilogue", None)]
    assert all(end(a) <= b["ts"] + 1e-3 for a, b in zip(chain, chain[1:]))
    (whole,) = named(spans, "serve.solve_multipass")
    assert end(chain[0]) <= whole["ts"] + 1e-3      # staged before it
    assert whole["ts"] <= chain[1]["ts"] + 1e-3


def test_the_span_that_crosses_batches_has_no_cpu_account(multipass_spans):
    """``serve.solve_multipass`` is a clock pair with another batch's
    work between its ends when one is in flight: no thread's CPU time
    is its own. Its fence is the host-sync bracket's own span, and the
    ``with`` spans of either half carry the account."""
    (whole,) = named(multipass_spans, "serve.solve_multipass")
    assert not {"cpu_ms", "offcpu_ms"} & set(whole["args"])
    for name in ("serve.mp_fetch", "serve.mp_pass", "serve.mp_merge",
                 "serve.solve_stage"):
        for e in named(multipass_spans, name):
            a = e["args"]
            assert abs(e["dur"] / 1e3 - a["cpu_ms"] - a["offcpu_ms"]) \
                < 1e-6, name
            assert a["cpu_ms"] > 0
    (fence,) = named(multipass_spans, "serve.mp_fetch")
    assert fence["args"]["site"] == "mp_fetch"


def test_a_refused_multipass_leaves_no_stage_span_behind(monkeypatch):
    """No kernel for the bucket: the driver says so before it opens a
    span, and the batch's spans are the fallback's alone."""
    from dmlp_tpu.ops import pallas_fused
    eng = multipass_engine()
    monkeypatch.setattr(pallas_fused, "resolve_topk_kernel",
                        lambda *a, **kw: (None, None))
    spans = solve_traced(eng)
    assert not named(spans, "serve.solve_stage")
    assert not named(spans, "serve.mp_pass")
    assert len(named(spans, "serve.solve_stream")) == 1


# -- where a delay lands, and the ring -----------------------------------------

@pytest.fixture(scope="module")
def slowed():
    """A batcher past its first 32 cycles, then one straggler at
    ``serve.solve`` (the batcher's own 150 ms, asleep), one delayed
    readback (``single.fetch``: 150 ms inside the host-sync bracket),
    one first half that SPINS 150 ms of the thread's CPU time, one
    readback that spins them inside the bracket, then 20 more
    stragglers; a straggler among the first 32 before all that."""
    eng = stream_engine()
    eng.warmup([(NQ, 4)])
    # as a daemon does: the running median is this lifetime's
    telemetry.registry().reset(prefix="serve")
    b = batcher_for(eng)
    rng = np.random.default_rng(5)
    out = {}
    tracer = obs_trace.install(obs_trace.Tracer())
    b.start()
    try:
        inject.install(delays("serve.solve", 150))
        serve_one(b, request(0, rng))
        for i in range(1, batching.SLOW_WARM_CYCLES):
            serve_one(b, request(i, rng))
        out["warm"] = closed(b, batching.SLOW_WARM_CYCLES)
        inject.install(delays("serve.solve", 150))
        out["own"] = serve_one(b, request(100, rng)).batch
        inject.install(delays("single.fetch", 150))
        out["device"] = serve_one(b, request(101, rng)).batch
        out["two"] = closed(b, batching.SLOW_WARM_CYCLES + 2)
        begin, get = eng.begin_batch, single.resilient_get
        eng.begin_batch = lambda *a, **kw: (spin(0.15), begin(*a, **kw))[1]
        try:
            out["spin"] = serve_one(b, request(102, rng)).batch
        finally:
            eng.begin_batch = begin
        single.resilient_get = lambda *a, **kw: (spin(0.15),
                                                 get(*a, **kw))[1]
        try:
            out["spun_wait"] = serve_one(b, request(103, rng)).batch
        finally:
            single.resilient_get = get
        out["four"] = closed(b, batching.SLOW_WARM_CYCLES + 4)
        # 100 ms, not the rule's bare 50 over the median: a loaded
        # machine's median cycle is tens of ms, and 3 x that passed a
        # 60 ms straggler once in the driver's whole run (PR 38)
        inject.install(delays("serve.solve", 100, times=20))
        for i in range(20):
            serve_one(b, request(200 + i, rng))
        out["ring"] = closed(b, batching.SLOW_WARM_CYCLES + 24)
    finally:
        inject.uninstall()
        b.stop(drain=True)
        obs_trace.uninstall()
    out["spans"] = spans_of(tracer)
    out["instants"] = [e for e in tracer.events() if e.get("ph") == "i"]
    return out


def test_the_ring_is_empty_for_the_first_32_cycles(slowed):
    warm = dict(slowed["warm"])
    assert set(warm.pop("cpu")) == CPU_TOTALS
    assert warm == {"cycles": batching.SLOW_WARM_CYCLES, "slow_cycles": []}
    (first,) = named(slowed["spans"], "serve.cycle", batch=1)
    assert first["args"]["own_ms"] >= 150       # slow, and not kept


def test_a_straggler_lands_in_own_and_in_the_ring(slowed):
    (c,) = named(slowed["spans"], "serve.cycle", batch=slowed["own"])
    assert c["args"]["own_ms"] >= 150
    assert c["args"]["device_wait_ms"] < 100
    rec = slowed["two"]["slow_cycles"][0]
    assert rec["batch"] == slowed["own"] and rec["own_ms"] >= 150
    assert rec["cycle_ms"] > 3 * rec["median_ms"]
    assert rec["queries"] == NQ and rec["requests"] == 1
    assert rec["overlapped"] == 0 and rec["queue_depth"] == 0
    assert rec["path"] and rec["unix_time"] > 1e9 and rec["gc_ms"] >= 0
    assert abs(rec["cycle_ms"] - sum(rec[p] for p in PARTS)) < 0.01
    # with a Tracer the record is an instant too
    assert [e for e in slowed["instants"]
            if e["name"] == "serve.slow_cycle"
            and e["args"]["batch"] == slowed["own"]]


def test_a_delayed_readback_lands_in_device_wait_with_its_site(slowed):
    (c,) = named(slowed["spans"], "serve.cycle", batch=slowed["device"])
    assert c["args"]["device_wait_ms"] >= 150
    assert c["args"]["own_ms"] < 100
    (w,) = named(slowed["spans"], "single.fetch", site="fetch",
                 batch=slowed["device"])
    assert w["dur"] / 1e3 >= 150        # the injected fault is inside
    rec = slowed["two"]["slow_cycles"][1]
    assert rec["batch"] == slowed["device"]
    assert rec["device_wait_ms"] >= 150
    assert rec["device_wait_sites_ms"]["fetch"] >= 150


def test_a_sleeping_straggler_is_off_the_core_and_a_spinning_one_on_it(
        slowed):
    """The same 150 ms of ``own``: asleep at ``serve.solve`` they are
    ``own_offcpu_ms``, spun in the first half ``own_cpu_ms``; the ring's
    record says which, with the rusage fields beside it."""
    (slept,) = named(slowed["spans"], "serve.cycle", batch=slowed["own"])
    (spun,) = named(slowed["spans"], "serve.cycle", batch=slowed["spin"])
    assert slept["args"]["own_offcpu_ms"] >= 150
    assert slept["args"]["own_cpu_ms"] < 100
    assert spun["args"]["own_cpu_ms"] >= 150 and spun["args"]["own_ms"] >= 150
    assert spun["args"]["wait_cpu_ms"] < 100
    recs = {r["batch"]: r for r in slowed["four"]["slow_cycles"]}
    assert recs[slowed["own"]]["own_offcpu_ms"] >= 150
    assert recs[slowed["spin"]]["own_cpu_ms"] >= 150
    for rec in recs.values():
        assert set(CPU_PARTS) <= set(rec)
        assert abs(rec["own_ms"] - rec["own_cpu_ms"]
                   - rec["own_offcpu_ms"]) < 0.01
        if batching._RUSAGE_THREAD is not None:
            assert set(RUSAGE_PARTS) <= set(rec)
    # ... and so does the instant a Tracer gets
    (inst,) = [e for e in slowed["instants"]
               if e["name"] == "serve.slow_cycle"
               and e["args"]["batch"] == slowed["spin"]]
    assert inst["args"]["own_cpu_ms"] >= 150


def test_a_readback_that_spins_lands_in_wait_cpu_and_not_in_own_cpu(slowed):
    """CPU burnt inside the host-sync bracket is the wait's
    (``wait_cpu_ms``, the span's ``cpu_ms``): ``own_cpu_ms`` is the
    thread's CPU outside its waits; a readback that SLEEPS burns none."""
    (c,) = named(slowed["spans"], "serve.cycle", batch=slowed["spun_wait"])
    assert c["args"]["wait_cpu_ms"] >= 150
    assert c["args"]["device_wait_ms"] >= 150
    assert c["args"]["own_cpu_ms"] < 100
    (w,) = named(slowed["spans"], "single.fetch", site="fetch",
                 batch=slowed["spun_wait"])
    assert w["args"]["cpu_ms"] >= 150
    assert abs(w["dur"] / 1e3 - w["args"]["cpu_ms"]
               - w["args"]["offcpu_ms"]) < 1e-6
    (asleep,) = named(slowed["spans"], "serve.cycle", batch=slowed["device"])
    assert asleep["args"]["wait_cpu_ms"] < 100
    (w,) = named(slowed["spans"], "single.fetch", site="fetch",
                 batch=slowed["device"])
    assert w["args"]["offcpu_ms"] >= 150


def test_the_interpreter_lock_held_elsewhere_is_off_the_core():
    """The same pure-Python second half twice: alone, and beside a
    thread that loops in Python under a short switch interval. The
    batcher waits for the lock every other slice: ``own_offcpu_ms``
    grows by about the work's own length, ``own_cpu_ms`` does not."""
    eng = stream_engine()
    eng.warmup([(NQ, 4)])
    finish = eng.finish_batch

    def working(pending):
        n = 0
        for i in range(1_500_000):      # holds the lock throughout
            n += i
        return finish(pending)
    eng.finish_batch = working
    b = batcher_for(eng)
    rng = np.random.default_rng(13)
    stop = threading.Event()

    def hog():
        n = 0
        while not stop.is_set():
            for i in range(10_000):
                n += i
    tracer = obs_trace.install(obs_trace.Tracer())
    interval = sys.getswitchinterval()
    b.start()
    try:
        quiet = serve_one(b, request(0, rng)).batch
        closed(b, 1)
        sys.setswitchinterval(1e-4)
        t = threading.Thread(target=hog, daemon=True)
        t.start()
        try:
            beside = serve_one(b, request(1, rng)).batch
        finally:
            stop.set()
            t.join(timeout=30)
        assert not t.is_alive()
        closed(b, 2)
    finally:
        sys.setswitchinterval(interval)
        b.stop(drain=True)
        obs_trace.uninstall()
    (c0,) = named(spans_of(tracer), "serve.cycle", batch=quiet)
    (c1,) = named(spans_of(tracer), "serve.cycle", batch=beside)
    work = c0["args"]["own_cpu_ms"]
    waited = c1["args"]["own_offcpu_ms"] - c0["args"]["own_offcpu_ms"]
    assert work > 10 and waited > 0.4 * work, (c0["args"], c1["args"])
    assert c1["args"]["own_cpu_ms"] - work < 0.5 * waited, \
        (c0["args"], c1["args"])


def test_the_ring_is_bounded(slowed):
    ring = slowed["ring"]["slow_cycles"]
    assert len(ring) == batching.SLOW_RING == 16
    assert slowed["ring"]["cycles"] == batching.SLOW_WARM_CYCLES + 24
    serials = [r["batch"] for r in ring]
    assert serials == sorted(serials) and serials[-1] == \
        slowed["ring"]["cycles"]        # the newest 16, newest last


# -- the collector --------------------------------------------------------------

def ask(port, obj, idle_s: float = 0.0):
    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        f = s.makefile("rwb")
        if idle_s:
            time.sleep(idle_s)
        line = (json.dumps(obj) + "\n").encode()
        f.write(line)
        f.flush()
        return json.loads(f.readline()), len(line)


def query(corpus, nq=3):
    return {"op": "query", "id": "q", "k": 4, "rid": "r-1",
            "queries": corpus.data_attrs[:nq].tolist()}


def gen2():
    reg = telemetry.registry()
    return (reg.counter("runtime.gc_pause_ms").value("gen2"),
            reg.counter("runtime.gc_collections").value("gen2"))


@pytest.fixture(scope="module")
def traced_daemon():
    """One daemon under a tracer: a request sent after 300 ms of an
    open, idle connection, and a request whose second half forces a
    full collection."""
    corpus = corpus_of(600)
    tracer = obs_trace.install(obs_trace.Tracer())
    out = {"hook_before": telemetry.gc_pauses()._on_gc in gc.callbacks}
    daemon = ServeDaemon(corpus, EngineConfig(), warm_buckets=[(3, 4)])
    try:
        daemon.start()
        out["hook_serving"] = telemetry.gc_pauses()._on_gc in gc.callbacks
        resp, out["line_bytes"] = ask(daemon.port, query(corpus),
                                      idle_s=0.3)
        assert resp["ok"], resp
        finish = daemon.engine.finish_batch

        def collecting(pending):
            gc.collect()
            return finish(pending)
        daemon.engine.finish_batch = collecting
        out["gen2_before"] = gen2()
        resp, _ = ask(daemon.port, query(corpus))
        assert resp["ok"], resp
        daemon.engine.finish_batch = finish
        closed(daemon.batcher, 2)
        out["stats"] = daemon.stats()
        out["gen2_after"] = gen2()
        out["openmetrics"] = telemetry.registry().to_openmetrics()
    finally:
        daemon.close()
        obs_trace.uninstall()
    out["hook_after"] = telemetry.gc_pauses()._on_gc in gc.callbacks
    out["spans"] = spans_of(tracer)
    return out


def test_a_forced_collection_shows_in_the_cycle_the_counters_and_a_span(
        traced_daemon):
    (c,) = named(traced_daemon["spans"], "serve.cycle", batch=2)
    assert c["args"]["gc_ms"] > 0
    assert c["args"]["gc_ms"] <= c["args"]["own_ms"]    # on this thread
    (ms0, n0), (ms1, n1) = (traced_daemon["gen2_before"],
                            traced_daemon["gen2_after"])
    assert n1 >= n0 + 1 and ms1 > ms0
    full = [g for g in named(traced_daemon["spans"], "runtime.gc",
                             generation=2)
            if c["ts"] <= g["ts"] and end(g) <= end(c)]
    assert full and full[0]["args"]["thread"] == "serve-batcher"
    assert full[0]["args"]["collected"] >= 0
    assert abs(sum(g["dur"] for g in named(traced_daemon["spans"],
                                           "runtime.gc")
                   if c["ts"] <= end(g) <= end(c)) / 1e3
               - c["args"]["gc_ms"]) < 1e-2


def test_the_hook_lives_as_long_as_the_daemon_serves(traced_daemon):
    assert traced_daemon["hook_serving"] is True
    assert traced_daemon["hook_after"] is traced_daemon["hook_before"] \
        is False


def test_the_callback_takes_no_lock():
    """A collection that starts under ``Registry._lock`` and the
    tracer's lock, held by the collecting thread itself (an allocation
    inside ``Registry._get`` or ``Tracer._append``), returns."""
    tracer = obs_trace.install(obs_trace.Tracer())
    watch = telemetry.gc_pauses()
    watch.install()
    done = threading.Event()

    def collect_under_the_locks():
        with telemetry.registry()._lock, tracer._lock:
            gc.collect()
        done.set()
    try:
        n0 = gen2()[1]
        t = threading.Thread(target=collect_under_the_locks, daemon=True)
        t.start()
        assert done.wait(timeout=30), "the gc callback waits for a lock"
        watch.drain()
        assert gen2()[1] >= n0 + 1
        assert named(spans_of(tracer), "runtime.gc", generation=2)
    finally:
        watch.remove()
        obs_trace.uninstall()
    assert watch._on_gc not in gc.callbacks


def test_notes_nobody_drains_are_bounded():
    """A daemon that closes no cycle (ingest only) and is asked for no
    stats keeps the newest notes, not all of them."""
    watch = telemetry.GcPauses()
    for _ in range(5000):
        watch._on_gc("start", {})
        watch._on_gc("stop", {"generation": 0, "collected": 0})
    assert len(watch._pending) == watch._pending.maxlen == 4096
    assert watch.total_s > 0


# -- the line read ---------------------------------------------------------------

def test_the_read_phase_starts_at_the_first_byte(traced_daemon):
    first, second = named(traced_daemon["spans"], "serve.phase.read",
                          rid="r-1")
    assert first["args"]["bytes"] == traced_daemon["line_bytes"]
    assert first["args"]["batch"] == 1 and second["args"]["batch"] == 2
    assert first["dur"] / 1e3 < 200     # 300 ms idle are not in it
    (parse,) = named(traced_daemon["spans"], "serve.phase.parse", batch=1)
    assert abs(parse["ts"] - end(first)) < 1e-3     # parse keeps its start
    assert traced_daemon["stats"]["phases_ms"]["request"]["read"][
        "count"] == 2


# -- always on, and exported ----------------------------------------------------

@pytest.fixture(scope="module")
def untraced():
    """Three micro-batches through a batcher with no sink installed,
    every span constructor counted."""
    assert not obs_trace.sinks_active()
    built = []
    inits = {cls: cls.__init__
             for cls in (obs_trace.Span, obs_trace._TelemetrySpan)}

    def counting(cls):
        def init(self, *a, **kw):
            built.append(cls.__name__)
            inits[cls](self, *a, **kw)
        return init
    eng = stream_engine()
    eng.warmup([(NQ, 4)])
    telemetry.registry().reset(prefix="serve")
    b = batcher_for(eng)
    rng = np.random.default_rng(9)
    for cls in inits:
        cls.__init__ = counting(cls)
    try:
        b.start()
        for i in range(3):
            serve_one(b, request(i, rng))
        b.stop(drain=True)
        null = obs_trace.span("serve.cycle")
    finally:
        for cls, init in inits.items():
            cls.__init__ = init
    reg = telemetry.registry()
    return {"built": built, "null": null, "stats": b.cycle_stats(),
            "counts": {name: reg.get(name).count
                       for _key, name in PHASE_HISTOGRAMS["cycle"]}}


def test_no_sink_builds_no_span_and_the_histograms_still_count(untraced):
    assert untraced["built"] == []
    assert untraced["null"] is obs_trace.NULL_SPAN
    stats = dict(untraced["stats"])
    cpu = stats.pop("cpu")
    assert stats == {"cycles": 3, "slow_cycles": []}
    assert untraced["counts"] == {
        "serve.cycle_ms": 3, "serve.cycle_ms.own": 3,
        "serve.cycle_ms.device_wait": 3, "serve.cycle_ms.queue_wait": 3,
        "serve.cycle_ms.own_cpu": 3, "serve.cycle_ms.own_offcpu": 3,
        "serve.cycle_ms.wait_cpu": 3}
    # the thread's totals since it started, as of its last cycle, and
    # the cores the process may run on (cores_busy's denominator)
    assert set(cpu) == CPU_TOTALS
    assert 0 < cpu["thread_s"] <= cpu["process_s"]
    assert cpu["cores"] == batching._usable_cores() >= 1
    if batching._RUSAGE_THREAD is not None:
        assert abs(cpu["user_s"] + cpu["sys_s"] - cpu["thread_s"]) < 0.05
        assert cpu["nvcsw"] >= 1 and cpu["minflt"] >= 0


def test_the_account_makes_a_fixed_number_of_calls_a_cycle(monkeypatch):
    """With no sink, a cycle of the pipelined order (no queue wait)
    reads the kernel's account once (``time.thread_time``,
    ``time.process_time``, ``resource.getrusage``: one call each) and
    the thread's CPU time twice more for each host sync; nothing else
    in the program calls them, and no span object is built."""
    assert not obs_trace.sinks_active()
    calls = {"thread_time": 0, "process_time": 0, "getrusage": 0,
             "device_wait": 0, "span": 0}

    def counted(key, fn):
        def call(*a, **kw):
            calls[key] += 1
            return fn(*a, **kw)
        return call
    thread_time = counted("thread_time", time.thread_time)
    monkeypatch.setattr(time, "thread_time", thread_time)
    monkeypatch.setattr(obs_trace, "_cpu", thread_time)
    monkeypatch.setattr(time, "process_time",
                        counted("process_time", time.process_time))
    if batching._RUSAGE_THREAD is not None:
        monkeypatch.setattr(batching.resource, "getrusage", counted(
            "getrusage", batching.resource.getrusage))
    monkeypatch.setattr(obs_trace.device_wait, "__enter__", counted(
        "device_wait", obs_trace.device_wait.__enter__))
    for cls in (obs_trace.Span, obs_trace._TelemetrySpan):
        monkeypatch.setattr(cls, "__init__", counted("span", cls.__init__))
    eng = stream_engine()
    eng.warmup([(NQ, 4)])
    b = batcher_for(eng)
    at_end = []
    end_cycle = b._end_cycle

    def noting(f):
        end_cycle(f)
        at_end.append(dict(calls))
    b._end_cycle = noting
    rng = np.random.default_rng(17)
    reqs = [request(i, rng) for i in range(4)]
    for r in reqs:
        assert b.submit(r)["verdict"] == "accept"
    b.start()
    for r in reqs:
        assert r.done.wait(timeout=WAIT) and r.error is None
    b.stop(drain=True)
    assert len(at_end) == 4 and calls["span"] == 0
    per_cycle = [{k: b_[k] - a[k] for k in a}
                 for a, b_ in zip(at_end, at_end[1:])]
    # cycles 2..4 began behind another batch: no queue wait inside
    for got in per_cycle:
        assert got["device_wait"] == per_cycle[0]["device_wait"] >= 1
        assert got["thread_time"] == 1 + 2 * got["device_wait"]
        assert got["process_time"] == 1
        assert got["getrusage"] == int(batching._RUSAGE_THREAD is not None)


@pytest.mark.parametrize("key", ["cycle", "own", "device_wait",
                                 "queue_wait", "own_cpu", "own_offcpu",
                                 "wait_cpu"])
def test_stats_reports_the_cycle(traced_daemon, key):
    stats = traced_daemon["stats"]
    batcher = dict(stats["batcher"])
    assert set(batcher.pop("cpu")) == CPU_TOTALS
    assert batcher == {"cycles": 2, "slow_cycles": []}
    got = stats["phases_ms"]["cycle"][key]
    # (own_offcpu is a difference of two clocks: a thread that never
    # left its core may read a microsecond under zero)
    assert got["count"] == 2 and -0.01 <= got["p50"] <= got["p95"]
    assert stats["phases_ms"]["cycle"]["cycle"]["p95"] >= got["p95"]


@pytest.mark.parametrize("series", [
    "serve_cycle_ms_count 2", "serve_cycle_ms_own_count 2",
    "serve_cycle_ms_device_wait_count 2",
    "serve_cycle_ms_queue_wait_count 2", "serve_cycle_ms_own_cpu_count 2",
    "serve_cycle_ms_own_offcpu_count 2", "serve_cycle_ms_wait_cpu_count 2",
    "serve_phase_ms_read_count 2",
    'runtime_gc_pause_ms_total{key="gen2"}',
    'runtime_gc_collections_total{key="gen2"}'])
def test_openmetrics_carries_the_new_series(traced_daemon, series):
    text = traced_daemon["openmetrics"]
    assert telemetry.validate_openmetrics(text) == []
    assert any(line.startswith(series) for line in text.splitlines())


def test_the_gate_series_and_the_tracer_counter_are_gone(traced_daemon):
    assert "_gate_" not in traced_daemon["openmetrics"]
    assert not hasattr(obs_trace.Tracer, "counter")
    assert not hasattr(obs_trace, "counter")
