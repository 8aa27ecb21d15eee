"""PR 33's per-layer reductions on hand-made spans and a hand-made
trace with hand-computed answers: the kernel time of a micro-batch that
takes several passes (batches counted from the program's spans, the
scan's cost once a batch), and the six metric files that read
``serve.solve_multipass`` and ``serve.phase.respond``.

Since PR 45 the six are entries of ``BENCHMARK.json`` and the kernel's
two count a batch's events inside a ``serve.cycle`` span (one a
delivered micro-batch, none overlapping), with the calls the batch made
taken from the ``serve.solve_multipass`` span of the same ``batch``
serial: that span itself crosses batches since PR 34 (two are alive)
and is no longer the interval. The files are loaded here by path."""

import json
import os

import pytest

from benchmark import kernel_cost, spec
from benchmark.readers import kernel_ms_by_span, kernel_roofline_by_span
from benchmark.run import Context

CELL = "bigann-gt1000.bulk"
NEW = ["multipass_ms.widek", "passes.widek", "mp_flagged_pct.widek",
       "kernel_ms.widek", "kernel_roofline.widek", "respond_p95_ms.widek"]
SPAN = "serve.solve_multipass"
CYCLE = "serve.cycle"
KERNEL = 'custom_call_target="tpu_custom_call"'
PLANE, LINE = "/device:TPU:0", "XLA Ops"
MS = 1e6                                    # ns


def kernel(start_ms, dur_ms, plane=PLANE, name=None):
    return {"plane": plane, "line": LINE, "start_ns": start_ms * MS,
            "dur_ns": dur_ms * MS,
            "name": name or f"%dmlp_topk_fused.6 = custom-call(), {KERNEL}"}


def other(start_ms, dur_ms):
    return kernel(start_ms, dur_ms, name="%sort.17 = sort()")


def ctx_of(spans, events, window_ms=(0.0, 10_000.0), sync_pc=100.0):
    """Host spans in seconds after ``sync_pc`` (the perf_counter read at
    the trace's clock_sync, which the trace puts at 0 ns): a span at
    (1.0, 1.5) lies at 1000-1500 ms on the trace's clock."""
    ctx = Context()
    ctx.spans = [{"name": n, "t0": sync_pc + a, "t1": sync_pc + b,
                  "args": args} for n, a, b, args in spans]
    ctx.window_pc = (sync_pc - 50.0, sync_pc + 50.0)
    ctx.trace = {"events": events, "sync_ns": 0.0,
                 "window_ns": [window_ms[0] * MS, window_ms[1] * MS]}
    ctx.notes["sync_pc"] = sync_pc
    ctx.peaks = {"flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
    ctx.scan_shape = {"nq": 1024, "n": 4_000_000, "na": 128, "kc": 1152,
                      "itemsize": 4, "dispatches": 999}   # the stat: unread
    return ctx


ARGS = {"batch": 1, "chunks": 2, "passes": 3, "queries": 1024,
        "flagged": 0}


def alone(a, b, serial=1, args=ARGS):
    """A batch solved with nothing else in flight: its cycle began and
    delivered it, and its multipass span lies inside the cycle."""
    return [(CYCLE, a, b, {"batch": serial, "begun": serial}),
            (SPAN, a + 0.005, b - 0.005, dict(args, batch=serial))]


#: one micro-batch: 2 folds of pass 1 and a sweep a further pass
BATCH = [kernel(1010, 20), kernel(1030, 20), kernel(1100, 100),
         kernel(1250, 100)]


def shifted(events, ms):
    return [dict(e, start_ns=e["start_ns"] + ms * MS) for e in events]


def test_kernel_time_is_summed_inside_each_whole_batch():
    spans = alone(1.0, 1.4) + alone(3.0, 3.4, 2)
    events = BATCH + shifted(BATCH, 2000) + [other(1360, 30)]
    ctx = ctx_of(spans, events)
    got = kernel_ms_by_span.whole_batches(ctx, KERNEL, SPAN)
    assert got == [{"seconds": pytest.approx(0.240), "chunks": 2,
                    "passes": 3}] * 2
    assert kernel_ms_by_span.read(ctx, KERNEL, SPAN) == pytest.approx(240.0)


def test_batches_come_from_the_spans_not_from_a_counter():
    """Two batches of different cost: the mean of the two, whatever
    the daemon's count of chunks (``scan_shape``'s 999) says."""
    slow = [dict(e, dur_ns=e["dur_ns"] * 2) for e in shifted(BATCH, 2000)]
    spans = alone(1.0, 1.4) + alone(3.0, 3.7, 2)
    ctx = ctx_of(spans, BATCH + slow)
    assert kernel_ms_by_span.read(ctx, KERNEL, SPAN) \
        == pytest.approx((240.0 + 480.0) / 2)


@pytest.mark.parametrize("spans,events,window", [
    # the trace's window cuts the only cycle: its last sweep is outside
    (alone(1.0, 1.4), BATCH, (0.0, 1300.0)),
    # the cycle starts before the window does
    (alone(1.0, 1.4), BATCH, (1005.0, 10_000.0)),
    # a kernel event is missing (chunks + passes - 1 = 4 are due)
    (alone(1.0, 1.4), BATCH[:3], (0.0, 10_000.0)),
    # the parent's span: it carries no chunks (and no batch)
    ([(CYCLE, 1.0, 1.4, {"batch": 1, "begun": 1}),
      (SPAN, 1.0, 1.005, {"passes": 3})], BATCH, (0.0, 10_000.0)),
    # a cycle that ends before the kernels do
    (alone(1.0, 1.05), BATCH, (0.0, 10_000.0)),
    # no span at all, no event at all
    ([], BATCH, (0.0, 10_000.0)),
    (alone(1.0, 1.4), [], (0.0, 10_000.0)),
    # a program that cuts no cycles (before PR 35)
    ([(SPAN, 1.0, 1.4, ARGS)], BATCH, (0.0, 10_000.0)),
    # the multipass span of ANOTHER batch than the cycle's
    ([(CYCLE, 1.0, 1.4, {"batch": 1, "begun": 1}),
      (SPAN, 1.0, 1.4, dict(ARGS, batch=7))], BATCH, (0.0, 10_000.0)),
], ids=["cut_by_the_window", "starts_outside", "event_missing",
        "parent_span", "cycle_too_short", "no_span", "no_event",
        "no_cycle", "another_batch"])
def test_a_window_with_no_whole_batch_gives_nothing(spans, events, window):
    ctx = ctx_of(spans, events, window)
    assert kernel_ms_by_span.read(ctx, KERNEL, SPAN) is None
    assert kernel_roofline_by_span.read(ctx, KERNEL, SPAN) is None


def test_two_batches_alive_are_told_apart_by_the_cycle():
    """The pipeline since PR 34: cycle K begins batch K + 1 (whose
    kernels the idle device runs at once) and then finishes batch K, so
    the multipass span of batch K + 1 runs from its enqueues in cycle K
    to its fence in cycle K + 1, over batch K + 2's enqueues AND
    kernels: it holds two batches' events (the reader that summed inside
    it found 8 where 4 were due and read nothing). A cycle holds the
    events of the batch it began, and no other."""
    spans = [
        (CYCLE, 1.00, 1.90, {"batch": 1, "begun": 2}),
        (CYCLE, 1.90, 2.80, {"batch": 2, "begun": 3}),
        (CYCLE, 2.80, 3.70, {"batch": 3, "begun": 4}),
        (SPAN, 0.10, 1.50, dict(ARGS, batch=1)),
        (SPAN, 1.005, 2.40, dict(ARGS, batch=2)),     # over batch 3's
        (SPAN, 1.905, 3.30, dict(ARGS, batch=3)),     # over batch 4's
        (SPAN, 2.805, 4.20, dict(ARGS, batch=4))]
    slow = [dict(e, dur_ns=e["dur_ns"] * 2) for e in shifted(BATCH, 900)]
    events = BATCH + slow + shifted(BATCH, 1800)
    ctx = ctx_of(spans, events)
    inside_2 = [e for e in events if 1005 * MS <= e["start_ns"]
                and e["start_ns"] + e["dur_ns"] <= 2400 * MS]
    assert len(inside_2) == 8                  # what the span holds
    got = kernel_ms_by_span.whole_batches(ctx, KERNEL, SPAN)
    assert [round(b["seconds"], 6) for b in got] == [0.24, 0.48, 0.24]
    assert kernel_ms_by_span.read(ctx, KERNEL, SPAN) == pytest.approx(320.0)
    assert kernel_roofline_by_span.read(ctx, KERNEL, SPAN) \
        == pytest.approx(100.0 * 10.48576 / 320.0)


def test_a_cycle_that_began_no_batch_holds_the_one_it_delivered():
    spans = [(CYCLE, 1.0, 1.4, {"batch": 1, "begun": 0}),
             (SPAN, 0.2, 1.39, ARGS)]
    ctx = ctx_of(spans, BATCH)
    assert kernel_ms_by_span.read(ctx, KERNEL, SPAN) == pytest.approx(240.0)


def test_a_whole_batch_beside_a_cut_one_is_read_alone():
    spans = alone(1.0, 1.4) + alone(3.0, 3.4, 2)
    events = BATCH + shifted(BATCH, 2000)
    ctx = ctx_of(spans, events, (0.0, 3300.0))      # the second is cut
    assert kernel_ms_by_span.read(ctx, KERNEL, SPAN) == pytest.approx(240.0)


def test_no_trace_gives_nothing():
    ctx = ctx_of(alone(1.0, 1.4), BATCH)
    ctx.trace = None
    assert kernel_ms_by_span.read(ctx, KERNEL, SPAN) is None
    assert kernel_roofline_by_span.read(ctx, KERNEL, SPAN) is None


def test_the_scan_is_counted_once_a_batch_whatever_the_passes():
    """2 * Q * N * A over the peak, over the kernel time of ALL the
    passes: 1024 x 4e6 x 128 x 2 = 1.048576e12 flops = 10.48576 ms at
    100 TFLOP/s, of 240 ms."""
    ctx = ctx_of(alone(1.0, 1.4), BATCH)
    got = kernel_roofline_by_span.read(ctx, KERNEL, SPAN)
    assert got == pytest.approx(100.0 * 10.48576 / 240.0)
    assert ctx.notes["kernel_roofline_by_span_bound"] == "compute"
    # the dispatches are the span's chunks, not the counter's 999
    cost = kernel_cost.topk_scan_cost(1024, 4_000_000, 128, 1152, 4, 2)
    assert got == pytest.approx(kernel_cost.roofline(
        cost, ctx.peaks, 0.240)["pct"])
    # five passes over the same corpus in the same total time: the same
    # share, and a driver that needed one pass of a third the time
    # would read three times it
    five = dict(ARGS, passes=5)
    more = BATCH + [kernel(1360, 5), kernel(1370, 5)]
    ctx5 = ctx_of(alone(1.0, 1.4, args=five), more)
    assert kernel_roofline_by_span.read(ctx5, KERNEL, SPAN) \
        == pytest.approx(100.0 * 10.48576 / 250.0)


def test_without_the_scan_shape_there_is_no_share():
    ctx = ctx_of(alone(1.0, 1.4), BATCH)
    ctx.scan_shape = None
    assert kernel_ms_by_span.read(ctx, KERNEL, SPAN) == pytest.approx(240.0)
    assert kernel_roofline_by_span.read(ctx, KERNEL, SPAN) is None


# -- the metric files ---------------------------------------------------------

def window_ctx(spans):
    ctx = Context()
    ctx.window_pc = (0.0, 100.0)
    ctx.spans = [{"name": n, "t0": a, "t1": b, "args": args}
                 for n, a, b, args in spans]
    return ctx


def doc_of(name):
    with open(os.path.join(spec.HERE, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def read(name, ctx):
    doc = doc_of(name)
    return spec.reader(doc["reader"]).read(ctx, **doc["args"])


SOLVES = [(SPAN, 1, 1.5, {"passes": 3, "queries": 1024, "flagged": 0}),
          (SPAN, 3, 3.7, {"passes": 3, "queries": 1024, "flagged": 2}),
          (SPAN, 5, 5.6, {"passes": 3, "queries": 512, "flagged": 0})]


@pytest.mark.parametrize("name", NEW)
def test_each_metric_file_is_ready_to_be_listed(name):
    """Everything an entry under ``per_layer`` needs, a layer the
    benchmark has, an end-to-end metric the cell reports, a reader."""
    doc = doc_of(name)
    bench = spec.benchmark()
    assert doc["name"] == name and spec.NAME_RE.match(name)
    assert spec.UNIT_RE.match(doc["unit"])
    assert doc["better"] in ("lower", "higher")
    assert doc["source"] in spec.SOURCES
    assert doc["layer"] in {m["layer"] for m in bench["per_layer"]}
    assert doc["moves"] in {m["name"] for m in spec.Cell(CELL).end_to_end()}
    assert callable(spec.reader(doc["reader"]).read) and doc["what"]


def test_multipass_ms_and_passes_are_medians_of_the_solve_span():
    ctx = window_ctx(SOLVES)
    assert read("multipass_ms.widek", ctx) == pytest.approx(600.0)
    assert read("passes.widek", ctx) == 3
    assert read("multipass_ms.widek", window_ctx([])) is None


def test_flagged_share_is_over_the_queries_the_solves_held():
    assert read("mp_flagged_pct.widek", window_ctx(SOLVES)) \
        == pytest.approx(100.0 * 2 / 2560)
    # the parent's spans carry neither argument: nothing to read
    bare = [(SPAN, 1, 1.5, {"passes": 3})]
    assert read("mp_flagged_pct.widek", window_ctx(bare)) is None


def test_respond_p95_is_the_debug_response():
    R = "serve.phase.respond"
    spans = [(R, i, i + 0.3, {}) for i in range(1, 20)] \
        + [(R, 30, 31.5, {})]
    assert read("respond_p95_ms.widek", window_ctx(spans)) \
        == pytest.approx(300.0)          # nearest rank: the 19th of 20
    spans += [(R, 40, 42.0, {})]
    assert read("respond_p95_ms.widek", window_ctx(spans)) \
        == pytest.approx(1500.0)         # the 20th of 21
