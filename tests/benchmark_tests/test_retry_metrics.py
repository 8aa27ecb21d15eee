"""PR 38's metrics of the device retry: ``retry_ms.bulk`` and
``retry_cleared_pct.bulk`` through readers the benchmark already had
(``span_arg``, ``span_arg_pct``) over ``single.retry`` spans made by
hand, with hand-computed answers, and what they read of a program that
has no retry (nothing); and the three that tell a retry's kernel events
from a batch's own by the shape in the event's name
(``retry_kernel_ms.bulk``, ``kernel_ms.fold``, ``kernel_roofline.fold``:
readers ``kernel_ms`` and ``kernel_roofline`` under a narrower
``pattern``), on a hand-made trace on which ``kernel_ms.bulk`` itself
reads low.

``kernel_ms.fold`` and ``kernel_roofline.fold`` are entries of
``BENCHMARK.json`` since PR 45 (until then a test held
``parse_native_pct.bulk`` to be the LAST per-layer entry). The three
``retry_*`` stay files: they read only in a window that holds a flagged
query, which about one seed in three does not draw, and a listed metric
has to be on every traced line of its cell (``test_open_list.py`` names
them with that reason). All five are loaded here by path, as
``test_cycle_metrics.py`` does."""

import json
import os

import pytest

from benchmark import kernel_cost, spec
from benchmark.run import Context

R = "single.retry"
NEW = ["retry_ms.bulk", "retry_cleared_pct.bulk"]
TRACED = ["retry_kernel_ms.bulk", "kernel_ms.fold", "kernel_roofline.fold"]


def retry(t0, t1, queries, cleared, batch=1, host_ms=2.0):
    return (R, t0, t1, {"batch": batch, "queries": queries, "kcap": 512,
                        "passes": 1, "enqueue_ms": 2.5, "cleared": cleared,
                        "fell_through": queries - cleared,
                        "wait_ms": round((t1 - t0) * 1e3 - host_ms, 3),
                        "host_ms": host_ms})


#: three flagged batches of a window: retries of 20, 30 and 100 ms (the
#: last waited for the batch begun behind it: the host's share of it is
#: no longer than the others'), five queries retried, four cleared; the
#: parent's spans of the same batches beside them
WINDOW = [
    retry(1.000, 1.020, 1, 1, batch=3, host_ms=2.0),
    retry(2.000, 2.030, 1, 1, batch=17, host_ms=3.0),
    retry(3.000, 3.100, 3, 2, batch=40, host_ms=4.5),
    ("single.retry_begin", 0.950, 0.953, {"queries": 1, "kcap": 512}),
    ("single.finalize", 0.950, 1.020, {"repairs": 1, "batch": 3}),
    ("single.repair", 3.100, 7.100, {"queries": 1, "batch": 40}),
    ("serve.micro_batch", 0.900, 1.030, {"queries": 1024, "batch": 3}),
]

WANT = {"retry_ms.bulk": 3.0, "retry_cleared_pct.bulk": 80.0}


def doc_of(name):
    with open(os.path.join(spec.HERE, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def read(name, spans, window=(0.0, 100.0)):
    ctx = Context()
    ctx.window_pc = window
    ctx.spans = [{"name": n, "t0": a, "t1": b, "args": args}
                 for n, a, b, args in spans]
    doc = doc_of(name)
    return spec.reader(doc["reader"]).read(ctx, **doc["args"])


@pytest.mark.parametrize("name", NEW)
def test_by_hand(name):
    assert read(name, WINDOW) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_only_the_windows_retries_count(name):
    """Warm-up makes a retry of its own (one query, cleared), outside
    the window."""
    spans = [retry(1.0, 6.0, 1, 1)] + [
        (n, a + 50, b + 50, args) for n, a, b, args in WINDOW]
    assert read(name, spans, window=(50.0, 100.0)) \
        == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_retry_gives_nothing_to_read(name):
    parent = [s for s in WINDOW if not s[0].startswith("single.retry")]
    assert read(name, parent) is None
    assert read(name, []) is None


def test_a_retry_span_without_the_hosts_share_is_left_out():
    """The first form of the span (a whole wait inside, no ``host_ms``)
    gives ``retry_ms.bulk`` nothing to read."""
    n, a, b, args = retry(1.0, 1.1, 1, 1)
    old = {k: v for k, v in args.items() if k not in ("host_ms", "wait_ms")}
    assert read("retry_ms.bulk", [(n, a, b, old)]) is None
    assert read("retry_cleared_pct.bulk", [(n, a, b, old)]) == 100.0


def test_every_flagged_query_falling_through_reads_zero():
    assert read("retry_cleared_pct.bulk",
                [retry(1.0, 1.1, 4, 0)]) == pytest.approx(0.0)


# -- a retry's kernel events told from a batch's own --------------------------

KERNEL = 'custom_call_target="tpu_custom_call"'
MS = 1e6                                    # ns
CHUNKS = 4                                  # resident chunks a fold visits


def kernel(start_ms, dur_ms, rows, slots, fresh=False):
    """One kernel event as the chip's trace names it: the outputs'
    shapes first, the custom call's target last."""
    name = (f"%dmlp_topk_fused{'_fresh.1' if fresh else '.4'} = "
            f"(f32[{rows},{slots}]{{1,0:T(8,128)S(1)}}, "
            f"s32[{rows},{slots}]{{1,0:T(8,128)S(1)}}, "
            f"s32[{rows},4]{{1,0:T(8,128)S(1)}}) custom-call(s32[1,2] %a), "
            f"{KERNEL}")
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
            "start_ns": start_ms * MS, "dur_ns": dur_ms * MS}


def fold(start_ms, dur_ms, rows, slots):
    return [kernel(start_ms + i * dur_ms, dur_ms, rows, slots, fresh=not i)
            for i in range(CHUNKS)]


#: three micro-batches' folds of 4 x 40 ms and one retry of 4 x 3 ms
#: between the second and the third, and the while loop that holds them
EVENTS = fold(1000, 40, 1024, 120) + fold(1200, 40, 1024, 120) \
    + fold(1400, 3, 16, 512) + fold(1500, 40, 1024, 120) + [
    {"plane": "/device:TPU:0", "line": "XLA Ops", "start_ns": 1000 * MS,
     "dur_ns": 160 * MS,
     "name": "%while.1 = (s32[], f32[1024,120]{1,0:T(8,128)S(1)}, "
             "s32[1024,120]{1,0:T(8,128)S(1)}) while()"}]


def traced(events=EVENTS):
    ctx = Context()
    ctx.trace = {"events": events, "sync_ns": 0.0,
                 "window_ns": [0.0, 10_000 * MS]}
    # the window's fold spans say how many kernel calls a batch makes
    ctx.window_pc = (0.0, 100.0)
    ctx.spans = [{"name": "serve.solve_extract", "t0": t, "t1": t + 0.001,
                  "args": {"chunks": CHUNKS}} for t in (1.0, 1.2, 1.5)]
    ctx.peaks = {"flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
    ctx.scan_shape = {"nq": 1024, "n": 10_000_000, "na": 128, "kc": 120,
                      "itemsize": 2, "dispatches": CHUNKS}
    return ctx


def read_traced(name, ctx):
    doc = doc_of(name)
    return spec.reader(doc["reader"]).read(ctx, **doc["args"])


def test_a_retry_bends_the_accepted_kernel_metric_and_not_the_folds():
    ctx = traced()
    # 3 x 160 ms + 12 ms over (16 events / 4) "batches": a retry counted
    # as a whole micro-batch
    bench = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    assert "bigann-10m.bulk" not in bench["kernel_ms.bulk"]["workloads"]
    assert "bigann-10m.bulk" not in bench["kernel_roofline.bulk"]["workloads"]
    assert read_traced("kernel_ms.bulk", ctx) == pytest.approx(492.0 / 4)
    assert read_traced("kernel_ms.fold", ctx) == pytest.approx(160.0)
    assert read_traced("retry_kernel_ms.bulk", ctx) == pytest.approx(12.0)
    cost = kernel_cost.topk_scan_cost(**ctx.scan_shape)
    want = kernel_cost.roofline(cost, ctx.peaks, 0.160)["pct"]
    assert read_traced("kernel_roofline.fold", ctx) == pytest.approx(want)
    assert want < 100.0


def test_without_a_retry_the_fold_metrics_read_as_the_accepted_ones():
    ctx = traced([e for e in EVENTS if "[16,512]" not in e["name"]])
    assert read_traced("kernel_ms.fold", ctx) \
        == read_traced("kernel_ms.bulk", ctx) == pytest.approx(160.0)
    assert read_traced("kernel_roofline.fold", ctx) \
        == pytest.approx(read_traced("kernel_roofline.bulk", ctx))
    assert read_traced("retry_kernel_ms.bulk", ctx) is None


@pytest.mark.parametrize("name", TRACED)
def test_an_untraced_run_gives_the_kernel_metrics_nothing_to_read(name):
    ctx = traced()
    ctx.trace = None
    assert read_traced(name, ctx) is None


@pytest.mark.parametrize("name", NEW + TRACED)
def test_the_files_are_legal_entries_of_layers_the_benchmark_has(name):
    doc = doc_of(name)
    assert doc["name"] == name and spec.NAME_RE.match(name)
    assert spec.UNIT_RE.match(doc["unit"])
    assert doc["source"] == ("device_trace" if name in TRACED
                             else "program_span")
    assert doc["moves"] == "qps"
    assert doc["better"] in ("lower", "higher")
    bench = spec.benchmark()
    layers = {m["layer"] for m in bench["per_layer"]}
    assert doc["layer"] in layers
    assert callable(spec.reader(doc["reader"]).read)
    # the two of the fold are listed since PR 45, and an entry agrees
    # with its file (spec.Cell); the retry's three are not (docstring)
    entry = next((m for m in bench["per_layer"] if m["name"] == name), None)
    assert (entry is None) == name.startswith("retry_")
    if entry is not None:
        assert all(entry[k] == doc[k] for k in ("unit", "better", "source",
                                                "layer", "moves"))
        assert "bigann-10m.bulk" in entry["workloads"]
