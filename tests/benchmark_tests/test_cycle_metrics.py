"""PR 35's twelve metrics of the batcher's cycle, each through a reader
the benchmark already had (``span_ms``, ``span_arg``, ``span_self_pct``)
over spans made by hand, with hand-computed answers, and what they read
of a program that records none of the new spans (nothing).

The twelve are entries of ``BENCHMARK.json`` since PR 45 (until then
a test held ``parse_native_pct.bulk`` to be the LAST per-layer entry and
nothing could be listed); the files are still loaded here by path, and
``WANT`` names the cells each must at least be listed for."""

import json
import os

import pytest

from benchmark import spec
from benchmark.run import Context

C = "serve.cycle"
BULK = ["bigann.bulk", "gist.bulk", "bigann-gt1000.bulk",
        "bigann-mesh4.bulk"]
STEADY = ["bigann.steady"]


def cyc(t0, t1, batch, own, device, queue, gc=0.0, overlapped=1):
    return (C, t0, t1, {"batch": batch, "begun": batch + 1, "queries": 1024,
                        "requests": 1, "overlapped": overlapped,
                        "own_ms": own, "device_wait_ms": device,
                        "queue_wait_ms": queue, "gc_ms": gc})


#: three cycles of 70, 100 and 80 ms, the second lengthened by a
#: collector pass, the first begun with nothing in flight; inside them
#: the batcher thread's spans, and the two that cross batches
WINDOW = [
    cyc(1.000, 1.070, 1, 28.0, 40.0, 2.0, overlapped=0),
    cyc(1.070, 1.170, 2, 61.0, 39.0, 0.0, gc=30.0),
    cyc(1.170, 1.250, 3, 30.0, 50.0, 0.0),
    # cycle 1: 60 of its 70 ms under a span (one nested, counted once)
    ("serve.wait.queue", 1.000, 1.002, {}),
    ("serve.batch_assemble", 1.002, 1.010, {"batch": 2}),
    ("serve.solve_stage", 1.010, 1.050, {"batch": 2}),
    ("serve.prune_score", 1.012, 1.050, {"batch": 2}),     # not a child
    ("single.finalize", 1.050, 1.060, {"batch": 1}),
    # cycle 2: 90 of 100
    ("serve.solve_stage", 1.070, 1.110, {"batch": 3}),
    ("single.fetch", 1.110, 1.111, {"batch": 2}),
    ("single.finalize", 1.111, 1.160, {"batch": 2}),
    # cycle 3: the mesh's spans tile 76 of 80
    ("fleet.merge_drain", 1.170, 1.220, {"batch": 3}),
    ("fleet.finalize", 1.220, 1.246, {"batch": 3}),
    # spans that cross batches cover everything and must not count
    ("serve.micro_batch", 1.002, 1.170, {"batch": 2, "overlapped": 1}),
    ("serve.solve_multipass", 1.010, 1.240, {"batch": 2}),
    # a request's line: 19 MB beside the finalize, and two small ones
    ("serve.phase.read", 1.020, 1.045, {"bytes": 18942849, "batch": 3}),
    ("serve.phase.read", 1.100, 1.101, {"bytes": 2527394, "batch": 4}),
    ("serve.phase.read", 1.200, 1.203, {"bytes": 2527394, "batch": 5}),
    # warm-up, before the window opens: never read
    cyc(0.100, 0.900, 0, 700.0, 100.0, 0.0, gc=500.0, overlapped=0),
    ("serve.phase.read", 0.100, 0.900, {"bytes": 1}),
]

#: metric -> (cells, answer over WINDOW)
WANT = {
    "cycle_ms.bulk": (BULK, 80.0),
    "cycle_max_ms.bulk": (BULK, 100.0),
    "host_own_ms.bulk": (BULK, 30.0),
    "device_wait_ms.bulk": (BULK, 40.0),
    "queue_wait_ms.bulk": (BULK, 0.0),
    "gc_ms.bulk": (BULK, 10.0),
    "overlap_pct.bulk": (BULK, 200.0 / 3),
    # (10 + 10 + 4) unseen of 250 ms
    "cycle_unseen_pct.bulk": (BULK, 100.0 * 24 / 250),
    "read_ms.bulk": (BULK, 3.0),
    "cycle_ms.steady": (STEADY, 80.0),
    "host_own_ms.steady": (STEADY, 30.0),
    "device_wait_ms.steady": (STEADY, 40.0),
}

#: what the parent commit records in the same window: no cycle, no
#: wait, no read span; its epilogue still crosses batches
PARENT = [s for s in WINDOW if s[0] not in (C, "serve.wait.queue",
                                            "serve.phase.read")] + [
    ("serve.solve_epilogue", 1.012, 1.110, {"batch": 2}),
    ("serve.phase.parse", 1.045, 1.050, {"queries": 1024, "bytes": 9})]


def ctx_of(spans, window=(0.95, 2.0)):
    ctx = Context()
    ctx.window_pc = window
    ctx.spans = [{"name": n, "t0": a, "t1": b, "args": args}
                 for n, a, b, args in spans]
    return ctx


def doc_of(name):
    with open(os.path.join(spec.HERE, "layer_metrics",
                           f"{name}.json")) as f:
        return json.load(f)


def read(name, spans):
    doc = doc_of(name)
    return spec.reader(doc["reader"]).read(ctx_of(spans), **doc["args"])


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_by_hand(name):
    assert read(name, WINDOW) == pytest.approx(WANT[name][1])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_program_without_the_spans_reads_nothing(name):
    assert read(name, PARENT) is None


def test_the_parts_sum_to_the_cycle_in_every_hand_made_span():
    for name, t0, t1, args in WINDOW:
        if name == C:
            assert (t1 - t0) * 1e3 == pytest.approx(
                args["own_ms"] + args["device_wait_ms"]
                + args["queue_wait_ms"])


@pytest.mark.parametrize("name,cell", [(name, cell)
                                       for name, (cells, _) in WANT.items()
                                       for cell in cells])
def test_each_metric_file_is_ready_to_be_listed(name, cell):
    """Everything an entry under ``per_layer`` needs: a reader the
    benchmark has, a layer it names, and in each cell the entry is to
    list the end-to-end metric it moves."""
    doc = doc_of(name)
    bench = spec.benchmark()
    assert doc["name"] == name and spec.NAME_RE.match(name)
    assert spec.UNIT_RE.match(doc["unit"])
    assert doc["better"] in ("lower", "higher")
    assert doc["reader"] in ("span_ms", "span_arg", "span_self_pct")
    assert callable(spec.reader(doc["reader"]).read) and doc["what"]
    assert doc["source"] == "program_span"
    assert doc["layer"] == ("front end" if name == "read_ms.bulk"
                            else "micro-batcher")
    assert doc["layer"] in {m["layer"] for m in bench["per_layer"]}
    assert doc["moves"] == ("p50_ms" if name.endswith(".steady")
                            else "qps")
    assert doc["moves"] in {m["name"]
                            for m in spec.Cell(cell).end_to_end()}


def test_children_leave_out_the_spans_that_cross_batches():
    args = doc_of("cycle_unseen_pct.bulk")["args"]
    kids = args["children"]
    assert args["parent"] == C
    assert not {"serve.micro_batch", "serve.solve_multipass", C} & set(kids)
    assert {"serve.wait.queue", "serve.solve_epilogue", "single.finalize",
            "serve.batch_deliver", "fleet.merge_drain"} <= set(kids)


def test_the_twelve_are_listed_by_name_wherever_they_stand():
    # nothing here holds the list's length or any entry's place in it:
    # a further entry appended after any other leaves this as it is
    bench = spec.benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    qps = next(m for m in bench["end_to_end"] if m["name"] == "qps")
    for name, (cells, _) in WANT.items():
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert set(cells) <= set(entry["workloads"])
        if entry["moves"] == "qps":
            assert set(entry["workloads"]) <= set(qps["workloads"])
