"""PR 32's one metric, ``parse_native_pct.bulk``: the accepted reader
``span_arg_pct`` over ``serve.phase.parse`` spans made by hand, with
hand-computed answers, and the cells that report it."""

import json
import os

import pytest

from benchmark import spec
from benchmark.run import Context

NAME = "parse_native_pct.bulk"
P = "serve.phase.parse"


def ctx_of(spans, window=(0.0, 100.0)):
    ctx = Context()
    ctx.window_pc = window
    ctx.spans = [{"name": n, "t0": a, "t1": b, "args": args}
                 for n, a, b, args in spans]
    return ctx


def read(spans, cell="gist.bulk", **kw):
    doc = next(m for m in spec.Cell(cell).per_layer() if m["name"] == NAME)
    return spec.reader(doc["reader"]).read(ctx_of(spans, **kw), **doc["args"])


CASES = [
    # every request of the window decoded by the scanner
    ("all_native", [(P, 1, 2, {"queries": 1024, "native_queries": 1024,
                               "bytes": 18942849}),
                    (P, 3, 4, {"queries": 1024, "native_queries": 1024,
                               "bytes": 18942849})], 100.0),
    # the parent's spans carry no such argument: 0, not nothing
    ("parent", [(P, 1, 2, {"queries": 1024, "bytes": 18942849}),
                (P, 3, 4, {"queries": 1024, "bytes": 18942849})], 0.0),
    # no library on the machine: the argument is there and reads 0
    ("fallback", [(P, 1, 2, {"queries": 1024, "native_queries": 0,
                             "bytes": 2527394})], 0.0),
    # a share of the QUERIES, not of the requests
    ("mixed", [(P, 1, 2, {"queries": 1024, "native_queries": 1024}),
               (P, 3, 4, {"queries": 256, "native_queries": 0}),
               ("serve.phase.respond", 5, 6, {"queries": 4096,
                                              "native_queries": 4096})],
     80.0),
]


@pytest.mark.parametrize("spans,want", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
@pytest.mark.parametrize("cell", ["bigann.bulk", "gist.bulk"])
def test_parse_native_pct_by_hand(cell, spans, want):
    assert read(spans, cell) == pytest.approx(want)


def test_only_the_windows_spans_count():
    spans = [(P, 1, 2, {"queries": 1024, "native_queries": 0}),      # warm-up
             (P, 60, 61, {"queries": 1024, "native_queries": 1024})]
    assert read(spans, window=(50.0, 100.0)) == pytest.approx(100.0)


def test_no_parse_span_in_the_window_reads_nothing():
    assert read([("serve.micro_batch", 1, 2, {"queries": 1024})]) is None


def test_listed_for_cells_that_report_qps():
    """Found by NAME: nothing here holds the entry's place in the list
    or closes its cells against additions (PR 45: an entry pinned last
    shut every later per-layer entry out for twelve PRs). A further
    entry appended after any other leaves this as it is."""
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert {k: entry[k] for k in entry if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "front end", "moves": "qps"}
    assert {"bigann.bulk", "gist.bulk"} <= set(entry["workloads"])
    for w in bench["workloads"]:
        listed = [m["name"] for m in spec.Cell(w["name"]).per_layer()]
        assert (NAME in listed) == (w["name"] in entry["workloads"])
    qps = next(m for m in bench["end_to_end"] if m["name"] == "qps")
    assert set(entry["workloads"]) <= set(qps["workloads"])
