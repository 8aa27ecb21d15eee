"""PR 50's ``read_pieces.bulk``: the receive calls a request's line took
(``serve.phase.read``'s ``pieces``), read by ``span_arg``, a reader the
benchmark already had, over spans made by hand with hand-computed
answers, and what it reads of a program whose read spans lack the
argument (the parent's: nothing, and the line leaves the metric out)."""

import pytest

from benchmark import spec
from benchmark.run import Context

NAME = "read_pieces.bulk"
R = "serve.phase.read"
WINDOW = (50.0, 100.0)
#: a recorded window: warm-up's read before it; inside it a 31 MB line
#: that found its connection's buffer at 64 KB (19 receives), three that
#: found it grown (11, 9, 14), a stats line in one receive, and a line
#: that came whole behind the one before it (none)
SPANS = [
    (R, 10.000, 10.300, {"bytes": 31457281, "pieces": 3841, "batch": 0}),
    (R, 60.000, 60.060, {"bytes": 31457281, "pieces": 19, "batch": 1}),
    (R, 61.000, 61.040, {"bytes": 31457281, "pieces": 11, "batch": 2}),
    (R, 62.000, 62.035, {"bytes": 31457281, "pieces": 9, "batch": 3}),
    (R, 63.000, 63.050, {"bytes": 31457281, "pieces": 14, "batch": 4}),
    (R, 64.000, 64.0001, {"bytes": 15, "pieces": 1}),
    (R, 64.001, 64.0011, {"bytes": 15, "pieces": 0}),
    ("serve.phase.parse", 60.060, 60.150, {"bytes": 31457281, "batch": 1}),
]


def ctx_of(spans, window=WINDOW):
    ctx = Context()
    ctx.window_pc = window
    ctx.spans = [{"name": n, "t0": a, "t1": b, "args": args}
                 for n, a, b, args in spans]
    return ctx


def entry():
    return next(m for m in spec.benchmark()["per_layer"]
                if m["name"] == NAME)


def read(cell, ctx):
    doc = next(d for d in spec.Cell(cell).per_layer() if d["name"] == NAME)
    return spec.reader(doc["reader"]).read(ctx, **doc["args"])


def test_the_entry_agrees_with_its_file():
    e = entry()
    doc = next(d for d in spec.Cell(e["workloads"][0]).per_layer()
               if d["name"] == NAME)
    want = {"unit": "calls", "better": "lower", "source": "program_span",
            "layer": "front end", "moves": "qps"}
    assert {k: doc[k] for k in want} == {k: e[k] for k in want} == want
    assert doc["reader"] == "span_arg" and doc["args"] == {
        "name": R, "arg": "pieces", "stat": "median"}
    assert doc["what"]


def test_its_cells_report_qps_and_the_read_it_counts():
    bench = spec.benchmark()
    cells = set(entry()["workloads"])
    qps = next(m for m in bench["end_to_end"] if m["name"] == "qps")
    assert cells and cells <= set(qps["workloads"])
    read_ms = next(m for m in bench["per_layer"]
                   if m["name"] == "read_ms.bulk")
    assert cells <= set(read_ms["workloads"])       # the same span
    assert [m["name"] for m in bench["per_layer"]].count(NAME) == 1


@pytest.mark.parametrize("cell", sorted(entry()["workloads"]))
def test_it_reads_the_windows_median(cell):
    # inside the window: 19, 11, 9, 14, 1, 0 -> (9 + 11) / 2
    assert read(cell, ctx_of(SPANS)) == pytest.approx(10.0)


def test_a_program_whose_read_lacks_the_argument_gives_nothing():
    """The parent's ``serve.phase.read`` carries ``bytes`` alone; a
    window with both kinds (it cannot happen in one program) reads the
    spans that carry the argument."""
    parent = [(n, a, b, {k: v for k, v in args.items() if k != "pieces"})
              for n, a, b, args in SPANS]
    assert read("dbpedia-openai-1m.bulk", ctx_of(parent)) is None
    mixed = parent[:3] + SPANS[3:]
    assert read("dbpedia-openai-1m.bulk", ctx_of(mixed)) \
        == pytest.approx(5.0)       # 9, 14, 1, 0
    assert read("dbpedia-openai-1m.bulk", ctx_of([])) is None
