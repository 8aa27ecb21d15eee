"""PR 52's two entries over ``serve.phase.write``, each a data file over a
reader the benchmark already had: ``write_pieces.widek`` (``span_arg``:
the 95th percentile of the span's ``pieces``, the socket writes a
response line left in) and ``write_p95_ms.widek`` (``span_ms``: the
95th percentile of the span itself), on spans made by hand with
hand-computed answers, and what each reads of a program whose write
spans lack the argument (the parent's: no count, the same time)."""

import pytest

from benchmark import spec
from benchmark.run import Context

CELL = "bigann-gt1000.bulk"
PIECES, P95 = "write_pieces.widek", "write_p95_ms.widek"
W = "serve.phase.write"
WINDOW = (50.0, 100.0)
#: a recorded window of 20 responses: warm-up's before it; inside it
#: every 8th a debug one (26.5 MB in 26 or 27 pieces, ~1.2 s), the rest
#: 23 kB in one piece (0.4 ms), and a stats reply in one
SPANS = [(W, 10.0, 11.5, {"bytes": 26500000, "pieces": 26, "batch": 0})] \
    + [(W, 60.0 + i, 60.0 + i + (1.2 + i / 100 if i % 8 == 0 else 0.0004),
        {"bytes": 26500000 if i % 8 == 0 else 23000,
         "pieces": (26 + i // 8) if i % 8 == 0 else 1, "batch": 1 + i})
       for i in range(19)] \
    + [(W, 90.0, 90.0002, {"bytes": 900, "pieces": 1}),
       ("serve.phase.respond", 60.0, 60.012, {"bytes": 26500000, "k": 1000})]


def ctx_of(spans, window=WINDOW):
    ctx = Context()
    ctx.window_pc = window
    ctx.spans = [{"name": n, "t0": a, "t1": b, "args": args}
                 for n, a, b, args in spans]
    return ctx


def entry(name):
    return next(m for m in spec.benchmark()["per_layer"]
                if m["name"] == name)


def read(name, ctx, cell=CELL):
    doc = next(d for d in spec.Cell(cell).per_layer() if d["name"] == name)
    return spec.reader(doc["reader"]).read(ctx, **doc["args"])


@pytest.mark.parametrize("name,want,reader,args", [
    (PIECES, {"unit": "calls", "better": "higher"}, "span_arg",
     {"name": W, "arg": "pieces", "stat": "p95"}),
    (P95, {"unit": "ms", "better": "lower"}, "span_ms",
     {"names": [W], "stat": "p95"}),
])
def test_the_entry_agrees_with_its_file(name, want, reader, args):
    e = entry(name)
    doc = next(d for d in spec.Cell(e["workloads"][0]).per_layer()
               if d["name"] == name)
    want = dict(want, source="program_span", layer="front end", moves="qps")
    assert {k: doc[k] for k in want} == {k: e[k] for k in want} == want
    assert doc["reader"] == reader and doc["args"] == args
    assert doc["what"]


@pytest.mark.parametrize("name", [PIECES, P95])
def test_its_cells_report_qps_and_the_debug_response(name):
    bench = spec.benchmark()
    cells = set(entry(name)["workloads"])
    qps = next(m for m in bench["end_to_end"] if m["name"] == "qps")
    assert CELL in cells and cells <= set(qps["workloads"])
    # the cells whose p95 response is read already: the same requests
    respond = entry("respond_p95_ms.widek")
    assert cells <= set(respond["workloads"])
    assert [m["name"] for m in bench["per_layer"]].count(name) == 1


@pytest.mark.parametrize("cell", sorted(entry(PIECES)["workloads"]))
def test_they_read_the_windows_debug_response(cell):
    # inside the window: 20 spans, debug ones of 26, 27, 28 pieces and
    # 1200, 1280, 1360 ms; nearest rank: the 19th of 20
    ctx = ctx_of(SPANS)
    assert read(PIECES, ctx, cell) == 27
    assert read(P95, ctx, cell) == pytest.approx(1280.0)


def test_a_program_whose_write_lacks_the_argument_gives_no_count():
    """The parent's ``serve.phase.write`` carries ``bytes`` alone: no
    count to read (the line leaves the metric out), and the same time."""
    parent = [(n, a, b, {k: v for k, v in args.items() if k != "pieces"})
              for n, a, b, args in SPANS]
    assert read(PIECES, ctx_of(parent)) is None
    assert read(P95, ctx_of(parent)) == pytest.approx(1280.0)
    assert read(PIECES, ctx_of([])) is None
    assert read(P95, ctx_of([])) is None
