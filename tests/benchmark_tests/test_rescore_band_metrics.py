"""PR 48's two metrics of the host finalize's gather-and-score, read
from a recorded window (hand-made spans with hand-computed answers, as
the span_arg reader's own test makes them) and from the cells that list
them: ``rescore_band_pct.bulk`` (the share of a micro-batch's candidate
slots whose float64 row the rescore gathered) and ``rescore_ms.bulk``
(the span itself, as ``rescore_ms.ip`` reads it in the inner-product
cell)."""

import json
import subprocess
import sys

import pytest

from benchmark import spec
from benchmark.run import Context

BAND, MS = "rescore_band_pct.bulk", "rescore_ms.bulk"
L2_CELLS = ["bigann.bulk", "gist.bulk", "bigann-gt1000.bulk",
            "bigann-10m.bulk", "msturing-10m.bulk"]

R = "single.rescore"
#: a recorded window: warm-up's batch before it, three batches inside
#: (durations 12, 20 and 16 ms; bands 44.5, 41.0 and 100%: the last a
#: batch nothing cut), a finalize span around each
WINDOW = (50.0, 100.0)
SPANS = [
    (R, 10.000, 10.030, {"queries": 1024, "slots": 120, "rows": 122880,
                         "band_pct": 100.0, "batch": 0}),
    ("single.finalize", 59.990, 60.040, {"gather_bytes": 56000000,
                                         "batch": 1}),
    (R, 60.000, 60.012, {"queries": 1024, "slots": 120, "rows": 54682,
                         "band_pct": 44.5, "batch": 1}),
    (R, 70.000, 70.020, {"queries": 1024, "slots": 120, "rows": 50381,
                         "band_pct": 41.0, "batch": 2}),
    (R, 80.000, 80.016, {"queries": 1024, "slots": 120, "rows": 122880,
                         "band_pct": 100.0, "batch": 3}),
]


def ctx_of(spans, window=WINDOW):
    ctx = Context()
    ctx.window_pc = window
    ctx.spans = [{"name": n, "t0": a, "t1": b, "args": args}
                 for n, a, b, args in spans]
    return ctx


def doc_of(cell, name):
    return next(m for m in spec.Cell(cell).per_layer()
                if m["name"] == name)


def read(cell, name, ctx):
    doc = doc_of(cell, name)
    return spec.reader(doc["reader"]).read(ctx, **doc["args"])


@pytest.mark.parametrize("cell", L2_CELLS + ["text2image-10m.bulk"])
def test_band_pct_reads_the_windows_median(cell):
    assert read(cell, BAND, ctx_of(SPANS)) == pytest.approx(44.5)


@pytest.mark.parametrize("cell", L2_CELLS)
def test_rescore_ms_reads_the_windows_median_span(cell):
    assert read(cell, MS, ctx_of(SPANS)) == pytest.approx(16.0)


def test_the_parents_span_has_a_duration_and_no_band():
    """The parent's ``single.rescore`` carries queries, slots and bytes:
    ``rescore_ms.bulk`` reads it, ``rescore_band_pct.bulk`` finds
    nothing and the line leaves it out; a program with no such span
    (fast mode, a mesh daemon) gives neither."""
    parent = [(R, 60.0, 60.036, {"queries": 1024, "slots": 120,
                                 "bytes": 125829120, "batch": 1})]
    assert read("bigann-10m.bulk", MS, ctx_of(parent)) \
        == pytest.approx(36.0)
    assert read("bigann-10m.bulk", BAND, ctx_of(parent)) is None
    other = [("fleet.finalize", 60.0, 60.016, {"band_pct": 36.0})]
    assert read("bigann-10m.bulk", MS, ctx_of(other)) is None
    assert read("bigann-10m.bulk", BAND, ctx_of(other)) is None


def test_the_entries_list_the_cells_whose_lines_carry_them():
    by_cell = {w["name"]: {d["name"] for d in
                           spec.Cell(w["name"]).per_layer()}
               for w in spec.benchmark()["workloads"]}
    assert {c for c, got in by_cell.items() if BAND in got} \
        == set(L2_CELLS) | {"text2image-10m.bulk"}
    # the inner-product cell has the span under rescore_ms.ip
    assert {c for c, got in by_cell.items() if MS in got} == set(L2_CELLS)
    assert "rescore_ms.ip" in by_cell["text2image-10m.bulk"]
    for name in (BAND, MS):
        doc = doc_of("bigann-10m.bulk", name)
        assert (doc["layer"], doc["moves"], doc["better"]) \
            == ("host finalize", "qps", "lower")


@pytest.mark.parametrize("cell", ["bigann-10m.bulk", "bigann-gt1000.bulk"])
def test_a_rehearsed_cell_reports_both(cell):
    """The bf16 cell and the wide-k cell through the served path in
    interpret mode: both metrics on the traced line, the band a share of
    the window, the gather the bytes of the rows it says."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "2148000048", "--seconds", "1", "--trace", "1",
         "--rehearse"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    metrics = line["metrics"]
    band = metrics[f"rehearsal.{BAND}"]["value"]
    assert 0 < band <= 100
    assert metrics[f"rehearsal.{MS}"]["value"] > 0
    assert metrics["rehearsal.finalize_gather_mb.gist"]["value"] > 0
