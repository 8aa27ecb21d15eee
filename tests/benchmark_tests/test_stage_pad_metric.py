"""PR 40's metric of what staging on whole lanes costs:
``stage_pad_mb.narrow`` reads ``pad_bytes`` of set-up's
``serve.stage_chunks`` span through ``span_arg_setup``, the sibling of
``span_arg`` that reads the spans BEFORE the window (staging is over
when the window opens, and ``span_arg`` reads the window's). Held here
to hand-made spans with hand-computed answers, and to what it reads of
a program whose span lacks the argument (nothing).

An entry of ``BENCHMARK.json`` since PR 45 (for ``msturing-10m.bulk``);
the file is still loaded here by path, as ``test_retry_metrics.py``
does."""

import json
import os

import pytest

from benchmark import spec
from benchmark.run import Context

NAME = "stage_pad_mb.narrow"
S = "serve.stage_chunks"
WINDOW = (100.0, 130.0)


def doc():
    with open(os.path.join(spec.HERE, "layer_metrics", f"{NAME}.json")) as f:
        return json.load(f)


def read(spans):
    ctx = Context()
    ctx.window_pc = WINDOW
    ctx.spans = [{"name": n, "t0": a, "t1": b, "args": args}
                 for n, a, b, args in spans]
    d = doc()
    return spec.reader(d["reader"]).read(ctx, **d["args"])


def staged(t0, t1, na, a_pad, chunks=328, rows=51200, item=2, **more):
    return (S, t0, t1, {"chunks": chunks, "chunk_rows": rows, "na": na,
                        "a_pad": a_pad,
                        "pad_bytes": chunks * rows * (a_pad - na) * item,
                        **more})


@pytest.mark.parametrize("span,want", [
    (staged(40.0, 54.0, 100, 128), 940.4416),            # msturing-10m
    (staged(40.0, 54.0, 960, 1024, 21, 51200, 4), 275.2512),   # gist-1m
    (staged(40.0, 54.0, 128, 128), 0.0),                 # whole lanes
], ids=["narrow", "wide", "whole_lanes"])
def test_by_hand(span, want):
    assert read([span]) == pytest.approx(want)


def test_only_set_up_is_read():
    """A span inside the window (no program makes one there today; an
    ingest that restaged would) and one that straddles its start are
    not set-up's."""
    spans = [staged(40.0, 54.0, 100, 128),
             staged(101.0, 102.0, 100, 128, chunks=1),
             staged(99.0, 100.5, 100, 128, chunks=2)]
    assert read(spans) == pytest.approx(940.4416)
    assert read(spans[1:]) is None


def test_a_program_without_the_argument_gives_nothing_to_read():
    parent = (S, 40.0, 54.0, {"chunks": 328, "chunk_rows": 51200})
    assert read([parent]) is None
    assert read([]) is None
    assert read([("serve.stage_resident", 30.0, 40.0,
                  {"rows": 1 << 24, "na": 100, "a_pad": 100,
                   "pad_bytes": 0})]) is None


def test_the_file_names_its_layer_and_what_it_moves():
    d = doc()
    assert (d["layer"], d["moves"], d["unit"], d["source"]) \
        == ("set-up", "setup_s", "MB", "program_span")
    assert spec.NAME_RE.match(d["name"]) and spec.UNIT_RE.match(d["unit"])
    bench = spec.benchmark()
    assert d["layer"] in {m["layer"] for m in bench["per_layer"]}
    assert d["moves"] in {m["name"] for m in bench["end_to_end"]}
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert "msturing-10m.bulk" in entry["workloads"]
