"""The reader of PR 31's two metrics (a statistic of one span argument)
against hand-made spans with hand-computed answers, and the cell that
reports them: ``gist.bulk``'s rehearsal runs on the extract path at the
configuration's real width."""

import json
import subprocess
import sys

import pytest

from benchmark import spec
from benchmark.readers import span_arg
from benchmark.run import Context

NEW = ["hazard_clear_x.gist", "finalize_gather_mb.gist"]


def ctx_of(spans, window=(0.0, 100.0)):
    ctx = Context()
    ctx.window_pc = window
    ctx.spans = [{"name": n, "t0": a, "t1": b, "args": args}
                 for n, a, b, args in spans]
    return ctx


H = "single.hazard"

CASES = [
    ("median_odd", [(H, 1, 2, {"clear_min": 3.0}),
                    (H, 3, 4, {"clear_min": 1.5}),
                    (H, 5, 6, {"clear_min": 9.0})], {}, 3.0),
    ("median_even", [(H, 1, 2, {"clear_min": 2.0}),
                     (H, 3, 4, {"clear_min": 4.0})], {}, 3.0),
    # a span without the argument (a batch whose window was never
    # full) and a span of another name are left out
    ("without_the_arg", [(H, 1, 2, {"clear_min": 2.0}),
                         (H, 3, 4, {"rows": 7}),
                         ("single.finalize", 5, 6, {"clear_min": 50.0})],
     {}, 2.0),
    ("scaled", [(H, 1, 2, {"clear_min": 251658240})],
     {"scale": 1e-6}, 251.65824),
    ("mean", [(H, 1, 2, {"clear_min": 1.0}), (H, 3, 4, {"clear_min": 2.0}),
              (H, 5, 6, {"clear_min": 6.0})], {"stat": "mean"}, 3.0),
    # only the window's spans: warm-up's batch is before it
    ("window_only", [(H, 1, 2, {"clear_min": 100.0}),
                     (H, 60, 61, {"clear_min": 1.25})], {}, 1.25),
]


@pytest.mark.parametrize("spans,extra,want", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_span_arg_by_hand(spans, extra, want):
    window = (50.0, 100.0) if any(s[1] >= 50 for s in spans) else \
        (0.0, 100.0)
    got = span_arg.read(ctx_of(spans, window), name=H, arg="clear_min",
                        **extra)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_argument_reads_nothing(name):
    """The parent's spans carry neither ``clear_min`` nor
    ``gather_bytes``: the metric is then left out of the line."""
    cell = spec.Cell("gist.bulk")
    doc = next(m for m in cell.per_layer() if m["name"] == name)
    args = doc["args"]
    ctx = ctx_of([(args["name"], 1, 2, {"rows": 5, "exact": True}),
                  ("some.other_span", 1, 2, {args["arg"]: 4.0})])
    assert spec.reader(doc["reader"]).read(ctx, **args) is None
    ctx = ctx_of([(args["name"], 1, 2, {args["arg"]: 4.0})])
    assert spec.reader(doc["reader"]).read(ctx, **args) \
        == pytest.approx(4.0 * args.get("scale", 1.0))


def test_gist_rehearsal_serves_the_real_width_on_the_extract_path():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "gist.bulk",
         "--seed", "12345", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    served = next(x for x in lines if x.get("event") == "served")
    assert set(served["paths"].values()) == {"extract"}
    assert served["scan_shape"]["na"] == 960
    metrics = lines[-1]["metrics"]
    assert metrics["rehearsal.finalize_gather_mb.gist"]["value"] > 0
    assert metrics["rehearsal.hazard_clear_x.gist"]["value"] > 1
    assert metrics["rehearsal.repair_pct.bulk"]["value"] == 0
    cfg = spec.Cell("gist.bulk").config
    assert (cfg["num_data"], cfg["num_attrs"], cfg["reduced"]) \
        == (1000000, 960, [])
