"""The two span readers of PR 25 against hand-made spans with
hand-computed answers: a span's self time, and the seconds a set of
spans covers over the whole run."""

import pytest

from benchmark import spec
from benchmark.readers import span_self_pct, span_total_s
from benchmark.run import Context


def ctx_of(spans, window=(0.0, 100.0)):
    ctx = Context()
    ctx.window_pc = window
    ctx.spans = [{"name": n, "t0": a, "t1": b, "args": {}}
                 for n, a, b in spans]
    return ctx


P, KIDS = "parent", ["a", "b"]

SELF_CASES = [
    # children tile the parent: nothing is the parent's own
    ("covered", [(P, 10, 20), ("a", 10, 14), ("b", 14, 20)], 0.0),
    # no child at all: every second is
    ("uncovered", [(P, 10, 20)], 100.0),
    # a gap of 3 s in 10
    ("gap", [(P, 10, 20), ("a", 10, 13), ("b", 16, 20)], 30.0),
    # overlapping and nested children are counted once: cover 10..16
    ("overlapping", [(P, 10, 20), ("a", 10, 15), ("b", 12, 16),
                     ("a", 13, 14)], 40.0),
    # a child that sticks out is clipped to the parent; one outside it
    # (another thread, another batch) covers nothing
    ("clipped", [(P, 10, 20), ("a", 5, 12), ("b", 18, 25),
                 ("a", 30, 40)], 60.0),
    # two parents: summed self over summed duration, (0 + 5) / (10 + 10)
    ("two_parents", [(P, 10, 20), ("a", 10, 20), (P, 30, 40),
                     ("b", 30, 35)], 25.0),
    # a span of another name is no child
    ("other_names", [(P, 10, 20), ("c", 10, 20)], 100.0),
]


@pytest.mark.parametrize("spans,want", [c[1:] for c in SELF_CASES],
                         ids=[c[0] for c in SELF_CASES])
def test_self_pct_by_hand(spans, want):
    got = span_self_pct.read(ctx_of(spans), parent=P, children=KIDS)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("spans,window", [
    ([("a", 10, 20)], (0.0, 100.0)),             # no parent anywhere
    ([(P, 10, 20), ("a", 10, 20)], (50.0, 100.0)),   # none in the window
    ([(P, 10, 10)], (0.0, 100.0)),               # parents of no duration
], ids=["no_parent", "outside_window", "empty_parent"])
def test_self_pct_finds_nothing(spans, window):
    assert span_self_pct.read(ctx_of(spans, window), parent=P,
                              children=KIDS) is None


def test_self_pct_reads_only_the_windows_spans():
    spans = [(P, 10, 20), ("a", 10, 20),         # before the window
             (P, 60, 70), ("a", 60, 65)]
    got = span_self_pct.read(ctx_of(spans, (50.0, 100.0)), parent=P,
                             children=KIDS)
    assert got == pytest.approx(50.0)


TOTAL_CASES = [
    ("disjoint", [("a", 1, 3), ("b", 5, 6)], {}, 3.0),
    ("nested", [("a", 1, 5), ("b", 2, 3)], {}, 4.0),
    ("overlapping", [("a", 1, 4), ("b", 3, 6)], {}, 5.0),
    ("repeated", [("a", 1, 2), ("a", 4, 6), ("c", 0, 100)], {}, 3.0),
    # warm-up without the staging nested in it, and without a staging
    # span that lies outside it
    ("minus_nested", [("a", 10, 20), ("m", 12, 15), ("m", 30, 40)],
     {"minus": ["m"]}, 7.0),
    ("minus_absent", [("a", 10, 20)], {"minus": ["m"]}, 10.0),
]


@pytest.mark.parametrize("spans,extra,want", [c[1:] for c in TOTAL_CASES],
                         ids=[c[0] for c in TOTAL_CASES])
def test_total_s_by_hand(spans, extra, want):
    # the window is late: set-up spans lie before it and still count
    got = span_total_s.read(ctx_of(spans, (50.0, 60.0)), names=KIDS,
                            **extra)
    assert got == pytest.approx(want)


def test_total_s_finds_nothing():
    assert span_total_s.read(ctx_of([("c", 1, 2)]), names=KIDS) is None
    assert span_total_s.read(ctx_of([("m", 1, 2)]), names=KIDS,
                             minus=["m"]) is None


NEW = ["parse_ms.bulk", "respond_ms.bulk", "deliver_ms.bulk",
       "dispatch_ms.bulk", "dispatch_ms.steady", "hazard_ms.bulk",
       "hazard_ms.steady", "cycle_unseen_pct.bulk", "cycle_ms.steady",
       "setup_host_prep_s", "setup_stage_s", "setup_warmup_s"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_reads_nothing_and_does_not_raise(name):
    """The parent commit records none of PR 25's spans: each new metric
    then returns None (the line leaves it out), whatever else is there.
    (``batch_self_pct.*`` stood here until PR 45 retired them for
    ``cycle_unseen_pct.bulk`` and ``cycle_ms.steady``.)"""
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == name)
    cell = spec.Cell(entry["workloads"][0])
    doc = next(m for m in cell.per_layer() if m["name"] == name)
    ctx = ctx_of([("some.other_span", 1, 2)])
    assert spec.reader(doc["reader"]).read(ctx, **doc["args"]) is None
