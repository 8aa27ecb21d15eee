"""The per-layer list is open (PR 45): every file under
``benchmark/layer_metrics/`` is an entry of ``BENCHMARK.json`` or is
named below with its reason, so that files cannot pile up unlisted
again (24 had, over PRs 33, 35, 38 and 40, behind a test that held one
entry last); and an entry appended after any other changes nothing for
the entries that were there."""

import copy
import glob
import os

import pytest

from benchmark import spec

_NO_FLAGGED_QUERY = (
    "reads only in a traced window that holds a flagged query; about one "
    "seed in three draws none (0 of 16 payloads), and a listed metric "
    "that a cell's traced line lacks is refused: repair_pct.bulk and "
    "the served event's repairs say whether a retry ran")

#: files that are not entries, each with its reason
UNLISTED = {"retry_ms.bulk": _NO_FLAGGED_QUERY,
            "retry_cleared_pct.bulk": _NO_FLAGGED_QUERY,
            "retry_kernel_ms.bulk": _NO_FLAGGED_QUERY}

FILES = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(spec.HERE, "layer_metrics", "*.json")))


def test_every_metric_file_is_an_entry_or_says_why_not():
    listed = {m["name"] for m in spec.benchmark()["per_layer"]}
    assert set(FILES) - listed == set(UNLISTED)
    assert all(UNLISTED.values())               # a reason, in words
    assert listed <= set(FILES)                 # and every entry a file


@pytest.mark.parametrize("name", [n for n in FILES if n not in UNLISTED])
def test_an_entry_agrees_with_its_file_in_every_cell_it_lists(name):
    bench = spec.benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    cells = {w["name"] for w in bench["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    assert len(entry["workloads"]) == len(set(entry["workloads"]))
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved.get("workloads", cells))
    doc = next(d for d in spec.Cell(entry["workloads"][0]).per_layer()
               if d["name"] == name)
    assert doc["what"] and callable(spec.reader(doc["reader"]).read)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_the_list_holds_what_pr_45_listed_and_not_what_it_retired():
    names = [m["name"] for m in spec.benchmark()["per_layer"]]
    assert len(names) == len(set(names)) >= 37 + 24 - 4 - len(UNLISTED)
    assert not {"batch_self_pct.bulk", "batch_self_pct.steady",
                "batch_self_pct.mesh", "batch_solve_ms.steady"} & set(names)
    by_cell = {w["name"]: {d["name"] for d in
                           spec.Cell(w["name"]).per_layer()}
               for w in spec.benchmark()["workloads"]}
    assert {"kernel_ms.widek", "kernel_roofline.widek", "passes.widek",
            "multipass_ms.widek", "mp_flagged_pct.widek",
            "respond_p95_ms.widek"} <= by_cell["bigann-gt1000.bulk"]
    for cell in ("bigann-10m.bulk", "msturing-10m.bulk"):
        assert {"kernel_ms.fold", "kernel_roofline.fold"} <= by_cell[cell]
    assert "stage_pad_mb.narrow" in by_cell["msturing-10m.bulk"]
    cycle = {"cycle_ms.bulk", "cycle_max_ms.bulk", "host_own_ms.bulk",
             "device_wait_ms.bulk", "queue_wait_ms.bulk", "gc_ms.bulk",
             "overlap_pct.bulk", "cycle_unseen_pct.bulk", "read_ms.bulk",
             "parse_ms.bulk", "parse_native_pct.bulk"}
    for cell, got in by_cell.items():
        if cell.endswith(".bulk"):
            assert cycle <= got, (cell, cycle - got)
    assert {"cycle_ms.steady", "host_own_ms.steady",
            "device_wait_ms.steady"} <= by_cell["bigann.steady"]


def test_an_entry_appended_after_any_other_moves_nothing(monkeypatch):
    """What a later PR does: one more file, one more entry at the end.
    Every cell's metrics that were there are there, in their order."""
    bench = spec.benchmark()
    before = {w["name"]: [d["name"] for d in
                          spec.Cell(w["name"]).per_layer()]
              for w in bench["workloads"]}
    more = copy.deepcopy(bench)
    last = copy.deepcopy(more["per_layer"][0])
    last["name"] = "appended.later"
    more["per_layer"].append(last)
    load = spec._load

    def loaded(path):
        if path == os.path.join(spec.ROOT, "BENCHMARK.json"):
            return copy.deepcopy(more)
        if path.endswith(os.path.join("layer_metrics",
                                      "appended.later.json")):
            doc = load(os.path.join(spec.HERE, "layer_metrics",
                                    f"{bench['per_layer'][0]['name']}.json"))
            return dict(doc, name="appended.later")
        return load(path)
    monkeypatch.setattr(spec, "_load", loaded)
    for cell, names in before.items():
        got = [d["name"] for d in spec.Cell(cell).per_layer()]
        assert [n for n in got if n != "appended.later"] == names
        assert ("appended.later" in got) == (cell in last["workloads"])
