"""The whole command end to end on the CPU at toy sizes (--rehearse), and
the data files behind every cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import check, spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def harness(name, *extra, seed=12345, seconds=1):
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", name,
           "--seed", str(seed), "--seconds", str(seconds), *extra]
    return subprocess.run(cmd, cwd=spec.ROOT, capture_output=True,
                          text=True, timeout=240)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_reports_the_end_to_end_metrics(name):
    line = last_line(harness(name, "--trace", "0", "--rehearse"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    cell = spec.Cell(name)
    want = {"rehearsal." + m["name"] for m in cell.end_to_end()}
    assert set(line["metrics"]) == want      # named as rehearsal output
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_traced_reports_layer_metrics_and_a_breakdown(name):
    line = last_line(harness(name, "--trace", "1", "--rehearse"))
    assert line["correct"] is True
    cell = spec.Cell(name)
    legal = {"rehearsal." + m["name"] for m in cell.per_layer()}
    assert line["metrics"] and set(line["metrics"]) <= legal
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["breakdown"]["idle_gaps"]      # the CPU "device" idles


def test_without_a_tpu_the_run_prints_no_result():
    proc = harness(CELLS[0], "--trace", "0")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(l.startswith('{"correct"') for l in
                   proc.stdout.splitlines())


def test_an_unknown_cell_is_refused():
    proc = harness("no.such.cell", "--trace", "0", "--rehearse")
    assert proc.returncode != 0 and "no workload" in proc.stderr


# -- the data files -----------------------------------------------------------

def test_names_units_and_sources_are_legal():
    bench = spec.benchmark()
    names = [bench["configs"], bench["workloads"], bench["end_to_end"],
             bench["per_layer"]]
    for group in names:
        got = [e["name"] for e in group]
        assert len(got) == len(set(got))
        assert all(spec.NAME_RE.match(n) for n in got), got
    for w in bench["workloads"]:
        assert spec.NAME_RE.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert spec.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in spec.SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]


@pytest.mark.parametrize("name", CELLS)
def test_every_file_of_a_cell_loads_and_agrees(name):
    cell = spec.Cell(name)
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.workload["config"])
    assert cell.config["name"] == entry["name"]
    assert cell.config["reduced"] == entry["reduced"]
    assert cell.config["source"] == entry["source"]
    assert cell.config["guarantees"] and cell.config["control"]["set"]
    # every number the check compares has a limit of the cell's own
    assert set(cell.workload["check"]["limits"]) == set(
        check.Verdict({}).numbers)
    plan = cell.kind.plan(cell.params, 1, 5)
    assert len(plan["sizes"]) == len(plan["debug"]) > 0
    assert cell.workload["warm_buckets"] and cell.config["serve"]
    e2e = {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = cell.per_layer()
    assert layers
    for m in layers:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert callable(spec.reader(m["reader"]).read)


@pytest.mark.parametrize("which", ["config", "workload"])
def test_a_file_without_toy_sizes_cannot_be_rehearsed(monkeypatch, which):
    """A cell added without a ``rehearse`` object in each of its files is
    refused, not run at its real size."""
    load = spec._load

    def stripped(path):
        doc = load(path)
        if os.path.basename(os.path.dirname(path)) == which + "s":
            doc.pop("rehearse", None)
        return doc
    monkeypatch.setattr(spec, "_load", stripped)
    spec.Cell(CELLS[0])                       # the real size still loads
    with pytest.raises(spec.SpecError, match="rehearse"):
        spec.Cell(CELLS[0], rehearse=True)


def test_rehearsal_overrides_only_sizes():
    real = spec.Cell("bigann.bulk")
    toy = spec.Cell("bigann.bulk", rehearse=True)
    assert toy.config["num_data"] < real.config["num_data"]
    assert toy.config["engine"] == real.config["engine"]
    assert toy.config["serve"] == real.config["serve"]
    assert toy.kind_name == real.kind_name
    control = spec.Cell("bigann.bulk", control=True)
    assert control.config["engine"]["exact"] is False
    assert real.config["engine"]["exact"] is True
