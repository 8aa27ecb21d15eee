"""A configuration names its reference and its generator (PR 45), and
the harness runs on them: a rehearsal-size cell whose configuration
names a test-only reference and a test-only generator (``seam/``: the
benchmark itself gains neither) goes through ``benchmark.run``'s own
code to ``correct: true``; the same run held to the WRONG reference
comes out ``correct: false``; an unknown name is a ``SpecError`` that
lists what is there; a configuration that names neither gets what the
parent ran."""

import importlib
import json
import os
import sys

import numpy as np
import pytest

import benchmark.generators
import benchmark.references
from benchmark import check, data, reference, spec
from benchmark import run as bench_run

SEAM = os.path.join(os.path.dirname(os.path.abspath(__file__)), "seam")
CONFIG = os.path.relpath(os.path.join(SEAM, "configs",
                                      "seam-clustered.json"), spec.ROOT)
CELL = "seam.bulk"


@pytest.fixture()
def seam(monkeypatch):
    """The test-only modules on the two packages' paths, and the
    test-only cell in what ``spec`` loads; ``seam(reference=...)`` names
    another reference in the configuration."""
    for pkg, sub in ((benchmark.references, "references"),
                     (benchmark.generators, "generators")):
        monkeypatch.setattr(pkg, "__path__",
                            [*pkg.__path__, os.path.join(SEAM, sub)])
    monkeypatch.setattr(bench_run.LoadGen, "COMMAND", [
        sys.executable, os.path.join(SEAM, "loadgen_shim.py")])
    load = spec._load
    over = {}

    def loaded(path):
        if path == os.path.join(spec.HERE, "workloads", f"{CELL}.json"):
            return load(os.path.join(SEAM, "workloads", f"{CELL}.json"))
        doc = load(path)
        if path == os.path.join(spec.ROOT, "BENCHMARK.json"):
            doc["configs"].append({
                "name": "seam-clustered", "source": "test-only",
                "file": CONFIG, "reduced": [], "why": "test-only"})
            doc["workloads"].append({
                "name": CELL, "config": "seam-clustered",
                "traffic": "bulk", "chips": 1, "why": "test-only"})
            for m in doc["end_to_end"]:
                if m["name"] == "qps":
                    m["workloads"].append(CELL)
        elif path == os.path.join(spec.ROOT, CONFIG):
            doc["modules"].update(over)
        return doc
    monkeypatch.setattr(spec, "_load", loaded)
    yield over
    for name in [n for n in sys.modules
                 if n.startswith(("benchmark.references.seam_",
                                  "benchmark.generators.seam_"))]:
        del sys.modules[name]


def run_cell(capsys, seed=2147483777):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "1",
            "--trace", "0", "--rehearse"]
    assert bench_run.main(argv) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out


def test_a_cell_on_a_named_reference_and_generator_is_correct(seam, capsys):
    line, out = run_cell(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # every number compared beside its limit: the line's last key and
    # the last lines on standard error
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == set(check.Verdict({}).numbers)
    assert all(c["compared"] > 0 for c in line["checks"].values())
    tail = out.err.strip().splitlines()[-3:]
    assert [t.split()[1].rstrip(":") for t in tail] == list(line["checks"])


def test_the_same_run_held_to_the_wrong_reference_is_not_correct(seam,
                                                                 capsys):
    seam["reference"] = "seam_l1"
    line, _ = run_cell(capsys)
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["checksum_mismatches"]["value"] > 0
    assert line["checks"]["reference_plain_mismatches"]["value"] == 0


def test_the_cell_carries_the_modules_its_configuration_names(seam):
    cell = spec.Cell(CELL, rehearse=True)
    assert cell.reference.__name__ == "benchmark.references.seam_l2"
    assert spec.Cell("bigann.bulk").reference is reference
    labels, rows = data.corpus(cell.config, 5)
    # clustered: every row within a few widths of one of the centres
    gen = spec.generator("seam_clustered")
    mid = gen.centres(cell.config["values"], rows.shape[1], 5)
    near = np.sqrt(((rows[:, None, :] - mid[None]) ** 2).sum(-1)).min(1)
    assert near.max() < 6.0 * 4 * np.sqrt(rows.shape[1])
    assert np.array_equal(rows.astype(np.float32).astype(np.float64), rows)
    # the load generator's process and the check draw the same queries
    q = data.request_queries(cell.config, 5, 3, 8)
    assert np.array_equal(q, data.request_queries(cell.config, 5, 3, 8))
    assert not np.array_equal(q, data.request_queries(cell.config, 6, 3, 8))


def test_the_same_seed_gives_the_same_rows_whatever_the_fill_threads(
        seam, monkeypatch):
    cfg = spec.Cell(CELL, rehearse=True).config
    want = data.corpus(cfg, 11)
    for threads in (1, 3):
        monkeypatch.setattr(data, "_FILL_THREADS", threads)
        got = data.corpus(cfg, 11)
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])


@pytest.mark.parametrize("key,find", [("reference", spec.reference),
                                      ("generator", spec.generator)])
def test_an_unknown_name_is_a_spec_error_that_lists_what_is_there(
        seam, key, find):
    with pytest.raises(spec.SpecError, match=f"no {key} 'no_such'.*seam_"):
        find("no_such")
    with pytest.raises(spec.SpecError, match=f"illegal {key}"):
        find("../escape")
    seam[key] = "no_such"
    with pytest.raises(spec.SpecError, match="no_such"):
        spec.Cell(CELL, rehearse=True)


def test_the_benchmark_itself_holds_no_named_module_yet():
    """Each comes with the configuration that needs it: this PR adds the
    seam and proves it with modules that live under tests/ only."""
    for pkg in (benchmark.references, benchmark.generators):
        here = os.path.dirname(pkg.__file__)
        assert sorted(f for f in os.listdir(here)
                      if f.endswith(".py")) == ["__init__.py"]
    for cfg in spec.benchmark()["configs"]:
        doc = spec._load(os.path.join(spec.ROOT, cfg["file"]))
        assert "modules" not in doc


def test_the_denominator_of_dist_rel_err_max_is_the_references(seam):
    """A served distance off by 1e-3 beside a reference distance of
    1e-2: a tenth by the default scale, a thousandth by ``seam_l2``'s
    (absolute under 1)."""
    l2 = spec.reference("seam_l2")
    rows = np.array([[0.0, 0.0], [0.1, 0.0], [5.0, 5.0]])
    ref = l2.knn_plain(rows, np.array([1, 1, 0]), np.array([[0.0, 0.0]]),
                       [2])[0]
    assert ref.dists.tolist() == [0.0, pytest.approx(0.01)]
    served = ref.dists + np.array([0.0, 1e-3])
    limits = {"checksum_mismatches": 0, "dist_rel_err_max": 5e-3,
              "reference_plain_mismatches": 0}
    for scale, err, ok in ((check.dist_scale, 0.1, False),
                           (l2.dist_scale, 1e-3, True)):
        v = check.Verdict(limits, scale)
        v.add_plain(ref, ref)
        v.add(ref, ref.label, ref.checksum, served)
        assert v.rel_err == pytest.approx(err) and v.correct is ok
