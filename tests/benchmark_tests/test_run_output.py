"""What one run prints besides its metrics (PR 45), from one rehearsal
of ``bigann-10m.bulk`` (bfloat16 staging: the cell whose flagged queries
an untraced run could not count): the ``served`` event names the
batcher's cycles, its slow ones, the cycle's histograms with the longest
cycle, and the repairs; each number compared stands beside its limit as
the result line's last key and as the last lines on standard error."""

import json
import subprocess
import sys

import pytest

from benchmark import check, spec


@pytest.fixture(scope="module")
def run():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "bigann-10m.bulk", "--seed", "2147483999", "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=spec.ROOT,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    return lines, proc.stderr.strip().splitlines()


def test_the_served_event_names_a_stall_and_the_flagged_queries(run):
    served = next(l for l in run[0] if l.get("event") == "served")
    b = served["batcher"]
    assert b["cycles"] == served["batches"] > 0
    assert b["cycles_before_window"] == 0 and b["slow_cycles"] == []
    c = served["cycle_ms"]
    assert {"cycle", "own", "device_wait", "queue_wait"} <= set(c)
    assert c["cycle"]["count"] == b["cycles"]
    # (the histogram's quantiles are rounded to a microsecond)
    assert c["max_ms"] >= c["cycle"]["p50"] - 1e-3 > 0
    assert set(served["repairs"]) == {"flagged_queries", "device", "host"}
    assert served["repairs"]["flagged_queries"] \
        == served["repairs"]["device"] + served["repairs"]["host"]


def test_the_lines_last_key_holds_each_number_beside_its_limit(run):
    line = run[0][-1]
    assert list(line)[-1] == "checks" and line["correct"] is True
    limits = spec.Cell("bigann-10m.bulk").workload["check"]["limits"]
    assert set(line["checks"]) == set(check.Verdict({}).numbers)
    for name, c in line["checks"].items():
        assert c["limit"] == limits[name] and c["value"] <= c["limit"]
        assert c["compared"] > 0
    events = {l["number"]: l for l in run[0] if l.get("event") == "check"}
    assert {k: v["value"] for k, v in line["checks"].items()} \
        == {k: v["value"] for k, v in events.items()}


def test_standard_error_ends_with_the_same_numbers(run):
    tail = run[1][-3:]
    line = run[0][-1]
    for text, (name, c) in zip(tail, line["checks"].items()):
        assert text.startswith(f"check {name}: {c['value']!r} "
                               f"(limit {c['limit']!r}, ")


def test_a_number_that_is_not_finite_still_makes_a_json_line():
    v = check.Verdict({"checksum_mismatches": 0, "dist_rel_err_max": 1e-11,
                       "reference_plain_mismatches": 0})
    v.rel_err = float("inf")
    from benchmark import run as bench_run
    got = json.loads(json.dumps(bench_run.checks_of(v.lines())),
                     parse_constant=lambda s: pytest.fail(s))
    assert got["dist_rel_err_max"]["value"] == "inf"
