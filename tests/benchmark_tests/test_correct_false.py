"""``correct`` can come out false: the configuration's stated control, and
a timed path broken underneath the harness. Both drive the whole run
in-process at toy sizes (--rehearse skips only the look for a chip)."""

import json

import pytest

from benchmark import run as bench_run
from benchmark import spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def in_process(capsys, name, *extra, seed=7):
    argv = ["--workload", name, "--seed", str(seed), "--seconds", "1",
            "--trace", "0", "--rehearse", *extra]
    assert bench_run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", CELLS)
def test_the_control_comes_out_not_correct(capsys, name, seed):
    """The configuration's control — the program's own float32 path,
    without the float64 rescore — at a size a test run can hold."""
    line = in_process(capsys, name, "--control", seed=seed)
    assert line["control"] is True and line["correct"] is False
    assert line["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch,
                                                   name):
    """Every answer's label altered where it is produced: the call every
    micro-batch of every served cell goes through, ``solve_batch`` of
    whichever engine the daemon built (resident or mesh-resident). The
    rest of the run is the harness's own."""
    import dataclasses

    build = bench_run.build_daemon

    def build_broken(*args, **kwargs):
        daemon = build(*args, **kwargs)
        honest = daemon.engine.solve_batch

        def broken(q, ks):
            return [dataclasses.replace(
                r, predicted_label=r.predicted_label + 1)
                for r in honest(q, ks)]
        daemon.engine.solve_batch = broken
        return daemon
    monkeypatch.setattr(bench_run, "build_daemon", build_broken)
    line = in_process(capsys, name)
    assert line["correct"] is False and line["failed"] == 0
    monkeypatch.setattr(bench_run, "build_daemon", build)
    assert in_process(capsys, name)["correct"] is True
