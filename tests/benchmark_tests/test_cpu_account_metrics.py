"""PR 51's twelve metrics of the host's CPU account: the batcher's cycle
split on and off the core (``serve.cycle``'s ``own_cpu_ms``,
``own_offcpu_ms``, ``own_sys_ms``, ``wait_cpu_ms``, ``minflt``,
``nivcsw``, ``cores_busy``) and the off-core time of four one-thread
spans (``offcpu_ms``), all read by ``span_arg``, a reader the benchmark
already had, over spans made by hand with hand-computed answers: the
MEAN wherever the argument is a difference of the thread's CPU clock
(the chip's host advances it in 10 ms ticks: one reading is a tick's
worth, the window's mean is not) or a count of 0 or 1, the median of
the two that are not (``minflt``, ``cores_busy``); and nothing of a
program whose spans lack the argument (the parent's: the line leaves
the metric out)."""

import pytest

from benchmark import spec
from benchmark.run import Context

WINDOW = (50.0, 100.0)
B8 = ["bigann.bulk", "bigann-mesh4.bulk", "gist.bulk", "bigann-gt1000.bulk",
      "bigann-10m.bulk", "msturing-10m.bulk", "text2image-10m.bulk",
      "dbpedia-openai-1m.bulk"]
B7 = [c for c in B8 if c != "bigann-mesh4.bulk"]

#: four cycles inside the window (one before it, which no metric reads):
#: own 56, 58, 70, 60 ms of which off the core 2, 4, 16, 3 (mean 6.25);
#: system time in ticks (0, 10, 0, 0: mean 2.5, median 0); a preemption
#: in one
CYCLES = [
    {"own_ms": 500.0, "own_cpu_ms": 100.0, "own_offcpu_ms": 400.0,
     "own_sys_ms": 90.0, "wait_cpu_ms": 9.0, "minflt": 9000,
     "nivcsw": 40, "cores_busy": 7.5},
    {"own_ms": 56.0, "own_cpu_ms": 54.0, "own_offcpu_ms": 2.0,
     "own_sys_ms": 0.0, "wait_cpu_ms": 0.02, "minflt": 12,
     "nivcsw": 0, "cores_busy": 2.2},
    {"own_ms": 58.0, "own_cpu_ms": 54.0, "own_offcpu_ms": 4.0,
     "own_sys_ms": 10.0, "wait_cpu_ms": 0.04, "minflt": 4100,
     "nivcsw": 0, "cores_busy": 2.4},
    {"own_ms": 70.0, "own_cpu_ms": 54.0, "own_offcpu_ms": 16.0,
     "own_sys_ms": 0.0, "wait_cpu_ms": 0.03, "minflt": 14,
     "nivcsw": 1, "cores_busy": 3.0},
    {"own_ms": 60.0, "own_cpu_ms": 57.0, "own_offcpu_ms": 3.0,
     "own_sys_ms": 0.0, "wait_cpu_ms": 0.05, "minflt": 16,
     "nivcsw": 0, "cores_busy": 2.0},
]
#: (span, offcpu_ms inside the window: three of each)
PHASES = {"single.rescore": [1.0, 12.0, 2.0],
          "serve.batch_deliver": [0.5, 0.1, 4.0],
          "serve.phase.parse": [30.0, 2.0, 8.0],
          "serve.phase.respond": [0.2, 9.0, 0.4]}

#: metric -> (span, argument, statistic, unit, layer, moves, cells,
#: the answer over the spans above)
METRICS = {
    "own_cpu_ms.bulk": ("serve.cycle", "own_cpu_ms", "mean", "ms",
                        "micro-batcher", "qps", B8, 54.75),
    "own_offcpu_ms.bulk": ("serve.cycle", "own_offcpu_ms", "mean", "ms",
                           "micro-batcher", "qps", B8, 6.25),
    "own_sys_ms.bulk": ("serve.cycle", "own_sys_ms", "mean", "ms",
                        "micro-batcher", "qps", B8, 2.5),
    "wait_cpu_ms.bulk": ("serve.cycle", "wait_cpu_ms", "mean", "ms",
                         "micro-batcher", "qps", B8, 0.035),
    "cycle_minflt.bulk": ("serve.cycle", "minflt", "median", "faults",
                          "micro-batcher", "qps", B8, 15.0),
    "cycle_nivcsw.bulk": ("serve.cycle", "nivcsw", "mean", "switches",
                          "micro-batcher", "qps", B8, 0.25),
    "cores_busy.bulk": ("serve.cycle", "cores_busy", "median", "cores",
                        "micro-batcher", "qps", B8, 2.3),
    "own_offcpu_ms.steady": ("serve.cycle", "own_offcpu_ms", "mean", "ms",
                             "micro-batcher", "p50_ms", ["bigann.steady"],
                             6.25),
    "rescore_offcpu_ms.bulk": ("single.rescore", "offcpu_ms", "mean", "ms",
                               "host finalize", "qps", B7, 5.0),
    "deliver_offcpu_ms.bulk": ("serve.batch_deliver", "offcpu_ms", "mean",
                               "ms", "micro-batcher", "qps", B8, 4.6 / 3),
    "parse_offcpu_ms.bulk": ("serve.phase.parse", "offcpu_ms", "mean",
                             "ms", "front end", "qps", B8, 40.0 / 3),
    "respond_offcpu_ms.bulk": ("serve.phase.respond", "offcpu_ms", "mean",
                               "ms", "front end", "qps", B8, 3.2),
}


def spans(with_account: bool = True):
    """The window's spans; without the account, what the parent's
    program emits: the same spans, none of PR 51's arguments."""
    out = []
    for i, args in enumerate(CYCLES):
        t0 = 10.0 if i == 0 else 50.0 + i
        keep = args if with_account else {"own_ms": args["own_ms"]}
        out.append({"name": "serve.cycle", "t0": t0, "t1": t0 + 0.06,
                    "args": dict(keep, batch=i)})
    for name, values in PHASES.items():
        for i, off in enumerate(values):
            args = {"batch": i + 1}
            if with_account:
                args.update(cpu_ms=5.0, offcpu_ms=off)
            out.append({"name": name, "t0": 51.0 + i, "t1": 51.02 + i,
                        "args": args})
    return out


def ctx_of(span_list):
    ctx = Context()
    ctx.window_pc = WINDOW
    ctx.spans = span_list
    return ctx


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_document_reads_its_argument_as_the_table_says(name):
    span, arg, stat, unit, layer, moves, cells, answer = METRICS[name]
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": moves, "workloads": cells}
    for cell in cells:
        doc = next(d for d in spec.Cell(cell).per_layer()
                   if d["name"] == name)
        assert doc["reader"] == "span_arg" and doc["what"]
        assert doc["args"] == {"name": span, "arg": arg, "stat": stat}
        read = spec.reader(doc["reader"]).read
        assert read(ctx_of(spans()), **doc["args"]) == pytest.approx(answer)
        # the parent's spans lack the argument: nothing to read
        assert read(ctx_of(spans(with_account=False)), **doc["args"]) is None
        assert read(ctx_of([]), **doc["args"]) is None


def test_the_cells_are_those_of_the_metrics_they_split():
    """Each list is the list of the metric whose time it splits, or
    wider by the mesh cell where that cell's line carries the span."""
    by_name = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    assert by_name["host_own_ms.bulk"]["workloads"] == B8
    assert by_name["parse_ms.bulk"]["workloads"] == B8
    assert set(by_name["rescore_ms.bulk"]["workloads"]) \
        | set(by_name["rescore_ms.ip"]["workloads"]) == set(B7)
    assert by_name["host_own_ms.steady"]["workloads"] == ["bigann.steady"]
    new = [m["name"] for m in spec.benchmark()["per_layer"]][-12:]
    assert sorted(new) == sorted(METRICS)       # appended, after the rest
