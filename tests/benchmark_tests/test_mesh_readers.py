"""The readers a four-chip cell needs, on a hand-built four-plane trace
(``benchmark/testdata/trace_mesh4.json``) with hand-computed answers:
kernel time and roofline share PER CHIP, the named merge program's
device time, the skew between the chips — and what the one-chip readers
make of the same trace."""

import copy
import json
import os

import pytest

from benchmark import kernel_cost, kernel_cost_mesh, spec
from benchmark.readers import (device_idle, device_skew, kernel_ms,
                               kernel_ms_mesh, kernel_roofline,
                               kernel_roofline_mesh, module_ops_ms)
from benchmark.run import Context

KERNEL = "%dmlp_topk_"
MESH = [4, 1]
MERGE = "jit_dmlp_mesh_merge"
PEAKS = {"flops_per_s": 2e14, "hbm_bytes_per_s": 8e11}
SHAPE = {"nq": 1024, "n": 4_000_000, "na": 128, "kc": 32, "itemsize": 4,
         "dispatches": 2}


def folds(ctx, n):
    """The window's fold spans, each saying it made ``n`` kernel calls:
    what the readers count a chip's micro-batches by."""
    ctx.window_pc = (0.0, 100.0)
    ctx.spans = [
        {"name": "fleet.solve_resident", "t0": 1.0, "t1": 1.1,
         "args": {"scheduled": n, "chunks": n}},
        {"name": "serve.solve_extract", "t0": 1.0, "t1": 1.1,
         "args": {"chunks": n}}]


@pytest.fixture()
def ctx():
    with open(os.path.join(spec.HERE, "testdata", "trace_mesh4.json")) as f:
        trace = json.load(f)
    c = Context()
    c.trace = trace
    folds(c, 2)
    c.scan_shape = dict(SHAPE)
    c.peaks = dict(PEAKS)
    return c


def test_kernel_ms_is_each_chips_own_time_over_its_own_batches(ctx):
    per = kernel_ms_mesh.per_plane_seconds(ctx, KERNEL, MESH)
    # two batches of two dispatches a chip: 4, 5, 4, 3 ms an event; the
    # fifth event of TPU:0 is cut by the window's end and left out
    assert per == {"/device:TPU:0": pytest.approx(8e-3),
                   "/device:TPU:1": pytest.approx(10e-3),
                   "/device:TPU:2": pytest.approx(8e-3),
                   "/device:TPU:3": pytest.approx(6e-3)}
    assert kernel_ms_mesh.read(ctx, KERNEL, MESH) == pytest.approx(8.0)


def test_a_chip_that_finished_one_batch_more_does_not_tilt_the_mean(ctx):
    """Two more whole events on TPU:3 (its third batch): its own mean
    stays 6 ms, the cell's 8 ms; the one-chip reader, which pools the
    planes, reads 70 ms / 9 batches."""
    for start in (80_000_000, 90_000_000):
        ev = copy.deepcopy(next(
            e for e in ctx.trace["events"]
            if e["plane"] == "/device:TPU:3" and KERNEL in e["name"]))
        ev["start_ns"] = start
        ctx.trace["events"].append(ev)
    assert kernel_ms_mesh.read(ctx, KERNEL, MESH) == pytest.approx(8.0)
    assert kernel_ms.read(ctx, KERNEL) == pytest.approx(70.0 / 9.0)


def test_per_chip_cost_by_hand():
    cost = kernel_cost_mesh.topk_scan_cost_per_chip(mesh=MESH, **SHAPE)
    assert cost["flops"] == 2.0 * 1024 * 1_000_000 * 128
    per_dispatch = 1024 * 128 * 4 + 2 * 1024 * 32 * 8
    assert cost["bytes"] == 1_000_000 * 128 * 4 + 2 * per_dispatch
    whole = kernel_cost.topk_scan_cost(**SHAPE)
    assert whole["flops"] == 4 * cost["flops"]
    # a query axis splits the panel and the lists, not the rows
    wide = kernel_cost_mesh.topk_scan_cost_per_chip(mesh=[2, 2], **SHAPE)
    assert wide["flops"] == cost["flops"]
    assert wide["bytes"] == 2_000_000 * 128 * 4 + 2 * (per_dispatch // 2)
    # rows that do not divide: the fullest chip's share
    odd = kernel_cost_mesh.topk_scan_cost_per_chip(
        mesh=[4, 1], **dict(SHAPE, n=4_000_001))
    assert odd["flops"] == 2.0 * 1024 * 1_000_001 * 128
    with pytest.raises(ValueError):
        kernel_cost_mesh.topk_scan_cost_per_chip(mesh=[0, 1], **SHAPE)


def test_roofline_is_a_chips_share_over_a_chips_peak(ctx):
    """2 x 1024 x 1e6 x 128 = 2.62144e11 operations over 2e14 /s is
    1.31072 ms (the bytes, 5.14e8 over 8e11 /s, take 0.64 ms: compute
    bound), over the 8 ms a chip's kernels took: 16.384%."""
    got = kernel_roofline_mesh.read(ctx, KERNEL, MESH)
    assert got == pytest.approx(16.384)
    assert ctx.notes["kernel_roofline_mesh_bound"] == "compute"


def test_the_one_chip_reader_reads_four_times_the_share(ctx):
    """The whole corpus's operations over ONE chip's peak and the
    per-chip time: 65.5% here; at a true 26% it would pass 100%."""
    one_chip = kernel_roofline.read(ctx, KERNEL)
    assert one_chip == pytest.approx(4 * 16.384)
    assert one_chip == pytest.approx(
        4 * kernel_roofline_mesh.read(ctx, KERNEL, MESH))


def test_merge_device_time_is_the_ops_inside_the_named_programs_runs(ctx):
    # a run: all-gather 0.1 + all-gather 0.1 + fusion 0.2 (0.4 on TPU:1)
    # = 0.4, 0.6, 0.4, 0.4 ms; the run the window's end cuts on TPU:0 and
    # the kernels outside any run are not counted
    assert module_ops_ms.read(ctx, MERGE) == pytest.approx(0.45)
    assert module_ops_ms.read(ctx, "jit_local") == pytest.approx(
        (4 + 5 + 4 + 3) / 4)
    assert module_ops_ms.read(ctx, "jit_no_such_program") is None


def test_skew_and_idle_by_hand(ctx):
    # busy: TPU:0 16 + 0.8 + 2 (the cut kernel, clipped) = 18.8 ms;
    # TPU:1 20 + 1.2; TPU:2 16 + 0.8; TPU:3 12 + 0.8; mean 17.4 of 100
    assert device_skew.read(ctx) == pytest.approx(
        100.0 * (21.2 - 12.8) / 17.4)
    assert device_idle.read(ctx) == pytest.approx(82.6)


def test_a_trace_on_fewer_chips_than_the_mesh_is_not_read(ctx):
    """The corpus on one device: kernel events on one plane only."""
    ctx.trace["events"] = [e for e in ctx.trace["events"]
                           if e["plane"] in ("/device:TPU:0", "/host:CPU")]
    assert kernel_ms_mesh.read(ctx, KERNEL, MESH) is None
    assert kernel_roofline_mesh.read(ctx, KERNEL, MESH) is None
    assert device_skew.read(ctx) is None        # one chip has no skew
    assert kernel_ms.read(ctx, KERNEL) == pytest.approx(8.0)


@pytest.mark.parametrize("reader,args", [
    (kernel_ms_mesh, {"pattern": KERNEL, "mesh": MESH}),
    (kernel_roofline_mesh, {"pattern": KERNEL, "mesh": MESH}),
    (module_ops_ms, {"module": MERGE}),
    (device_skew, {}),
], ids=["kernel_ms_mesh", "kernel_roofline_mesh", "module_ops_ms",
        "device_skew"])
def test_without_a_trace_a_reader_returns_nothing(reader, args):
    assert reader.read(Context(), **args) is None


def test_less_than_one_whole_batch_on_a_chip_is_not_read(ctx):
    folds(ctx, 164)
    assert kernel_ms_mesh.read(ctx, KERNEL, MESH) is None
    folds(ctx, 2)
    ctx.scan_shape = None
    assert kernel_roofline_mesh.read(ctx, KERNEL, MESH) is None


@pytest.mark.parametrize("name", [
    m["name"] for m in spec.benchmark()["per_layer"]
    if m["name"].endswith(".mesh")])
def test_every_mesh_metric_reads_the_mesh_cell_only(name):
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == name)
    assert entry["workloads"] == ["bigann-mesh4.bulk"]
    cell = spec.Cell("bigann-mesh4.bulk")
    doc = next(d for d in cell.per_layer() if d["name"] == name)
    assert callable(spec.reader(doc["reader"]).read)
    if "mesh" in doc["args"]:
        assert doc["args"]["mesh"] == cell.config["serve"]["mesh_shape"]
