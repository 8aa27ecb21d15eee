"""End-to-end multi-host contract run (round-1 review item 2).

Spawns real OS processes that form a jax.distributed CPU cluster (Gloo
collectives), each seeing its own virtual devices — the closest a single
host gets to the reference's 2-node mpirun operating mode
(run_bench.sh:82-84). Process 0's stdout must be byte-identical to the
golden oracle's.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.sharded import ShardedEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.datagen import generate_input_text
from dmlp_tpu.io.grammar import parse_input_text
from dmlp_tpu.io.report import format_results
from dmlp_tpu.parallel.mesh import make_mesh


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _spawn(input_path, port, nprocs, pid, devices_per_proc, extra=()):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_proc}")
    return subprocess.Popen(
        [sys.executable, "-m", "dmlp_tpu.distributed",
         "--input", str(input_path),
         "--coordinator", f"localhost:{port}",
         "--processes", str(nprocs), "--process-id", str(pid), *extra],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.parametrize("extra", [(), ("--select", "topk")])
def test_two_process_contract_run_matches_golden(tmp_path, extra):
    text = generate_input_text(211, 23, 5, -4, 4, 1, 12, 4, seed=9)
    path = tmp_path / "in.txt"
    path.write_text(text)
    want = format_results(knn_golden(parse_input_text(text)))

    port = _free_port()
    procs = [_spawn(path, port, 2, pid, devices_per_proc=2, extra=extra)
             for pid in (0, 1)]
    outs = [p.communicate(timeout=240) for p in procs]
    assert all(p.returncode == 0 for p in procs), \
        [o[1].decode()[-2000:] for o in outs]
    assert outs[0][0].decode() == want          # proc 0: canonical stdout
    assert outs[1][0].decode() == ""            # proc 1: silent
    assert "Time taken:" in outs[0][1].decode()  # contract stderr line


def test_process_slice_matches_addressable_shards():
    """process_slice must agree with what the sharding actually assigns
    (the ADVICE r1 item: no shard_bounds-style process/axis assumptions)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dmlp_tpu.parallel.distributed import process_slice

    mesh = make_mesh()  # (4, 2) over the 8 virtual devices
    npad = 64
    sh = NamedSharding(mesh, P("data", None))
    lo, hi = process_slice(sh, (npad, 3))
    # single process: the addressable block is the whole axis
    assert (lo, hi) == (0, npad)
    qsh = NamedSharding(mesh, P("query", None))
    assert process_slice(qsh, (16, 3)) == (0, 16)


def test_contract_run_single_process_matches_golden(tmp_path, capsys):
    """The same entry point, degenerate single-process form, all selects."""
    from dmlp_tpu.parallel.distributed import distributed_contract_run

    text = generate_input_text(97, 11, 4, 0, 9, 1, 30, 3, seed=4)
    path = tmp_path / "in.txt"
    path.write_text(text)
    inp = parse_input_text(text)
    want = [r.checksum() for r in knn_golden(inp)]

    for select, dtype in (("sort", "auto"), ("topk", "auto"),
                          ("seg", "auto"), ("topk", "bfloat16")):
        engine = ShardedEngine(
            EngineConfig(mode="sharded", select=select, query_block=8,
                         dtype=dtype),
            mesh=make_mesh())
        got = distributed_contract_run(str(path), engine,
                                       out=open(os.devnull, "w"),
                                       err=open(os.devnull, "w"))
        assert [r.checksum() for r in got] == want, (select, dtype)


def test_distributed_rescore_repairs_duplicate_ties(tmp_path):
    """Adversarial duplicate-heavy data: every point identical, so every
    shard's f32 tie boundary overflows and the per-shard f64 repair path
    must fire — and still match golden."""
    from dmlp_tpu.parallel.distributed import distributed_contract_run

    n, q, a = 96, 8, 3
    lines = [f"{n} {q} {a}"]
    for i in range(n):
        lines.append(" ".join([str(i % 4)] + ["1.000000"] * a))
    for _ in range(q):
        lines.append("Q 7 " + " ".join(["1.000000"] * a))
    text = "\n".join(lines) + "\n"
    path = tmp_path / "dup.txt"
    path.write_text(text)
    inp = parse_input_text(text)
    want = [r.checksum() for r in knn_golden(inp)]

    engine = ShardedEngine(
        EngineConfig(mode="sharded", select="topk", query_block=8,
                     data_block=16),
        mesh=make_mesh())
    got = distributed_contract_run(str(path), engine,
                                   out=open(os.devnull, "w"),
                                   err=open(os.devnull, "w"))
    assert [r.checksum() for r in got] == want


def test_two_process_tiny_input_empty_shard(tmp_path):
    """num_data small enough that one process's padded block holds no real
    rows at all — the all-sentinel shard path must not crash and the
    output must still match golden."""
    text = generate_input_text(10, 5, 3, -2, 2, 1, 10, 3, seed=2)
    path = tmp_path / "tiny.txt"
    path.write_text(text)
    want = format_results(knn_golden(parse_input_text(text)))

    port = _free_port()
    procs = [_spawn(path, port, 2, pid, devices_per_proc=4) for pid in (0, 1)]
    outs = [p.communicate(timeout=240) for p in procs]
    assert all(p.returncode == 0 for p in procs), \
        [o[1].decode()[-2000:] for o in outs]
    assert outs[0][0].decode() == want


def test_four_process_contract_run_matches_golden(tmp_path):
    """round-2 review item 6: beyond 2 processes. 4 procs x 2 devices form a
    (4, 2) mesh, one data-axis row per process."""
    text = generate_input_text(193, 17, 4, -3, 3, 1, 10, 4, seed=13)
    path = tmp_path / "in4.txt"
    path.write_text(text)
    want = format_results(knn_golden(parse_input_text(text)))

    port = _free_port()
    procs = [_spawn(path, port, 4, pid, devices_per_proc=2)
             for pid in (0, 1, 2, 3)]
    outs = [p.communicate(timeout=240) for p in procs]
    assert all(p.returncode == 0 for p in procs), \
        [o[1].decode()[-2000:] for o in outs]
    assert outs[0][0].decode() == want
    assert all(outs[pid][0].decode() == "" for pid in (1, 2, 3))


def test_two_process_four_devices_spans_data_rows(tmp_path):
    """round-2 review item 6: a process owning multiple data-axis rows — 2
    procs x 4 devices on the auto (4, 2) mesh, each process spans two
    rows of the data axis (the exact shape the r1 advisory warned
    shard_bounds-style arithmetic gets wrong)."""
    text = generate_input_text(301, 19, 5, -6, 6, 1, 14, 5, seed=31)
    path = tmp_path / "in24.txt"
    path.write_text(text)
    want = format_results(knn_golden(parse_input_text(text)))

    port = _free_port()
    procs = [_spawn(path, port, 2, pid, devices_per_proc=4)
             for pid in (0, 1)]
    outs = [p.communicate(timeout=240) for p in procs]
    assert all(p.returncode == 0 for p in procs), \
        [o[1].decode()[-2000:] for o in outs]
    assert outs[0][0].decode() == want


def test_process_slice_rejects_non_contiguous_block():
    """The documented error path (round-2 review item 6): a layout whose
    process-addressable shards leave a gap must raise, not feed wrong
    rows."""
    from dmlp_tpu.parallel.distributed import process_slice

    class GappySharding:
        def addressable_devices_indices_map(self, shape):
            return {"d0": (slice(0, 8), slice(None)),
                    "d1": (slice(16, 24), slice(None))}

    with pytest.raises(ValueError, match="not contiguous"):
        process_slice(GappySharding(), (32, 4))


def test_contract_run_hetk_routing_matches_golden(tmp_path):
    """Heterogeneous-k routing on the multi-host path: data placed once,
    bulk queries on the per-shard extraction kernel, wide-k outliers on
    the streaming select with their own query feed; proc-0 output must
    still be byte-identical to golden."""
    from dmlp_tpu.io.grammar import KNNInput, Params, format_input
    from dmlp_tpu.parallel.distributed import distributed_contract_run

    rng = np.random.default_rng(91)
    n, nq, na = 700, 12, 4
    data = rng.uniform(0, 40, (n, na))
    queries = rng.uniform(0, 40, (nq, na))
    labels = rng.integers(0, 5, n).astype(np.int32)
    ks = rng.integers(1, 25, nq).astype(np.int32)
    ks[3], ks[9] = 600, 700
    inp = parse_input_text(format_input(
        KNNInput(Params(n, nq, na), labels, data, ks, queries)))
    path = tmp_path / "hetk.txt"
    path.write_text(format_input(inp))
    want = [r.checksum() for r in knn_golden(inp)]

    engine = ShardedEngine(
        EngineConfig(mode="sharded", select="extract", use_pallas=True),
        mesh=make_mesh())
    got = distributed_contract_run(str(path), engine,
                                   out=open(os.devnull, "w"),
                                   err=open(os.devnull, "w"))
    assert [r.query_id for r in got] == list(range(nq))
    assert [r.checksum() for r in got] == want


def test_two_process_hetk_contract_run_matches_golden(tmp_path):
    """The same routed solve across a real 2-process Gloo cluster."""
    from dmlp_tpu.io.grammar import KNNInput, Params, format_input

    rng = np.random.default_rng(92)
    n, nq, na = 640, 8, 3
    data = rng.uniform(0, 30, (n, na))
    queries = rng.uniform(0, 30, (nq, na))
    labels = rng.integers(0, 4, n).astype(np.int32)
    ks = rng.integers(1, 20, nq).astype(np.int32)
    ks[5] = 640
    inp = parse_input_text(format_input(
        KNNInput(Params(n, nq, na), labels, data, ks, queries)))
    path = tmp_path / "hetk2.txt"
    path.write_text(format_input(inp))
    want = format_results(knn_golden(inp))

    port = _free_port()
    extra = ("--select", "extract", "--pallas")
    procs = [_spawn(path, port, 2, pid, 4, extra) for pid in range(2)]
    outs = [p.communicate(timeout=420) for p in procs]
    for p, (o, e) in zip(procs, outs):
        assert p.returncode == 0, e.decode()[-2000:]
    assert outs[0][0].decode() == want


def test_contract_run_all_wide_k_f32_staging(tmp_path, monkeypatch):
    """Multi-host path at ALL-wide k (every k > the kernel window): the
    wide-k staging policy (staging_for_k) must govern the contract run
    too — simulate TPU's bf16 auto-resolution and assert the engine is
    swapped to f32 staging inside the solve while output stays golden."""
    from dmlp_tpu.io.grammar import KNNInput, Params, format_input
    from dmlp_tpu.parallel.distributed import distributed_contract_run

    monkeypatch.setattr(EngineConfig, "resolve_dtype",
                        lambda self: "bfloat16" if self.dtype == "auto"
                        else self.dtype)
    rng = np.random.default_rng(95)
    n, nq, na = 1400, 6, 4
    data = rng.uniform(0, 30, (n, na))
    queries = rng.uniform(0, 30, (nq, na))
    labels = rng.integers(0, 4, n).astype(np.int32)
    ks = rng.integers(700, n + 1, nq).astype(np.int32)
    text = format_input(
        KNNInput(Params(n, nq, na), labels, data, ks, queries))
    inp = parse_input_text(text)
    path = tmp_path / "widek.txt"
    path.write_text(text)
    want = [r.checksum() for r in knn_golden(inp)]

    engine = ShardedEngine(EngineConfig(mode="sharded", dtype="auto"),
                           mesh=make_mesh())
    assert engine._staging == "bfloat16"
    seen = {}
    orig = ShardedEngine.solve_local_shards

    def spy(self, *a, **kw):
        seen["staging"] = self._staging
        return orig(self, *a, **kw)

    monkeypatch.setattr(ShardedEngine, "solve_local_shards", spy)
    with open(os.devnull, "w") as devnull:
        got = distributed_contract_run(str(path), engine,
                                       out=devnull, err=devnull)
    assert seen["staging"] == "float32"  # wide-k swap reached the solve
    assert engine._staging == "bfloat16"  # restored
    assert [r.checksum() for r in got] == want
