"""Real-pipe CLI tests + adversarial duplicate fuzzing (round-1 review item 7).

The in-process CLI tests (test_cli.py) never exercise the actual
stdin-file-descriptor path or the >= 1 MB native-parser dispatch
(io/grammar._NATIVE_THRESHOLD_BYTES) end-to-end; these do, by spawning
``python -m dmlp_tpu`` exactly the way the grader would run
``./engine < input``.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.ring import RingEngine
from dmlp_tpu.engine.sharded import ShardedEngine
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.golden.reference import knn_golden
from dmlp_tpu.io.datagen import generate_input_text
from dmlp_tpu.io.grammar import KNNInput, Params, parse_input_text
from dmlp_tpu.io.report import format_results


def _run_cli_subprocess(text: str, *args: str):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "dmlp_tpu", *args],
        input=text.encode(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env=env, cwd=repo, timeout=240)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    return proc.stdout.decode(), proc.stderr.decode()


def test_subprocess_pipe_large_input_native_parser_path():
    """>= 1 MB stdin over a real pipe: parse_input must take the native C++
    tokenizer branch (grammar.py _NATIVE_THRESHOLD_BYTES) and the output
    must match the golden oracle byte for byte."""
    # ~2000 rows x 64 attrs x ~9 bytes/field ~= 1.2 MB
    text = generate_input_text(2000, 40, 64, 0.0, 100.0, 1, 16, 8, seed=5)
    assert len(text.encode()) >= (1 << 20)
    want = format_results(knn_golden(parse_input_text(text)))
    out, err = _run_cli_subprocess(text)
    assert out == want
    assert "Time taken:" in err


def test_subprocess_pipe_debug_mode():
    text = generate_input_text(120, 6, 4, 0.0, 9.0, 1, 5, 3, seed=8)
    want = format_results(knn_golden(parse_input_text(text)), debug=True)
    out, _ = _run_cli_subprocess(text, "--debug")
    assert out == want


def _duplicate_heavy_input(rng, n, q, a, num_labels, k_hi):
    """Adversarial instance: attributes drawn from a tiny value set, so
    distance ties (including whole tie groups straddling the candidate
    boundary) are everywhere."""
    vals = np.array([0.0, 1.0, 2.0])
    data = rng.choice(vals, size=(n, a))
    queries = rng.choice(vals, size=(q, a))
    labels = rng.integers(0, num_labels, n).astype(np.int32)
    ks = rng.integers(1, k_hi + 1, q).astype(np.int32)
    return KNNInput(Params(n, q, a), labels, np.asarray(data, np.float64),
                    ks, np.asarray(queries, np.float64))


@pytest.mark.parametrize("select", ["sort", "topk", "seg"])
def test_fuzz_duplicate_heavy_all_engines_vs_golden(select):
    """Seeded fuzz loop: 3 engines x this select on duplicate-heavy data
    must equal golden checksums exactly (the boundary repair is what makes
    the fast selects exact — asserted separately below)."""
    rng = np.random.default_rng(1234)
    for trial in range(4):
        inp = _duplicate_heavy_input(rng, n=128 + 32 * trial, q=12, a=3,
                                     num_labels=4, k_hi=10)
        want = [r.checksum() for r in knn_golden(inp)]
        engines = [
            SingleChipEngine(EngineConfig(select=select, data_block=32,
                                          query_block=8)),
            ShardedEngine(EngineConfig(mode="sharded", select=select,
                                       data_block=16, query_block=8)),
            RingEngine(EngineConfig(mode="ring", select=select,
                                    data_block=16, query_block=8)),
        ]
        for eng in engines:
            got = [r.checksum() for r in eng.run(inp)]
            assert got == want, (select, trial, type(eng).__name__)


def test_boundary_overflow_repair_actually_fires():
    """Statistical check on the repair machinery itself: on duplicate-heavy
    data the device tie-overflow flags must trigger for some queries (if
    they never fire, the 'repair' path is dead code and parity on the topk
    path is luck)."""
    from dmlp_tpu.engine import finalize as fin

    rng = np.random.default_rng(77)
    inp = _duplicate_heavy_input(rng, n=256, q=16, a=2, num_labels=3,
                                 k_hi=12)
    calls = []
    orig = fin.repair_boundary_overflow

    eng = SingleChipEngine(EngineConfig(select="topk", data_block=32,
                                        query_block=8))
    import dmlp_tpu.engine.single as single_mod
    try:
        single_mod.repair_boundary_overflow = \
            lambda *a, **kw: (calls.append(len(a[1])), orig(*a, **kw))[1]
        got = [r.checksum() for r in eng.run(inp)]
    finally:
        single_mod.repair_boundary_overflow = orig
    want = [r.checksum() for r in knn_golden(inp)]
    assert got == want
    assert calls and calls[0] > 0, "tie-overflow repair never fired"
