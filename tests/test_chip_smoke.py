"""chip_smoke.py's orchestration, dry-run on CPU, and the fallbacks that
used to hide the device (ISSUE 21): each one now fails or says so.
"""

import json
import os
import subprocess
import sys

import pytest

from dmlp_tpu.config import EngineConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- chip_smoke.py -------------------------------------------------------------

def test_chip_smoke_dry_run_matches_oracle_and_refuses_cpu():
    """``--configs 1`` under JAX_PLATFORMS=cpu (inherited from conftest):
    every child's checksums match the captured reference output — no
    miss names them — and the smoke still exits non-zero, naming the
    platform, with no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--configs", "1"],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    out = proc.stdout
    assert proc.returncode == 1, out + proc.stderr
    assert "FAIL config 1 batch: platform is cpu" in out
    assert "FAIL config 1 serve: stats reply: platform is cpu" in out
    assert "FAIL config 1 batch: pallas_interpret is True" in out
    # the float32-staged child ran too, on the three-pass form
    assert "FAIL config 1 batch.f32: platform is cpu" in out
    assert "the first pass ran as" not in out
    # the bf16-staged fold ran too (interpreted, at its toy chunk):
    # only the platform and the interpreter are named
    assert "fold.bf16: wall" in out and "'chunk_rows': 1024" in out
    assert "mxu_passes {'bfloat16': 1, 'float32': 6}" in out
    assert "FAIL config 1 fold.bf16: platform is cpu" in out
    assert "FAIL config 1 fold.bf16: pallas_interpret is True" in out
    assert out.count("FAIL config 1 fold.bf16") == 2
    assert "MXU passes a visit" not in out
    # and the fold of 100-wide signed rows staged on 128 lanes: float64's
    # candidates in every list, nothing named but platform and interpreter
    assert "fold.narrow: wall" in out and "staged 128 wide" in out
    assert "missing from a list: 0" in out
    assert "FAIL config 1 fold.narrow: platform is cpu" in out
    assert "FAIL config 1 fold.narrow: pallas_interpret is True" in out
    assert out.count("FAIL config 1 fold.narrow") == 2
    # and the resident corpus ranked by inner product (PR 46): the golden
    # model's answers, the tied queries flagged and repaired
    assert "ip: wall" in out and "score ip" in out
    assert "FAIL config 1 ip: platform is cpu" in out
    assert "FAIL config 1 ip: pallas_interpret is True" in out
    assert out.count("FAIL config 1 ip") == 2
    # and the same engine ranked by cosine (PR 49): unit rows in float32,
    # the zero row, the zero query and the exact copies as the golden model
    assert "cosine: wall" in out and "score cosine" in out
    assert "first pass bf16x3" in out
    assert out.count("answers off the golden model's: 0") == 2
    assert "FAIL config 1 cosine: platform is cpu" in out
    assert "FAIL config 1 cosine: pallas_interpret is True" in out
    assert out.count("FAIL config 1 cosine") == 2
    # both children ran to the end and answered byte-identically
    assert "serve: 3 requests x 256 queries" in out
    assert "differ" not in out and "exited" not in out
    # ... on the path the smoke is about, from their own stamps
    for child in ("batch", "batch.f32", "serve: ready file",
                  "serve: stats reply"):
        for check in ("select is", "extract_impl is", "degrade rung is",
                      "degradations recorded", "retries recorded",
                      "kernel variant"):
            assert f"config 1 {child}: {check}" not in out
    assert "no backend initialised" in out
    assert not out.rstrip().endswith("}")       # no result line
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke",
                           "config1_batch.out")) as got, \
            open(os.path.join(REPO, "oracle_capture",
                              "oracle_1.out")) as want:
        assert got.read() == want.read()


def test_chip_smoke_needs_the_repo_beside_it(tmp_path):
    """Alone in a directory it exits non-zero and prints no result."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"],
                          capture_output=True, text=True, timeout=60,
                          cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""


def _load_chip_smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_stamp_names_every_miss():
    cs = _load_chip_smoke()
    good = {"platform": "tpu", "device_kind": "TPU v5 lite",
            "peak_flops_known": True, "device_count": 4, "mesh": [4, 1],
            "select": "extract", "extract_impl": "fused",
            "pallas_interpret": False, "degrade_rung": None,
            "kernel_variant": {"tile_q": 64},
            "degradations": [], "retries": 0,
            "corpus_rows_per_device": {str(d): 51200 for d in range(4)}}
    assert cs.check_stamp(good, [4, 1], False, 200000) == []
    one = dict(good, corpus_rows_per_device={"0": 204800, "1": 0,
                                             "2": 0, "3": 0})
    assert "not one equal share" in cs.check_stamp(
        one, [4, 1], False, 200000)[0]
    bad = dict(good, degrade_rung="host", degradations=["lowp->prune"],
               retries=2, extract_impl="extract")
    misses = " | ".join(cs.check_stamp(bad, [4, 1], True, 200000))
    for want in ("degrade rung is host", "degradations recorded",
                 "retries recorded: 2", "extract_impl is extract"):
        assert want in misses
    assert cs.check_stamp(None, None, True, 1) == [
        "child wrote no device stamp"]


@pytest.mark.parametrize("ran, named", [("bf16x3", False), ("f32", True),
                                        (None, True)])
def test_batch_f32_names_a_first_pass_that_did_not_split(
        tmp_path, monkeypatch, ran, named):
    """The float32-staged child's record names the form its first pass
    ran at; "bf16x3" there means the engine's split check passed on the
    device the child ran on. Anything else is a miss that says so."""
    cs = _load_chip_smoke()
    monkeypatch.setattr(cs, "LOGS", str(tmp_path))
    c = cs.Config(1)
    stamp = {"platform": "tpu", "device_kind": "TPU v5 lite",
             "peak_flops_known": True, "device_count": 1, "mesh": None,
             "select": "extract", "extract_impl": "fused",
             "pallas_interpret": False, "degrade_rung": "lowp",
             "kernel_variant": {"tile_q": 64},
             "degradations": [], "retries": 0}

    def child(argv, stdin_path, out_path, err_path):
        assert argv[-2:] == ["--dtype", "float32"]
        with open(out_path, "w") as f:
            f.writelines(c.oracle_lines)
        with open(err_path, "w") as f:
            f.write("RuntimeWarning: this backend's compiler ...\n")
        summary = {"event": "summary", "device": stamp}
        if ran is not None:
            summary["precision"] = {"active": ran}
        with open(argv[argv.index("--metrics") + 1], "w") as f:
            f.write(json.dumps(summary) + "\n")
        return 0, 1.0

    monkeypatch.setattr(cs, "run_child", child)
    bad, got = cs.phase_solve(c, "batch.f32", ["--dtype", "float32"],
                              None, ladder=True, form="bf16x3")
    assert got == stamp
    if named:
        (miss,) = bad
        assert f"the first pass ran as {ran!r}, not 'bf16x3'" in miss
        assert "split_holds" in miss and "RuntimeWarning" in miss
    else:
        assert bad == []


# -- the removed fallbacks -----------------------------------------------------

_FOLD_OK = {
    "device": {"platform": "tpu", "pallas_interpret": False},
    "mxu_passes": {"bfloat16": 1, "float32": 6},
    "kernel_data_operands": ["bf16[51200,128]", "bf16[51200,128]"],
    "ids_equal": True, "dists_equal": True, "dists_differ": 0,
    "dists_max_abs_diff": 0.0, "ids_valid": True}


@pytest.mark.parametrize("change, named", [
    ({}, None),
    ({"mxu_passes": {"bfloat16": 6, "float32": 6}}, "mxu_passes is"),
    ({"kernel_data_operands": ["f32[51200,128]", "bf16[51200,128]"]},
     "not the bfloat16 rows"),
    ({"kernel_data_operands": None}, "not the bfloat16 rows"),
    ({"dists_equal": False, "dists_differ": 3}, "NOT the HIGHEST dot's"),
    ({"ids_equal": False}, "NOT the HIGHEST dot's"),
    ({"ids_valid": False}, "outside the corpus"),
    ({"device": {"platform": "tpu", "pallas_interpret": True}},
     "pallas_interpret is True"),
    ({"device": {"platform": "cpu", "pallas_interpret": False},
      "kernel_data_operands": None, "dists_equal": False},
     "platform is cpu")])
def test_fold_bf16_names_every_miss(change, named):
    """The ``fold.bf16`` phase's verdict on its child's record: bfloat16
    rows in the compiled program's kernel calls, ``mxu_passes`` 1, and
    the float32 ``HIGHEST`` run's lists to the bit, on the chip."""
    cs = _load_chip_smoke()
    misses = cs.fold_misses(dict(_FOLD_OK, **change))
    if named is None:
        assert misses == []
    else:
        assert len(misses) == 1 and named in misses[0], misses


_NARROW_OK = {
    "device": {"platform": "tpu", "pallas_interpret": False},
    "a_pad": 128,
    "kernel_data_operands": ["bf16[51200,128]", "bf16[51200,128]"],
    "temp_bytes": 1052672, "chunk_bytes": 13107200, "sure_missing": 0,
    "ids_valid": True, "err_over_scale_vs_float64": 2e-7,
    "err_bound_over_scale": 7.3e-5}


@pytest.mark.parametrize("change, named", [
    ({}, None),
    ({"a_pad": 100}, "not on one whole lane vector"),
    ({"kernel_data_operands": ["bf16[51200,100]", "bf16[51200,100]"]},
     "not the bfloat16 rows on 128 lanes"),
    ({"kernel_data_operands": ["f32[51200,128]", "bf16[51200,128]"]},
     "not the bfloat16 rows on 128 lanes"),
    ({"temp_bytes": 4300468224}, "of the stack is back"),
    ({"temp_bytes": 13107200}, "of the stack is back"),
    ({"temp_bytes": None}, "of the stack is back"),
    ({"sure_missing": 3}, "3 of float64's nearest candidates"),
    ({"ids_valid": False}, "outside the corpus"),
    ({"err_over_scale_vs_float64": 1e-3}, "over the bound"),
    ({"device": {"platform": "tpu", "pallas_interpret": True}},
     "pallas_interpret is True"),
    ({"device": {"platform": "cpu", "pallas_interpret": False},
      "kernel_data_operands": None, "temp_bytes": None},
     "platform is cpu")])
def test_fold_narrow_names_every_miss(change, named):
    """The ``fold.narrow`` phase's verdict on its child's record: rows
    of 100 attributes on 128 lanes in the compiled program's kernel
    calls, no copy of the stack beside it, float64's candidates in
    every list."""
    cs = _load_chip_smoke()
    misses = cs.narrow_misses(dict(_NARROW_OK, **change))
    if named is None:
        assert misses == []
    else:
        assert len(misses) == 1 and named in misses[0], misses


_IP_OK = {
    "device": {"platform": "tpu", "pallas_interpret": False,
               "score": "ip", "select": "extract"},
    "staging": "bfloat16", "staged_attrs": 256,
    "paths": {"q128k16": "extract"}, "wrong": 0, "err_over_scale": 0.0,
    "tied_answers": 6,
    "repairs": {"flagged_queries": 40, "device": 34, "host": 6}}


@pytest.mark.parametrize("change, named", [
    ({}, None),
    ({"device": dict(_IP_OK["device"], score="l2")}, "not ip on the"),
    ({"device": dict(_IP_OK["device"], select="seg")}, "not ip on the"),
    ({"paths": {"q128k16": "stream"}}, "not extract"),
    ({"staging": "float32"}, "not bfloat16"),
    ({"staged_attrs": 200}, "not on two whole lane vectors"),
    ({"wrong": 2}, "2 answers differ from the golden"),
    ({"err_over_scale": 1e-7}, "off float64's by"),
    ({"tied_answers": 5}, "5 of 6 tied queries"),
    ({"repairs": {"flagged_queries": 0, "device": 0, "host": 0}},
     "were not flagged"),
    ({"device": dict(_IP_OK["device"], pallas_interpret=True)},
     "pallas_interpret is True")])
def test_ip_phase_names_every_miss(change, named):
    """The ``ip`` phase's verdict on its child's record: score ip on
    the extract path, the default dtype's bfloat16 on 256 lanes, every
    answer the golden model's, the tied queries flagged."""
    cs = _load_chip_smoke()
    misses = cs.ip_misses(dict(_IP_OK, **change))
    if named is None:
        assert misses == []
    else:
        assert len(misses) == 1 and named in misses[0], misses


_COSINE_OK = {
    "device": {"platform": "tpu", "pallas_interpret": False,
               "score": "cosine", "select": "extract"},
    "staging": "float32", "staged_attrs": 1536, "first_pass": "bf16x3",
    "paths": {"q128k16": "extract"}, "wrong": 0, "err": 0.0,
    "tied_answers": 4, "staged_norm_err": 3e-9, "staged_zero_row": 0.0,
    "zero_query_ids": list(range(11999, 11989, -1)),
    "repairs": {"flagged_queries": 5, "device": 4, "host": 1}}


@pytest.mark.parametrize("change, named", [
    ({}, None),
    ({"device": dict(_COSINE_OK["device"], score="ip")},
     "not cosine on the"),
    ({"device": dict(_COSINE_OK["device"], select="seg")},
     "not cosine on the"),
    ({"paths": {"q128k16": "stream"}}, "not extract"),
    ({"staging": "bfloat16"}, "not float32 rows"),
    ({"first_pass": "f32"}, "not float32 rows"),
    ({"staged_attrs": 2048}, "not on their own twelve lane vectors"),
    ({"staged_norm_err": 0.3}, "are not unit rows"),
    ({"staged_zero_row": 1.0}, "are not unit rows"),
    ({"wrong": 2}, "2 answers differ from the golden"),
    ({"err": 1e-7}, "off float64's by"),
    ({"tied_answers": 3}, "3 of 4 tied queries"),
    ({"zero_query_ids": list(range(10))}, "not the largest ids"),
    ({"repairs": {"flagged_queries": 0, "device": 0, "host": 0}},
     "were not flagged"),
    ({"device": dict(_COSINE_OK["device"], platform="cpu")},
     "platform is cpu")])
def test_cosine_phase_names_every_miss(change, named):
    """The ``cosine`` phase's verdict on its child's record: score
    cosine on the extract path, float32 unit rows on their own 1536
    lanes under the three-pass split, every answer the golden model's,
    the zero query's the largest ids, the tied queries flagged."""
    cs = _load_chip_smoke()
    misses = cs.cosine_misses(dict(_COSINE_OK, **change))
    if named is None:
        assert misses == []
    else:
        assert len(misses) == 1 and named in misses[0], misses


@pytest.mark.parametrize("dtype_args, passes, got, named", [
    ([], 1, 1, False), ([], 1, 6, True),
    (["--dtype", "float32"], 3, 3, False),
    (["--dtype", "float32"], 3, 6, True)])
def test_batch_phases_name_a_cross_term_of_other_passes(
        tmp_path, monkeypatch, dtype_args, passes, got, named):
    """A batch child on a chip stamps the MXU passes its kernel's cross
    term took a visit: 1 under the default (bfloat16) staging, 3 under
    float32; another count is a miss that says so."""
    cs = _load_chip_smoke()
    monkeypatch.setattr(cs, "LOGS", str(tmp_path))
    c = cs.Config(1)
    stamp = {"platform": "tpu", "device_kind": "TPU v5 lite",
             "peak_flops_known": True, "device_count": 1, "mesh": None,
             "select": "extract", "extract_impl": "fused",
             "pallas_interpret": False, "degrade_rung": "lowp",
             "kernel_variant": {"tile_q": 64, "mxu_passes": got},
             "degradations": [], "retries": 0}

    def child(argv, stdin_path, out_path, err_path):
        with open(out_path, "w") as f:
            f.writelines(c.oracle_lines)
        open(err_path, "w").close()
        with open(argv[argv.index("--metrics") + 1], "w") as f:
            f.write(json.dumps({"event": "summary", "device": stamp})
                    + "\n")
        return 0, 1.0

    monkeypatch.setattr(cs, "run_child", child)
    bad, _ = cs.phase_solve(c, "batch", dtype_args, None, ladder=True,
                            passes=passes)
    if named:
        assert bad == [f"the cross term took {got} MXU passes a visit, "
                       f"not {passes}"]
    else:
        assert bad == []


def test_pallas_interpret_is_chosen_from_the_platform(monkeypatch):
    """Interpret mode is a statement about the backend — cpu: yes,
    anything else: no — never the outcome of a trial compile."""
    import jax
    from dmlp_tpu.ops import pallas_distance as pd
    assert not hasattr(pd, "native_pallas_backend")
    assert pd.pallas_interpret() is True          # the suite runs on cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pd.pallas_interpret() is False


class MosaicError(Exception):
    """Same name as jax's Mosaic compile failure."""


@pytest.mark.parametrize("cls,msg", [
    (MosaicError, "INTERNAL: Mosaic failed to compile TPU kernel: Bad lhs "
                  "type"),
    (RuntimeError, "RESOURCE_EXHAUSTED: Mosaic failed to compile TPU "
                   "kernel: scoped allocation of 130.2M exceeds the vmem "
                   "limit"),
    (RuntimeError, "RESOURCE_EXHAUSTED: Ran out of memory in memory space "
                   "vmem. Used 130.00M of 128.00M"),
], ids=["mosaic", "mosaic_vmem_oom", "xla_vmem_oom"])
def test_kernel_compile_failure_is_fatal_and_never_degrades(
        monkeypatch, cls, msg):
    """A kernel that does not compile is not a capacity event: it
    classifies fatal (also when its VMEM overflow reads
    RESOURCE_EXHAUSTED), engine.run re-raises it from the first rung,
    and the streaming and host rungs are never reached."""
    from dmlp_tpu.engine.single import SingleChipEngine
    from dmlp_tpu.io.datagen import generate_input_text
    from dmlp_tpu.io.grammar import parse_input_text
    from dmlp_tpu.ops import pallas_fused
    from dmlp_tpu.resilience import degrade, retry, stats
    assert retry.classify(cls(msg)) == "fatal"
    # HBM exhaustion keeps the ladder
    assert retry.classify(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "17179869184 bytes.")) == "oom"

    def broken_kernel(*args, **kwargs):
        # a fresh exception per raise: one kept at module level would pin
        # its traceback's frames, and the staged device arrays in them
        raise cls(msg)

    monkeypatch.setattr(pallas_fused, "fused_topk", broken_kernel)
    monkeypatch.setattr(degrade, "_host_fallback", lambda inp: pytest.fail(
        "the host rung ran"))
    stats.reset()
    inp = parse_input_text(generate_input_text(9000, 16, 8, 0, 10, 1, 8, 4))
    eng = SingleChipEngine(EngineConfig(use_pallas=True))
    monkeypatch.setattr(eng, "_solve_pipelined", lambda inp: pytest.fail(
        "the streaming rung ran"))
    with pytest.raises(cls, match="vmem|Mosaic"):
        eng.run(inp)
    assert eng.last_degrade_rung == "lowp"
    assert stats.snapshot()["degradations"] == []


def test_resolve_dtype_lets_a_failing_backend_raise(monkeypatch):
    import jax

    def no_devices():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_devices)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        EngineConfig().resolve_dtype()


def test_explicit_mesh_takes_first_devices_or_is_an_error():
    import jax
    from dmlp_tpu.parallel.mesh import make_mesh
    mesh = make_mesh((2, 1))                      # 8 virtual devices here
    assert [d.id for d in mesh.devices.flat] == [
        d.id for d in jax.devices()[:2]]
    with pytest.raises(ValueError, match="needs 16 devices, have 8"):
        make_mesh((8, 2))


def test_launchers_leave_the_platform_to_the_caller(monkeypatch):
    """Replicas spawned by the fleet harness inherit JAX_PLATFORMS (and
    reach the chip on a chip host); nothing forces cpu into their env."""
    from dmlp_tpu.fleet.harness import _repo_env
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert _repo_env()["JAX_PLATFORMS"] == "tpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    assert "JAX_PLATFORMS" not in _repo_env()


def test_jax_compilation_cache_dir_is_never_set_in_code(monkeypatch,
                                                        tmp_path):
    """With $JAX_COMPILATION_CACHE_DIR set, enable_compile_cache leaves
    jax's directory alone (jax read the variable itself) — the flag does
    not move it."""
    import jax
    from dmlp_tpu.utils import compile_cache as cc
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append(name))
    monkeypatch.setenv(cc.JAX_ENV_VAR, str(tmp_path / "placed"))
    assert cc.enable_compile_cache(str(tmp_path / "flag")) \
        == str(tmp_path / "placed")
    assert "jax_compilation_cache_dir" not in updates
    assert cc.stats()["dir"] == str(tmp_path / "placed")
    # unplaced, on the cpu backend: the default stays off (stderr is the
    # engine's contract channel; see utils.compile_cache)
    monkeypatch.delenv(cc.JAX_ENV_VAR)
    del updates[:]
    assert cc.enable_compile_cache(None) is None
    assert "jax_compilation_cache_dir" not in updates
    # ... but a flag is a placement, honoured there too
    assert cc.enable_compile_cache(str(tmp_path / "flag")) \
        == str(tmp_path / "flag")
    assert updates.count("jax_compilation_cache_dir") == 1


def test_cli_summary_says_where_it_ran(tmp_path):
    """The device stamp rides the record that already exists (the
    daemon's ready file and stats reply: the dry run above)."""
    from dmlp_tpu.cli import main
    from dmlp_tpu.io.datagen import generate_input_text
    import io
    text = generate_input_text(9000, 16, 8, 0, 10, 1, 8, 4)
    metrics = tmp_path / "m.jsonl"
    rc = main(["--pallas", "--metrics", str(metrics)],
              stdin=io.StringIO(text), stdout=io.StringIO(),
              stderr=io.StringIO())
    assert rc == 0
    summary = [json.loads(ln) for ln in metrics.read_text().splitlines()
               if '"summary"' in ln][-1]
    stamp = summary["device"]
    assert stamp["platform"] == "cpu" and stamp["device_kind"] == "cpu"
    assert stamp["device_count"] == 8 and stamp["mesh"] is None
    assert stamp["peak_flops_known"] is False
    assert stamp["pallas_interpret"] is True
    assert stamp["select"] == "extract"
    assert stamp["extract_impl"] == "fused"
    assert stamp["degrade_rung"] == "lowp"
    assert stamp["degradations"] == [] and stamp["retries"] == 0
    assert set(stamp["kernel_variant"]) == {
        "tile_q", "ne", "unroll", "fold", "kc", "mxu_passes"}
    assert summary["parser"] == "python"
    assert set(summary["compile_cache"]) == {
        "dir", "requests", "hits", "misses", "backend_compile_ms"}


def test_mesh_solve_stamps_each_device_share():
    from dmlp_tpu.engine.sharded import ShardedEngine
    from dmlp_tpu.io.datagen import generate_input_text
    from dmlp_tpu.io.grammar import parse_input_text
    from dmlp_tpu.obs.run import device_stamp
    inp = parse_input_text(generate_input_text(400, 8, 4, 0, 10, 1, 4, 3))
    eng = ShardedEngine(EngineConfig(mode="sharded", mesh_shape=(4, 1)))
    eng.run(inp)
    stamp = device_stamp(eng)
    assert stamp["mesh"] == [4, 1] and stamp["degrade_rung"] is None
    rows = stamp["corpus_rows_per_device"]
    assert len(rows) == 4 and set(rows.values()) == {104}  # 100, padded
