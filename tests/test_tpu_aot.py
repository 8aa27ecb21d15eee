"""The kernels must COMPILE for the chip, checked without one.

libtpu is installed in the CPU container, and
``jax.experimental.topologies`` builds a compile-only "TPU v5 lite"
topology from it, so Mosaic and XLA:TPU can be asked to compile every
kernel variant the engine can dispatch at the bench config 4 shape
(input3: 200 000 x 10 000 x 64, k in [1, 32]) — the dispatch the engine
plans on a TPU: bf16 staging, kcap 144, qpad 10112, chunks of 51 200
rows, under the tiles it ran with until PR 47 (tile_q 64, ne 4, the
full-width loop alone: what ``fold`` 0 still compiles) and, further down,
under the two-level selection's. This catches what interpret mode cannot: PR 18's
bf16 first pass had only ever run interpreted and did not compile
("Bad lhs type": bf16 operands under an fp32 contract precision).
Compiling says nothing about running; chip_smoke.py does that.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.ops.pallas_extract import _extract_topk_jit

QPAD, CHUNK, NA, KCAP = 10112, 51200, 64, 144
VARIANT = dict(tile_q=64, tile_n=12800, ne=4, unroll=1)   # PR 46's, kcap > 64


@pytest.fixture(scope="module")
def v5e():
    """The four compile-only devices of a v5e 2x2 host."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu / no such topology in this build
        pytest.skip(f"libtpu cannot build the v5e:2x2 topology: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.mark.parametrize("carry", [False, True], ids=["fresh", "carry"])
@pytest.mark.parametrize("precision", ["f32", "bf16x3", "bf16"])
@pytest.mark.parametrize("mxu_gate", [True, False],
                         ids=["fused", "two_pass"])
def test_extract_kernel_compiles_for_v5e(v5e, mxu_gate, precision, carry):
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    lists = ((spec((QPAD, KCAP), jnp.float32),
              spec((QPAD, KCAP), jnp.int32)) if carry else (None, None))
    # the split form is float32 staging's (a bf16 value has no low
    # half); under the other two names the kernel is handed the
    # bfloat16 block itself and spends one pass (_dot_cross)
    staged = jnp.float32 if precision == "bf16x3" else jnp.bfloat16
    compiled = _extract_topk_jit.lower(
        spec((QPAD, NA), staged), spec((CHUNK, NA), staged),
        *lists, n_real=spec((), jnp.int32), id_base=spec((), jnp.int32),
        kc=KCAP, interpret=False, block_skip=True, mxu_gate=mxu_gate,
        floor=None, precision=precision, **VARIANT).compile()
    # The custom call's HLO instruction — what a device trace shows as
    # the event's name — states the kernel's form.
    name = ("dmlp_topk_fused" if mxu_gate else "dmlp_topk_extract") \
        + ("" if carry else "_fresh")
    call = next(line for line in compiled.as_text().splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert call.lstrip().removeprefix("ROOT ").startswith(f"%{name}."), \
        call[:120]


#: (q, attrs, staged dtype, first-pass form, kc, score): the dispatch
#: shapes this file holds, under the two-level selection (PR 47), by
#: the cell that runs them
TWO_LEVEL = {
    "bigann.bulk": (1024, 128, jnp.float32, "bf16x3", 32, "l2"),
    "bigann.steady": (128, 128, jnp.float32, "bf16x3", 32, "l2"),
    "bigann.fast_f32": (1024, 128, jnp.float32, "f32", 32, "l2"),
    "gist.bulk": (1024, 1024, jnp.float32, "bf16x3", 40, "l2"),
    "a2048": (1024, 2048, jnp.float32, "bf16x3", 32, "l2"),
    "a64": (1024, 64, jnp.bfloat16, "f32", 32, "l2"),
    "bigann-10m.bulk": (1024, 128, jnp.bfloat16, "f32", 120, "l2"),
    "text2image-10m.bulk": (1024, 256, jnp.bfloat16, "f32", 120, "ip"),
    "narrow100": (1024, 100, jnp.bfloat16, "f32", 120, "l2"),
    "batch.config4": (10112, 64, jnp.bfloat16, "f32", 144, "l2"),
    "bigann-gt1000.bulk": (1024, 128, jnp.float32, "bf16x3", 512, "l2"),
    "retry": (16, 128, jnp.bfloat16, "f32", 512, "l2"),
}


@pytest.mark.parametrize("carry", [False, True], ids=["fresh", "carry"])
@pytest.mark.parametrize("shape", TWO_LEVEL.values(), ids=TWO_LEVEL.keys())
def test_two_level_selection_compiles_for_v5e(v5e, shape, carry):
    """The kernel WITH the fold pass (slab slices of the distance
    scratch, two more VMEM scratches, a ``cond`` that hands two scalars
    out of the gated branch, two while loops) at every shape above:
    Mosaic takes the slicing at 12 800, 6 400 and 2 560 rows a block,
    under float32 and bfloat16 blocks, both scores, fresh and carried,
    at 32 to 512 slots.
    The engines' fold programs in this file compile the same kernel
    through ``_kernel_statics``; here the statics say so."""
    from dmlp_tpu.ops.pallas_extract import fold_slabs
    from dmlp_tpu.serve.engine import _kernel_statics
    q, attrs, staged, precision, kc, score = shape
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    kern = _kernel_statics("fused", kc, 51200, q, attrs, precision, False,
                           score)
    assert kern["fold"] == fold_slabs(kern["tile_n"]) >= 2
    assert kern["tile_n"] % (128 * kern["fold"]) == 0
    lists = ((spec((q, kc), jnp.float32), spec((q, kc), jnp.int32))
             if carry else (None, None))
    compiled = _extract_topk_jit.lower(
        spec((q, attrs), staged), spec((51200, attrs), staged), *lists,
        n_real=spec((), jnp.int32), id_base=spec((), jnp.int32),
        block_skip=True, floor=None, **kern).compile()
    assert len(_kernel_calls(compiled.as_text())) == 1


def test_the_one_level_kernel_still_compiles_for_v5e(v5e):
    """``fold`` 0 is what a block of one lane vector resolves to and
    what a caller may pass: PR 46's kernel, the full-width loop alone."""
    from dmlp_tpu.serve.engine import _kernel_statics
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    kern = _kernel_statics("fused", 32, 51200, 1024, 128, "bf16x3", False)
    _extract_topk_jit.lower(
        spec((1024, 128), jnp.float32), spec((51200, 128), jnp.float32),
        spec((1024, 32), jnp.float32), spec((1024, 32), jnp.int32),
        n_real=spec((), jnp.int32), id_base=spec((), jnp.int32),
        block_skip=True, floor=None, **{**kern, "fold": 0}).compile()


def test_the_compilers_schedule_has_a_narrow_round_a_fraction_of_a_wide_one(
        v5e):
    """``tools/kernel_bundles.py`` reads the VLIW schedule libtpu writes
    for the described chip: at ``bigann.steady``'s shape (the kernel of
    ``bigann.bulk``, one query tile) the visit is the slab loop, which
    holds every MXU slot, then the two extraction loops' rounds, and a
    round over the folded array is under a fifth of a full-width one: the
    1 / F the two-level selection rests on, less what a round spends on
    the lists whatever its width."""
    import re
    import subprocess
    import sys
    from pathlib import Path

    tool = Path(__file__).resolve().parents[1] / "tools/kernel_bundles.py"
    out = subprocess.run(
        [sys.executable, str(tool), "--shape", "bigann.steady"],
        capture_output=True, text=True, timeout=600).stdout
    if "wrote no schedule" in out:
        pytest.skip("this libtpu writes no schedule dump")
    assert "'fold': 10" in out.splitlines()[0]
    parts = re.findall(r"(\d+) bundles, (straight line|a round of a loop)"
                       r": MXU (\d+)", out)
    loops = [(int(n), int(mxu)) for n, what, mxu in parts
             if what == "a round of a loop"]
    # the slab loop holds the MXU's work (five slabs a round); the two
    # extraction loops none
    (slabs,) = [n for n, mxu in loops if mxu > 0]
    (wide, narrow) = [n for n, mxu in loops if mxu == 0]
    assert narrow * 5 < wide < slabs * 2


def test_split_check_kernel_compiles_for_v5e(v5e):
    """The kernel ops.pallas_extract.split_holds runs once a process
    before an engine names "bf16x3": Mosaic takes it, one bf16 tile in
    and out. (What the casts come out as, only the chip says: that is
    the check's whole point.)"""
    import functools
    from dmlp_tpu.ops.pallas_extract import _split_in_kernel
    sh = SingleDeviceSharding(v5e[0])
    compiled = jax.jit(functools.partial(
        _split_in_kernel, interpret=False)).lower(
        jax.ShapeDtypeStruct((16, 128), jnp.float32,
                             sharding=sh)).compile()
    assert "dmlp_split_check" in compiled.as_text()


#: the resident programs' two float32 forms: the exact engines' three
#: passes over split operands, and fast mode's one HIGHEST dot
F32_FORMS = pytest.mark.parametrize("precision", ["bf16x3", "f32"])


@F32_FORMS
def test_resident_fold_program_compiles_for_v5e(v5e, precision):
    """The serving engine's one-program fold at ``bigann.bulk``'s shape
    (q1024, 82 resident chunks of 51 200 x 128 float32, kcap 32): a
    while loop whose trip count is data, the ``_fresh`` kernel before
    it and the carried kernel in its body, so a device trace shows one
    kernel event a chunk (benchmark/readers/kernel_ms.py counts them)."""
    from dmlp_tpu.serve.engine import _fold_stack, _kernel_statics
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    compiled = _fold_stack.lower(
        spec((1024, 128), jnp.float32), spec((82, 51200, 128), jnp.float32),
        spec((82, 1, 51200), jnp.float32),
        spec((82,), jnp.int32), spec((), jnp.int32), spec((), jnp.int32),
        **_kernel_statics("fused", 32, 51200, 1024, 128, precision, False)
    ).compile()
    hlo = compiled.as_text()
    calls = [line.lstrip().removeprefix("ROOT ").split(" ", 1)[0]
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(c.rsplit(".", 1)[0] for c in calls) == [
        "%dmlp_topk_fused", "%dmlp_topk_fused_fresh"], calls
    assert " while(" in hlo
    # the stack is an argument, not a constant; the kernel reads its
    # blocks out of it and its rows' norms out of the array beside it
    # (16.8 MB: a dense row a chunk), so the program holds neither a
    # second corpus nor a copy of a chunk
    mem = compiled.memory_analysis()
    assert 82 * 51200 * (128 + 1) * 4 <= mem.argument_size_in_bytes \
        < 82 * 51200 * (128 + 1) * 4 + 2 ** 20
    _assert_fold_reads_the_stack(compiled, "f32", 82, 51200, 128)


def _result_type(line: str) -> str:
    """The result type of one HLO instruction line, a tuple's whole
    ("(f32[51200]{0}, bf16[51200,128]{1,0}) fusion(...)" gives both)."""
    rest = line.split(" = ", 1)[-1] if " = " in line else ""
    end = rest.find(") ") + 1 if rest.startswith("(") else rest.find(" ")
    return rest[:max(end, 0)]


def _assert_fold_reads_the_stack(compiled, dtype: str, chunks: int,
                                 rows: int, attrs: int):
    """The resident fold reads, it does not derive (PR 41): both kernel
    calls take the (chunks, rows, attrs) stack itself (operand 2) and
    the staged (chunks, 1, rows) norms (operand 4), no instruction of
    the program has a chunk's shape or a chunk's row count as its
    result (the pass that computed the norms and copied the chunk out
    of the stack, ``%multiply_reduce_fusion``, cannot come back
    unseen), and beside its arguments the program allocates under a
    quarter of one chunk."""
    from dmlp_tpu.obs.hlo import kernel_operand_types
    hlo = compiled.as_text()
    calls = kernel_operand_types(hlo)
    assert len(calls) == 2
    for operands in calls:
        assert len(operands) == 8, operands
        assert operands[2] == f"{dtype}[{chunks},{rows},{attrs}]", operands
        assert operands[4] == f"f32[{chunks},1,{rows}]", operands
    for shape in (f"[{rows},{attrs}]", f"[{rows}]", f"[1,{rows}]"):
        made = [line.strip()[:160] for line in hlo.splitlines()
                if f"{shape}{{" in _result_type(line)]
        assert made == [], made
    itemsize = {"f32": 4, "bf16": 2}[dtype]
    assert compiled.memory_analysis().temp_size_in_bytes \
        < rows * attrs * itemsize // 4


def _kernel_calls(hlo: str):
    return [line for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _materialized(hlo: str, shape: str):
    """The program's instructions of result type ``shape`` that are NOT
    inside a fused computation: arrays the program writes out (what a
    fusion computes on the way to its outputs is never stored)."""
    found, fused = [], False
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = "fused_computation" in line.split(" ", 1)[0]
        elif not fused and f" = {shape}" in line:
            found.append(line.strip()[:160])
    return found


def _assert_bf16_rows_reach_the_kernel(compiled, chunks: int, chunk: str):
    """The fold of a bfloat16 stack hands BOTH kernel calls the rows
    as bfloat16 (operand 2 of the custom call, the data BlockSpec's:
    the stack itself), the queries as float32, writes no float32 copy
    of a chunk, and allocates under a quarter of a chunk of bfloat16
    beside its arguments."""
    from dmlp_tpu.obs.hlo import kernel_operand_types
    rows, attrs = (int(x) for x in chunk.split(","))
    _assert_fold_reads_the_stack(compiled, "bf16", chunks, rows, attrs)
    for operands in kernel_operand_types(compiled.as_text()):
        assert operands[1].startswith("f32["), operands
    assert _materialized(compiled.as_text(), f"f32[{chunk}]") == []


@pytest.mark.parametrize(
    "staged,precision,chunks,attrs",
    [(jnp.bfloat16, "f32", 328, 128), (jnp.float32, "bf16x3", 82, 128),
     (jnp.float32, "bf16x3", 21, 1024)],
    ids=["bigann-10m_bf16", "bigann_f32", "gist_f32"])
def test_retry_fold_program_compiles_for_v5e(v5e, staged, precision, chunks,
                                             attrs):
    """The device retry of flagged queries (PR 38), which warm-up
    compiles with every extract bucket: ONE short query tile of 16 rows
    at the kernel's widest window, 512 slots, over the resident stack
    of each one-chip cell: the buckets' own fold program at one more
    shape. ``bigann-10m.bulk``'s 328 chunks of 51 200 x 128 under the
    default bfloat16 staging (whose rows reach the kernel as bfloat16
    and take ONE MXU pass, PR 39: no float32 copy of a chunk),
    ``bigann.bulk``'s 82 under float32, ``gist.bulk``'s 21 of 960
    attributes on 1024 lanes."""
    from dmlp_tpu.serve.engine import (ResidentEngine, _fold_stack,
                                       _kernel_statics)
    q, kc = ResidentEngine._RETRY_QUERIES, ResidentEngine._MP_KC
    assert (q, kc) == (16, 512)
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    statics = _kernel_statics("fused", kc, 51200, q, attrs, precision,
                              False)
    assert statics["tile_q"] >= q           # one tile: all 16 rows
    compiled = _fold_stack.lower(
        spec((q, attrs), staged), spec((chunks, 51200, attrs), staged),
        spec((chunks, 1, 51200), jnp.float32),
        spec((chunks,), jnp.int32), spec((), jnp.int32),
        spec((), jnp.int32), **statics).compile()
    hlo = compiled.as_text()
    assert len(_kernel_calls(hlo)) == 2
    assert " while(" in hlo
    if staged == jnp.bfloat16:
        _assert_bf16_rows_reach_the_kernel(compiled, chunks,
                                           f"51200,{attrs}")
    else:
        _assert_fold_reads_the_stack(compiled, "f32", chunks, 51200, attrs)
        assert _materialized(hlo, f"bf16[51200,{attrs}]") == []


def test_default_dtype_fold_program_compiles_for_v5e(v5e):
    """``bigann-10m.bulk``'s own fold: q1024 at the 120-slot window its
    bucket plans under bfloat16 staging (tile_q 128 since PR 47: a data
    block is read 8 times a chunk), over 328 resident chunks of 51 200 x 128
    bfloat16. The kernel streams the stack's rows as they are; a
    float32 query panel beside bfloat16 rows (no engine stages that)
    converts the chunk, and only the chunk, to float32 first."""
    from dmlp_tpu.engine.single import resolve_kcap
    from dmlp_tpu.serve.engine import _fold_stack, _kernel_statics
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    cfg = EngineConfig(dtype="bfloat16", use_pallas=True)
    assert cfg.resolve_precision("bfloat16") == "f32"
    kc = resolve_kcap(cfg, 16, "extract", 1 << 24, staging="bfloat16",
                      precision="f32", na=128)
    kern = _kernel_statics("fused", kc, 51200, 1024, 128, "f32", False)
    assert (kc, kern["tile_q"], kern["tile_n"], kern["ne"]) \
        == (120, 128, 12800, 4)

    def fold(q_dtype):
        return _fold_stack.lower(
            spec((1024, 128), q_dtype),
            spec((328, 51200, 128), jnp.bfloat16),
            spec((328, 1, 51200), jnp.float32), spec((328,), jnp.int32),
            spec((), jnp.int32), spec((), jnp.int32), **kern).compile()

    compiled = fold(jnp.bfloat16)
    hlo = compiled.as_text()
    assert sorted(c.lstrip().removeprefix("ROOT ").split(".", 1)[0]
                  for c in _kernel_calls(hlo)) == [
        "%dmlp_topk_fused", "%dmlp_topk_fused_fresh"]
    assert " while(" in hlo
    _assert_bf16_rows_reach_the_kernel(compiled, 328, "51200,128")
    assert compiled.memory_analysis().argument_size_in_bytes \
        >= 328 * 51200 * 128 * 2
    assert _materialized(fold(jnp.float32).as_text(), "f32[1,51200,128]")


def _narrow_fold(v5e, q: int, kc: int, attrs: int):
    """``_fold_stack`` at ``msturing-10m.bulk``'s stack (328 chunks of
    51 200 rows, bfloat16 staging) with rows ``attrs`` wide and ``q``
    query rows at ``kc`` slots, compiled for one v5e chip."""
    from dmlp_tpu.serve.engine import _fold_stack, _kernel_statics
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    kern = _kernel_statics("fused", kc, 51200, q, attrs, "f32", False)
    return _fold_stack.lower(
        spec((q, attrs), jnp.bfloat16),
        spec((328, 51200, attrs), jnp.bfloat16),
        spec((328, 1, 51200), jnp.float32), spec((328,), jnp.int32),
        spec((), jnp.int32), spec((), jnp.int32), **kern).compile()


@pytest.mark.parametrize("program", ["fold", "retry"])
def test_narrow_row_fold_programs_hold_no_copy_of_the_stack(v5e, program):
    """``msturing-10m.bulk``'s two programs (PR 40): the bucket's fold
    (q1024 at the 120-slot window bfloat16 staging plans from 100
    attributes) and the device retry (16 rows at 512 slots), over 328
    resident chunks of 51 200 rows at the width ``lane_padded(100)``
    gives. The kernel is handed the staged bfloat16 stack and beside
    its 4.3 GB the program allocates under a quarter of a chunk: left
    100 wide the compiler keeps the stack attribute-major and
    re-lays-out ALL of it every fold (the next test), and that copy
    must not come back unseen."""
    from dmlp_tpu.engine.single import resolve_kcap
    from dmlp_tpu.ops.pallas_extract import lane_padded
    from dmlp_tpu.serve.engine import ResidentEngine
    a = lane_padded(100)
    assert a == 128
    cfg = EngineConfig(dtype="bfloat16", use_pallas=True)
    kc = resolve_kcap(cfg, 16, "extract", 1 << 24, staging="bfloat16",
                      precision="f32", na=100)
    assert kc == 120
    q, kc = {"fold": (1024, kc),
             "retry": (ResidentEngine._RETRY_QUERIES,
                       ResidentEngine._MP_KC)}[program]
    compiled = _narrow_fold(v5e, q, kc, a)
    hlo = compiled.as_text()
    assert len(_kernel_calls(hlo)) == 2 and " while(" in hlo
    _assert_bf16_rows_reach_the_kernel(compiled, 328, f"51200,{a}")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 328 * 51200 * a * 2
    assert not any(" copy(" in line for line in _materialized(
        hlo, f"bf16[328,51200,{a}]"))


def test_a_stack_left_100_wide_is_relaid_out_in_full(v5e):
    """Why ``lane_padded`` reaches below one lane vector: the same fold
    with the stack left 100 wide. The compiler gives the argument an
    attribute-major layout and the program copies the whole stack into
    a row-major temporary the size of the 128-wide stack, every fold
    (12.4 ms of the chip's time a batch, measured in PR 40: PERF.md
    section 6). The day this stops holding, the rule can be looked at
    again."""
    compiled = _narrow_fold(v5e, 1024, 120, 100)
    copies = _materialized(compiled.as_text(), "bf16[328,51200,100]")
    assert any(" copy(" in line for line in copies), copies
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 328 * 51200 * 128 * 2
    assert mem.temp_size_in_bytes >= 328 * 51200 * 128 * 2


@pytest.mark.parametrize("program", ["fold", "retry"])
def test_inner_product_fold_programs_compile_for_v5e(v5e, program):
    """``text2image-10m.bulk``'s two programs (PR 46): the bucket's
    fold (q1024 at the window its own plan gives at k = 10: 120 slots,
    as the other bfloat16 cells) and the device retry (16 rows at 512
    slots), with ``score="ip"``, over the 198 resident chunks its
    stated capacity stages (196 hold rows), 51 200 rows a chunk,
    bfloat16, at the 256 lanes ``lane_padded(200)`` gives: the first
    row between one and two lane vectors. The kernel is handed the
    staged stack and the program allocates under a quarter of a chunk
    beside it, so a second stack or a re-layout at 200 wide shows here,
    on the CPU."""
    from dmlp_tpu.engine.single import resolve_kcap
    from dmlp_tpu.ops.pallas_extract import lane_padded
    from dmlp_tpu.serve.engine import (ResidentEngine, _fold_stack,
                                       _kernel_statics)
    a = lane_padded(200)
    assert a == 256
    cfg = EngineConfig(dtype="bfloat16", use_pallas=True, score="ip")
    kc = resolve_kcap(cfg, 16, "extract", 10092544, staging="bfloat16",
                      precision="f32", na=200)
    assert kc == 120
    q, kc = {"fold": (1024, kc),
             "retry": (ResidentEngine._RETRY_QUERIES,
                       ResidentEngine._MP_KC)}[program]
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    chunks = 198
    kern = _kernel_statics("fused", kc, 51200, q, a, "f32", False, "ip")
    assert kern["score"] == "ip" and kern["tile_n"] == 12800
    compiled = _fold_stack.lower(
        spec((q, a), jnp.bfloat16), spec((chunks, 51200, a), jnp.bfloat16),
        spec((chunks, 1, 51200), jnp.float32), spec((chunks,), jnp.int32),
        spec((), jnp.int32), spec((), jnp.int32), **kern).compile()
    hlo = compiled.as_text()
    assert len(_kernel_calls(hlo)) == 2 and " while(" in hlo
    _assert_bf16_rows_reach_the_kernel(compiled, chunks, f"51200,{a}")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= chunks * 51200 * a * 2
    assert not any(" copy(" in line for line in _materialized(
        hlo, f"bf16[{chunks},51200,{a}]"))


def test_cosine_fold_program_compiles_for_v5e(v5e):
    """``dbpedia-openai-1m.bulk``'s fold (PR 49): q1024, the 21 resident
    chunks of 51 200 rows the default 2^20 capacity stages, 1536
    attributes (twelve whole lane vectors: no padding), float32, the
    window its own plan gives at k = 10, under the engine's
    ``score="cosine"``, which reaches the kernel as its "ip" form
    (config.kernel_score: no kernel of its own and no further static)
    in the three-pass split every exact float32 engine runs. The data
    block follows the width, the kernel reads its blocks out of the
    6.6 GB stack itself and the program allocates less than one chunk
    (315 MB) beside it."""
    from dmlp_tpu.engine.single import resolve_kcap
    from dmlp_tpu.ops.pallas_extract import lane_padded
    from dmlp_tpu.serve.engine import _fold_stack, _kernel_statics
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    a = lane_padded(1536)
    assert a == 1536
    cfg = EngineConfig(dtype="float32", use_pallas=True, score="cosine")
    kc = resolve_kcap(cfg, 16, "extract", 1 << 20, staging="float32",
                      precision="bf16x3", na=1536)
    kern = _kernel_statics("fused", kc, 51200, 1024, a, "bf16x3", False,
                           cfg.score)
    assert kern["score"] == "ip" and kern["precision"] == "bf16x3"
    chunks = 21
    compiled = _fold_stack.lower(
        spec((1024, a), jnp.float32), spec((chunks, 51200, a), jnp.float32),
        spec((chunks, 1, 51200), jnp.float32), spec((chunks,), jnp.int32),
        spec((), jnp.int32), spec((), jnp.int32), **kern).compile()
    hlo = compiled.as_text()
    assert len(_kernel_calls(hlo)) == 2 and " while(" in hlo
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= chunks * 51200 * a * 4
    assert mem.temp_size_in_bytes < 51200 * a * 4
    _assert_fold_reads_the_stack(compiled, "f32", chunks, 51200, a)


@pytest.mark.parametrize("na", [100, 96])
@pytest.mark.parametrize("staged,precision", [
    (jnp.bfloat16, "f32"), (jnp.float32, "bf16x3")],
    ids=["bf16_one_pass", "bf16x3"])
def test_extract_kernel_compiles_for_v5e_at_narrow_rows(v5e, staged,
                                                        precision, na):
    """The kernel alone on rows that are not whole lanes below 128, as
    the batch engines and the mesh daemon still stage them (MS
    Turing's and MS SPACEV's 100, DEEP's 96): the one-pass form over
    bfloat16 blocks, and the split form, whose stacked operands
    (a contraction of 300 or 288) then join off a lane boundary."""
    from dmlp_tpu.serve.engine import _kernel_statics
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    kern = _kernel_statics("fused", 32, 51200, 1024, na, precision, False)
    assert kern["tile_n"] == 12800
    compiled = _extract_topk_jit.lower(
        spec((1024, na), staged), spec((51200, na), staged),
        spec((1024, 32), jnp.float32), spec((1024, 32), jnp.int32),
        n_real=spec((), jnp.int32), id_base=spec((), jnp.int32),
        block_skip=True, floor=None, **kern).compile()
    assert len(_kernel_calls(compiled.as_text())) == 1


@F32_FORMS
def test_wide_row_fold_program_compiles_for_v5e(v5e, precision):
    """The same program at ``gist.bulk``'s shape: q1024, 21 resident
    chunks of 51 200 rows of 960 attributes, staged on whole lanes
    (1024), kcap 40 (the window the width deepens:
    ``resolve_kcap``). The data block follows the width (6 400 rows: the
    parent's 12 800 priced 106 MB of VMEM and the bucket fell to the
    streaming select), Mosaic takes it, and beside the 4.4 GB stack the
    program allocates under a quarter of a chunk: the kernel's DMA
    reads its (6 400, 1 024) blocks out of the stack itself (PR 41;
    one chunk's copy until then, two when the stack was left 960
    wide)."""
    from dmlp_tpu.ops.pallas_extract import lane_padded
    from dmlp_tpu.serve.engine import _fold_stack, _kernel_statics
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    from dmlp_tpu.engine.single import resolve_kcap
    a = lane_padded(960)
    kc = resolve_kcap(EngineConfig(), 16, "extract", 1 << 20, na=960)
    assert kc == 40
    kern = _kernel_statics("fused", kc, 51200, 1024, a, precision, False)
    assert (a, kern["tile_q"], kern["tile_n"], kern["ne"]) \
        == (1024, 128, 6400, 2)
    compiled = _fold_stack.lower(
        spec((1024, a), jnp.float32), spec((21, 51200, a), jnp.float32),
        spec((21, 1, 51200), jnp.float32),
        spec((21,), jnp.int32), spec((), jnp.int32), spec((), jnp.int32),
        **kern).compile()
    hlo = compiled.as_text()
    calls = [line.lstrip().removeprefix("ROOT ").split(" ", 1)[0]
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(c.rsplit(".", 1)[0] for c in calls) == [
        "%dmlp_topk_fused", "%dmlp_topk_fused_fresh"], calls
    assert " while(" in hlo
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 21 * 51200 * a * 4
    _assert_fold_reads_the_stack(compiled, "f32", 21, 51200, a)


@F32_FORMS
def test_wide_k_programs_compile_for_v5e(v5e, precision):
    """The multipass driver's two kernel programs at
    ``bigann-gt1000.bulk``'s shape (q1024, 82 resident chunks of
    51 200 x 128 float32, k = 1000: bucket 1024, 1152 slots, 3 passes
    at ``kc`` 512, tiles tile_q 128 / ne 4 / fold 10 since PR 47): pass 1
    is the one-program
    fold with 512-wide lists, every further pass ONE kernel call over
    the stack as a (4 198 400, 128) array above a per-query floor. The
    reshape is free inside the program: the sweep allocates no
    temporary, so no second corpus: in the split form too, whose
    halves are made in the kernel, a visit."""
    from dmlp_tpu.engine.single import resolve_kcap, resolve_sweep_kernel
    from dmlp_tpu.serve.engine import (_fold_stack, _kernel_statics,
                                       _sweep_stack, k_bucket)
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    kcap = resolve_kcap(EngineConfig(dtype="float32"), k_bucket(1000),
                        "extract", 1 << 22, staging="float32", na=128)
    assert (k_bucket(1000), kcap, -(-kcap // 512)) == (1024, 1152, 3)
    rows = 82 * 51200
    _kern, impl = resolve_sweep_kernel(1024, rows, 128, 512,
                                       chunk_rows=51200, rung="fused")
    fold = _kernel_statics("fused", 512, 51200, 1024, 128, precision,
                           False)
    sweep = _kernel_statics(impl, 512, rows, 1024, 128, precision, False)
    assert impl == "fused" and fold == sweep
    assert (fold["tile_q"], fold["tile_n"], fold["ne"], fold["fold"]) \
        == (128, 12800, 4, 10)
    stack = spec((82, 51200, 128), jnp.float32)
    norms = spec((82, 1, 51200), jnp.float32)
    folded = _fold_stack.lower(
        spec((1024, 128), jnp.float32), stack, norms,
        spec((82,), jnp.int32), spec((), jnp.int32), spec((), jnp.int32),
        **fold).compile()
    assert " while(" in folded.as_text()
    _assert_fold_reads_the_stack(folded, "f32", 82, 51200, 128)
    swept = _sweep_stack.lower(
        spec((1024, 128), jnp.float32), stack, norms, spec((), jnp.int32),
        spec((1024, 1), jnp.float32), **sweep).compile()
    calls = [line.lstrip().removeprefix("ROOT ").split(" ", 1)[0]
             for line in swept.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert [c.rsplit(".", 1)[0] for c in calls] \
        == ["%dmlp_topk_fused_fresh"], calls
    # the sweep reads the stack and the staged norms as one array each
    # (free reshapes): no norm pass over the corpus, no temporary
    assert not any("reduce" in line.split(" = ", 1)[0]
                   for line in swept.as_text().splitlines()
                   if f"f32[{rows}]" in line or f"f32[1,{rows}]" in line)
    for compiled in (folded, swept):
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes >= rows * 128 * 4
        assert mem.temp_size_in_bytes < 51200 * 128 * 4 // 4


@F32_FORMS
@pytest.mark.parametrize("na, tile_n", [(2048, 2560), (960, 6400)])
def test_extract_kernel_compiles_for_v5e_at_wide_rows(v5e, precision, na,
                                                      tile_n):
    """The width rule's far end (ROADMAP R7): a 2048-attribute row tiles
    51 200 rows by 2 560, and Mosaic compiles the carried kernel; and a
    row that is not whole lanes, as the batch engines stage it (960: the
    split form's stacked operands then join off a lane boundary)."""
    from dmlp_tpu.serve.engine import _kernel_statics
    sh = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    kern = _kernel_statics("fused", 32, 51200, 1024, na, precision,
                           False)
    assert kern["tile_n"] == tile_n
    _extract_topk_jit.lower(
        spec((1024, na), jnp.float32), spec((51200, na), jnp.float32),
        spec((1024, 32), jnp.float32), spec((1024, 32), jnp.int32),
        n_real=spec((), jnp.int32), id_base=spec((), jnp.int32),
        block_skip=True, floor=None, **kern).compile()


@F32_FORMS
def test_mesh_resident_fold_program_compiles_for_v5e_4x1(v5e, precision):
    """The mesh daemon's one-program fold at ``bigann-mesh4.bulk``'s
    shape (q1024, a 4x1 mesh, 164 resident chunks of 4 x 51 200 x 128
    float32, kcap 32): the one-chip program's body under ``shard_map``.
    A shard's 4.3 GB of the stack is an argument and nothing near a
    chunk's size is allocated beside it; no collective runs in the fold
    (the merge is its own program); and the donated chunk update that
    builds and restages the stack, and writes the chunk's row norms
    beside it, aliases the whole of both, shard by shard."""
    from dmlp_tpu.fleet.mesh_engine import MeshResidentEngine
    from dmlp_tpu.parallel.mesh import DATA_AXIS, QUERY_AXIS
    from dmlp_tpu.serve.engine import _kernel_statics, _update_chunk
    mesh = Mesh(np.asarray(v5e).reshape(4, 1), (DATA_AXIS, QUERY_AXIS))
    t, cr, na = 164, 51200, 128

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    # (the constructor stages a corpus, which described devices cannot
    # hold: the program needs the mesh and the chunk plan only)
    eng = object.__new__(MeshResidentEngine)
    eng.mesh, eng._fns = mesh, {}
    eng._chunk_rows, eng._shard_rows = cr, 2 ** 23
    stack = spec((t, 4 * cr, na), jnp.float32, None, DATA_AXIS, None)
    norms = spec((t, 1, 4 * cr), jnp.float32, None, None, DATA_AXIS)
    compiled = eng._resident_fold_fn(
        _kernel_statics("fused", 32, cr, 1024, na, precision, False)).lower(
        spec((1024, na), jnp.float32, QUERY_AXIS, None), stack, norms,
        spec((t,), jnp.int32), spec((), jnp.int32), spec((), jnp.int32),
        spec((4, t), jnp.int32, DATA_AXIS, None)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_dmlp_mesh_fold")
    calls = [line.lstrip().removeprefix("ROOT ").split(" ", 1)[0]
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(c.rsplit(".", 1)[0] for c in calls) == [
        "%dmlp_topk_fused", "%dmlp_topk_fused_fresh"], calls
    assert " while(" in hlo
    assert "all-reduce" not in hlo and "all-gather" not in hlo
    mem = compiled.memory_analysis()          # of one device
    assert mem.argument_size_in_bytes >= t * cr * na * 4
    # a shard's program: its (t, cr, na) of the stack, its (t, 1, cr)
    # of the norms, nothing of a chunk's shape made, under a quarter of
    # a chunk allocated
    _assert_fold_reads_the_stack(compiled, "f32", t, cr, na)
    update = _update_chunk.lower(
        stack, norms, spec((4 * cr, na), jnp.float32, DATA_AXIS, None),
        spec((), jnp.int32)).compile()
    # stack and norms both written in place, each shard its own piece
    assert update.memory_analysis().alias_size_in_bytes \
        == t * cr * (na + 1) * 4
    assert [s.spec for s in update.output_shardings] \
        == [P(None, DATA_AXIS, None), P(None, None, DATA_AXIS)]
    assert "all-" not in update.as_text()


def test_mesh_cosine_fold_and_merge_compile_for_v5e_4x1(v5e):
    """``openai-c4-mesh4.bulk``'s two programs (PR 53): the mesh fold at
    q1024 over a 4x1 mesh's 14 resident chunks of 4 x 51 200 x 1536
    float32 (a stated capacity of 2 800 000 rows: 704 000 a shard),
    the 168-slot window its own plan gives at k = 100, under the
    engine's ``score="cosine"``, which reaches each shard's kernel as
    the "ip" form in the three-pass split, exactly as the one-chip fold
    takes it (``_kernel_statics``: no static the l2 program lacks); and
    the all-gather merge of the four shards' lists behind it, which has
    no score: it re-selects what the kernel emits. A shard's 4.4 GB of
    the stack is an argument and less than a chunk is allocated beside
    it; the fold holds no collective."""
    from dmlp_tpu.engine.single import plan_chunks, resolve_kcap
    from dmlp_tpu.fleet.mesh_engine import MeshResidentEngine
    from dmlp_tpu.parallel.mesh import DATA_AXIS, QUERY_AXIS
    from dmlp_tpu.serve.engine import _kernel_statics
    mesh = Mesh(np.asarray(v5e).reshape(4, 1), (DATA_AXIS, QUERY_AXIS))
    cfg = EngineConfig(mode="sharded", dtype="float32", use_pallas=True,
                       score="cosine")
    na = 1536
    sr, t, cr = plan_chunks(2800000 // 4, cfg.resolve_granule("extract"),
                            cfg.data_block)
    assert (sr, t, cr) == (704000, 14, 51200)
    kc = resolve_kcap(cfg, 128, "extract", 4 * sr, staging="float32",
                      precision="bf16x3", na=na)
    assert kc == 168

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    eng = object.__new__(MeshResidentEngine)
    eng.mesh, eng._fns = mesh, {}
    eng._chunk_rows, eng._shard_rows = cr, sr
    eng._merge_strategy = "allgather"
    kern = _kernel_statics("fused", kc, cr, 1024, na, "bf16x3", False,
                           cfg.score)
    assert kern["score"] == "ip"
    compiled = eng._resident_fold_fn(kern).lower(
        spec((1024, na), jnp.float32, QUERY_AXIS, None),
        spec((t, 4 * cr, na), jnp.float32, None, DATA_AXIS, None),
        spec((t, 1, 4 * cr), jnp.float32, None, None, DATA_AXIS),
        spec((t,), jnp.int32), spec((), jnp.int32), spec((), jnp.int32),
        spec((4, t), jnp.int32, DATA_AXIS, None)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_dmlp_mesh_fold")
    assert len(_kernel_calls(hlo)) == 2 and " while(" in hlo
    assert "all-reduce" not in hlo and "all-gather" not in hlo
    mem = compiled.memory_analysis()          # of one device
    assert mem.argument_size_in_bytes >= t * cr * na * 4
    assert mem.temp_size_in_bytes < cr * na * 4
    _assert_fold_reads_the_stack(compiled, "f32", t, cr, na)
    lists = spec((4, 1024, kc), jnp.float32, DATA_AXIS, QUERY_AXIS, None)
    merged = eng._chunk_merge_fn(kc).lower(
        lists, spec((4, 1024, kc), jnp.int32, DATA_AXIS, QUERY_AXIS, None),
        spec((4 * sr,), jnp.int32)).compile()
    text = merged.as_text()
    assert text.startswith("HloModule jit_dmlp_mesh_merge")
    assert "all-gather" in text


@pytest.mark.slow   # ~25 s, nearly all of it XLA:TPU compiling the merge sort
def test_sharded_engine_program_compiles_for_v5e_2x2(v5e):
    """The all-gather-merge mesh program, kernel inside shard_map, on
    the four-chip host's 2x2 mesh."""
    from dmlp_tpu.engine.sharded import ShardedEngine
    from dmlp_tpu.parallel.mesh import DATA_AXIS, QUERY_AXIS
    mesh = Mesh(np.asarray(v5e).reshape(2, 2), (DATA_AXIS, QUERY_AXIS))
    eng = ShardedEngine(EngineConfig(mode="sharded", use_pallas=True,
                                     dtype="bfloat16"), mesh=mesh)
    rows, qpad = 2 * 102400, 2 * 5056       # 2 data shards, 2 query shards

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    compiled = eng._fn(KCAP, 102400, "extract", "fused", "f32").lower(
        spec((rows, NA), jnp.bfloat16, DATA_AXIS, None),
        spec((rows,), jnp.int32, DATA_AXIS),
        spec((rows,), jnp.int32, DATA_AXIS),
        spec((qpad, NA), jnp.bfloat16, QUERY_AXIS, None)).compile()
    assert "all-gather" in compiled.as_text()
