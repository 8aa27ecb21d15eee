"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the exact-search main path once through the entry points a user
calls, at the full width of bench config 4 (``input3.in``: 200 000 x
10 000 x 64, k in [1, 32], exact mode, ``--pallas``), and checks every
answer byte for byte against the captured output of the reference's own
binary (``oracle_capture/oracle_4.out``):

  generate  python -m dmlp_tpu.io.datagen            sha256 pinned
  batch     python -m dmlp_tpu --pallas ...          cmp oracle
  batch.f32 ... --dtype float32                      cmp oracle; the first
                                                     pass ran as "bf16x3"
  fold.bf16 python chip_smoke.py --fold-child        one resident fold of
                                                     bf16 rows: one MXU
                                                     pass, HIGHEST's lists
  fold.narrow python chip_smoke.py --narrow-child    one resident fold of
                                                     100-wide signed bf16
                                                     rows staged on 128
                                                     lanes: float64's
                                                     candidates
  ip        python chip_smoke.py --ip-child          a resident corpus
                                                     ranked by inner
                                                     product: the golden
                                                     model's answers, ties
                                                     included
  cosine    python chip_smoke.py --cosine-child      the same ranked by
                                                     cosine: unit rows on
                                                     the device, float32,
                                                     zero rows and exact
                                                     copies included
  serve     python -m dmlp_tpu.serve --pallas ...    queries over TCP,
                                                     stats, SIGTERM drain
  mesh      --mode sharded|ring --mesh 4,1           only on >= 4 chips
  mesh serve  python -m dmlp_tpu.serve --mesh 4x1    the same requests, the
                                                     corpus sharded

A chip serves one process at a time, so this parent never initialises a
JAX backend: every phase that needs the chip is one child, run to its end
before the next starts, and everything checked about the device comes
from what the child itself wrote (the device stamp in the ``--metrics``
summary, the daemon's ready file and ``stats`` reply). Any miss — a child
on another platform, a Pallas kernel in interpret mode, a degrade-ladder
rung below the first, a retry, a mesh that left the corpus on one
device, a float32 first pass that did not take the three-pass form — is
a non-zero exit naming the check, and no result line.

``batch.f32`` stages float32, as the served cells of the benchmark do
(the default on a chip stages bfloat16, which has no low half to split).
An engine names "bf16x3" only after ops.pallas_extract.split_holds has
run the split through Mosaic on THIS chip and found |x - hi - lo| <=
2^-17 |x|; so the form in the child's record is that check's answer, and
a compiler that starts folding the casts (as XLA:TPU does outside a
kernel) fails here instead of running a one-pass error under the
three-pass bound, which no checksum shows.

``fold.bf16`` is the same kind of proof for rows staged in bfloat16 (the
default on a chip). The kernel is handed the bf16 block itself and
contracts it in ONE MXU pass, on the argument that bf16 x bf16 products
are exact in float32 and the MXU accumulates in float32, so the pass
drops nothing of what the six-pass ``HIGHEST`` dot computes from the
same values, and widens no bound for it (LOWP_COEF["f32"] = 0). The
child folds one seeded stack through the serving engine's own program
twice, as bfloat16 and as the same values in float32 (the ``HIGHEST``
dot), and fails unless the compiled program hands the kernel bfloat16
rows, ``mxu_passes`` reads 1 and the two runs' lists are equal to the
bit: a chip whose single pass rounded differently would show here and
nowhere on a CPU, whose two dots are one float32 loop.

``fold.narrow`` (PR 40) holds the chip to rows that are not whole lanes:
signed reals of 100 attributes (MS Turing-ANNS's width) rounded to
bfloat16, staged as the serving engine stages them (zero-padded to
``lane_padded(100)`` = 128) and folded by the same program. It fails
unless the compiled program hands the kernel the padded bfloat16 rows
(since PR 41 the resident stack itself, read by a prefetched chunk
index, with the rows' staged norms beside it) and allocates less than a
quarter of a chunk beside the stack (left 100 wide the compiler
re-lays-out the whole stack every fold), every list holds the
float64 brute force's nearest candidates over the same values, and the
listed distances are float64's within the engine's own float32 bound.

``ip`` (PR 46) holds the chip to the second score: one child builds the
serving engine (``ResidentEngine``, ``score="ip"``, the default staging
dtype, 200 attributes staged on 256 lanes) over seeded reals with a block
of integer rows a hundred copies each, solves one micro-batch and fails
unless every answer is the golden model's under ip (labels, ids in
order, products within 1e-11 of |q| max|x|), the stamp says select
``extract`` and score ``ip``, and the tied queries were flagged and
repaired (the device retry, then the host oracle).

``cosine`` (PR 49) holds the chip to the third score: one child builds
the serving engine under ``score="cosine"`` and ``dtype="float32"`` (as
the benchmark's cell stages it) over seeded reals of 1536 attributes,
twelve whole lane vectors, that are NOT unit vectors, with a zero row,
a block of rows a hundred exact copies each and a zero query, solves one
micro-batch and fails unless every answer is the golden model's under
cosine (labels, ids in order, angular distances within 1e-11), the
stamp says select ``extract`` and score ``cosine``, the first pass ran
as ``bf16x3`` over rows the device holds at unit norm, and the tied
queries were flagged and repaired.

The configs run in the order given (default ``1,4``): config 1 is the
same path at a size that takes seconds, so a machine with no chip fails
there instead of after minutes at full width; a config is started only
if every one before it passed. ``--configs 1`` under JAX_PLATFORMS=cpu
is the dry run before a chip call: all checksums must match and the
exit is non-zero naming ``platform is cpu``.

Timings printed here are smoke timings of single cold runs, not
measurements. The last stdout line of a passing run is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, "outputs", "chip_smoke")        # large inputs
LOGS = os.path.join(REPO, "chiprun_out", "chip_smoke")    # child records

SERVE_REQUESTS = 4         # query requests sent to the daemon ...
SERVE_REQUEST_QUERIES = 256  # ... each this many queries, own k each
CHILD_TIMEOUT_S = 900.0


def say(msg: str) -> None:
    print(msg, flush=True)


# -- children ------------------------------------------------------------------

def child_env() -> Dict[str, str]:
    """The caller's environment (platform and compile-cache placement
    included) plus the checkout on the path."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_child(argv: List[str], stdin_path: Optional[str], out_path: str,
              err_path: str) -> Tuple[int, float]:
    """One child to completion; (exit code, wall seconds)."""
    t0 = time.monotonic()
    with open(out_path, "wb") as out, open(err_path, "wb") as err, \
            open(stdin_path or os.devnull, "rb") as stdin:
        proc = subprocess.Popen([sys.executable] + argv, stdin=stdin,
                                stdout=out, stderr=err, env=child_env(),
                                cwd=REPO)
        try:
            rc = proc.wait(timeout=CHILD_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            proc.kill()
            rc = proc.wait()
    return rc, time.monotonic() - t0


def tail(path: str, lines: int = 15) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError:
        return ""


# -- checks --------------------------------------------------------------------

def check_stamp(stamp: Any, mesh: Optional[List[int]], ladder: bool,
                num_data: int) -> List[str]:
    """Every miss in one child's device stamp, named."""
    if not isinstance(stamp, dict):
        return ["child wrote no device stamp"]
    bad = []
    if stamp.get("platform") != "tpu":
        bad.append(f"platform is {stamp.get('platform')}")
    if not stamp.get("peak_flops_known"):
        bad.append(f"device_kind {stamp.get('device_kind')!r} is not in "
                   "the peaks table")
    want_devices = mesh[0] * mesh[1] if mesh else 1
    if stamp.get("device_count", 0) < want_devices:
        bad.append(f"device_count is {stamp.get('device_count')}, the "
                   f"phase needs {want_devices}")
    if stamp.get("mesh") != mesh:
        bad.append(f"mesh is {stamp.get('mesh')}, asked for {mesh}")
    if stamp.get("select") != "extract":
        bad.append(f"select is {stamp.get('select')}")
    if stamp.get("extract_impl") != "fused":
        bad.append(f"extract_impl is {stamp.get('extract_impl')}")
    if stamp.get("pallas_interpret") is not False:
        bad.append("pallas_interpret is "
                   f"{stamp.get('pallas_interpret')}")
    if ladder and stamp.get("degrade_rung") != "lowp":
        bad.append(f"degrade rung is {stamp.get('degrade_rung')}")
    if stamp.get("degradations"):
        bad.append(f"degradations recorded: {stamp['degradations']}")
    if stamp.get("retries"):
        bad.append(f"retries recorded: {stamp['retries']}")
    if mesh and mesh[0] * mesh[1] > 1:
        rows = stamp.get("corpus_rows_per_device") or {}
        share = set(rows.values())
        if len(rows) != mesh[0] * mesh[1] or len(share) != 1:
            bad.append(f"corpus rows per device are {rows}, not one "
                       f"equal share on each of {mesh[0] * mesh[1]}")
        else:
            s = share.pop()
            if not (s * mesh[0] >= num_data and s < num_data):
                bad.append(f"each device holds {s} rows: not a 1/"
                           f"{mesh[0]} share of {num_data}")
    return bad


def stamp_line(stamp: Dict[str, Any], cache: Dict[str, Any]) -> str:
    v = stamp.get("kernel_variant") or {}
    hit = ("off" if not cache.get("dir") else
           f"{cache.get('hits')} hit(s) / {cache.get('misses')} written "
           f"of {cache.get('requests')} in {cache.get('dir')}")
    rows = stamp.get("corpus_rows_per_device")
    return ((f"    corpus rows per device: {rows}\n" if rows else "")
            + f"    device: {stamp.get('platform')} "
            f"{stamp.get('device_kind')!r} x{stamp.get('device_count')} "
            f"mesh={stamp.get('mesh')} select={stamp.get('select')} "
            f"impl={stamp.get('extract_impl')} "
            f"interpret={stamp.get('pallas_interpret')} "
            f"rung={stamp.get('degrade_rung')} "
            f"variant=tq{v.get('tile_q')}/ne{v.get('ne')}/kc{v.get('kc')}"
            f"/mxu_passes{v.get('mxu_passes')} "
            f"repairs={stamp.get('repairs')}\n"
            f"    compile (smoke timing): backend "
            f"{cache.get('backend_compile_ms')} ms; cache {hit}")


# -- phases --------------------------------------------------------------------

class Config:
    """One bench config: generator arguments (dmlp_tpu.bench.configs)
    and the pinned input hash + captured reference output."""

    def __init__(self, config_id: int):
        from dmlp_tpu.bench.configs import BENCH_CONFIGS
        with open(os.path.join(REPO, "oracle_capture",
                               "ORACLE_GOLDEN.json")) as f:
            golden = json.load(f)["configs"][str(config_id)]
        self.id = config_id
        self.cfg = BENCH_CONFIGS[config_id]
        self.input_sha256 = golden["input_sha256"]
        self.input_path = os.path.join(WORK, self.cfg.input_name)
        with open(os.path.join(REPO, "oracle_capture",
                               golden["out_file"])) as f:
            self.oracle_lines = f.read().splitlines(keepends=True)

    def log(self, name: str) -> str:
        return os.path.join(LOGS, f"config{self.id}_{name}")


def phase_generate(c: Config) -> List[str]:
    g = c.cfg
    rc, wall = run_child(
        ["-m", "dmlp_tpu.io.datagen", "--num_data", str(g.num_data),
         "--num_queries", str(g.num_queries), "--num_attrs",
         str(g.num_attrs), "--min", str(g.min_attr), "--max",
         str(g.max_attr), "--minK", str(g.min_k), "--maxK", str(g.max_k),
         "--num_labels", str(g.num_labels), "--seed", str(g.seed),
         "--output", c.input_path],
        None, c.log("generate.out"), c.log("generate.err"))
    if rc != 0:
        return [f"datagen exited {rc}: {tail(c.log('generate.err'), 3)}"]
    h = hashlib.sha256()
    with open(c.input_path, "rb") as f:
        for block in iter(lambda: f.read(1 << 22), b""):
            h.update(block)
    say(f"  generate: {c.cfg.input_name} {g.num_data} x {g.num_queries} "
        f"x {g.num_attrs}, k in [{g.min_k}, {g.max_k}]; wall {wall:.1f} s")
    if h.hexdigest() != c.input_sha256:
        return [f"input sha256 is {h.hexdigest()}, ORACLE_GOLDEN.json "
                f"pins {c.input_sha256}"]
    return []


def phase_solve(c: Config, name: str, mode_args: List[str],
                mesh: Optional[List[int]], ladder: bool,
                form: Optional[str] = None, passes: Optional[int] = None
                ) -> Tuple[List[str], Optional[Dict[str, Any]]]:
    """A batch solve through ``python -m dmlp_tpu``; (misses, stamp).
    ``form``: the first-pass form the child's record must name;
    ``passes``: the MXU passes a visit its variant stamp must (a child
    on a chip: the default staging dtype follows the platform)."""
    metrics = c.log(f"{name}.metrics.jsonl")
    if os.path.exists(metrics):
        os.remove(metrics)
    rc, wall = run_child(
        ["-m", "dmlp_tpu", "--pallas", "--warmup", "--phase-times",
         "--metrics", metrics] + mode_args,
        c.input_path, c.log(f"{name}.out"), c.log(f"{name}.err"))
    if rc != 0:
        return [f"engine exited {rc}:\n{tail(c.log(name + '.err'))}"], None
    err_lines = [ln.strip() for ln in tail(c.log(f"{name}.err"), 12)
                 .splitlines() if ln.startswith(("Time taken", "phase "))]
    with open(metrics) as f:
        summary = [json.loads(ln) for ln in f if '"summary"' in ln][-1]
    stamp = summary.get("device")
    say(f"  {name}: wall {wall:.1f} s (smoke timing); child: "
        + "; ".join(err_lines) + f"; parser {summary.get('parser')}")
    if isinstance(stamp, dict):
        say(stamp_line(stamp, summary.get("compile_cache") or {}))
    bad = []
    with open(c.log(f"{name}.out")) as f:
        if f.read().splitlines(keepends=True) != c.oracle_lines:
            bad.append("stdout differs from the captured reference "
                       "output")
    bad += check_stamp(stamp, mesh, ladder, c.cfg.num_data)
    ran = (summary.get("precision") or {}).get("active")
    if form is not None and ran != form:
        bad.append(f"the first pass ran as {ran!r}, not {form!r}: the "
                   "split check (ops.pallas_extract.split_holds) "
                   f"refused it on this compiler:\n"
                   f"{tail(c.log(name + '.err'))}")
    if passes is not None and isinstance(stamp, dict) \
            and stamp.get("platform") == "tpu":
        got = (stamp.get("kernel_variant") or {}).get("mxu_passes")
        if got != passes:
            bad.append(f"the cross term took {got} MXU passes a visit, "
                       f"not {passes}")
    return bad, stamp if isinstance(stamp, dict) else None


FOLD_SHAPE = dict(queries=1024, attrs=128, kc=120, chunks=3)


def fold_child(out_path: str) -> int:
    """The ``fold.bf16`` child: one process, the chip its own. Seeded
    reals in [0, 255) rounded to bfloat16, folded by
    serve.engine._fold_stack at ``bigann-10m.bulk``'s dispatch shape
    (q1024, kc 120, chunks of 51 200 x 128; 1 024-row chunks where the
    kernel runs interpreted, the dry run) as bfloat16 and as the same
    values in float32; what it found goes to ``out_path`` as JSON."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmlp_tpu.obs.hlo import kernel_operand_types
    from dmlp_tpu.obs.run import device_stamp
    from dmlp_tpu.ops import pallas_fused
    from dmlp_tpu.ops.pallas_distance import pallas_interpret
    from dmlp_tpu.ops.pallas_extract import row_norms
    from dmlp_tpu.serve.engine import _fold_stack, _kernel_statics
    interpret = pallas_interpret()
    nq, na, kc, chunks = (FOLD_SHAPE[k] for k in (
        "queries", "attrs", "kc", "chunks"))
    rows = 1024 if interpret else 51200
    rng = np.random.default_rng(39)
    q16 = jnp.asarray(rng.uniform(0, 255, (nq, na)), jnp.bfloat16)
    d16 = jnp.asarray(rng.uniform(0, 255, (chunks, rows, na)), jnp.bfloat16)
    kern = _kernel_statics("fused", kc, rows, nq, na, "f32", interpret)
    order = jnp.arange(chunks, dtype=jnp.int32)
    n_real = chunks * rows - 77           # the last block holds sentinels
    lists, data_operands = {}, None
    for name, cast in (("bfloat16", jnp.bfloat16), ("float32", jnp.float32)):
        # (the rows' norms beside the stack, as _update_chunk stages them)
        args = (q16.astype(cast), d16.astype(cast),
                row_norms(d16)[:, None, :], order,
                jnp.int32(chunks), jnp.int32(n_real))
        if name == "bfloat16" and not interpret:
            hlo = _fold_stack.lower(*args, **kern).compile().as_text()
            # operand 2 of each kernel call: the data BlockSpec's
            data_operands = [ops[2] for ops in kernel_operand_types(hlo)]
        od, oi, _gated = _fold_stack(*args, **kern)
        od, oi = jax.device_get((od, oi))
        # a list's slots fill in the order its distances compare:
        # judged in one order, by (distance, id)
        slot = np.lexsort((oi, od), axis=1)
        lists[name] = (np.take_along_axis(od, slot, 1),
                       np.take_along_axis(oi, slot, 1))
    (od16, oi16), (od32, oi32) = lists["bfloat16"], lists["float32"]
    # the lists against float64, as a share of the scale |q|^2 + |d|^2
    q64 = np.asarray(q16.astype(jnp.float32), np.float64)
    d64 = np.asarray(d16.astype(jnp.float32), np.float64).reshape(-1, na)
    diff = d64[oi16] - q64[:, None, :]
    true = np.einsum("qka,qka->qk", diff, diff)
    scale = np.einsum("qa,qa->q", q64, q64).max() \
        + np.einsum("na,na->n", d64, d64).max()
    with open(out_path, "w") as f:
        json.dump({
            "device": device_stamp(None),
            "shape": dict(FOLD_SHAPE, chunk_rows=rows, n_real=n_real),
            "mxu_passes": {
                st: pallas_fused.variant_stamp(
                    kc, rows, nq, na, "f32", st)["mxu_passes"]
                for st in ("bfloat16", "float32")},
            "kernel_data_operands": data_operands,
            "ids_equal": bool(np.array_equal(oi16, oi32)),
            "dists_equal": bool(np.array_equal(od16, od32)),
            "dists_differ": int(np.count_nonzero(od16 != od32)),
            "dists_max_abs_diff": float(np.max(np.abs(
                od16.astype(np.float64) - od32))),
            "err_over_scale_vs_float64": float(
                np.max(np.abs(od16 - true)) / scale),
            "ids_valid": bool((oi16 >= 0).all() and (oi16 < n_real).all()),
        }, f)
    return 0


def run_fold_child(c: Config, name: str, flag: str
                   ) -> Tuple[Optional[Dict[str, Any]], float, List[str]]:
    """One fold phase's child (this file under ``flag``), run to its
    end: (the JSON record it wrote, its wall seconds, the miss if it
    wrote none)."""
    out = c.log(f"{name}.json")
    if os.path.exists(out):
        os.remove(out)
    rc, wall = run_child([os.path.abspath(__file__), flag, out], None,
                         c.log(f"{name}.out"), c.log(f"{name}.err"))
    if rc != 0 or not os.path.exists(out):
        return None, wall, [
            f"child exited {rc}:\n{tail(c.log(f'{name}.err'))}"]
    with open(out) as f:
        return json.load(f), wall, []


def device_misses(stamp: Dict[str, Any]) -> List[str]:
    """What a fold child's own device stamp says against it."""
    bad = []
    if stamp.get("platform") != "tpu":
        bad.append(f"platform is {stamp.get('platform')}")
    if stamp.get("pallas_interpret") is not False:
        bad.append(f"pallas_interpret is {stamp.get('pallas_interpret')}")
    return bad


def phase_fold_bf16(c: Config) -> List[str]:
    """``fold.bf16``: rows staged in bfloat16 reach the MXU as bfloat16,
    in one pass, and give the ``HIGHEST`` dot's lists."""
    got, wall, bad = run_fold_child(c, "fold.bf16", "--fold-child")
    if got is None:
        return bad
    say(f"  fold.bf16: wall {wall:.1f} s (smoke timing); {got['shape']}; "
        f"mxu_passes {got['mxu_passes']}; the kernel's data operands "
        f"{got['kernel_data_operands']}; lists against the float32 "
        f"HIGHEST run: ids equal {got['ids_equal']}, distances unequal "
        f"{got['dists_differ']} (largest gap "
        f"{got['dists_max_abs_diff']:g}); "
        f"against float64 {got['err_over_scale_vs_float64']:.3g} of the "
        "scale")
    return fold_misses(got)


def fold_misses(got: Dict[str, Any]) -> List[str]:
    """Every miss in the ``fold.bf16`` child's record, named."""
    stamp = got.get("device") or {}
    bad = device_misses(stamp)
    if got.get("mxu_passes") != {"bfloat16": 1, "float32": 6}:
        bad.append(f"mxu_passes is {got.get('mxu_passes')}, not 1 for "
                   "bfloat16 rows and 6 for float32's HIGHEST dot")
    ops = got.get("kernel_data_operands")
    if stamp.get("platform") == "tpu" and (
            not ops or any(not o.startswith("bf16[") for o in ops)):
        bad.append(f"the compiled fold hands the kernel {ops}, not the "
                   "bfloat16 rows")
    if not got.get("ids_valid"):
        bad.append("the fold's lists hold ids outside the corpus")
    # (judged on the chip alone: a CPU's two dots are float32 loops
    # that sum in different orders, and no MXU)
    if stamp.get("platform") == "tpu" \
            and not (got.get("ids_equal") and got.get("dists_equal")):
        bad.append(
            "one pass over bfloat16 rows is NOT the HIGHEST dot's value "
            f"on this device: ids equal {got.get('ids_equal')}, "
            f"{got.get('dists_differ')} distances differ (largest "
            f"{got.get('dists_max_abs_diff')}): LOWP_COEF['f32'] = 0 "
            "does not hold for it")
    return bad


#: ``msturing-10m.bulk``'s dispatch shape; ``sure``: the nearest rows by
#: float64 every 120-slot list must hold (the last 8 slots may trade
#: places with their neighbours outside by a float32 rounding)
NARROW_SHAPE = dict(queries=1024, attrs=100, kc=120, chunks=3, sure=112)


def narrow_child(out_path: str) -> int:
    """The ``fold.narrow`` child: seeded reals in [-1, 1) of 100
    attributes rounded to bfloat16, zero-padded to ``lane_padded(100)``
    as ``ResidentEngine._restage_chunk`` pads them, folded by
    serve.engine._fold_stack (chunks of 51 200 rows; 1 024 where the
    kernel runs interpreted), against a float64 brute force over the
    same values; what it found goes to ``out_path`` as JSON."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmlp_tpu.engine.finalize import EPS_CANCEL_COEF
    from dmlp_tpu.obs.hlo import kernel_operand_types
    from dmlp_tpu.obs.run import device_stamp
    from dmlp_tpu.ops.pallas_distance import pallas_interpret
    from dmlp_tpu.ops.pallas_extract import lane_padded, row_norms
    from dmlp_tpu.serve.engine import _fold_stack, _kernel_statics
    interpret = pallas_interpret()
    nq, na, kc, chunks, sure = (NARROW_SHAPE[k] for k in (
        "queries", "attrs", "kc", "chunks", "sure"))
    rows = 1024 if interpret else 51200
    a_pad = lane_padded(na)
    rng = np.random.default_rng(40)
    pad = ((0, 0),) * 2 + ((0, a_pad - na),)
    q16 = jnp.asarray(rng.uniform(-1, 1, (nq, na)), jnp.bfloat16)
    d16 = jnp.asarray(rng.uniform(-1, 1, (chunks, rows, na)), jnp.bfloat16)
    kern = _kernel_statics("fused", kc, rows, nq, a_pad, "f32", interpret)
    n_real = chunks * rows - 77           # the last block holds sentinels
    args = (jnp.pad(q16, pad[1:]), jnp.pad(d16, pad),
            row_norms(d16)[:, None, :],
            jnp.arange(chunks, dtype=jnp.int32), jnp.int32(chunks),
            jnp.int32(n_real))
    data_operands = temp_bytes = None
    if not interpret:
        compiled = _fold_stack.lower(*args, **kern).compile()
        data_operands = [ops[2] for ops in kernel_operand_types(
            compiled.as_text())]
        temp_bytes = int(compiled.memory_analysis().temp_size_in_bytes)
    od, oi, _gated = jax.device_get(_fold_stack(*args, **kern))
    # float64 over the same bfloat16 values, a block of queries at a time
    q64 = np.asarray(q16.astype(jnp.float32), np.float64)
    d64 = np.asarray(d16.astype(jnp.float32),
                     np.float64).reshape(-1, na)[:n_real]
    dn = np.einsum("na,na->n", d64, d64)
    qn = np.einsum("qa,qa->q", q64, q64)
    missing = 0
    for lo in range(0, nq, 128):
        dist = qn[lo:lo + 128, None] + dn[None, :] \
            - 2.0 * q64[lo:lo + 128] @ d64.T
        near = np.argpartition(dist, sure, axis=1)[:, :sure]
        missing += sum(int(np.setdiff1d(n, got).size)
                       for n, got in zip(near, oi[lo:lo + 128]))
    valid = bool((oi >= 0).all() and (oi < n_real).all())
    diff = d64[np.clip(oi, 0, n_real - 1)] - q64[:, None, :]
    true = np.einsum("qka,qka->qk", diff, diff)
    scale = qn.max() + dn.max()
    with open(out_path, "w") as f:
        json.dump({
            "device": device_stamp(None),
            "shape": dict(NARROW_SHAPE, chunk_rows=rows, n_real=n_real),
            "a_pad": a_pad,
            "kernel_data_operands": data_operands,
            "temp_bytes": temp_bytes,
            "chunk_bytes": rows * a_pad * 2,
            "sure_missing": missing,
            "ids_valid": valid,
            "err_over_scale_vs_float64": float(
                np.max(np.abs(od - true)) / scale),
            "err_bound_over_scale": EPS_CANCEL_COEF * (na + 2),
        }, f)
    return 0


def phase_fold_narrow(c: Config) -> List[str]:
    """``fold.narrow``: rows of 100 signed attributes staged on whole
    lanes fold to the float64 brute force's candidates, and the program
    holds no copy of the stack."""
    got, wall, bad = run_fold_child(c, "fold.narrow", "--narrow-child")
    if got is None:
        return bad
    say(f"  fold.narrow: wall {wall:.1f} s (smoke timing); {got['shape']}; "
        f"staged {got['a_pad']} wide; the kernel's data operands "
        f"{got['kernel_data_operands']}; temporaries {got['temp_bytes']} B "
        f"beside the stack; float64's nearest {got['shape']['sure']} "
        f"missing from a list: {got['sure_missing']}; distances against "
        f"float64 {got['err_over_scale_vs_float64']:.3g} of the scale "
        f"(bound {got['err_bound_over_scale']:.3g})")
    return narrow_misses(got)


def narrow_misses(got: Dict[str, Any]) -> List[str]:
    """Every miss in the ``fold.narrow`` child's record, named."""
    stamp = got.get("device") or {}
    on_chip = stamp.get("platform") == "tpu"
    bad = device_misses(stamp)
    if got.get("a_pad") != 128:
        bad.append(f"100-wide rows are staged {got.get('a_pad')} wide, "
                   "not on one whole lane vector")
    ops = got.get("kernel_data_operands")
    if on_chip and (not ops or any(
            not o.startswith("bf16[") or not o.endswith(",128]")
            for o in ops)):
        bad.append(f"the compiled fold hands the kernel {ops}, not the "
                   "bfloat16 rows on 128 lanes")
    if on_chip and not (got.get("temp_bytes") is not None
                        and got["temp_bytes"] < 0.25 * got["chunk_bytes"]):
        bad.append(f"the compiled fold allocates {got.get('temp_bytes')} "
                   "B beside the stack: a quarter of a chunk or more, a "
                   "copy of a chunk or of the stack is back")
    if not got.get("ids_valid"):
        bad.append("the fold's lists hold ids outside the corpus")
    if got.get("sure_missing"):
        bad.append(f"{got['sure_missing']} of float64's nearest "
                   "candidates are missing from the fold's lists")
    if not got.get("err_over_scale_vs_float64", 1.0) \
            <= got.get("err_bound_over_scale", 0.0):
        bad.append("the fold's distances are off float64's by "
                   f"{got.get('err_over_scale_vs_float64')} of the scale, "
                   f"over the bound {got.get('err_bound_over_scale')}")
    return bad


#: the ``ip`` phase's corpus: uniform rows, then ``points`` integer rows
#: ``copies`` times each (their products tie by the hundred)
IP_SHAPE = dict(rows=20000, attrs=200, points=6, copies=100, queries=128,
                k=10)


def ip_child(out_path: str) -> int:
    """The ``ip`` child: a resident engine under ``score="ip"`` over a
    seeded corpus whose last rows are integer points a hundred copies
    each, one micro-batch of uniform queries and of the points
    themselves (so the best products tie past the candidate window),
    against golden.fast under ip; what it found goes to ``out_path``."""
    import numpy as np

    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.golden.fast import knn_golden_fast
    from dmlp_tpu.io.grammar import KNNInput, Params
    from dmlp_tpu.obs.run import device_stamp
    from dmlp_tpu.serve.engine import ResidentEngine
    n, na, points, copies, nq, k = (IP_SHAPE[x] for x in (
        "rows", "attrs", "points", "copies", "queries", "k"))
    rng = np.random.default_rng(46)
    rows = rng.uniform(-1, 1, (n, na)).astype(np.float32).astype(np.float64)
    pts = rng.integers(-2, 3, (points, na)).astype(np.float64)
    rows[n - points * copies:] = np.repeat(pts, copies, axis=0)
    labels = rng.integers(0, 10, n).astype(np.int32)
    queries = rng.uniform(-1, 1, (nq, na)).astype(np.float32).astype(
        np.float64)
    queries[:points] = pts
    ks = np.full(nq, k, np.int32)
    corpus = KNNInput(Params(n, 0, na), labels, rows, np.zeros(0, np.int32),
                      np.zeros((0, na)))
    eng = ResidentEngine(corpus, EngineConfig(use_pallas=True, score="ip"))
    got = eng.solve_batch(queries, ks)
    want = knn_golden_fast(KNNInput(Params(n, nq, na), labels, rows, ks,
                                    queries), score="ip")
    scale = np.linalg.norm(queries, axis=1) * np.linalg.norm(
        rows, axis=1).max()
    wrong = sum(int(g.predicted_label != w.predicted_label
                    or not np.array_equal(g.neighbor_ids, w.neighbor_ids))
                for g, w in zip(got, want))
    err = max(float(np.max(np.abs(g.neighbor_dists - w.neighbor_dists))
                    / s) for g, w, s in zip(got, want, scale))
    tied = sum(int(len(set(g.neighbor_dists.tolist())) == 1)
               for g in got[:points])
    stats = eng.bucket_stats()
    with open(out_path, "w") as f:
        json.dump({
            "device": device_stamp(eng), "shape": IP_SHAPE,
            "staging": eng._staging, "staged_attrs": stats["staged_attrs"],
            "paths": stats["paths"], "wrong": wrong,
            "err_over_scale": err, "tied_answers": tied,
            "repairs": stats["repairs"],
        }, f)
    return 0


def phase_ip(c: Config) -> List[str]:
    """``ip``: a resident corpus ranked by inner product answers as the
    golden model does, ties included, on the extract path."""
    got, wall, bad = run_fold_child(c, "ip", "--ip-child")
    if got is None:
        return bad
    stamp = got.get("device") or {}
    say(f"  ip: wall {wall:.1f} s (smoke timing); {got['shape']}; staged "
        f"{got['staging']} on {got['staged_attrs']} lanes; paths "
        f"{got['paths']}; score {stamp.get('score')}; answers off the "
        f"golden model's: {got['wrong']}; products against float64 "
        f"{got['err_over_scale']:.3g} of |q| max|x|; repairs "
        f"{got['repairs']}")
    return ip_misses(got)


def ip_misses(got: Dict[str, Any]) -> List[str]:
    """Every miss in the ``ip`` child's record, named."""
    stamp = got.get("device") or {}
    bad = device_misses(stamp)
    if stamp.get("score") != "ip" or stamp.get("select") != "extract":
        bad.append(f"the stamp says score {stamp.get('score')!r}, select "
                   f"{stamp.get('select')!r}, not ip on the extract path")
    if set((got.get("paths") or {}).values()) != {"extract"}:
        bad.append(f"bucket paths are {got.get('paths')}, not extract")
    if stamp.get("platform") == "tpu" and got.get("staging") != "bfloat16":
        bad.append(f"the default dtype staged {got.get('staging')} on a "
                   "chip, not bfloat16")
    if got.get("staged_attrs") != 256:
        bad.append(f"200-wide rows are staged {got.get('staged_attrs')} "
                   "wide, not on two whole lane vectors")
    if got.get("wrong"):
        bad.append(f"{got['wrong']} answers differ from the golden "
                   "model's under ip")
    if not got.get("err_over_scale", 1.0) <= 1e-11:
        bad.append(f"the products are off float64's by "
                   f"{got.get('err_over_scale')} of |q| max|x|")
    if got.get("tied_answers") != IP_SHAPE["points"]:
        bad.append(f"{got.get('tied_answers')} of {IP_SHAPE['points']} "
                   "tied queries came back as one tie group")
    repairs = got.get("repairs") or {}
    if repairs.get("flagged_queries", 0) < IP_SHAPE["points"]:
        bad.append(f"repairs {repairs}: the tied queries were not flagged")
    return bad


#: the ``cosine`` phase's corpus: uniform rows of norms over two decades,
#: one of them zero, then ``points`` rows ``copies`` exact copies each
COSINE_SHAPE = dict(rows=12000, attrs=1536, points=4, copies=100,
                    queries=128, k=10)


def cosine_child(out_path: str) -> int:
    """The ``cosine`` child: a resident engine under ``score="cosine"``
    and float32 staging over a seeded corpus (a zero row; its last rows
    exact copies by the hundred), one micro-batch of uniform queries, of
    scaled copies of the copied rows (the best cosines tie past the
    candidate window) and one zero query, against golden.fast under
    cosine; what it found goes to ``out_path``."""
    import numpy as np

    from dmlp_tpu.config import EngineConfig
    from dmlp_tpu.golden.fast import knn_golden_fast
    from dmlp_tpu.io.grammar import KNNInput, Params
    from dmlp_tpu.obs.run import device_stamp
    from dmlp_tpu.serve.engine import ResidentEngine
    n, na, points, copies, nq, k = (COSINE_SHAPE[x] for x in (
        "rows", "attrs", "points", "copies", "queries", "k"))
    rng = np.random.default_rng(49)
    rows = (rng.uniform(-1, 1, (n, na)) * 10.0 ** rng.uniform(-1, 1, (n, 1))
            ).astype(np.float32).astype(np.float64)
    rows[17] = 0.0
    pts = rows[:points].copy()
    rows[n - points * copies:] = np.repeat(pts, copies, axis=0)
    labels = rng.integers(0, 10, n).astype(np.int32)
    queries = rng.uniform(-1, 1, (nq, na)).astype(np.float32).astype(
        np.float64)
    queries[:points] = pts * 4.0
    queries[points] = 0.0
    ks = np.full(nq, k, np.int32)
    corpus = KNNInput(Params(n, 0, na), labels, rows, np.zeros(0, np.int32),
                      np.zeros((0, na)))
    eng = ResidentEngine(corpus, EngineConfig(
        use_pallas=True, score="cosine", dtype="float32"))
    got = eng.solve_batch(queries, ks)
    want = knn_golden_fast(KNNInput(Params(n, nq, na), labels, rows, ks,
                                    queries), score="cosine")
    wrong = sum(int(g.predicted_label != w.predicted_label
                    or not np.array_equal(g.neighbor_ids, w.neighbor_ids))
                for g, w in zip(got, want))
    err = max(float(np.max(np.abs(g.neighbor_dists - w.neighbor_dists)))
              for g, w in zip(got, want))
    tied = sum(int(len(set(g.neighbor_dists.tolist())) == 1)
               for g in got[:points])
    staged = np.asarray(eng._chunks[0, :64, :], np.float64)
    stats = eng.bucket_stats()
    with open(out_path, "w") as f:
        json.dump({
            "device": device_stamp(eng), "shape": COSINE_SHAPE,
            "staging": eng._staging, "staged_attrs": stats["staged_attrs"],
            "paths": stats["paths"], "wrong": wrong, "err": err,
            "tied_answers": tied,
            "zero_query_ids": got[points].neighbor_ids.tolist(),
            "staged_norm_err": float(np.abs(np.sqrt(
                (staged[:17] ** 2).sum(axis=1)) - 1.0).max()),
            "staged_zero_row": float(np.abs(staged[17]).max()),
            "first_pass": (eng.last_precision or {}).get("active"),
            "repairs": stats["repairs"],
        }, f)
    return 0


def phase_cosine(c: Config) -> List[str]:
    """``cosine``: a resident corpus ranked by cosine answers as the
    golden model does, zero rows and exact copies included, on the
    extract path, from unit rows in float32."""
    got, wall, bad = run_fold_child(c, "cosine", "--cosine-child")
    if got is None:
        return bad
    stamp = got.get("device") or {}
    say(f"  cosine: wall {wall:.1f} s (smoke timing); {got['shape']}; "
        f"staged {got['staging']} on {got['staged_attrs']} lanes, |x^| - 1 "
        f"at most {got['staged_norm_err']:.3g}; paths {got['paths']}; "
        f"score {stamp.get('score')}; first pass {got['first_pass']}; "
        f"answers off the golden model's: {got['wrong']}; distances "
        f"against float64 {got['err']:.3g}; repairs {got['repairs']}")
    return cosine_misses(got)


def cosine_misses(got: Dict[str, Any]) -> List[str]:
    """Every miss in the ``cosine`` child's record, named."""
    stamp = got.get("device") or {}
    bad = device_misses(stamp)
    if stamp.get("score") != "cosine" or stamp.get("select") != "extract":
        bad.append(f"the stamp says score {stamp.get('score')!r}, select "
                   f"{stamp.get('select')!r}, not cosine on the extract "
                   "path")
    if set((got.get("paths") or {}).values()) != {"extract"}:
        bad.append(f"bucket paths are {got.get('paths')}, not extract")
    if got.get("staging") != "float32" or got.get("first_pass") != "bf16x3":
        bad.append(f"staged {got.get('staging')}, first pass "
                   f"{got.get('first_pass')!r}: not float32 rows under the "
                   "three-pass split")
    if got.get("staged_attrs") != COSINE_SHAPE["attrs"]:
        bad.append(f"1536-wide rows are staged {got.get('staged_attrs')} "
                   "wide, not on their own twelve lane vectors")
    if not got.get("staged_norm_err", 1.0) <= 1e-6 \
            or got.get("staged_zero_row") != 0.0:
        bad.append(f"the device's rows are not unit rows (|x^| - 1 up to "
                   f"{got.get('staged_norm_err')}; the zero row holds "
                   f"{got.get('staged_zero_row')})")
    if got.get("wrong"):
        bad.append(f"{got['wrong']} answers differ from the golden "
                   "model's under cosine")
    if not got.get("err", 1.0) <= 1e-11:
        bad.append(f"the angular distances are off float64's by "
                   f"{got.get('err')}")
    if got.get("tied_answers") != COSINE_SHAPE["points"]:
        bad.append(f"{got.get('tied_answers')} of {COSINE_SHAPE['points']} "
                   "tied queries came back as one tie group")
    n, k = COSINE_SHAPE["rows"], COSINE_SHAPE["k"]
    if got.get("zero_query_ids") != list(range(n - 1, n - 1 - k, -1)):
        bad.append(f"the zero query's neighbours are "
                   f"{got.get('zero_query_ids')}, not the largest ids")
    repairs = got.get("repairs") or {}
    if repairs.get("flagged_queries", 0) < COSINE_SHAPE["points"] + 1:
        bad.append(f"repairs {repairs}: the tied queries were not flagged")
    return bad


def read_queries(c: Config, count: int) -> Tuple[List[int], List[list]]:
    """The first ``count`` queries of the input's query section, with
    their own k — straight from the text the batch child parsed."""
    ks, rows = [], []
    with open(c.input_path) as f:
        f.readline()
        for _ in range(c.cfg.num_data):
            f.readline()
        for _ in range(count):
            parts = f.readline().split()
            ks.append(int(parts[1]))
            rows.append([float(v) for v in parts[2:]])
    return ks, rows


def phase_serve(c: Config, mesh: Optional[List[int]] = None) -> List[str]:
    """Daemon up (mesh-resident over ``mesh`` when given), a few query
    requests in input order, one stats, SIGTERM drain; answers compared
    with the matching oracle lines."""
    from dmlp_tpu.serve import client as sc  # imports jax, touches no device
    nreq = min(SERVE_REQUESTS,
               max(c.cfg.num_queries // SERVE_REQUEST_QUERIES, 1))
    per = min(SERVE_REQUEST_QUERIES, c.cfg.num_queries)
    name = "serve.mesh" if mesh else "serve"
    mesh_args = ["--mesh", f"{mesh[0]}x{mesh[1]}", "--mesh-merge",
                 "allgather"] if mesh else []
    ready_path, errlog = c.log(f"{name}.ready.json"), c.log(f"{name}.err")
    if os.path.exists(ready_path):
        os.remove(ready_path)
    t0 = time.monotonic()
    with open(errlog, "wb") as err:
        proc = subprocess.Popen(
            [sys.executable, "-m", "dmlp_tpu.serve", "--corpus",
             c.input_path, "--pallas", "--ready-file", ready_path,
             "--warm-buckets", f"{per}x{c.cfg.max_k}"] + mesh_args,
            stdout=subprocess.DEVNULL, stderr=err, env=child_env(),
            cwd=REPO)
    bad: List[str] = []
    try:
        ready = sc.await_ready(proc, ready_path,
                               timeout_s=CHILD_TIMEOUT_S, errlog=errlog)
        t_ready = time.monotonic()
        ks, rows = read_queries(c, nreq * per)
        cli = sc.ServeClient(ready["port"], timeout_s=CHILD_TIMEOUT_S)
        try:
            resps = [cli.query(rows[i * per:(i + 1) * per],
                               ks=ks[i * per:(i + 1) * per],
                               req_id=f"smoke{i}") for i in range(nreq)]
            stats = cli.stats()["stats"]
        finally:
            cli.close()
        t_served = time.monotonic()
        for r in resps:
            if not r.get("ok"):
                bad.append(f"request {r.get('id')} failed: "
                           f"{r.get('error')}")
        if not bad and sc.contract_text(
                [r["checksums"] for r in resps]) \
                != "".join(c.oracle_lines[:nreq * per]):
            bad.append("served checksums differ from the captured "
                       "reference output")
        sc.sigterm_drain(proc, timeout_s=120, errlog=errlog)
        if "drained clean" not in tail(errlog, 5):
            bad.append("daemon exited 0 without 'drained clean'")
        say(f"  {name}: {nreq} requests x {per} queries; wall to ready "
            f"{t_ready - t0:.1f} s (warm-up "
            f"{ready.get('cold_start_compile_ms')} ms), requests + stats "
            f"{t_served - t_ready:.2f} s, drain "
            f"{time.monotonic() - t_served:.1f} s (smoke timings); "
            f"parser {ready.get('parser')}")
        say(stamp_line(stats.get("device") or {},
                       stats.get("compile_cache") or {}))
        for where, doc in (("ready file", ready), ("stats reply", stats)):
            # the mesh engines solve without the degrade ladder
            bad += [f"{where}: {m}" for m in check_stamp(
                doc.get("device"), mesh, mesh is None, c.cfg.num_data)]
        # every bucket the daemon built, warmed or served
        bad += [f"bucket {k} took path {p}" for k, p in sorted(
            stats["engine"]["paths"].items()) if p != "extract"]
    except (RuntimeError, OSError, KeyError, ValueError) as e:
        bad.append(f"{type(e).__name__}: {e}\n{tail(errlog)}")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return bad


def run_config(config_id: int) -> Tuple[List[str], Optional[Dict]]:
    """All phases of one config; (misses naming phase and check, the
    batch child's stamp)."""
    c = Config(config_id)
    say(f"config {config_id}:")
    misses = [f"config {config_id} generate: {m}"
              for m in phase_generate(c)]
    if misses:
        return misses, None
    # the default dtype on a chip stages bfloat16: one pass
    bad, stamp = phase_solve(c, "batch", [], None, ladder=True, passes=1)
    misses += [f"config {config_id} batch: {m}" for m in bad]
    if stamp is None:
        return misses, None
    bad, _ = phase_solve(c, "batch.f32", ["--dtype", "float32"], None,
                         ladder=True, form="bf16x3", passes=3)
    misses += [f"config {config_id} batch.f32: {m}" for m in bad]
    misses += [f"config {config_id} fold.bf16: {m}"
               for m in phase_fold_bf16(c)]
    misses += [f"config {config_id} fold.narrow: {m}"
               for m in phase_fold_narrow(c)]
    misses += [f"config {config_id} ip: {m}" for m in phase_ip(c)]
    misses += [f"config {config_id} cosine: {m}" for m in phase_cosine(c)]
    misses += [f"config {config_id} serve: {m}" for m in phase_serve(c)]
    from dmlp_tpu.config import EngineConfig
    chips = stamp.get("device_count", 0)
    shard = -(-c.cfg.num_data // 4)
    if chips < 4:
        say(f"  sharded, ring, serve at --mesh 4,1: not run: {chips} "
            "chip(s)")
    elif shard <= EngineConfig.AUTO_SELECT_THRESHOLD:
        say(f"  sharded, ring, serve at --mesh 4,1: not run: a {shard}-row "
            "shard is below the size at which the engine selects the "
            "kernel")
    else:
        for mode in ("sharded", "ring"):
            bad, _ = phase_solve(c, mode, ["--mode", mode, "--mesh", "4,1"],
                                 [4, 1], ladder=False)
            misses += [f"config {config_id} {mode} 4,1: {m}" for m in bad]
        misses += [f"config {config_id} serve --mesh 4x1: {m}"
                   for m in phase_serve(c, [4, 1])]
    return misses, stamp


def parent_backend_state() -> Tuple[bool, str]:
    """(clean, what to say): this process must not have initialised a
    JAX backend — it would hold the chip its children need."""
    if "jax" not in sys.modules:
        return True, "jax never imported"
    from jax._src import xla_bridge  # read-only; jax has no public query
    if xla_bridge.backends_are_initialized():
        return False, "a JAX backend was initialised"
    return True, "jax imported (by dmlp_tpu.serve.client), no backend " \
        "initialised"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--configs", default="1,4",
                    help="bench configs to run, in order (default 1,4: "
                         "the same path small, then at full width)")
    ap.add_argument("--fold-child", metavar="OUT", default=None,
                    help=argparse.SUPPRESS)   # the fold.bf16 phase's child
    ap.add_argument("--narrow-child", metavar="OUT", default=None,
                    help=argparse.SUPPRESS)   # the fold.narrow phase's child
    ap.add_argument("--ip-child", metavar="OUT", default=None,
                    help=argparse.SUPPRESS)   # the ip phase's child
    ap.add_argument("--cosine-child", metavar="OUT", default=None,
                    help=argparse.SUPPRESS)   # the cosine phase's child
    args = ap.parse_args(argv)
    if args.fold_child:
        return fold_child(args.fold_child)
    if args.narrow_child:
        return narrow_child(args.narrow_child)
    if args.ip_child:
        return ip_child(args.ip_child)
    if args.cosine_child:
        return cosine_child(args.cosine_child)
    if not os.path.isdir(os.path.join(REPO, "dmlp_tpu")):
        print(f"chip_smoke: no dmlp_tpu package beside {__file__}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    os.makedirs(WORK, exist_ok=True)
    os.makedirs(LOGS, exist_ok=True)
    t0 = time.monotonic()
    misses: List[str] = []
    stamp = None
    for config_id in (int(v) for v in args.configs.split(",")):
        misses, stamp = run_config(config_id)
        if misses:
            break   # no wider config on a machine that failed this one
    clean, state = parent_backend_state()
    say(f"parent: {state}; total wall {time.monotonic() - t0:.1f} s")
    if not clean:
        misses.append(f"parent: {state}")
    if misses:
        say("chip_smoke: FAILED")
        for m in misses:
            say(f"  FAIL {m}")
        return 1
    say("chip_smoke: every phase passed")
    print(json.dumps({"ok": True, "device": {
        "platform": stamp["platform"], "kind": stamp["device_kind"],
        "count": stamp["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
