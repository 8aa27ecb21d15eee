"""What the chip's compiler schedules for one visit of the top-k kernel,
read without a chip.

libtpu compiles for a described "TPU v5 lite" (tests/test_tpu_aot.py) and,
asked to (``--xla_jf_dump_to`` / ``--xla_jf_dump_llo_text``), writes the
kernel's final VLIW bundles and the slots each one fills. A visit is one
grid step: the slab loop (the MXU passes, the expansion and the fold pass
of five slabs a round, two rounds a block), a straight-line part around
it, then the body of each extraction loop, once a round. This tool
compiles the carried fused kernel at a cell's dispatch shape and prints
the bundles of each part with the slots used of MXU / XLU / VALU / vector
load / vector store (capacity a bundle 4 / 3 / 4 / 3 / 1 on a v5e), so
the part that binds is named by the compiler itself. A bundle is about a
cycle at 1.5 GHz; DMA waits and loop-condition syncs are not in it:

    bigann.bulk's visit, PR 46's kernel: 148 + 10 632 + 692 bundles and
    4 164 a round; at the 3.18 rounds a visit its trace shows, 24 700
    bundles = 16.5 us, where the profiler reads 17.2.

A COUNT from the compiler, never a time: a time comes from a chip run
(PERF.md section 3).

    python3 tools/kernel_bundles.py --shape bigann.bulk
    python3 tools/kernel_bundles.py --shape bigann-10m.bulk --tile_q 64 \\
        --ne 4 --fold 0
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import subprocess
import sys
import tempfile

#: cell -> (queries, attributes, staged dtype, first-pass form, kc, score)
SHAPES = {
    "bigann.bulk": (1024, 128, "float32", "bf16x3", 32, "l2"),
    "bigann.steady": (128, 128, "float32", "bf16x3", 32, "l2"),
    "gist.bulk": (1024, 1024, "float32", "bf16x3", 40, "l2"),
    "bigann-gt1000.bulk": (1024, 128, "float32", "bf16x3", 512, "l2"),
    "bigann-10m.bulk": (1024, 128, "bfloat16", "f32", 120, "l2"),
    "text2image-10m.bulk": (1024, 256, "bfloat16", "f32", 120, "ip"),
    # (cosine reaches the kernel as "ip": config.kernel_score)
    "dbpedia-openai-1m.bulk": (1024, 1536, "float32", "bf16x3", 56, "ip"),
}
UNITS = ["MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD:FILL", "VSTORE",
         "VSTORE:SPILL", "SALU"]
SHOWN = ("MXU", "XLU", "VALU", "VLOAD", "VSTORE", "VSTORE:SPILL")
_BUNDLE = re.compile(r"\s*(0x[0-9a-f]+|\d+)\s+(LH|LB|LE|PB|PF|CT)?:?\s*(>*)"
                     r"\s*\{")


def compile_with_dump(shape, over, dump: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "")
        + f" --xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dmlp_tpu.ops.pallas_extract import _extract_topk_jit
    from dmlp_tpu.serve.engine import _kernel_statics
    q, a, dtype, form, kc, score = shape
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])

    def spec(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sh)

    kern = {**_kernel_statics("fused", kc, 51200, q, a, form, False, score),
            **over}
    print("statics:", {k: kern[k] for k in ("tile_q", "tile_n", "ne", "fold",
                                             "kc", "precision", "score")})
    _extract_topk_jit.lower(
        spec((q, a), dtype), spec((51200, a), dtype),
        spec((q, kc), jnp.float32), spec((q, kc), jnp.int32),
        n_real=spec((), jnp.int32), id_base=spec((), jnp.int32),
        block_skip=True, floor=None, **kern).compile()


def regions(dump: str):
    """[(first bundle, last + 1, loop depth, slots used a unit)] of the
    kernel's final schedule, cut at its control targets."""
    final = [f for f in glob.glob(f"{dump}/*dmlp_topk_*final_bundles.txt")
             if "schedule-analysis" not in f][0]
    cuts, last = [], 0
    for line in open(final):
        m = _BUNDLE.match(line)
        if m:
            last = int(m.group(1), 0)
            if m.group(2):
                cuts.append((last, len(m.group(3))))
    used, on = [], False
    for line in open(glob.glob(
            f"{dump}/*dmlp_topk_*final_hlo-static-per-bundle-utilization"
            ".txt")[0]):
        if line.startswith("== UTILIZATION"):
            on = True
        elif on and line.strip():
            used.append([int(x) for x in line.split()])
    edges = [(0, 0)] + cuts + [(last + 1, 0)]
    return [(lo, hi, depth,
             [sum(r[u] for r in used[lo:hi]) for u in range(len(UNITS))])
            for (lo, depth), (hi, _) in zip(edges, edges[1:])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", choices=sorted(SHAPES), default="bigann.bulk")
    for name in ("tile_q", "ne", "fold"):
        ap.add_argument(f"--{name}", type=int)
    ap.add_argument("--least", type=int, default=100,
                    help="parts shorter than this many bundles are summed")
    ap.add_argument("--dump", help=argparse.SUPPRESS)   # the child's
    args = ap.parse_args(argv)
    over = {k: v for k in ("tile_q", "ne", "fold")
            if (v := getattr(args, k)) is not None}
    if args.dump:
        compile_with_dump(SHAPES[args.shape], over, args.dump)
        return 0
    with tempfile.TemporaryDirectory() as dump:
        # The compile runs in a child: this libtpu build aborts after it
        # has written the schedule (its dumper then looks for a report
        # template the wheel does not ship), so the child's exit code
        # says nothing and its files are read here.
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--dump", dump]
            + [a for a in (argv if argv is not None else sys.argv[1:])],
            capture_output=True, text=True)
        print("".join(ln + "\n" for ln in child.stdout.splitlines()
                      if ln.startswith("statics:")), end="")
        try:
            parts = regions(dump)
        except IndexError:
            sys.stderr.write(child.stderr[-4000:])
            print("the compiler wrote no schedule (stderr above)")
            return 1
    small = 0
    for lo, hi, depth, slots in parts:
        if hi - lo < args.least:
            small += hi - lo
            continue
        what = "a round of a loop" if depth > 1 else "straight line"
        print(f"[{lo:6d}, {hi:6d}) {hi - lo:6d} bundles, {what}: "
              + ", ".join(f"{u} {slots[UNITS.index(u)]}" for u in SHOWN))
    print(f"{small} bundles in shorter parts (prologue, loop heads, the "
          "diagnostics)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
