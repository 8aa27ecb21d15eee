"""CLI for the measured extract-kernel autotuner.

Regenerate the variant cache on the current backend::

    python -m dmlp_tpu.tune [--n 204800 --q 10240 --a 64 --k 32]
                            [--kc 40 --kc 136 ...] [--reps 3]
                            [--out PATH] [--record RUNRECORD.json]

The sweep measures at the CHUNKED dispatch shape the engines actually
use (plan_chunks on the extract granule) and merges winners into the
cache file (``$DMLP_TPU_TUNE_CACHE`` or
``~/.cache/dmlp_tpu/extract_variants.json``) keyed by (kernel, device
kind, data-rows bucket, kc, dtype, precision). Existing entries for
other keys are kept. ``--kernel extract|fused|both|prune_score|all``
(default both) picks which kernel's variant space to sweep — the fused
megakernel (ops.pallas_fused) caches under its own namespace, and
``prune_score`` sweeps the HOST block-scoring chunk that
ops.summaries.resolve_score_variant reads (satellite of the
low-precision first pass: the measured tiling replaces the guessed
_SCORE_BLOCK_CHUNK default). ``--precision f32|bf16x3|bf16|both``
(default bf16x3, the form the exact engines run at float32 staging;
"f32" is fast mode's one HIGHEST dot, and what an exact engine runs
over operands staged in bfloat16; both = every form, the name kept
from when there were two) re-sweeps the
device kernels per first-pass form — the MXU pass count per tile
(six, three, one) moves the winning tiles, which persist under the
precision key axis (cache schema 3).

``--smoke`` runs a tiny-shape sweep (CPU interpret mode works) over a
4-variant slice PER KERNEL — the ``make tune-smoke`` CI gate that
proves the measure -> pick -> persist -> reload pipeline (fused sweep
included) and validates the cache schema end-to-end. ``--validate
PATH`` just schema-checks an existing cache file and exits.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="dmlp_tpu.tune", description=__doc__)
    ap.add_argument("--n", type=int, default=204800)
    ap.add_argument("--q", type=int, default=10240)
    ap.add_argument("--a", type=int, default=64)
    ap.add_argument("--k", type=int, action="append", default=None,
                    help="workload k (repeatable); kc derives via "
                         "resolve_kcap with float32 staging")
    ap.add_argument("--kc", type=int, action="append", default=None,
                    help="candidate-list width to tune directly "
                         "(repeatable; overrides --k derivation)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--kernel",
                    choices=("extract", "fused", "both",
                             "prune_score", "all"),
                    default="both",
                    help="which kernel's variant space to sweep (the "
                         "fused megakernel caches under its own "
                         "namespace; prune_score sweeps the host "
                         "block-scoring chunk; all = every kernel)")
    ap.add_argument("--precision",
                    choices=("f32", "bf16x3", "bf16", "both"),
                    default="bf16x3",
                    help="first-pass form(s) to sweep the device "
                         "kernels at (both = every form) — winners "
                         "persist under "
                         "the cache's precision key axis (prune_score "
                         "is host f64 and ignores this)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="cache file (default: the lookup path — "
                         "$DMLP_TPU_TUNE_CACHE or ~/.cache/dmlp_tpu/"
                         "extract_variants.json)")
    ap.add_argument("--record", default=None,
                    help="also write one schema-1 RunRecord (obs.run) "
                         "summarizing the sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape 4-variant sweep (CPU CI gate)")
    ap.add_argument("--validate", metavar="PATH", default=None,
                    help="schema-check an existing cache file and exit")
    ap.add_argument("--compile-cache", metavar="DIR", default=None,
                    help="persistent XLA compilation cache dir; "
                         "default <checkout>/.jax_cache, and "
                         "$JAX_COMPILATION_CACHE_DIR, when set, "
                         "wins over both (utils.compile_cache)")
    args = ap.parse_args(argv)

    from dmlp_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache(args.compile_cache)

    from dmlp_tpu.tune.cache import (VariantCache, cache_path,
                                     clear_lookup_memo)

    if args.validate:
        try:
            with open(args.validate) as f:
                doc = json.load(f)
            VariantCache.validate_doc(doc)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"tune: INVALID cache {args.validate}: {e}",
                  file=sys.stderr)
            return 1
        print(f"tune: cache ok — {len(doc['entries'])} entries "
              f"({args.validate})")
        return 0

    from dmlp_tpu.tune.sweep import (smoke_space, sweep_extract,
                                     sweep_prune_score)

    if args.smoke:
        n, nq, a = 1024, 16, 8
        kcs = [16]
        reps = 1
        space_fn = smoke_space
    else:
        n, nq, a = args.n, args.q, args.a
        reps = args.reps
        space_fn = None
        if args.kc:
            kcs = sorted(set(args.kc))
        else:
            from dmlp_tpu.config import EngineConfig
            from dmlp_tpu.engine.single import resolve_kcap
            ks = args.k or [32]
            kcs = sorted({resolve_kcap(EngineConfig(), k, "extract",
                                       1 << 30, staging="float32")
                          for k in ks})

    out_path = args.out or cache_path()
    kernels = {"both": ("extract", "fused"),
               "all": ("extract", "fused", "prune_score")}.get(
        args.kernel, (args.kernel,))
    precisions = ("f32", "bf16x3", "bf16") if args.precision == "both" \
        else (args.precision,)
    print(f"tune: sweeping {'+'.join(kernels)} variants at n={n} q={nq} "
          f"a={a} kcs={kcs} reps={reps} "
          f"precisions={'+'.join(precisions)} -> {out_path}",
          flush=True)
    kwargs = {} if space_fn is None else {"space_fn": space_fn}
    winners, rows = [], []
    for kern in kernels:
        if kern == "prune_score":
            # Host f64 scoring has no first-pass precision axis.
            w, r = sweep_prune_score(n, nq, a, reps=reps,
                                     seed=args.seed, out=sys.stdout)
            winners += w
            rows += r
            continue
        for prec in precisions:
            w, r = sweep_extract(n, nq, a, kcs, reps=reps,
                                 seed=args.seed, out=sys.stdout,
                                 kernel=kern, precision=prec, **kwargs)
            winners += w
            rows += r
    if not winners:
        print("tune: FAIL — no variant measured for any kc",
              file=sys.stderr)
        return 1

    import os

    from dmlp_tpu.tune.cache import _current_device_kind
    kind = _current_device_kind()
    try:
        cache = VariantCache.load(out_path) if os.path.exists(out_path) \
            else VariantCache()
    except Exception:
        cache = VariantCache()  # unreadable/stale-schema file: rebuild
    for w in winners:
        cache.put(kind, w["b"], w["kc"], w["variant"], a=a,
                  dtype="float32",
                  kernel={"fused": "fused_topk",
                          "prune_score": "prune_score"}.get(
                      w["kernel"], "extract_topk"),
                  precision=w.get("precision", "f32"),
                  measured_ms=w["measured_ms"],
                  swept=w["swept"], shape=(w["qb"], w["b"], a))
    cache.save(out_path)
    clear_lookup_memo()  # this process sees its own fresh winners
    VariantCache.validate_doc(cache.to_dict())

    if args.record:
        from dmlp_tpu.obs.run import RunRecord
        RunRecord(kind="tune", tool="dmlp_tpu.tune",
                  config={"n": n, "q": nq, "a": a, "kcs": list(kcs),
                          "reps": reps, "device_kind": kind,
                          "smoke": bool(args.smoke)},
                  metrics={"winners": winners, "sweep_rows": rows},
                  artifacts={"cache": out_path}).write(args.record)

    print(json.dumps({"device_kind": kind, "cache": out_path,
                      "entries": len(cache.entries),
                      "winners": [{"kernel": w["kernel"], "kc": w["kc"],
                                   "b": w["b"], "variant": w["variant"],
                                   "precision": w.get("precision",
                                                      "f32")}
                                  for w in winners]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
