"""Engine configuration.

The reference spreads configuration over three mechanisms (survey §5.6):
compile-time ``-DDEBUG`` (Makefile:14), the generator's argparse CLI
(generate_input.py:27-40), and hardcoded SLURM configs in run_bench.sh:77-162.
Here everything is one dataclass; problem-size parameters still travel in-band
as the input header (common.cpp:12-15), exactly like the reference.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


#: what a corpus may be ranked by (EngineConfig.score): THE single
#: definition
SCORES = ("l2", "ip", "cosine")

#: the forms the extraction kernel has (its ``score`` static,
#: ops.pallas_extract). "cosine" is none of them: it is the "ip" form
#: over operands normalised at staging (kernel_score)
KERNEL_SCORES = ("l2", "ip")


def kernel_score(score: str) -> str:
    """The kernel form that orders a corpus ranked by ``score``: its
    own for "l2" and "ip"; "ip" for "cosine", whose staged rows and
    queries are x / |x| and q / |q|, so that -q^.x^ is -s."""
    return "ip" if score == "cosine" else score


def score_of(engine) -> str:
    """What ``engine`` ranks by: its configuration's score, squared L2
    for an object that carries no configuration (a test's stand-in)."""
    return getattr(getattr(engine, "config", None), "score", "l2")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Configuration for the KNN engines.

    Attributes:
      mode: "single" | "sharded" | "ring" | "auto" — which engine to
        run. "single" is the one-chip engine; "sharded" is the 2D-mesh
        all-gather-merge engine (analog of the reference's grid +
        MPI_Gather merge, engine.cpp:40-57,282-308); "ring" streams data
        shards around the mesh ring with a running top-k (the
        long-context / memory-bounded variant); "auto" is the
        compiler-sharded engine (engine.auto): the same solve as pure
        jit + NamedSharding constraints, with GSPMD choosing the
        collective schedule instead of the hand-written merges.
      mesh_shape: (data_axis_size, query_axis_size). None = auto from
        available devices (mirrors MPI_Dims_create at engine.cpp:41).
      data_block: data points processed per inner step on one chip.
        Bounds the live distance-tile to query_block x data_block.
        None = pick per select strategy (2048 for "sort", whose per-step
        cost grows superlinearly in the block; 65536 for "topk", which
        prefers large tiles).
      query_block: queries processed per outer step.
      dtype: on-device staging/distance dtype ("auto", "float32" or
        "bfloat16"). The reference computes in float64 (engine.cpp:12);
        TPU MXU is f32/bf16, so strict-parity runs add host rescoring
        (``exact``). "auto" resolves to bfloat16 on TPU backends in exact
        mode — staging in bf16 halves the host->device bytes while the
        f64 rescore + the tie-overflow repair keep results identical
        (speed on the chip: not measured) — and to float32
        everywhere else (CPU bf16 is emulated and slower; fast mode's
        output IS the device ordering, so it never changes dtype
        implicitly).
      exact: if True, rescore the top-(k+margin) candidates on host in
        float64 and re-select — restores float64 ordering (and hence
        checksum parity with the golden model) while keeping the O(Q*N*A)
        work on the MXU.
      margin: extra candidates (beyond max-k) carried to the host rescore.
      select: device k-selection strategy. "sort" = strict total-order
        multi-operand sort (reference tie semantics on device, slow);
        "topk" = ``lax.top_k`` partial reduce, ~4x faster but
        tie-order-blind — engines detect candidate lists where a
        distance-tie group hit the boundary and recompute those queries
        exactly on host (engine.finalize.boundary_overflow), so ``run()``
        parity holds on either path; "seg" = segment-min threshold
        selection (ops.topk.step_seg): reduces each 128-column segment
        to its min and runs top_k on ~(k+16)*128 gathered candidates
        instead of the whole tile — exact by distance, with an in-jit
        fallback to "topk" when segment-min ties make the threshold
        inconclusive; "extract" = fused Pallas distance + in-VMEM
        iterative-extraction running top-k (ops.pallas_extract) — the
        distance tile never reaches HBM; exact by distance with the same
        lowest-position tie behavior (and host repair) as "topk";
        "auto" = "sort" for small inputs (ties can be adversarial there,
        cost is negligible), then "extract" with use_pallas (fastest,
        119 ms vs 231/400 at the benchmark shape on v5e) or "topk"
        without, once the padded dataset exceeds AUTO_SELECT_THRESHOLD.
      debug: human-readable output instead of checksums — the -DDEBUG
        build of the reference (common.cpp:72-78).
      use_pallas: use the fused Pallas distance kernel where available.
      precision: FIRST-PASS dot precision for the extract-path kernels
        ("auto" | "f32" | "bf16"). "f32" is a float32 cross term. In
        exact mode at float32 staging the kernel computes it from bf16
        halves of its operands in THREE MXU passes with f32
        accumulation (the "bf16x3" form, ops.pallas_extract._dot_cross)
        where one ``HIGHEST`` dot makes Mosaic spend six; what the
        three drop is at most 2^-15 of the scale a distance
        (engine.finalize.LOWP_COEF). Fast mode, whose device ordering
        IS the answer, keeps the one ``HIGHEST`` dot. Operands staged
        in bfloat16 (a bf16 value has no low half to split) reach the
        kernel AS bfloat16 and take ONE pass in either mode, still
        under the name "f32": bf16 x bf16 products are exact in
        float32 and the MXU accumulates in float32, so the pass is the
        ``HIGHEST`` dot's value from the same operands and drops
        nothing (ops.pallas_extract.mxu_passes says which dot a
        staging and a form give: 1, 3 or 6; the engines stamp it).
        "bf16" casts the streamed q/d tiles before the MXU dot: ONE
        pass. Either way the engines widen every candidate window /
        prune threshold / hazard test by the form's analytic
        engine.finalize.lowp_eps bound so the unchanged f64 rescore +
        boundary repair keeps results byte-identical to the f32 dense
        scan. "bf16" is active only in exact mode on the resilience
        ladder's top "lowp" rung (fast mode has no repair backstop).
        "auto" resolves to "f32" (the one-pass form is opt-in).
        $DMLP_TPU_PRECISION overrides at resolve time ("f32" = kill
        switch, "bf16" = force). int8 is the gated follow-on (ROADMAP):
        its bound needs data-dependent quantization scales.
      boundary_retry: where the serving engine repairs a query the
        boundary-hazard test flagged. True (the default): the flagged
        queries of a micro-batch are solved again ON THE DEVICE over
        the resident stack at the kernel's widest single-pass window
        (serve.engine.ResidentEngine._retry_begin) and only what that
        window does not clear goes to the host oracle
        (engine.finalize.repair_boundary_overflow: a pass over the
        whole float64 host corpus on the batcher thread, 13 s a batch
        at 10^7 x 128 rows). False: the oracle alone, and no retry
        program compiled or priced. Answers are identical either way.
        A batch solve and a mesh daemon have the oracle alone whatever
        this says (they keep nothing resident to fold again, or no
        one-chip stack).
      score: what a corpus is ranked by, "l2" (the default: smallest
        squared Euclidean distance) or "ip" (LARGEST inner product
        s(q, x) = sum_a q_a x_a, float64; neighbours by (s descending,
        id DESCENDING on ties) or "cosine" (LARGEST s(q, x) = q.x /
        (|q| |x|), 0 against a zero vector, same order; reported as the
        angular distance 1 - s); golden.reference has the contracts.
        A property of the corpus, never of a request: every program an
        engine compiles is keyed on it, and inside the program the
        ordered quantity under "ip" and "cosine" is -s so that every
        list still ascends. The two serving engines' extract paths
        (serve.engine.ResidentEngine on one chip,
        fleet.mesh_engine.MeshResidentEngine over a mesh) and the
        golden model have the "ip" and "cosine" forms (cosine is the ip
        kernel over rows and queries normalised in float64 at staging:
        kernel_score). The batch engines (engine.single's solve,
        engine.sharded's ShardedEngine and RingEngine, engine.auto's
        AutoShardedEngine) and the multi-host feed
        (parallel.distributed) refuse them at construction by name
        (require_score), as does a serving engine's bucket off the
        extract path (the streaming select, the multipass driver, the
        mesh engine's monolithic stream path): none answers an ip or
        cosine corpus in L2.
    """

    AUTO_SELECT_THRESHOLD = 8192

    mode: str = "single"
    mesh_shape: Optional[Tuple[int, int]] = None
    data_block: Optional[int] = None
    query_block: int = 1024
    dtype: str = "auto"
    exact: bool = True
    margin: int = 16
    select: str = "auto"
    debug: bool = False
    use_pallas: bool = False
    precision: str = "auto"
    boundary_retry: bool = True
    score: str = "l2"

    def __post_init__(self) -> None:
        if self.score not in SCORES:
            raise ValueError(f"unknown score {self.score!r} "
                             f"(one of {SCORES})")
        if self.mode not in ("single", "sharded", "ring", "auto"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.dtype not in ("auto", "float32", "bfloat16"):
            raise ValueError(f"unsupported dtype {self.dtype!r}")
        if self.precision not in ("auto", "f32", "bf16"):
            raise ValueError(
                f"unsupported first-pass precision {self.precision!r} "
                "(int8 is the gated follow-on — see ROADMAP)")
        if self.select not in ("auto", "sort", "topk", "seg", "extract"):
            raise ValueError(f"unknown select {self.select!r}")
        if (self.data_block is not None and self.data_block <= 0) \
                or self.query_block <= 0:
            raise ValueError("block sizes must be positive")
        if self.margin < 0:
            raise ValueError("margin must be >= 0")

    def require_score(self, engine: str,
                      scores: Tuple[str, ...] = ("l2",)) -> None:
        """Refuse, by name, an engine that lacks this configuration's
        score: ``engine`` ranks by ``scores`` alone (squared L2 unless
        it says otherwise), and answering an inner-product or a cosine
        corpus in L2 would be a wrong answer, not a slow one."""
        if self.score not in scores:
            raise ValueError(
                f"{engine} has no score={self.score!r} form (it ranks by "
                f"{' | '.join(scores)}): serve an inner-product or a "
                "cosine corpus through a daemon's extract path (python -m "
                f"dmlp_tpu.serve --pallas --score {self.score}, on one "
                "chip or with --mesh RxC) or the golden model (--engine "
                "golden)")

    def resolve_dtype(self) -> str:
        """Concrete staging dtype ("float32" | "bfloat16") for this run.

        Resolved at engine construction (first backend touch), not in
        __post_init__, so building a config never initializes JAX.
        """
        if self.dtype != "auto":
            return self.dtype
        if not self.exact:
            return "float32"
        import jax
        return ("bfloat16" if jax.devices()[0].platform == "tpu"
                else "float32")

    def f32_form(self, staging: str | None = None) -> str:
        """The form a float32 first pass takes ("bf16x3" | "f32"):
        three bf16 MXU passes over split operands where a float64
        rescore and a repair stand behind the pass (exact mode), the
        operands have a low half to split (``staging`` "float32"; the
        engine's, resolve_dtype() when None) and the backend's compiler
        makes the split as written (ops.pallas_extract.split_holds:
        one small kernel on the device, once a process); else "f32",
        a cross term exact to float32 accumulation: the one
        ``HIGHEST`` dot over float32 operands, ONE pass over operands
        staged in bfloat16 (the kernel is handed the bf16 block:
        ops.pallas_extract._dot_cross)."""
        if not self.exact:
            return "f32"
        if staging is None:
            staging = self.resolve_dtype()
        if staging != "float32":
            return "f32"
        from dmlp_tpu.ops.pallas_extract import split_holds
        return "bf16x3" if split_holds() else "f32"

    def resolve_precision(self, staging: str | None = None,
                          allow_bf16: bool = True) -> str:
        """Concrete first-pass form ("f32" | "bf16x3" | "bf16") for
        this run, env override included: ``$DMLP_TPU_PRECISION`` wins
        when set to a legal value ("f32" doubles as the kill switch,
        "bf16" forces the one-pass form on), else the configured value,
        with "auto" resolving to "f32"; and "f32" means f32_form's
        answer for ``staging`` (an engine passes its own), which is
        also what "bf16" gives way to where the caller cannot run it
        (``allow_bf16`` False: off the ladder's top rung, or under
        windows planned for another form). Read per
        call (no import-time snapshot) so tests and operators can flip
        the env without re-imports — the engines resolve it OUTSIDE
        every jit and key their compiled programs on the result (R2
        discipline). Fast mode always runs "f32" (the one ``HIGHEST``
        dot; one exact pass over bfloat16 rows): a form that drops
        products is only sound with the f64 rescore + boundary repair
        behind it."""
        import os
        if not self.exact:
            return "f32"
        env = os.environ.get("DMLP_TPU_PRECISION")
        prec = env if env in ("f32", "bf16") else self.precision
        if prec == "bf16" and allow_bf16:
            return "bf16"
        return self.f32_form(staging)

    def resolve_select(self, padded_rows: int) -> str:
        """Concrete selection strategy for a dataset of ``padded_rows``."""
        if self.select != "auto":
            return self.select
        if padded_rows <= self.AUTO_SELECT_THRESHOLD:
            return "sort"
        # Measured on TPU v5e (204800x10240x64, k=40): "extract" (fused
        # distance + in-VMEM iterative extraction, ops.pallas_extract)
        # 119 ms; "seg" with the fused producer 231 ms; XLA "topk" ~400 ms.
        # The engines gate "extract" on pallas_extract.supports() per shape
        # and fall back to "seg"/"topk" when it cannot tile.
        return "extract" if self.use_pallas else "topk"

    def resolve_streaming_select(self, padded_rows: int) -> str:
        """Like resolve_select, for paths that fold blocks with arbitrary
        (non-affine) id arrays — the chunk-fold driver's fallback, the
        multi-host per-shard programs: the extraction kernel needs
        affine per-shard ids, so "extract" maps to the best array-ids
        strategy there. Paths that DO satisfy the affine-ids contract
        (engine.single's chunk loop, the mesh engines' contiguous shards)
        run "extract" natively and legitimately record it as
        _last_select; every run() tie-repair gate lists "extract"
        alongside "topk"/"seg" (same tie semantics)."""
        select = self.resolve_select(padded_rows)
        if select == "extract":
            from dmlp_tpu.ops.topk import streaming_fallback
            return streaming_fallback(self.use_pallas)
        return select

    def resolve_granule(self, select: str) -> int:
        """data_block granularity: whole 1024-column Pallas tiles for the
        fused seg producer, whole 128-column segments for XLA seg, whole
        extraction blocks (pallas_extract.BLOCK_ROWS) for "extract",
        8 rows otherwise (must stay in sync with
        ops.pallas_distance/pallas_extract supports)."""
        if select == "seg":
            return 1024 if self.use_pallas else 128
        if select == "extract":
            # Whole extraction blocks (ops.pallas_extract._TN): a merely
            # lane-divisible size can have no large divisor (200000 pads to
            # 512*391, 391 = 17*23, so the largest tileable block would be
            # 512 — measured 4x slower). Padding to whole blocks wastes
            # < _TN sentinel rows (~2% at the benchmark shape) and keeps
            # the block size maximal.
            from dmlp_tpu.ops.pallas_extract import BLOCK_ROWS
            return BLOCK_ROWS
        return 8

    def resolve_data_block(self, select: str) -> int:
        if self.data_block is not None:
            return self.data_block
        return 65536 if select in ("topk", "seg", "extract") else 2048
