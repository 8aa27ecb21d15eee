# Build system — the analog of the reference's Makefile (mpicxx engine /
# engine.debug targets). Here the compiled artifact is the native input
# parser; the engines are JAX programs compiled by XLA at run time.

CXX ?= g++
CXXFLAGS ?= -O3 -Wall -shared -fPIC

.PHONY: all native test tier1 obs-smoke obs-dist-smoke \
	check lint chaos-smoke telemetry-smoke serve-smoke \
	race-smoke prune-smoke precision-smoke fleet-smoke \
	fleet-chaos-smoke fleet-trace-smoke slo-smoke auto-smoke \
	hlo-smoke fleet-bench clean

all: native

native: native/_fastparse.so

native/_fastparse.so: native/fastparse.cpp
	$(CXX) $(CXXFLAGS) -o $@ $<

test: obs-smoke obs-dist-smoke check lint \
	chaos-smoke telemetry-smoke serve-smoke race-smoke prune-smoke \
	precision-smoke fleet-smoke fleet-chaos-smoke fleet-trace-smoke \
	slo-smoke auto-smoke hlo-smoke
	python -m pytest tests/ -q

# Static analysis + runtime-sanitizer smoke (README "Static analysis &
# sanitizers"): the AST rule families R1-R7 (collective-axis contract,
# recompilation hazards, host-sync hazards, compat-bypass, resilience
# swallowing, metric names, concurrency discipline) over the whole
# package, gated by check_baseline.json — the committed baseline is EMPTY,
# so ANY finding fails. Results are cached per file content hash under
# ~/.cache/dmlp_tpu/check, so re-runs only re-analyze changed files
# (--no-cache opts out). Then the runtime half: bench config 1 through the
# real CLI under DMLP_TPU_SANITIZE=1 (jax.transfer_guard("disallow") +
# jax.checking_leaks active around the solve) must complete with contract
# stdout byte-identical to the plain run — the hot path is transfer-clean
# end to end, with only the annotated explicit device_get fences reading
# back.
check:
	mkdir -p outputs
	JAX_PLATFORMS=cpu python -m dmlp_tpu.check
	JAX_PLATFORMS=cpu python -c "from dmlp_tpu.bench.configs import BENCH_CONFIGS; \
	from dmlp_tpu.bench.harness import ensure_input; \
	ensure_input(BENCH_CONFIGS[1], 'inputs')"
	JAX_PLATFORMS=cpu DMLP_TPU_SANITIZE= python -m dmlp_tpu \
	  < inputs/input1.in \
	  > outputs/check_plain.out 2> outputs/check_plain.err
	rm -f outputs/check_sanitized_metrics.jsonl
	JAX_PLATFORMS=cpu DMLP_TPU_SANITIZE=1 python -m dmlp_tpu \
	  --trace outputs/check_sanitized_trace.json \
	  --metrics outputs/check_sanitized_metrics.jsonl \
	  < inputs/input1.in \
	  > outputs/check_sanitized.out 2> outputs/check_sanitized.err
	grep -q "Time taken:" outputs/check_sanitized.err
	cmp outputs/check_plain.out outputs/check_sanitized.out
	python tools/check_trace.py outputs/check_sanitized_trace.json \
	  outputs/check_sanitized_metrics.jsonl

# Generic hygiene (the conservative ruff subset, pyproject [tool.ruff]):
# ruff when the environment has it, plus the checker's built-in R0
# family either way — this container ships no ruff, so R0 IS the gate
# here, over the package, tools and tests.
lint:
	@if command -v ruff >/dev/null 2>&1; then \
	  ruff check dmlp_tpu tools tests; \
	else \
	  echo "ruff not installed; R0 family covers the same rule set"; \
	fi
	JAX_PLATFORMS=cpu python -m dmlp_tpu.check --families R0 \
	  --no-baseline dmlp_tpu tools tests

# Tier-1 no-regression guard (ROADMAP "Tier-1 verify"): on this
# container's jax (0.4.37, CPU backend) the suite must hold >= 277
# passed with the failure set no worse than PR 2's 11 environment-limited
# cases (6 multi-process spawn + 3 offload + 1 multipass-semantics +
# 1 offload-loop — all pre-existing jax/container limits, none
# engine-correctness). Run before merging anything that touches the
# engines, the kernels, or obs.
tier1:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
	  --continue-on-collection-errors

# Observability smoke: run bench config 1 through the real CLI with
# --trace/--metrics on CPU, then validate the artifacts' structural
# contract (Perfetto-loadable spans; summary record with cost-analysis
# counters or the explicit counters_unavailable marker).
obs-smoke:
	mkdir -p outputs
	JAX_PLATFORMS=cpu python -c "from dmlp_tpu.bench.configs import BENCH_CONFIGS; \
	from dmlp_tpu.bench.harness import ensure_input; \
	ensure_input(BENCH_CONFIGS[1], 'inputs')"
	rm -f outputs/obs_metrics.jsonl
	JAX_PLATFORMS=cpu python -m dmlp_tpu --trace outputs/obs_trace.json \
	  --metrics outputs/obs_metrics.jsonl < inputs/input1.in \
	  > outputs/obs_smoke.out 2> outputs/obs_smoke.err
	grep -q "Time taken:" outputs/obs_smoke.err
	python tools/check_trace.py outputs/obs_trace.json outputs/obs_metrics.jsonl

# Distributed-observability smoke: a 2-process CPU cluster (emulated
# ranks where the jax build lacks multi-process CPU computations) runs
# the contract entry with per-rank tracing; tools/merge_traces.py merges
# the rank files and tools/check_trace.py --dist validates the merged
# Perfetto trace (distinct rank pids, clock-sync markers, monotonic
# per-rank timestamps).
obs-dist-smoke:
	JAX_PLATFORMS=cpu python tools/obs_dist_smoke.py --dir outputs/dist_obs

# Chaos smoke (README "Resilience & chaos testing"): bench config 1 and
# a short --nan-guard train run replayed under three seeded fault
# schedules (straggler delays, transient exceptions + corrupt parse,
# simulated RESOURCE_EXHAUSTED driving the degradation ladder). Every
# faulted run's output must be BYTE-IDENTICAL to the fault-free golden
# run, faults must actually fire, recovery must be visible in the
# resilience counters and resilience.* trace events, one schedule must
# replay with a bit-identical injection log, and the zero-fault overhead
# of the wrappers is measured with an interleaved on/off A/B into a
# RunRecord.
chaos-smoke:
	mkdir -p outputs/chaos
	rm -f outputs/chaos/CHAOS_SMOKE.jsonl
	JAX_PLATFORMS=cpu python tools/chaos_run.py --smoke \
	  --out outputs/chaos --record outputs/chaos/CHAOS_SMOKE.jsonl

# Live-telemetry smoke (README "Live telemetry, memory watermarks &
# flight recorder"): bench config 1 through the real CLI in interleaved
# --telemetry on/off pairs — contract stdout byte-identical, the
# OpenMetrics snapshot structurally valid (with the honest
# mem.stats_unavailable gauge on this CPU backend), the analytic
# peak-HBM model reconciled against the measured watermark within the
# documented basis bounds (or the explicit marker), a FLIGHT_*.json
# post-mortem left by a retries-exhausted fault, and the overhead +
# watermark numbers written as a kind="telemetry" RunRecord with raw
# per-arm samples.
telemetry-smoke:
	mkdir -p outputs/telemetry
	rm -f outputs/telemetry/TELEMETRY_SMOKE.jsonl
	JAX_PLATFORMS=cpu python tools/telemetry_smoke.py \
	  --out outputs/telemetry \
	  --record outputs/telemetry/TELEMETRY_SMOKE.jsonl

# Online-serving smoke (README "Serving"): the real daemon subprocess
# on a scratch corpus — warmed shape buckets with the cold-start number
# in the ready file, a mixed-(nq, k) trace replayed over concurrent
# connections with every response byte-identical to the golden oracle,
# the compile counter pinned across the replay (no per-request
# recompilation), a valid OpenMetrics scrape from --telemetry-port, an
# injected memory squeeze shed by admission control (visible rejection,
# no ladder degradation), wire ingestion verified against the grown
# corpus, and a SIGTERM drain that exits 0 with no flight dump — with
# the serve RunRecord read back (RunRecord.load).
serve-smoke:
	mkdir -p outputs/serve
	rm -f outputs/serve/SERVE_SMOKE.jsonl
	JAX_PLATFORMS=cpu python tools/serve_smoke.py --out outputs/serve \
	  --record outputs/serve/SERVE_SMOKE.jsonl

# Concurrency-discipline smoke (README "Static analysis & sanitizers",
# rule family R7): the lock-order / guarded-field / blocking-under-lock
# / thread-lifecycle analyzer must be clean over the whole package with
# no baseline, then tools/race_stress.py proves the runtime half — the
# race sanitizer first catches a SEEDED inversion and sleep-under-lock
# (teeth), then the live daemon is hammered by concurrent query +
# ingest + stats + scrape workers with the Sampler and fault injection
# running: every stressed response must be byte-identical to the golden
# oracle and the sanitizer's verdict over the real system must be
# empty (zero inversions, zero blocking calls under a lock).
race-smoke:
	mkdir -p outputs/race
	JAX_PLATFORMS=cpu python -m dmlp_tpu.check --families R7 \
	  --no-baseline
	JAX_PLATFORMS=cpu python tools/race_stress.py --out outputs/race

# Pruned two-stage solve smoke (README "Pruned two-stage solve"): a
# norm-banded corpus through the real CLI in DMLP_TPU_PRUNE=1/0 arms —
# both byte-identical to the f64 golden model, the pruned arm must
# prune > 0.5 of the blocks and stream < 0.5x the dense bytes (read
# from the metrics summary's prune block), scan.bytes_streamed must be
# visible in the OpenMetrics scrape, and a seeded oom schedule must
# step the degrade ladder prune->fused with byte-identical recovery.
# Then the capacity tool's --cpu-smoke proves the same scanned-bytes
# ratio on its banded beyond-HBM stand-in shape.
prune-smoke:
	mkdir -p outputs/prune
	JAX_PLATFORMS=cpu python tools/prune_smoke.py --out outputs/prune
	JAX_PLATFORMS=cpu BENCH_OUT=outputs/prune/CAPACITY_PRUNE_SMOKE.json \
	  python tools/capacity_beyond_hbm.py --cpu-smoke > /dev/null

# Low-precision first-pass smoke (README "Low-precision first pass"):
# on the banded corpus, forced-bf16 and kill-switch-f32 CLI runs must
# be byte-identical to each other and to the f64 golden model; the
# bf16 arm's metrics must show an ACTIVE bf16 pass with a widened
# (kcap-inflated) rescore window; and a seeded staging oom must step
# the degrade ladder lowp->prune with byte-identical recovery.
precision-smoke:
	mkdir -p outputs/precision
	JAX_PLATFORMS=cpu python tools/precision_smoke.py \
	  --out outputs/precision

# Serving-fleet smoke (README "Fleet serving"): a REAL fleet on CPU —
# a plain resident replica + a mesh-resident replica (--mesh 2x1,
# per-shard resident chunk buffers, allgather merge as the micro-batch
# epilogue) behind the `python -m dmlp_tpu.fleet` router. Eight
# proofs: both replicas warm and announce; the committed paced trace
# (inputs/serve_trace2.jsonl) replayed closed-loop THROUGH the router
# is byte-identical to the golden oracle with traffic actually fanned;
# compile counters stay flat on both replicas; paced OPEN-LOOP replay
# at two offered-load multipliers lands p50/p95/p99 in one
# kind="fleet" RunRecord a level; a wide-k request (k past the kernel's
# single-pass window) serves through the multipass driver against the
# resident chunks, golden and compile-flat; one ingest through the
# router fans out to every replica and the grown-corpus replay stays
# golden with zero new compiles; the router's /metrics merges both
# replicas' scrapes into one valid OpenMetrics exposition (counters
# summed, histograms bucket-wise, per-replica gauges) and the serve
# trace validator rejects non-monotonic t_ms; one in-band drain
# propagates router -> replicas with every process exiting 0 and no
# flight dumps.
fleet-smoke:
	mkdir -p outputs/fleet
	rm -f outputs/fleet/FLEET_SMOKE.jsonl
	JAX_PLATFORMS=cpu python tools/fleet_smoke.py --out outputs/fleet \
	  --record outputs/fleet/FLEET_SMOKE.jsonl

# Self-healing-fleet chaos smoke (README "Fleet self-healing"): three
# seeded failure campaigns over REAL fleets on CPU, every served
# response byte-identical to the golden oracle throughout. (1) A
# SUPERVISED fleet (the router spawns/owns 2 mesh-2x1 replicas): one
# replica SIGKILLed mid-replay — every in-flight response still golden
# via bounded retry, the supervisor detects the death and relaunches
# within its budget, the revived fleet serves golden. (2) Far-row
# ingest pushes both replicas past the capacity-buffer threshold while
# open-loop traffic keeps firing: the supervisor stages one shard
# re-split at a time (grown-layout replacement, checksum-verified
# corpus replay, routing-table swap, old replica drained rc 0) until
# the whole fleet runs the doubled capacity — zero lost responses,
# post-split replay golden on the grown corpus. (3) A seeded
# serve.ingest transient fault (the PR 7 injection machinery) drops
# one replica's ingest: the router reports the divergence, the health
# prober's corpus-checksum comparison detects it, and the targeted
# delta re-ingest repairs it — counters non-vacuous, repaired fleet
# golden, every process exits 0, no flight dumps. Each campaign
# writes one kind="fleet" RunRecord, read back at the end.
fleet-chaos-smoke:
	mkdir -p outputs/fleet_chaos
	rm -f outputs/fleet_chaos/FLEET_CHAOS_SMOKE.jsonl
	JAX_PLATFORMS=cpu python tools/fleet_chaos_smoke.py \
	  --out outputs/fleet_chaos \
	  --record outputs/fleet_chaos/FLEET_CHAOS_SMOKE.jsonl

# Request-tracing smoke (README "Request tracing & tail attribution"):
# five proofs over a REAL 2-replica fleet on CPU. (1) Untraced arm:
# responses carry no rid and checksum golden. (2) Traced arm (x2 + x8
# open-loop replay, rid-stamped client + traced router + replicas):
# every rid echoed, contract checksums BYTE-IDENTICAL to the untraced
# arm. (3) merge_traces --fleet clock-aligns the four per-process
# traces and reconstructs one x8 request client->route->hop->
# queue->coalesce->solve->finalize->write, phase sums reconciling with
# client latency within tolerance. (4) check_trace --fleet passes the
# merged trace and REJECTS a tampered one (fabricated retry hop).
# (5) tail_attrib names each level's dominant phase and writes its
# per-level phase p99s as kind="fleet" RunRecords.
fleet-trace-smoke:
	mkdir -p outputs/fleet_trace
	rm -f outputs/fleet_trace/TAILATTRIB.jsonl
	JAX_PLATFORMS=cpu python tools/fleet_trace_smoke.py \
	  --out outputs/fleet_trace \
	  --record outputs/fleet_trace/TAILATTRIB.jsonl

# Streaming SLO engine smoke (README "SLO objectives & predictive
# autoscaling"): (1) a seeded breach on a deterministic clock fires
# exactly one ok->pending->firing->ok alert cycle with the
# FLIGHT_slo_breach_* dump + slo_* OpenMetrics families; (2) a
# predictive-vs-reactive ramp A/B over a real supervised fleet — the
# serve.solve delay fault makes replica capacity sleep-bound, the
# reactive watermark arm rides one replica into a p99 breach (its
# slo.alert stream validated by check_trace --fleet after the causal
# merge) while the predictive arm follows the canary burn rate and
# scales ahead of the hot level with zero customer-objective burn;
# both arms byte-identical to the golden oracle, one kind="slo" ramp
# RunRecord an arm.
slo-smoke:
	mkdir -p outputs/slo
	rm -f outputs/slo/SLO_SMOKE.jsonl
	JAX_PLATFORMS=cpu python tools/slo_smoke.py \
	  --out outputs/slo \
	  --record outputs/slo/SLO_SMOKE.jsonl

# Compiler-sharded engine smoke (README "Compiler-driven sharding &
# persistent compile cache"): the `--engine auto` CLI alias end-to-end
# on bench input 1 — contract stdout byte-identical to the default
# single-chip run. The warm-relaunch cold-start check (persistent
# compile cache) lives in fleet-chaos-smoke campaign 4.
auto-smoke:
	mkdir -p outputs/auto
	JAX_PLATFORMS=cpu python -c "from dmlp_tpu.bench.configs import BENCH_CONFIGS; \
	from dmlp_tpu.bench.harness import ensure_input; \
	ensure_input(BENCH_CONFIGS[1], 'inputs')"
	JAX_PLATFORMS=cpu python -m dmlp_tpu < inputs/input1.in \
	  > outputs/auto/single.out 2> /dev/null
	JAX_PLATFORMS=cpu python -m dmlp_tpu --engine auto \
	  < inputs/input1.in \
	  > outputs/auto/auto.out 2> outputs/auto/auto.err
	grep -q "Time taken:" outputs/auto/auto.err
	cmp outputs/auto/single.out outputs/auto/auto.out

# Compiled-program introspection smoke (README "Compiler
# introspection"): bench input 1 through the real CLI per engine mode
# (sharded / ring / auto) with --hlo-report — contract stdout
# byte-identical to the plain run; the sharded engine's compiled
# all-gather bytes and the ring engine's compiled collective-permute
# bytes (while-loop trip counts folded in) reconcile against their own
# analytic comms models within COMMS_RATIO_BOUNDS; the auto (GSPMD)
# engine's report names at least one partitioner-chosen collective
# with nonzero per-mesh-axis bytes and exactly-reconciling gspmd_*
# records; the memory leg carries hlo_peak_bytes or the explicit
# hlo_memory_unavailable marker; and each mode writes one kind="hlo"
# RunRecord.
hlo-smoke:
	mkdir -p outputs/hlo
	JAX_PLATFORMS=cpu python tools/hlo_smoke.py --out outputs/hlo

# Fleet load sweep (not in `make test`; CPU rehearsal of the fleet
# path, not a performance record: PERF.md): 2 replicas (one
# mesh-resident) + router, the paced trace replayed OPEN-LOOP at a
# sweep of offered-load multipliers, 3 reps per level.
# On a TPU host drop JAX_PLATFORMS and add
# --replica-flags "--pallas --select extract".
fleet-bench:
	mkdir -p outputs/fleet_bench
	JAX_PLATFORMS=cpu python tools/fleet_bench.py \
	  --metrics outputs/fleet_bench/FLEET_BENCH.jsonl \
	  --out outputs/fleet_bench --replicas 2 --mesh-replica --reps 3

clean:
	rm -f native/_fastparse.so
