"""Unified observability: span traces, XLA cost counters, collective
traffic accounting, and versioned run artifacts.

The reference's entire observability surface is one ``Time taken: <ms> ms``
stderr line (common.cpp:130). That contract line stays byte-identical
(utils.timing); this package is everything on top of it, unified so the
engines, the train loop, and the bench harness stop inventing private
timing/metrics schemas:

- :mod:`dmlp_tpu.obs.trace` — lightweight span tracer exporting
  Chrome-trace / Perfetto-loadable JSON, with an optional bridge to
  ``jax.profiler`` annotations on real TPUs.
- :mod:`dmlp_tpu.obs.dist_trace` — the multi-process half: per-rank
  tracers (rank = Perfetto pid) writing ``trace-rank<NN>.json`` with
  barrier-stamped clock-sync markers; ``tools/merge_traces.py`` merges
  the rank files into one aligned multi-process trace.
- :mod:`dmlp_tpu.obs.counters` — static per-dispatch FLOPs / HBM-bytes
  counters from XLA's ``compiled.cost_analysis()``, with an
  achieved-vs-peak roofline summary.
- :mod:`dmlp_tpu.obs.kernel_cost` — analytic FLOPs/bytes models for the
  Pallas kernels (which expose no XLA cost model); the counters probe
  resolves registered kernels through these instead of reporting
  ``counters_unavailable``.
- :mod:`dmlp_tpu.obs.comms` — analytic collective-traffic accounting
  (bytes per mesh axis for the all-gather merge, the ring ``ppermute``
  merge, grad ``psum``, the MoE all-to-all, and the pipeline's
  activation ``ppermute``).
- :mod:`dmlp_tpu.obs.run` — the versioned :class:`RunRecord` artifact
  writer all emitters share, and the device stamp a solving process
  hands its parent.
- :mod:`dmlp_tpu.obs.telemetry` — the LIVE half: a process-wide
  thread-safe metrics registry (counters / gauges / log-bucket
  streaming histograms with bounded-error p50/p95/p99), a background
  device-memory sampler, OpenMetrics file/HTTP export
  (``--telemetry``), and the crash flight recorder (bounded
  span/event ring dumped as ``FLIGHT_*.json`` on crash, fatal fault,
  or SIGTERM). The resilience counters write through this registry —
  one source of truth for live scrapes and end-of-run blocks.
- :mod:`dmlp_tpu.obs.memwatch` — device-memory watermarks: the
  analytic peak-HBM resident-set model per engine/config (the comms
  model's missing memory sibling), measured bases
  (``memory_stats()`` / live-array bytes, with the explicit
  ``mem_stats_unavailable`` marker), and their reconciliation under
  documented per-basis tolerance bounds.
- :mod:`dmlp_tpu.obs.hlo` — compiled-program introspection: the
  collectives, fusions and memory of an engine's compiled HLO text
  (``--hlo-report``), reconciled against the comms and memory models.
- :mod:`dmlp_tpu.obs.slo` — the streaming SLO engine: objectives over
  registry histograms, burn rates, alert state and the breach flight
  dump the fleet's autoscaler reads.

None of this is the performance record: speed is measured on the chip
by ``python3 -m benchmark.run`` and recorded by the driver in
``PERF_LEDGER.jsonl`` (PERF.md).

Every module here is import-light: none of them import jax at module
level, so the CLI's fast startup path is unaffected when observability is
off, and the no-op span/probe hooks in the engine hot paths cost one
module-global read each.
"""

from dmlp_tpu.obs.run import SCHEMA_VERSION, RunRecord  # noqa: F401
