"""Serving fleet — both axes of scale on top of the serving layer.

The PR 10 daemon is a single-chip server: serveable corpus is capped by
one chip's HBM and throughput by one replica. This package is the
fleet layer that removes both caps, plus the honest-latency harness
the ROADMAP calls for:

- :mod:`dmlp_tpu.fleet.mesh_engine` — :class:`MeshResidentEngine`:
  the resident serving engine over the 2D mesh. The corpus is staged
  ONCE into per-shard capacity-padded resident chunk buffers
  (``P("data", None)``), per-(shard, chunk) block summaries stay
  resident for the pruned two-stage solve, and every micro-batch runs
  the mesh engines' chunk-fold programs with the existing
  allgather/ring candidate merge as the epilogue — serveable corpus
  size passes one chip's HBM while every response stays byte-identical
  to the solo solve and the golden oracle. Ingest routes rows to their
  owning shard's buffers with zero solve recompilation (chunk arrays
  and ``[n, toff, shard_rows]`` scalars are data inputs, never shapes).
- :mod:`dmlp_tpu.fleet.router` — the thin line-JSON TCP front end
  fanning requests across N daemon replicas: per-replica health/drain
  awareness, bounded retry-on-replica-failure via the resilience
  classification (queries only — they are idempotent reads; admission
  sheds propagate as explicit rejections, never retries), ingest
  fan-out to every replica, and one aggregated fleet OpenMetrics view
  over the per-replica telemetry scrapes.
- :mod:`dmlp_tpu.fleet.scrape` — the OpenMetrics merge: counters sum,
  log-bucket histograms merge bucket-wise, gauges keep per-replica
  labels; the merged exposition passes ``validate_openmetrics``.
- :mod:`dmlp_tpu.fleet.loadgen` — the open-loop SLO harness: paced
  replay fires requests ON SCHEDULE regardless of completions (queue
  delay lands in the measured latency), swept over offered-load
  multipliers into one kind="fleet" RunRecord a level — the
  p99-under-offered-load curve, not just closed-loop throughput.
- :mod:`dmlp_tpu.fleet.autoscale` — the self-healing half's lifecycle
  owner: a router-side supervisor that spawns/retires replica daemons
  against the probed load, detects crashed/hung replicas
  (process-exit + probe-dead deadline), relaunches within a bounded
  budget, and degrades to a smaller fleet when it runs out.
- :mod:`dmlp_tpu.fleet.reshard` — the staged shard re-split: when
  ingest approaches a replica's capacity-padded buffer limit, a
  grown-layout replacement is spawned, the corpus replayed into it
  checksum-verified, the routing table swapped, and the old replica
  drained — growth past the fixed resident layout with zero dropped
  or wrong responses.
- :mod:`dmlp_tpu.fleet.consistency` — checksum-driven repair: rolling
  per-engine corpus signatures (layout-independent), divergence
  diagnosis across replicas, and targeted delta re-ingest via
  idempotent global-row-id-keyed writes; unrepairable divergence
  escalates to quarantine.

``python -m dmlp_tpu.fleet`` runs the router — static over existing
replicas, or SUPERVISED (``--spawn-corpus``) where it owns the whole
replica lifecycle (see :mod:`dmlp_tpu.fleet.__main__`); ``make
fleet-smoke`` proves the serving stack end to end against the golden
oracle and ``make fleet-chaos-smoke`` proves the self-healing under
seeded kills, injected ingest divergence, and a forced re-split.
"""

# Same early racecheck hook as dmlp_tpu.serve: `python -m dmlp_tpu.fleet`
# executes this __init__ before the router/engine imports create any
# serving locks, so DMLP_TPU_RACECHECK=1 tracks the full fleet surface.
from dmlp_tpu.check import racecheck as _racecheck

_racecheck.install_from_env()
