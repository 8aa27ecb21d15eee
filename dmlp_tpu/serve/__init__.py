"""Online serving layer — the millions-of-users path (ROADMAP).

Everything else in the repo is batch: one stdin parse, one solve, exit —
every request pays parse + staging + jit warm-up. This package is the
persistent daemon (``python -m dmlp_tpu.serve``) that pays those costs
ONCE and then serves query streams at throughput:

- :mod:`dmlp_tpu.serve.engine` — :class:`ResidentEngine`: the corpus is
  parsed and staged once into a capacity-padded resident device buffer
  behind a row-count mask (incremental ingestion appends rows with NO
  recompilation), and each engine path is compiled once per
  power-of-two (qpad, k) shape bucket (``serve.engine.shape_bucket`` is
  the template) — ahead of the first request when warmed, with the
  compile counter proving steady-state serving never recompiles.
- :mod:`dmlp_tpu.serve.batching` — continuous micro-batching: an
  admission queue coalesces whatever is queued each tick into one
  padded micro-batch so the MXU sees full tiles; per-request results
  are sliced back out bit-identically to the solo solve.
- :mod:`dmlp_tpu.serve.admission` — admission control reads memory
  headroom from the analytic peak-HBM model (obs.memwatch) vs the
  telemetry sampler's live watermark and sheds load BEFORE the
  allocator OOMs (the resilience ladder stays a backstop, not the
  first responder).
- :mod:`dmlp_tpu.serve.daemon` / :mod:`~dmlp_tpu.serve.protocol` — the
  line-JSON TCP daemon with live telemetry (``--telemetry-port`` is
  the scrape surface), periodic serve RunRecords,
  and a graceful SIGTERM drain (in-flight micro-batches finish, the
  final snapshot flushes, no flight-recorder dump on an orderly exit).
- :mod:`dmlp_tpu.serve.client` — the replay client + recorded-trace
  format the bench harness and ``make serve-smoke`` drive.

Responses are byte-identical to the float64 golden oracle on every
path: the resident solves reuse the engines' candidates -> host-f64
finalize -> boundary-hazard repair pipeline unchanged.
"""

# Race-sanitizer hook BEFORE the imports below pull in batching/
# admission/engine (and transitively obs.telemetry + resilience.stats),
# whose import creates module-level locks: `python -m dmlp_tpu.serve`
# executes this __init__ first, so this is the earliest point where
# DMLP_TPU_RACECHECK=1 can wrap the lock factories and have EVERY
# serving-surface lock tracked (telemetry's import-time globals are
# retrofitted by install either way).
import os as _os

if _os.environ.get("DMLP_TPU_RACECHECK") == "1":
    from dmlp_tpu.check import racecheck as _racecheck

    _racecheck.install()

from dmlp_tpu.serve.admission import AdmissionController  # noqa: F401
from dmlp_tpu.serve.batching import MicroBatcher, Request  # noqa: F401
from dmlp_tpu.serve.engine import (CapacityError, ResidentEngine,  # noqa: F401
                                   k_bucket, query_bucket)
