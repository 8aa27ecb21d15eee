"""Versioned run-artifact records — the one schema every emitter shares.

:class:`RunRecord` is a small versioned envelope (schema, tool, kind,
host context) around free-form ``config``/``metrics`` payloads plus the
structured observability blocks (``counters`` from obs.counters,
``comms`` from obs.comms, ``artifacts`` paths to trace files). The engine
CLI (``--record``, ``--hlo-report``), the serve daemon, the telemetry
session, the train loop, the differential harness (``python -m
dmlp_tpu.bench --metrics``) and the smokes under ``tools/`` write it.
A record says what a run did and counted; it is not a performance
record (those are the driver's ``PERF_LEDGER.jsonl``, from ``python3 -m
benchmark.run`` on the chip: PERF.md).

Records serialize as strict JSON. ``write`` emits one record per file;
``append_jsonl`` appends one record per line for multi-run logs — both
atomic enough for the single-writer tooling here.

Schema 2 added two optional envelope fields: ``round`` (taken from an
``_rNN`` suffix of the output file's name, when it has one) and
``device`` (the device kind the run measured on, from the solving
process's own stamp). Schema-1 records load unchanged.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import platform
import re
import time
from typing import Any, Dict, List, Optional

from dmlp_tpu.config import score_of

#: bump on any backward-incompatible field change; consumers key on this
SCHEMA_VERSION = 2


def round_from_name(path: str) -> Optional[int]:
    """The measurement round encoded in an artifact filename (the
    ``_rNN`` convention: BENCH_r08.jsonl -> 8), or None."""
    m = re.search(r"_r(\d+)", os.path.basename(path))
    return int(m.group(1)) if m else None


def current_device() -> str:
    """Device kind for the envelope ``device`` field, as jax reports it.
    Touches ``jax.devices()``, so it belongs to the process that solves:
    a chip serves one process at a time, and a parent that asked would
    hold the chip its children need. Parents record what the child
    wrote (:func:`device_stamp`, :func:`stamp_device_kind`)."""
    import jax
    return str(jax.devices()[0].device_kind)


def device_stamp(engine=None) -> Dict[str, Any]:
    """Where this process ran and which path its last solve took — the
    one block the CLI ``--metrics`` summary, the daemon's ready file and
    its ``stats`` reply all carry, so that a parent that never touches
    jax (chip_smoke.py, the fleet launchers) can check it. Only the
    solving process may call this (see :func:`current_device`)."""
    import jax

    from dmlp_tpu.ops.pallas_distance import pallas_interpret
    from dmlp_tpu.resilience import stats as rs_stats
    from dmlp_tpu.train.metrics import peak_flops_for_kind
    devs = jax.devices()
    res = rs_stats.snapshot()
    kind = str(devs[0].device_kind)
    stamp: Dict[str, Any] = {
        "platform": devs[0].platform,
        "device_kind": kind,
        "device_count": len(devs),
        # a utilisation can be computed for this kind (the peaks table
        # knows it); on any other kind only rates are reported
        "peak_flops_known": peak_flops_for_kind(kind) is not None,
        "pallas_interpret": pallas_interpret(),
        "degradations": res["degradations"],
        "retries": res["retries"],
    }
    if engine is not None:
        mesh = getattr(engine, "mesh", None)
        stamp.update(
            mesh=list(mesh.devices.shape) if mesh is not None else None,
            select=getattr(engine, "_last_select", None),
            # what the corpus is ranked by (config.SCORES)
            score=score_of(engine),
            extract_impl=getattr(engine, "last_extract_impl", None),
            # None where the engine has no ladder (the mesh engines)
            degrade_rung=getattr(engine, "last_degrade_rung", None),
            kernel_variant=getattr(engine, "last_variant", None),
            repairs=getattr(engine, "last_repairs", None))
        rows = getattr(engine, "corpus_rows_per_device", None)
        if rows is not None:
            stamp["corpus_rows_per_device"] = rows()
    return stamp


def rows_per_device(arrays, into: Optional[Dict[str, int]] = None
                    ) -> Dict[str, int]:
    """Rows of ``arrays`` (data-sharded device arrays, attributes on
    the last axis: (rows, A), or a stack of chunks (T, rows, A)) each
    device holds, summed by device id — what shows that a mesh solve
    spread the corpus instead of staging it all on the first device."""
    rows = {} if into is None else into
    for arr in arrays:
        for shard in arr.addressable_shards:
            key = str(shard.device.id)
            rows[key] = rows.get(key, 0) + math.prod(shard.data.shape[:-1])
    return rows


def stamp_device_kind(stamp: Optional[Dict[str, Any]]) -> Optional[str]:
    """The envelope ``device`` value out of a child's device stamp
    (None when the child wrote none)."""
    return stamp.get("device_kind") if isinstance(stamp, dict) else None


def _host_context() -> Dict[str, Any]:
    ctx: Dict[str, Any] = {"python": platform.python_version()}
    try:
        import jax
        ctx["jax"] = jax.__version__
        # Touching jax.devices() would initialize a backend as a side
        # effect (and claim the chip); record only what is free.
    except ImportError:
        pass
    return ctx


@dataclasses.dataclass
class RunRecord:
    """One run's artifact: envelope + payload.

    ``kind`` names the workload family ("engine", "bench", "train", ...);
    ``tool`` names the emitter (e.g. "dmlp_tpu.cli", "dmlp_tpu.bench").
    ``config`` holds the inputs that produced the run, ``metrics`` its
    measurements; ``counters``/``comms``/``artifacts`` carry the obs
    subsystem's structured blocks when present."""

    kind: str
    tool: str
    config: Dict[str, Any] = dataclasses.field(default_factory=dict)
    metrics: Dict[str, Any] = dataclasses.field(default_factory=dict)
    counters: Optional[Dict[str, Any]] = None
    comms: Optional[Dict[str, Any]] = None
    artifacts: Dict[str, str] = dataclasses.field(default_factory=dict)
    round: Optional[int] = None      # schema 2: measurement round (_rNN)
    device: Optional[str] = None     # schema 2: device kind measured on
    schema: int = SCHEMA_VERSION
    created_unix: float = dataclasses.field(default_factory=time.time)
    host: Dict[str, Any] = dataclasses.field(default_factory=_host_context)

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        return {k: v for k, v in d.items() if v not in (None, {})}

    def to_json(self) -> str:
        try:
            return json.dumps(self.to_dict(), sort_keys=True)
        except TypeError as e:
            raise TypeError(
                f"RunRecord for tool={self.tool!r} contains a "
                f"non-JSON-serializable value: {e}") from None

    def write(self, path: str) -> str:
        """One record per file (atomic rename)."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.to_json() + "\n")
        os.replace(tmp, path)
        return path

    def append_jsonl(self, path: str) -> str:
        """One record per line, appended — the multi-run log form."""
        line = self.to_json()
        with open(path, "a") as f:
            f.write(line + "\n")
        return path

    @staticmethod
    def load(path: str) -> "RunRecord":
        with open(path) as f:
            return RunRecord.from_dict(json.loads(f.readline()))

    @staticmethod
    def load_all(path: str) -> List["RunRecord"]:
        """Every record of an ``append_jsonl`` log, in file order."""
        with open(path) as f:
            return [RunRecord.from_dict(json.loads(ln)) for ln in f
                    if ln.strip()]

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "RunRecord":
        known = {f.name for f in dataclasses.fields(RunRecord)}
        schema = d.get("schema")
        if schema is not None and schema > SCHEMA_VERSION:
            raise ValueError(f"RunRecord schema {schema} is newer than "
                             f"this reader ({SCHEMA_VERSION})")
        return RunRecord(**{k: v for k, v in d.items() if k in known})
