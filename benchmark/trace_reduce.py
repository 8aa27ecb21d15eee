"""From a profiler trace and host spans to numbers.

The reduction works on a neutral form, so that the tests can hold it to
hand-computed answers on a small trace committed under ``testdata/``:

    {"events": [{"plane", "line", "name", "start_ns", "dur_ns"}, ...],
     "window_ns": [start, end]}

``from_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into
that form. Device planes are those whose name starts with ``/device:``;
an operation "ran on the device" when it is an event of a line matching
``OPS_LINE``. Host spans come from the program's own tracer and are put
on the trace's clock by the offset between one ``perf_counter`` read and
the ``bench.clock_sync`` annotation recorded at the same instant.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Sequence, Tuple

import numpy as np

SYNC_NAME = "bench.clock_sync"
END_NAME = "bench.trace_end"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = re.compile(r"^XLA Ops")

Interval = Tuple[float, float]


def from_xplane(path: str) -> Dict[str, Any]:
    """Neutral form of one ``.xplane.pb``; the window runs from the
    ``bench.clock_sync`` annotation to ``bench.trace_end``."""
    from jax.profiler import ProfileData
    events: List[Dict[str, Any]] = []
    sync = end = None
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                if device:
                    events.append({"plane": plane.name, "line": line.name,
                                   "name": ev.name,
                                   "start_ns": float(ev.start_ns),
                                   "dur_ns": float(ev.duration_ns)})
                elif ev.name == SYNC_NAME:
                    sync = float(ev.start_ns)
                elif ev.name == END_NAME:
                    end = float(ev.start_ns)
    if sync is None or end is None:
        raise ValueError(f"{path}: no {SYNC_NAME}/{END_NAME} annotation")
    return {"events": events, "window_ns": [sync, end], "sync_ns": sync}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the intervals."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def device_ops(trace: Dict[str, Any]) -> Dict[str, List[Dict[str, Any]]]:
    """Per device plane, the events that are operations on the device."""
    planes: Dict[str, List[Dict[str, Any]]] = {}
    for ev in trace["events"]:
        if DEVICE_PLANE.match(ev["plane"]) and OPS_LINE.match(ev["line"]):
            planes.setdefault(ev["plane"], []).append(ev)
    return planes


def busy(trace: Dict[str, Any]) -> Dict[str, Any]:
    """Seconds in which an operation ran, averaged over the devices that
    ran any, the window's length, and the idle gaps of the busiest-first
    device (the one the gap attribution reads)."""
    lo, hi = trace["window_ns"]
    per: Dict[str, float] = {}
    gaps: Dict[str, List[Interval]] = {}
    for plane, evs in device_ops(trace).items():
        cover = union(_clip(((e["start_ns"], e["start_ns"] + e["dur_ns"])
                             for e in evs), lo, hi))
        per[plane] = sum(b - a for a, b in cover) / 1e9
        edges = [lo] + [x for ab in cover for x in ab] + [hi]
        gaps[plane] = [(edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]]
    if not per:
        return {"busy_s": 0.0, "window_s": (hi - lo) / 1e9, "devices": 0,
                "gaps_ns": [(lo, hi)]}
    first = sorted(per)[0]
    return {"busy_s": sum(per.values()) / len(per),
            "window_s": (hi - lo) / 1e9, "devices": len(per),
            "per_device_s": per, "gaps_ns": gaps[first]}


def idle_pct(trace: Dict[str, Any]) -> float:
    b = busy(trace)
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def top_ops(trace: Dict[str, Any], limit: int = 10) -> List[List[Any]]:
    """Device operations by total seconds inside the window (summed over
    events of one name, averaged over devices), largest first; a name is
    cut to its first 160 characters (XLA names an op by its whole HLO
    line, and the driver copies this list into the ledger)."""
    lo, hi = trace["window_ns"]
    planes = device_ops(trace)
    total: Dict[str, float] = {}
    for evs in planes.values():
        for e in evs:
            a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"],
                                               hi)
            if b > a:
                total[e["name"]] = total.get(e["name"], 0.0) + (b - a)
    n = max(len(planes), 1)
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:limit]
    return [[name[:160], ns / 1e9 / n] for name, ns in ranked]


def kernel_events(trace: Dict[str, Any], pattern: str
                  ) -> List[Dict[str, Any]]:
    """Device-op events whose name matches ``pattern``, wholly inside
    the window (a kernel cut by the window's edge is left out, so that
    events over dispatches stays a whole number of micro-batches)."""
    rx = re.compile(pattern)
    lo, hi = trace["window_ns"]
    return [e for evs in device_ops(trace).values() for e in evs
            if rx.search(e["name"]) and e["start_ns"] >= lo
            and e["start_ns"] + e["dur_ns"] <= hi]


def spans_on_trace_clock(spans: Sequence[Dict[str, Any]], sync_pc_s: float,
                         sync_ns: float) -> List[Dict[str, Any]]:
    """Host spans ({"name", "t0", "t1"} in perf_counter seconds) with
    ``start_ns``/``end_ns`` on the trace's clock."""
    off = sync_ns - sync_pc_s * 1e9
    return [{"name": s["name"], "start_ns": s["t0"] * 1e9 + off,
             "end_ns": s["t1"] * 1e9 + off} for s in spans]


def attribute_gaps(gaps_ns: Sequence[Interval],
                   spans: Sequence[Dict[str, Any]],
                   limit: int = 10) -> List[List[Any]]:
    """Idle seconds by what the host was doing: every gap goes to the
    span that covers most of it — among spans within 5% of the best
    cover, the shortest, i.e. the innermost — or to ``(no span)``.
    Returns [[name, seconds], ...], largest first."""
    total: Dict[str, float] = {}
    start = np.asarray([s["start_ns"] for s in spans], float)
    end = np.asarray([s["end_ns"] for s in spans], float)
    length = end - start
    for a, b in gaps_ns:
        name = "(no span)"
        if len(spans):
            cover = np.minimum(b, end) - np.maximum(a, start)
            best = cover.max()
            if best > 0:
                near = np.nonzero(cover >= 0.95 * best)[0]
                name = spans[int(near[np.argmin(length[near])])]["name"]
        total[name] = total.get(name, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:limit]
    return [[name, ns / 1e9] for name, ns in ranked]
