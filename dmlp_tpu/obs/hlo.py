"""Compiled-program introspection: the HLO-derived collective/memory ledger.

Every analytic model in this package (obs.comms traffic, obs.memwatch
peak-HBM, obs.kernel_cost FLOPs/bytes) is checked against traces and
watermarks — but never against what XLA actually compiled. This module
closes that loop: given any ``jax.stages.Compiled`` (or a jitted fn plus
abstract args to lower), it extracts

- the **collective schedule** — ``compiled.as_text()`` parsed for
  ``all-reduce`` / ``all-gather`` / ``reduce-scatter`` /
  ``collective-permute`` / ``all-to-all`` ops with operand shapes,
  element types and ``replica_groups``, with while-loop trip counts
  (``known_trip_count`` backend config) folded in so a scanned ring
  ppermute counts its R-1 hops, not 1;
- **memory** — ``compiled.memory_analysis()`` (temp / argument / output /
  alias bytes), with the explicit ``hlo_memory_unavailable`` marker where
  the backend returns nothing;
- **cost** — the existing ``cost_analysis()`` path (obs.counters
  .normalize_cost), unified behind the same record.

One schema-versioned :class:`HloReport` per compiled executable, cached
by executable fingerprint (sha-256 of the HLO text — two lowers of the
same program parse once).

**Byte convention.** ``bytes_moved`` uses the same per-device wire-byte
accounting obs.comms documents (the ring-algorithm bound), so the two
sides reconcile without per-kind fudge factors: all-gather moves
(g-1) x shard bytes per device, all-reduce 2(g-1)/g x buffer,
reduce-scatter and all-to-all (g-1)/g x buffer, collective-permute the
full operand per source->target pair. Totals cover all devices, groups
and loop iterations.

**Three-way reconcile** (:func:`three_way`): HLO-derived collective
bytes vs the ``# check: comms-model=`` analytic models
(:data:`MODEL_COLLECTIVE_KINDS` is the annotation->kind table check
family R10 validates against), vs traced ``dist.*`` span traffic where
traces exist, and ``memory_analysis`` vs the memwatch model + live
watermark. Tolerances are documented ratio bounds
(:data:`COMMS_RATIO_BOUNDS`, :data:`MEMORY_RATIO_BOUNDS`); an
unavailable basis yields an explicit ``*_unavailable`` marker, never
silence (markers never gate — PR 5 convention).

Import-light: jax is touched only when a compiled object is actually
introspected; parsing is pure text.
"""

from __future__ import annotations

import dataclasses
import hashlib
import re
from typing import Any, Dict, List, Optional, Tuple

#: bump on any backward-incompatible HloReport field change
SCHEMA_VERSION = 1

#: the HLO collective opcodes the parser recognizes (async ``-start``
#: forms normalize onto these; ``-done`` halves are bookkeeping, skipped)
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

#: obs.comms model *function* -> the HLO collective kind its formula
#: prices. This is the reconcile table: every ``# check: comms-model=``
#: annotation must name a key here (check family R10), so a renamed
#: model cannot leave a dangling annotation that reconciles nothing.
MODEL_COLLECTIVE_KINDS: Dict[str, str] = {
    "allgather_topk_traffic": "all-gather",
    "host_allgather_candidates_traffic": "all-gather",
    "ring_topk_traffic": "collective-permute",
    "pipeline_ppermute_traffic": "collective-permute",
    "psum_traffic": "all-reduce",
    "tp_psum_activation_traffic": "all-reduce",
    "ep_psum_combine_traffic": "all-reduce",
    "moe_a2a_traffic": "all-to-all",
}

#: CollectiveTraffic.collective record name -> HLO collective kind (the
#: runtime face of the same table: engine.last_comms entries map through
#: this when reconciling a live record instead of a source annotation)
TRAFFIC_COLLECTIVE_KINDS: Dict[str, str] = {
    "all_gather_merge_topk": "all-gather",
    "host_allgather_candidates": "all-gather",
    "ring_allreduce_topk": "collective-permute",
    "ppermute_pipeline": "collective-permute",
    "psum_grads": "all-reduce",
    "psum_tp_activations": "all-reduce",
    "psum_ep_combine": "all-reduce",
    "moe_all_to_all": "all-to-all",
    # gspmd_* records are HLO-derived (traffic_from_report) — identity
    "gspmd_all-reduce": "all-reduce",
    "gspmd_all-gather": "all-gather",
    "gspmd_reduce-scatter": "reduce-scatter",
    "gspmd_collective-permute": "collective-permute",
    "gspmd_all-to-all": "all-to-all",
}

#: traced span name -> collective kind, for the trace leg of the
#: reconcile (spans must carry an ``nbytes`` arg to participate;
#: dist.allgather_candidates is the multi-host candidate gather whose
#: analytic twin tools/merge_traces.py already checks per rank)
SPAN_COLLECTIVE_KINDS: Dict[str, str] = {
    "dist.allgather_candidates": "all-gather",
}

#: documented model-vs-HLO tolerance, as ratio bounds on
#: hlo_bytes/model_bytes: padding rounds differently on the two sides
#: (the model prices q_local x k exactly; the compiled program moves the
#: padded buffers), and XLA may fuse or resplit a collective — within
#: [0.5, 2.0]x the schedule corroborates the model, outside it one of
#: the two is wrong.
COMMS_RATIO_BOUNDS: Tuple[float, float] = (0.5, 2.0)

#: memory_analysis-vs-model ratio bounds (hlo/model). The two sides
#: price different things on purpose: the memwatch model prices the
#: solve's RESIDENT arrays, while XLA's static buffer assignment prices
#: one executable's full temp set without the liveness sharing a real
#: run gets (observed ~9x above the model on the monolithic CPU solve)
#: and, on chunked paths, sits far BELOW the model (one chunk's buffers
#: vs the staged corpus). This leg is an order-of-magnitude
#: corroboration, not an equality check — hence bounds much wider than
#: :data:`COMMS_RATIO_BOUNDS`.
MEMORY_RATIO_BOUNDS: Tuple[float, float] = (0.02, 16.0)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "tf32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
}

# f32[8,1,16] — dtype token then dims (scalars: f32[] -> 1 element)
_SHAPE_RE = re.compile(r"([a-z]+[0-9a-z]*)\[([0-9,]*)\]")
_OPCODE_RE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start|-done)?\(")
_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\}"
                                r"(?:,\{[^}]*\})*)?\}")
_REPLICA_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[^}]*\}"
                       r"(?:,\{[^}]*\})*)\}")
_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")
_TRIP_COUNT_RE = re.compile(r"known_trip_count[\"':\s{]+n[\"':\s]+(\d+)")
_WHILE_RE = re.compile(r"\swhile\(")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
# instruction definition: `[ROOT] %name = <shape> opcode(`
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s+=\s+")
_OPERAND_NAME_RE = re.compile(r"%[\w.\-]+")
# computation definition: `%name (args...) -> type {` (args may nest
# parens and carry /*index=N*/ comments — only the leading name matters)
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")


def _shape_bytes(segment: str) -> Tuple[int, List[str]]:
    """Total bytes + dtypes of every ``dtype[dims]`` shape in ``segment``
    (layout suffixes like ``{2,1,0}`` follow the bracket and don't
    match). Unknown dtypes count 0 bytes rather than guessing."""
    total = 0
    dtypes: List[str] = []
    for dt, dims in _SHAPE_RE.findall(segment):
        item = _DTYPE_BYTES.get(dt)
        if item is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * item
        dtypes.append(dt)
    return total, dtypes


def _paren_close(text: str, start: int) -> int:
    """Index of the ``)`` closing the ``(`` at ``text[start]`` (the last
    index when the text ends unbalanced)."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(text) - 1


def _result_segment(rest: str) -> str:
    """The result-shape text at the head of ``rest`` (an instruction
    line after its ``name = ``): a parenthesised tuple, or one token."""
    if rest.startswith("("):
        return rest[:_paren_close(rest, 0) + 1]
    return rest.split(" ", 1)[0]


def _instruction_shapes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its result-shape text. The compiled text of
    jax >= 0.9 names a collective's operands without their shapes
    (``collective-permute(%gte.3)``); the shape is on the operand's own
    defining line."""
    shapes: Dict[str, str] = {}
    for raw in hlo_text.splitlines():
        m = _INSTR_RE.match(raw)
        if m:
            shapes[m.group(1).lstrip("%")] = _result_segment(
                raw[m.end():])
    return shapes


def _parse_groups(line: str,
                  num_partitions: Optional[int]) -> Tuple[int, int]:
    """(group_size, n_groups) from ``replica_groups`` — explicit list or
    iota form; an absent/empty attribute means one group of every
    partition (XLA's default)."""
    m = _REPLICA_IOTA_RE.search(line)
    if m:
        return int(m.group(2)), int(m.group(1))
    m = _REPLICA_GROUPS_RE.search(line)
    if m and m.group(1):
        groups = re.findall(r"\{([^}]*)\}", m.group(1))
        sizes = [len([x for x in g.split(",") if x.strip()])
                 for g in groups]
        return (max(sizes) if sizes else 1), len(groups)
    return (num_partitions or 1), 1


def _parse_pairs(line: str) -> Tuple[int, int, int]:
    """(n_pairs, ring_length, n_rings) from ``source_target_pairs``:
    follow the permutation's cycles — the ring length is the mesh-axis
    size the permute walks, the number of cycles its group count."""
    m = _PAIRS_RE.search(line)
    if not m:
        return 0, 1, 1
    pairs = [tuple(int(x) for x in g.split(","))
             for g in re.findall(r"\{([^{}]*)\}", m.group(1))
             if "," in g]
    if not pairs:
        return 0, 1, 1
    nxt = dict(pairs)
    seen: set = set()
    cycles: List[int] = []
    for start in nxt:
        if start in seen:
            continue
        length, cur = 0, start
        while cur not in seen:
            seen.add(cur)
            length += 1
            cur = nxt.get(cur, start)
            if cur == start:
                break
        cycles.append(length)
    ring = max(cycles) if cycles else 1
    return len(pairs), ring, max(len(cycles), 1)


def _bytes_moved(kind: str, operand_bytes: int, group_size: int,
                 n_groups: int, n_pairs: int, count: int) -> int:
    """Total wire bytes under the obs.comms ring-bound convention
    (module docstring), across all devices, groups and iterations."""
    g = max(group_size, 1)
    if kind == "collective-permute":
        return operand_bytes * max(n_pairs, 1) * count
    if kind == "all-gather":
        per_dev = (g - 1) * operand_bytes
    elif kind == "all-reduce":
        per_dev = round(2 * (g - 1) * operand_bytes / g)
    else:  # reduce-scatter, all-to-all: (g-1)/g of the buffer leaves
        per_dev = round((g - 1) * operand_bytes / g)
    return per_dev * g * n_groups * count


def parse_collectives(hlo_text: str) -> List[Dict[str, Any]]:
    """Every collective op in the (scheduled, SPMD per-device) HLO text,
    with derived byte counts.

    Tracks which computation each op sits in and multiplies ops inside
    ``while`` bodies by the loop's ``known_trip_count`` (transitively for
    nested loops). A loop without a statically-known trip count marks its
    collectives ``trip_count_unknown`` and counts them once — an honest
    lower bound, never a guess."""
    num_partitions = None
    m = _NUM_PARTITIONS_RE.search(hlo_text)
    if m:
        num_partitions = int(m.group(1))

    shapes: Optional[Dict[str, str]] = None   # built on first need
    ops: List[Dict[str, Any]] = []
    # body computation -> (trip_count or None), caller computation
    loops: Dict[str, Tuple[Optional[int], str]] = {}
    comp = ""
    for raw in hlo_text.splitlines():
        stripped = raw.strip()
        # instruction lines assign with ` = `; /*index=N*/ and
        # source_line=N carry bare '=' and must not disqualify a def
        if stripped.endswith("{") and " = " not in stripped \
                and "->" in stripped:
            cm = _COMPUTATION_RE.match(stripped)
            if cm:
                comp = cm.group(1)
                continue
        if _WHILE_RE.search(raw):
            bm = _BODY_RE.search(raw)
            if bm:
                tm = _TRIP_COUNT_RE.search(raw)
                loops[bm.group(1)] = (
                    int(tm.group(1)) if tm else None, comp)
            continue
        om = _OPCODE_RE.search(raw)
        if not om or om.group(2) == "-done":
            continue
        kind = om.group(1)
        # result shapes sit between '=' and the opcode; operands inside
        # the opcode's parens (balanced scan — attrs follow the close)
        eq = raw.find("=")
        result_seg = raw[eq + 1: om.start()] if eq >= 0 else ""
        start = raw.find("(", om.end() - 1)
        operand_seg = raw[start:_paren_close(raw, start) + 1]
        result_bytes, result_dtypes = _shape_bytes(result_seg)
        operand_bytes, operand_dtypes = _shape_bytes(operand_seg)
        shape_unresolved = False
        if not operand_bytes:
            # operands named without shapes: read each one's defining
            # line (a name the text never defines stays unpriced)
            if shapes is None:
                shapes = _instruction_shapes(hlo_text)
            names = [n.lstrip("%")
                     for n in _OPERAND_NAME_RE.findall(operand_seg)]
            shape_unresolved = not names or any(
                n not in shapes for n in names)
            operand_bytes, operand_dtypes = _shape_bytes(
                " ".join(shapes.get(n, "") for n in names))
        n_pairs = ring = n_rings = 0
        if kind == "collective-permute":
            n_pairs, ring, n_rings = _parse_pairs(raw)
            group_size, n_groups = ring, n_rings
        else:
            group_size, n_groups = _parse_groups(raw, num_partitions)
            if kind == "all-gather" and result_bytes \
                    and group_size > 1 and not operand_bytes:
                # degenerate text without operand shapes: derive the
                # shard payload from the gathered result
                operand_bytes = result_bytes // group_size
                shape_unresolved = False
        ops.append({
            "kind": kind, "computation": comp,
            "dtypes": operand_dtypes or result_dtypes,
            "operand_bytes": operand_bytes,
            "result_bytes": result_bytes,
            "group_size": group_size, "n_groups": n_groups,
            **({"n_pairs": n_pairs} if n_pairs else {}),
            # bytes_moved is then a lower bound, and says so
            **({"operand_shape_unresolved": True}
               if shape_unresolved else {}),
        })

    # transitive loop multiplier per computation (nested whiles multiply)
    def _trip(c: str, depth: int = 0) -> Tuple[int, bool]:
        if c not in loops or depth > 16:
            return 1, False
        n, caller = loops[c]
        outer, unknown = _trip(caller, depth + 1)
        if n is None:
            return outer, True
        return n * outer, unknown

    for op in ops:
        count, unknown = _trip(op.pop("computation"))
        op["count"] = count
        if unknown:
            op["trip_count_unknown"] = True
        op["bytes_moved"] = _bytes_moved(
            op["kind"], op["operand_bytes"], op["group_size"],
            op["n_groups"], op.get("n_pairs", 0), count)
    return ops


def collective_totals(
        collectives: List[Dict[str, Any]],
        dispatch_count: int = 1) -> Dict[str, Dict[str, int]]:
    """Per-kind {ops, count, bytes_moved} aggregate; ``dispatch_count``
    scales a program executed N times (the probe's multiplicity)."""
    out: Dict[str, Dict[str, int]] = {}
    for op in collectives:
        agg = out.setdefault(op["kind"],
                             {"ops": 0, "count": 0, "bytes_moved": 0})
        agg["ops"] += 1
        agg["count"] += op["count"] * dispatch_count
        agg["bytes_moved"] += op["bytes_moved"] * dispatch_count
        if op.get("operand_shape_unresolved"):
            agg["ops_shape_unresolved"] = \
                agg.get("ops_shape_unresolved", 0) + 1
    return out


def guess_axis(group_size: int,
               mesh_axes: Optional[Dict[str, int]]) -> str:
    """Best-effort mesh-axis attribution: a group size that matches
    exactly one declared axis size names that axis; anything else is an
    honest ``unknown`` (never a guess between ambiguous axes)."""
    if not mesh_axes:
        return "unknown"
    hits = [a for a, s in mesh_axes.items() if s == group_size]
    return hits[0] if len(hits) == 1 else "unknown"


def kernel_operand_types(hlo_text: str) -> List[List[str]]:
    """The operand types ("bf16[51200,128]") of every Pallas kernel
    call (``tpu_custom_call``) in a compiled program's text, in
    argument order, a list a call: read from the call's
    ``operand_layout_constraints``, which name each operand as the
    kernel's BlockSpecs see it (chip_smoke.py's ``fold.bf16`` phase and
    tests/test_tpu_aot.py ask whether bfloat16 rows reach the kernel
    as bfloat16)."""
    calls = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        layouts = line.split("operand_layout_constraints={", 1)[1]
        calls.append([op.split("{")[0]
                      for op in layouts.split("}}", 1)[0].split("}, ")])
    return calls


# -- the per-executable record ------------------------------------------------

def fingerprint_text(hlo_text: str) -> str:
    """Executable fingerprint: sha-256 of the compiled HLO text (16 hex
    chars — the cache key and the schedule-identity token the serve
    smoke compares between ready and drain)."""
    return hashlib.sha256(hlo_text.encode()).hexdigest()[:16]


@dataclasses.dataclass
class HloReport:
    """One compiled executable's introspection record."""

    label: str
    fingerprint: str
    collectives: List[Dict[str, Any]]
    totals: Dict[str, Dict[str, int]]
    memory: Dict[str, Any]
    cost: Dict[str, Any]
    platform: Optional[str] = None
    schema: int = SCHEMA_VERSION

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        return {k: v for k, v in d.items() if v is not None}


def memory_report(compiled) -> Dict[str, Any]:
    """``memory_analysis()`` as a plain dict, or the explicit
    ``hlo_memory_unavailable`` marker when the backend reports
    nothing."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:
        return {"hlo_memory_unavailable": f"memory_analysis raised "
                                          f"{type(e).__name__}: {e}"}
    if ma is None:
        return {"hlo_memory_unavailable":
                "backend returned no memory analysis"}
    out: Dict[str, Any] = {}
    for field in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        v = getattr(ma, field, None)
        if v is not None:
            out[field.replace("_size_in_bytes", "_bytes")] = int(v)
    if not out:
        return {"hlo_memory_unavailable":
                f"unrecognized memory_analysis shape: "
                f"{type(ma).__name__}"}
    return out


def cost_report(compiled) -> Dict[str, Any]:
    """``cost_analysis()`` normalized (obs.counters.normalize_cost), or
    the explicit marker."""
    from dmlp_tpu.obs.counters import normalize_cost
    try:
        cost = normalize_cost(compiled.cost_analysis())
    except Exception as e:
        return {"cost_unavailable": f"cost_analysis raised "
                                    f"{type(e).__name__}: {e}"}
    if cost is None:
        return {"cost_unavailable": "no usable flops/bytes in "
                                    "cost_analysis output"}
    return cost


# fingerprint -> HloReport; two lowers of the same program parse once.
_REPORT_CACHE: Dict[str, HloReport] = {}
cache_stats = {"hits": 0, "misses": 0}


def clear_cache() -> None:
    _REPORT_CACHE.clear()
    cache_stats["hits"] = cache_stats["misses"] = 0


def report_for(compiled, label: str = "") -> HloReport:
    """The :class:`HloReport` for a ``jax.stages.Compiled``, cached by
    executable fingerprint (the label of the first introspection
    sticks)."""
    text = compiled.as_text()
    fp = fingerprint_text(text)
    cached = _REPORT_CACHE.get(fp)
    if cached is not None:
        cache_stats["hits"] += 1
        return cached
    cache_stats["misses"] += 1
    collectives = parse_collectives(text)
    platform = None
    try:
        platform = compiled.runtime_executable().platform  # pragma: no cover
    except Exception:
        try:
            import jax
            platform = jax.default_backend()
        except Exception:
            pass
    rep = HloReport(label=label, fingerprint=fp,
                    collectives=collectives,
                    totals=collective_totals(collectives),
                    memory=memory_report(compiled),
                    cost=cost_report(compiled),
                    platform=platform)
    _REPORT_CACHE[fp] = rep
    return rep


def report_for_fn(fn, specs, statics: Optional[dict] = None,
                  label: str = "") -> Optional[HloReport]:
    """Lower + compile a jitted fn on abstract args and introspect the
    result; None when the signature cannot lower (non-jitted callable,
    tracing error) — introspection never raises into a solve."""
    try:
        compiled = fn.lower(*specs, **(statics or {})).compile()
    except Exception:
        return None
    return report_for(compiled, label=label)


def probe_reports(probe) -> Tuple[List[Tuple[HloReport, int, str]], int]:
    """Introspect every dispatch signature an obs.counters.CostProbe
    recorded: [(report, dispatch_count, site)], plus how many signatures
    could not lower (Pallas kernels expose no HLO executable this way —
    counted, never silent)."""
    out: List[Tuple[HloReport, int, str]] = []
    skipped = 0
    for fn, specs, statics, count, site in probe.dispatches():
        rep = report_for_fn(fn, specs, statics=statics, label=site)
        if rep is None:
            skipped += 1
            continue
        out.append((rep, count, site))
    return out, skipped


def traffic_from_report(report: HloReport,
                        mesh_axes: Optional[Dict[str, int]] = None,
                        count: int = 1) -> List[Any]:
    """The compiled schedule as obs.comms.CollectiveTraffic records —
    how a compiler-chosen (GSPMD) schedule becomes a REAL comms record
    instead of the honest-but-empty one: collective names are
    ``gspmd_<kind>``, axes are best-effort mesh attribution
    (:func:`guess_axis`), and bytes reproduce ``bytes_moved`` under the
    shared convention."""
    from dmlp_tpu.obs.comms import CollectiveTraffic
    out: List[Any] = []
    for kind, agg in sorted(report.totals.items()):
        sized = [op for op in report.collectives if op["kind"] == kind]
        g = max((op["group_size"] for op in sized), default=1)
        n_groups = max((op["n_groups"] for op in sized), default=1)
        per_dev = round(agg["bytes_moved"] / max(g * n_groups, 1))
        out.append(CollectiveTraffic(
            f"gspmd_{kind}", guess_axis(g, mesh_axes), g,
            per_dev, per_dev, n_groups=n_groups, count=count,
            note=f"HLO-derived: {agg['ops']} op(s), "
                 f"{agg['count']} execution(s), fingerprint "
                 f"{report.fingerprint}"))
    return out


# -- the three-way reconcile --------------------------------------------------

def _traffic_kind_bytes(traffics) -> Tuple[Dict[str, int],
                                           Dict[str, List[str]]]:
    per_kind: Dict[str, int] = {}
    names: Dict[str, List[str]] = {}
    for t in traffics or []:
        d = t.to_dict() if hasattr(t, "to_dict") else dict(t)
        kind = TRAFFIC_COLLECTIVE_KINDS.get(d.get("collective", ""))
        if kind is None:
            kind = "unknown"
        per_kind[kind] = per_kind.get(kind, 0) + int(d["bytes_total"])
        names.setdefault(kind, []).append(d.get("collective", "?"))
    return per_kind, names


def reconcile_comms(reports: List[Tuple[HloReport, int, str]],
                    traffics) -> Dict[str, Any]:
    """HLO-derived collective bytes vs the analytic obs.comms records.

    Per collective kind: both sides' totals, their ratio and the
    :data:`COMMS_RATIO_BOUNDS` verdict. A kind only one side claims gets
    the honest one-sided marker instead of a fake ratio — ``hlo_only``
    is exactly what a partitioner-chosen (GSPMD) schedule looks like,
    ``model_only`` means the model prices a collective the compiled
    program never dispatches."""
    hlo_bytes: Dict[str, int] = {}
    unresolved: Dict[str, int] = {}
    for rep, count, _site in reports:
        for kind, agg in rep.totals.items():
            hlo_bytes[kind] = hlo_bytes.get(kind, 0) \
                + agg["bytes_moved"] * count
            if agg.get("ops_shape_unresolved"):
                unresolved[kind] = unresolved.get(kind, 0) \
                    + agg["ops_shape_unresolved"]
    model_bytes, model_names = _traffic_kind_bytes(traffics)
    kinds: Dict[str, Any] = {}
    for kind in sorted(set(hlo_bytes) | set(model_bytes)):
        h, mdl = hlo_bytes.get(kind, 0), model_bytes.get(kind, 0)
        ent: Dict[str, Any] = {"hlo_bytes": h, "model_bytes": mdl}
        if model_names.get(kind):
            ent["models"] = sorted(set(model_names[kind]))
        if unresolved.get(kind):
            # the compiled text names operands this parser could not
            # price: the HLO side is a lower bound, so no verdict
            ent.update(hlo_shape_unavailable=unresolved[kind],
                       within_tolerance=False)
        elif h and mdl:
            ratio = h / mdl
            lo, hi = COMMS_RATIO_BOUNDS
            ent.update(ratio=round(ratio, 3),
                       ratio_bounds=[lo, hi],
                       within_tolerance=bool(lo <= ratio <= hi))
        elif h:
            ent["hlo_only"] = True
        else:
            ent["model_only"] = True
        kinds[kind] = ent
    out: Dict[str, Any] = {"kinds": kinds}
    if not kinds:
        out["no_collectives"] = True
    return out


def reconcile_trace(reports: List[Tuple[HloReport, int, str]],
                    events: Optional[List[dict]]) -> Dict[str, Any]:
    """HLO bytes vs traced collective span traffic, where traces exist.

    Only spans named in :data:`SPAN_COLLECTIVE_KINDS` AND carrying an
    ``nbytes`` arg participate (the dist/fleet hand-offs); a run with no
    such spans — every single-process solve — reports the explicit
    ``trace_unavailable`` marker. Host-level collectives
    (process_allgather) never appear in a compiled program, so a traced
    kind with no HLO twin is expected cross-domain, marked
    ``hlo_side_absent`` rather than failed."""
    span_bytes: Dict[str, int] = {}
    for ev in events or []:
        kind = SPAN_COLLECTIVE_KINDS.get(ev.get("name", ""))
        nbytes = (ev.get("args") or {}).get("nbytes")
        if kind is None or not isinstance(nbytes, (int, float)):
            continue
        span_bytes[kind] = span_bytes.get(kind, 0) + int(nbytes)
    if not span_bytes:
        return {"trace_unavailable":
                "no traced collective spans carry byte counts "
                "(single-process solves dispatch collectives inside "
                "the compiled program only)"}
    hlo_bytes: Dict[str, int] = {}
    for rep, count, _site in reports:
        for kind, agg in rep.totals.items():
            hlo_bytes[kind] = hlo_bytes.get(kind, 0) \
                + agg["bytes_moved"] * count
    kinds: Dict[str, Any] = {}
    for kind, sb in sorted(span_bytes.items()):
        ent: Dict[str, Any] = {"trace_bytes": sb,
                               "hlo_bytes": hlo_bytes.get(kind, 0)}
        if not ent["hlo_bytes"]:
            ent["hlo_side_absent"] = True
        else:
            ratio = ent["hlo_bytes"] / sb
            lo, hi = COMMS_RATIO_BOUNDS
            ent.update(ratio=round(ratio, 3), ratio_bounds=[lo, hi],
                       within_tolerance=bool(lo <= ratio <= hi))
        kinds[kind] = ent
    return {"kinds": kinds}


def reconcile_memory(reports: List[Tuple[HloReport, int, str]],
                     mem_block: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """``memory_analysis`` vs the memwatch model + live watermark.

    The HLO side is the LARGEST single executable's static footprint
    (argument + output + temp bytes — per device); the model side is the
    memwatch ``mem`` block the CLI already computes (model bytes +
    measured watermark + its own verdict). Bounds are
    :data:`MEMORY_RATIO_BOUNDS`; either side missing yields its
    marker."""
    sized = []
    for rep, _count, site in reports:
        m = rep.memory
        if "hlo_memory_unavailable" in m:
            continue
        sized.append((m.get("argument_bytes", 0)
                      + m.get("output_bytes", 0)
                      + m.get("temp_bytes", 0), site))
    if not sized:
        why = "no executable reported memory analysis"
        for rep, _count, _site in reports:
            mark = rep.memory.get("hlo_memory_unavailable")
            if mark:
                why = mark
                break
        return {"hlo_memory_unavailable": why}
    peak, peak_site = max(sized)
    out: Dict[str, Any] = {"hlo_peak_bytes": int(peak),
                           "hlo_peak_site": peak_site,
                           "executables_with_memory": len(sized)}
    if not mem_block or "model_bytes" not in mem_block:
        out["mem_model_unavailable"] = \
            "no memwatch mem block to reconcile against"
        return out
    model = int(mem_block.get("model_bytes_per_device",
                              mem_block["model_bytes"]))
    lo, hi = MEMORY_RATIO_BOUNDS
    ratio = peak / max(model, 1)
    out.update(model_bytes_per_device=model, ratio=round(ratio, 3),
               ratio_bounds=[lo, hi],
               within_tolerance=bool(lo <= ratio <= hi))
    if mem_block.get("measured_bytes"):
        out["measured_bytes"] = mem_block["measured_bytes"]
        out["measured_basis"] = mem_block.get("basis")
    elif mem_block.get("mem_stats_unavailable"):
        out["mem_stats_unavailable"] = mem_block["mem_stats_unavailable"]
    return out


def three_way(reports: List[Tuple[HloReport, int, str]],
              traffics=None, events: Optional[List[dict]] = None,
              mem_block: Optional[Dict[str, Any]] = None
              ) -> Dict[str, Any]:
    """The full reconcile: HLO vs analytic comms models, vs traced span
    traffic, vs the memwatch model + watermark. Each leg carries its own
    verdicts/markers; none ever raises."""
    return {"comms_model": reconcile_comms(reports, traffics),
            "trace": reconcile_trace(reports, events),
            "memory": reconcile_memory(reports, mem_block)}


# -- the run-level document (what --hlo-report writes) ------------------------

def build_report_doc(reports: List[Tuple[HloReport, int, str]],
                     skipped: int = 0, traffics=None,
                     events: Optional[List[dict]] = None,
                     mem_block: Optional[Dict[str, Any]] = None,
                     mesh_axes: Optional[Dict[str, int]] = None
                     ) -> Dict[str, Any]:
    """One run's introspection document: every executable's report (with
    dispatch multiplicity), merged per-kind/per-axis totals, and the
    three-way reconcile. ``skipped`` names the signatures that could not
    lower — no silent caps."""
    totals: Dict[str, Dict[str, int]] = {}
    by_axis: Dict[str, int] = {}
    for rep, count, _site in reports:
        for kind, agg in rep.totals.items():
            t = totals.setdefault(kind, {"ops": 0, "count": 0,
                                         "bytes_moved": 0})
            t["ops"] += agg["ops"]
            t["count"] += agg["count"] * count
            t["bytes_moved"] += agg["bytes_moved"] * count
        for op in rep.collectives:
            ax = guess_axis(op["group_size"], mesh_axes)
            by_axis[ax] = by_axis.get(ax, 0) \
                + op["bytes_moved"] * count
    doc: Dict[str, Any] = {
        "schema": SCHEMA_VERSION,
        "executables": [dict(rep.to_dict(), dispatch_count=count,
                             site=site)
                        for rep, count, site in reports],
        "collective_totals": totals,
        "collective_bytes_total": sum(t["bytes_moved"]
                                      for t in totals.values()),
        "bytes_by_axis": by_axis,
        "reconcile": three_way(reports, traffics=traffics, events=events,
                               mem_block=mem_block),
    }
    if skipped:
        doc["signatures_skipped_no_hlo"] = skipped
    if not reports:
        doc["hlo_unavailable"] = "no dispatch signature could be " \
                                 "lowered to a compiled executable"
    return doc


def flat_metrics(doc: Dict[str, Any]) -> Dict[str, Any]:
    """The scalars of a report doc the ``kind="hlo"`` RunRecord carries
    (collective bytes per kind, op counts, static peak memory)."""
    out: Dict[str, Any] = {
        "collective_bytes_total": doc.get("collective_bytes_total", 0),
        "executables_introspected": len(doc.get("executables", ())),
    }
    for kind, agg in (doc.get("collective_totals") or {}).items():
        key = kind.replace("-", "_")
        out[f"{key}_bytes"] = agg["bytes_moved"]
        out[f"{key}_count"] = agg["count"]
    mem = (doc.get("reconcile") or {}).get("memory") or {}
    if "hlo_peak_bytes" in mem:
        out["hlo_peak_bytes"] = mem["hlo_peak_bytes"]
    if "ratio" in mem:
        out["mem_ratio_vs_model"] = mem["ratio"]
    return out


__all__ = [
    "SCHEMA_VERSION", "COLLECTIVE_KINDS", "MODEL_COLLECTIVE_KINDS",
    "TRAFFIC_COLLECTIVE_KINDS", "SPAN_COLLECTIVE_KINDS",
    "COMMS_RATIO_BOUNDS", "MEMORY_RATIO_BOUNDS",
    "parse_collectives", "collective_totals", "guess_axis",
    "fingerprint_text", "HloReport", "memory_report", "cost_report",
    "clear_cache", "cache_stats", "report_for", "report_for_fn",
    "probe_reports", "traffic_from_report",
    "reconcile_comms", "reconcile_trace", "reconcile_memory",
    "three_way", "build_report_doc", "flat_metrics",
]
