#!/usr/bin/env python
"""Merge per-rank cluster traces into one Perfetto-loadable trace.

``python -m dmlp_tpu.distributed --trace DIR`` leaves one
``trace-rank<NN>.json`` per rank (obs.dist_trace), each with its own clock
epoch (``time.perf_counter`` is per-process) and its rank as the Perfetto
``pid``. This tool:

1. loads every rank file in DIR (the rank set must be contiguous 0..N-1
   and match each file's recorded ``num_ranks`` — a missing rank means a
   crashed or unstarted process, which the merge must fail on, not paper
   over);
2. aligns clocks: every rank stamped a ``dist.clock_sync`` instant
   immediately after the cluster barrier released it, so shifting each
   rank's timestamps by (reference sync − its sync) puts all ranks on a
   common timeline to ~barrier-release accuracy. Rank 0's sync is the
   reference; per-rank offsets are recorded in the merged ``dist`` block;
3. cross-checks span counts per rank: every rank must carry spans at all,
   and the per-rank count of ``dist.solve`` spans (the contract solve —
   dispatched identically on every rank) must agree across ranks;
4. reconciles comms accounting per rank: each
   ``dist.allgather_candidates`` span carries the REAL payload bytes
   (``nbytes``) plus the gathered shapes; the merge recomputes the
   analytic expectation (obs.comms.host_allgather_candidates_traffic —
   the same model the engines' one-shot comms records use) and embeds a
   per-rank traced-vs-analytic table in the merged ``dist`` block
   (``comms_reconcile``); ``tools/check_trace.py --dist`` fails on any
   rank whose two numbers disagree. Pre-r6 traces without the shape
   args get an explicit ``analytic_unavailable`` marker, not a failure;
5. analyzes per-rank span-duration skew (the straggler detector): each
   rank's total ``dist.solve`` duration vs the across-rank median,
   flagging ranks beyond ``--straggler-threshold`` (default 1.5x) in
   the merged ``dist.straggler`` block. Rank files from MIXED clock
   domains (the trace metadata's ``clock.source`` — "monotonic" raw
   per-process tracers vs an already-"synced" merged doc) are refused
   with an explicit ``straggler_unavailable`` marker instead of
   nonsense skew numbers;
6. writes one merged Chrome-trace JSON, events sorted by aligned ``ts``
   (per-rank monotonicity is then checkable by tools/check_trace.py
   --dist), stamped ``clock.source: "synced"``, with distinct pids so
   ui.perfetto.dev renders one process track per rank.

Usage: python tools/merge_traces.py DIR [-o MERGED.json] [--no-align]
       [--straggler-threshold X]
Exit 0 on success; 1 with a message naming the violated invariant.

**Fleet mode** (``--fleet``): merge one serving fleet's rid-tagged
traces instead of dist rank files. Inputs in DIR are
``trace-router.json`` (required), ``trace-replicaNN.json`` (>= 1), and
optionally ``trace-client.json`` (the loadgen-side tracer). Fleet
processes share no barrier, so alignment uses each process's
``fleet.clock_sync`` instant — a back-to-back (perf_counter, wall
clock) pair — against the router's: localhost processes share the wall
clock, so the recovered offsets are sub-millisecond. Merged pids are
reassigned (client 0, router 1, replica i -> 10+i) and every span is
stitched by its ``rid`` arg into a per-request causal tree (client
fire -> route -> hops -> replica phases). When the client trace is
present the merge reconciles, per rid, the replica-side phase sum
(queue + coalesce + solve + finalize + write) against the
client-measured scheduled-fire latency minus its recorded pacing lag:

    residual_ms = client_ms - lag_ms - phase_sum_ms

The residual is the un-phased remainder (connect/parse/router relay +
clock-rate noise), so the tolerance is one-sided-wide:
``-tol_clock_ms <= residual <= tol_abs_ms + tol_rel * client_ms``.
Durations are clock-OFFSET invariant, so the check survives imperfect
alignment; ``tol_clock_ms`` only absorbs perf_counter rate noise on
the negative side. The ``serve.phase.admission`` span runs on the
handler thread CONCURRENT with the queue wait and is therefore
reported but EXCLUDED from the sum. Verdicts are embedded per rid in
the merged ``fleet`` block for ``tools/check_trace.py --fleet``. The
MEDIAN residual over reconciled rids is recorded as
``reconcile_residual_ms`` alongside ``residual_budget_ms``
(RESIDUAL_BUDGET_MS); exceeding the budget stamps a non-gating
``residual_budget_exceeded`` marker rather than failing the merge.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fail(msg: str):
    print(f"merge_traces: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def load_rank_files(trace_dir: str):
    """-> (docs, missing): (rank, doc) pairs sorted by rank, plus a
    {rank: reason} table for ranks whose file is absent or
    unreadable/truncated. A missing rank is the EXPECTED artifact of
    the failure being diagnosed (a crashed or hung process is exactly
    when you need the surviving ranks' trace) — so the merge records
    the explicit ``rank_trace_missing`` marker and proceeds instead of
    refusing. At least one readable rank file is still required, and a
    file whose embedded rank disagrees with its name still fails (that
    is corruption of identity, not absence)."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "trace-rank*.json")))
    if not paths:
        fail(f"no trace-rank*.json files in {trace_dir}")
    docs = []
    missing = {}
    for p in paths:
        m = re.search(r"trace-rank(\d+)\.json$", p)
        if not m:
            continue
        frank = int(m.group(1))
        try:
            with open(p) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            missing[frank] = f"unreadable or truncated: {e}"
            continue
        dist = doc.get("dist") or {}
        rank = dist.get("rank", frank)
        if rank != frank:
            fail(f"{p}: embedded rank {rank} != filename rank {frank}")
        docs.append((rank, doc))
    if not docs:
        fail(f"no readable trace-rank*.json in {trace_dir} "
             f"(all {len(missing)} candidate file(s) truncated?)")
    docs.sort()
    present = {r for r, _ in docs}
    want_n = docs[0][1].get("dist", {}).get(
        "num_ranks", max(present | set(missing)) + 1)
    beyond = sorted(r for r in present if r >= want_n)
    if beyond:
        fail(f"rank(s) {beyond} exceed the recorded num_ranks {want_n} "
             "(inconsistent trace metadata)")
    for r in range(want_n):
        if r not in present and r not in missing:
            missing[r] = "file missing (crashed or never started?)"
    return docs, missing


def sync_ts(doc, rank: int) -> float:
    """The rank's barrier-aligned clock-sync timestamp (us)."""
    ts = (doc.get("dist") or {}).get("clock_sync_ts_us")
    if ts is not None:
        return float(ts)
    for e in doc.get("traceEvents", []):
        if e.get("ph") == "i" and e.get("name") == "dist.clock_sync":
            return float(e["ts"])
    fail(f"rank {rank}: no dist.clock_sync event — was the trace written "
         "by dmlp_tpu.distributed --trace (obs.dist_trace)?")


_AG_SHAPE_KEYS = ("ranks", "r_shards", "qpad", "kcap")


def reconcile_comms(docs) -> dict | None:
    """Per-rank traced-vs-analytic byte table for the candidate
    all-gather, or None when no rank traced one (single-process or
    emulated runs dispatch no host all-gather — absence is normal, not
    a violation).

    The analytic side deliberately uses the MODEL'S OWN per-candidate
    itemsizes (obs.comms defaults: f64 dists + i32 labels + i32 ids),
    not the itemsizes the span recorded from the live arrays — the
    traced ``nbytes`` comes from the real buffers, so if the
    implementation's dtypes ever drift from what obs.comms assumes, the
    two sides disagree and the check FLAGS it instead of following the
    drift. (Shapes still come from the span: they are structural — the
    same r/qpad/kcap every dist.* span of the solve shares.) The span's
    recorded itemsizes ride along as a diagnostic on mismatch. Import
    of the analytic model is lazy and failure maps to an explicit
    marker: the merge must work from a bare checkout."""
    per_rank = {}
    for rank, doc in docs:
        spans = [e for e in doc.get("traceEvents", [])
                 if e.get("ph") == "X"
                 and e.get("name") == "dist.allgather_candidates"]
        if not spans:
            continue
        entry: dict = {"spans": len(spans),
                       "traced_bytes": sum(int(e.get("args", {})
                                               .get("nbytes", 0))
                                           for e in spans)}
        analytic = 0
        marker = None
        for e in spans:
            a = e.get("args", {})
            if not all(k in a for k in _AG_SHAPE_KEYS):
                marker = ("span args lack shape fields "
                          "(pre-r6 trace — re-record to reconcile)")
                break
            try:
                from dmlp_tpu.obs.comms import \
                    host_allgather_candidates_traffic
                t = host_allgather_candidates_traffic(
                    int(a["ranks"]), int(a["r_shards"]), int(a["qpad"]),
                    int(a["kcap"]))
                analytic += t.bytes_out_per_device
            except Exception as exc:
                marker = f"analytic model unavailable ({exc})"
                break
        if marker is not None:
            entry["analytic_unavailable"] = marker
        else:
            entry["analytic_bytes"] = analytic
            entry["match"] = analytic == entry["traced_bytes"]
            if not entry["match"]:
                entry["span_itemsizes"] = [
                    e.get("args", {}).get("itemsizes") for e in spans]
        per_rank[str(rank)] = entry
    return per_rank or None


def _rank_clock_source(doc) -> str:
    """The rank file's declared clock domain; pre-r6 traces (no clock
    metadata) are per-process monotonic by construction."""
    src = (doc.get("clock") or {}).get("source")
    if src is None:
        src = (doc.get("dist") or {}).get("clock_source")
    return src or "monotonic"


def straggler_analysis(docs, threshold: float = 1.5) -> dict:
    """Per-rank span-duration skew table — the straggler detector.

    Durations (``dur``) are clock-OFFSET invariant, so the skew metric
    compares each rank's total ``dist.solve`` time (the contract solve
    every rank dispatches identically) and total span-busy time against
    the across-rank median; a rank whose solve time exceeds
    ``threshold`` x the median is flagged. Ranks from MIXED clock
    domains (one trace already merge-aligned/"synced", another raw
    "monotonic" — their timestamps AND tick provenance differ) are
    refused with an explicit ``straggler_unavailable`` marker instead
    of a nonsense table.
    """
    domains = {rank: _rank_clock_source(doc) for rank, doc in docs}
    if len(set(domains.values())) > 1:
        return {"straggler_unavailable":
                f"mixed clock domains {domains} — re-record all ranks "
                "with one tracer generation before skew-comparing"}
    per_rank = {}
    solve_ms = {}
    for rank, doc in docs:
        busy = solve = 0.0
        last_end = 0.0
        for e in doc.get("traceEvents", []):
            if e.get("ph") != "X":
                continue
            dur = float(e.get("dur", 0.0))
            busy += dur
            last_end = max(last_end, float(e.get("ts", 0.0)) + dur)
            if e.get("name") == "dist.solve":
                solve += dur
        solve_ms[rank] = solve / 1e3
        per_rank[str(rank)] = {"span_busy_ms": round(busy / 1e3, 3),
                               "solve_ms": round(solve / 1e3, 3),
                               "last_span_end_ms":
                                   round(last_end / 1e3, 3)}
    import statistics
    med = statistics.median(solve_ms.values())
    flagged = []
    for rank in sorted(solve_ms):
        skew = (solve_ms[rank] / med) if med > 0 else None
        per_rank[str(rank)]["skew_vs_median"] = \
            round(skew, 3) if skew is not None else None
        if skew is not None and skew > threshold:
            flagged.append(rank)
    return {"threshold": threshold, "clock_source": domains[docs[0][0]],
            "median_solve_ms": round(med, 3), "per_rank": per_rank,
            "flagged_ranks": flagged}


def merge(trace_dir: str, align: bool = True,
          straggler_threshold: float = 1.5) -> dict:
    docs, missing = load_rank_files(trace_dir)
    if missing:
        print(f"merge_traces: WARNING: rank trace(s) missing or "
              f"truncated: { {r: missing[r] for r in sorted(missing)} } "
              "— merging the surviving ranks with the explicit "
              "rank_trace_missing marker", file=sys.stderr)
    offsets = {}
    if align:
        ref = sync_ts(docs[0][1], 0)
        offsets = {rank: ref - sync_ts(doc, rank) for rank, doc in docs}

    events = []
    span_counts = {}
    solve_counts = {}
    for rank, doc in docs:
        off = offsets.get(rank, 0.0)
        n_spans = 0
        for e in doc.get("traceEvents", []):
            e = dict(e)
            if "ts" in e:
                e["ts"] = e["ts"] + off
            events.append(e)
            if e.get("ph") == "X":
                n_spans += 1
                if e.get("name") == "dist.solve":
                    solve_counts[rank] = solve_counts.get(rank, 0) + 1
        span_counts[rank] = n_spans
        if n_spans == 0:
            fail(f"rank {rank}: zero spans — tracing was installed but "
                 "nothing recorded")
    # Cross-check only the SURVIVING ranks: a missing rank already
    # carries its marker; divergence among present ranks is still a
    # different-program error.
    if len(set(solve_counts.get(r, 0) for r, _ in docs)) > 1:
        fail(f"per-rank dist.solve span counts disagree: {solve_counts} "
             "(every rank runs the same contract solve; a mismatch means "
             "a rank died mid-run or traced a different program)")

    # Rebase so the merged timeline starts at 0: alignment shifts a
    # rank's pre-barrier events negative relative to the reference
    # rank's epoch, and downstream consumers (check_trace --dist) hold
    # timestamps non-negative.
    stamped = [e["ts"] for e in events if "ts" in e]
    base = min(stamped) if stamped else 0.0
    if base < 0:
        for e in events:
            if "ts" in e:
                e["ts"] -= base
    # Stable sort by aligned ts, metadata (M) events first per pid so
    # Perfetto names tracks before their first slice arrives.
    events.sort(key=lambda e: (0 if e.get("ph") == "M" else 1,
                               e.get("ts", 0.0)))
    dist_block = {
        "num_ranks": len(docs) + len(missing),
        "aligned": bool(align),
        "clock_offsets_us": {str(r): offsets.get(r, 0.0)
                             for r, _ in docs},
        "span_counts": {str(r): span_counts[r] for r, _ in docs},
    }
    if missing:
        dist_block["rank_trace_missing"] = {
            "ranks": sorted(missing),
            "reasons": {str(r): missing[r] for r in sorted(missing)},
        }
    reconcile = reconcile_comms(docs)
    if reconcile is not None:
        dist_block["comms_reconcile"] = reconcile
        bad = [r for r, e in reconcile.items() if e.get("match") is False]
        if bad:
            # Embedded for check_trace --dist to FAIL on; the merge
            # itself still writes the artifact (the mismatch is the
            # finding, and the trace is the evidence).
            print(f"merge_traces: WARNING: analytic vs traced all-gather "
                  f"bytes disagree for rank(s) {bad}: "
                  f"{ {r: reconcile[r] for r in bad} }", file=sys.stderr)
    straggler = straggler_analysis(docs, threshold=straggler_threshold)
    dist_block["straggler"] = straggler
    if straggler.get("flagged_ranks"):
        print(f"merge_traces: WARNING: rank(s) "
              f"{straggler['flagged_ranks']} exceed "
              f"{straggler['threshold']}x the median dist.solve time "
              f"(median {straggler['median_solve_ms']} ms) — straggler/"
              "skew suspects", file=sys.stderr)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        # Post-alignment, all ranks share one timeline; downstream skew
        # consumers key on this (a re-merge of this doc must not
        # re-align or mix it with raw monotonic rank files).
        "clock": {"source": "synced" if align else "monotonic"},
        "dist": dist_block,
    }


# -- fleet mode ---------------------------------------------------------------

#: the replica-side request phases the reconcile sums; one rid's phases
#: tile ITS OWN wall time (solve is the full micro-batch interval, from
#: the start of its first half to the end of its second, attributed to
#: every coalesced rid; two micro-batches overlap in time when one was
#: begun behind the other, so phases are matched by ``rid`` and never by
#: the ``serve.micro_batch`` they lie in) — never sum across rids.
FLEET_PHASES = ("queue", "coalesce", "solve", "finalize", "write")

#: budget for the MEDIAN per-request residual (client_ms - lag_ms -
#: phase_sum_ms) across reconciled rids. The residual is real un-phased
#: work — connect/parse/router relay — measured at ~9 ms on the CPU
#: reference fleet; 20 ms leaves 2x headroom before the marker trips.
#: The budget is NON-GATING: exceeding it stamps
#: ``residual_budget_exceeded`` in the reconcile block (surfaced by
#: ``check_trace --fleet``) so round-over-round residual creep is
#: visible, but never fails the merge.
RESIDUAL_BUDGET_MS = 20.0


def fleet_sync(doc, pname: str):
    """-> (ts_us, unix_us) of the process's fleet.clock_sync marker."""
    for e in doc.get("traceEvents", []):
        if e.get("ph") == "i" and e.get("name") == "fleet.clock_sync":
            a = e.get("args", {})
            if "unix_ms" not in a:
                fail(f"{pname}: fleet.clock_sync lacks unix_ms — "
                     "re-record with Tracer.sync_instant")
            return float(e["ts"]), float(a["unix_ms"]) * 1e3
    fail(f"{pname}: no fleet.clock_sync event — was the process started "
         "with --trace (router/replica) or a sync-stamped client Tracer?")


def _load_fleet_docs(trace_dir: str):
    """-> [(pname, new_pid, doc)] — router required, >=1 replica
    required, client optional (reconcile degrades to a marker)."""
    procs = []
    cpath = os.path.join(trace_dir, "trace-client.json")
    if os.path.exists(cpath):
        try:
            with open(cpath) as f:
                procs.append(("client", 0, json.load(f)))
        except (OSError, json.JSONDecodeError) as e:
            fail(f"{cpath}: unreadable or truncated: {e}")
    rpath = os.path.join(trace_dir, "trace-router.json")
    if not os.path.exists(rpath):
        fail(f"no trace-router.json in {trace_dir} (fleet mode needs "
             "the router started with --trace)")
    try:
        with open(rpath) as f:
            procs.append(("router", 1, json.load(f)))
    except (OSError, json.JSONDecodeError) as e:
        fail(f"{rpath}: unreadable or truncated: {e}")
    reps = sorted(glob.glob(os.path.join(trace_dir,
                                         "trace-replica*.json")))
    if not reps:
        fail(f"no trace-replica*.json in {trace_dir}")
    for i, p in enumerate(reps):
        name = re.sub(r"^trace-|\.json$", "", os.path.basename(p))
        try:
            with open(p) as f:
                procs.append((name, 10 + i, json.load(f)))
        except (OSError, json.JSONDecodeError) as e:
            fail(f"{p}: unreadable or truncated: {e}")
    return procs


def _stitch_rids(events) -> dict:
    """Per-rid causal table from the ALIGNED merged event stream."""
    table: dict = {}

    def ent(rid):
        return table.setdefault(rid, {"phases": {}, "hops": []})

    for e in events:
        if e.get("ph") != "X":
            continue
        a = e.get("args", {})
        name = e.get("name", "")
        dur_ms = float(e.get("dur", 0.0)) / 1e3
        if name == "client.request" and a.get("rid"):
            ent(a["rid"])["client"] = {
                "client_ms": round(dur_ms, 3),
                "lag_ms": float(a.get("lag_ms", 0.0)),
                "ok": bool(a.get("ok")),
                "hops": int(a.get("hops", 1)),
                **({"level": a["level"]} if "level" in a else {})}
        elif name == "fleet.route" and a.get("rid"):
            ent(a["rid"])["route"] = {
                "outcome": a.get("outcome"),
                **({"hops": int(a["hops"])} if "hops" in a else {})}
        elif name == "fleet.hop" and a.get("rid"):
            ent(a["rid"])["hops"].append(
                {"replica": a.get("replica"),
                 "outcome": a.get("outcome"),
                 **({"attempt": int(a["attempt"])}
                    if "attempt" in a else {}),
                 **({"fanout": True} if a.get("fanout") else {})})
        elif name.startswith("serve.phase.") and a.get("rid"):
            ph = name[len("serve.phase."):]
            d = ent(a["rid"])["phases"]
            d[ph] = round(d.get(ph, 0.0) + dur_ms, 3)
    return table


def reconcile_fleet(table: dict, have_client: bool, tol_abs_ms: float,
                    tol_rel: float, tol_clock_ms: float) -> dict:
    """Phase-sum vs client-latency verdicts (see module docstring)."""
    block = {"tol_abs_ms": tol_abs_ms, "tol_rel": tol_rel,
             "tol_clock_ms": tol_clock_ms,
             "phases_summed": list(FLEET_PHASES)}
    if not have_client:
        block["reconcile_unavailable"] = (
            "no trace-client.json — replay with a sync-stamped client "
            "Tracer (serve.client.replay_open_loop rid_prefix) to "
            "reconcile phase sums against client latency")
        return block
    n = n_ok = 0
    residuals = []
    for rid in sorted(table):
        ent = table[rid]
        cl = ent.get("client")
        if cl is None or not cl["ok"]:
            continue          # rejected/unrouted: nothing to reconcile
        n += 1
        phases = ent["phases"]
        if not all(p in phases for p in FLEET_PHASES):
            ent["reconciled"] = False
            ent["reconcile_gap"] = sorted(
                p for p in FLEET_PHASES if p not in phases)
            continue
        phase_sum = sum(phases[p] for p in FLEET_PHASES)
        residual = cl["client_ms"] - cl["lag_ms"] - phase_sum
        ent["phase_sum_ms"] = round(phase_sum, 3)
        ent["residual_ms"] = round(residual, 3)
        ent["reconciled"] = (
            -tol_clock_ms <= residual
            <= tol_abs_ms + tol_rel * cl["client_ms"])
        n_ok += bool(ent["reconciled"])
        if ent["reconciled"]:
            residuals.append(residual)
    block.update(n_requests=n, n_reconciled=n_ok,
                 fraction=round(n_ok / n, 4) if n else None)
    # The residual used to be silent (each rid carried its own but no
    # aggregate) — surface the median so creep is visible per round.
    if residuals:
        residuals.sort()
        m = len(residuals) // 2
        med = residuals[m] if len(residuals) % 2 else \
            (residuals[m - 1] + residuals[m]) / 2.0
        block["reconcile_residual_ms"] = round(med, 3)
        block["residual_budget_ms"] = RESIDUAL_BUDGET_MS
        if med > RESIDUAL_BUDGET_MS:
            block["residual_budget_exceeded"] = True  # non-gating
    return block


def merge_fleet(trace_dir: str, tol_abs_ms: float = 75.0,
                tol_rel: float = 0.25,
                tol_clock_ms: float = 10.0) -> dict:
    procs = _load_fleet_docs(trace_dir)
    have_client = any(p[0] == "client" for p in procs)
    ref_ts, ref_unix = fleet_sync(
        next(d for n, _, d in procs if n == "router"), "router")
    offsets = {}
    events = []
    span_counts = {}
    for pname, pid, doc in procs:
        ts_p, unix_p = fleet_sync(doc, pname)
        off = ref_ts - ts_p + (unix_p - ref_unix)
        offsets[pname] = off
        n_spans = 0
        for e in doc.get("traceEvents", []):
            e = dict(e)
            e["pid"] = pid
            if "ts" in e:
                e["ts"] = e["ts"] + off
            events.append(e)
            n_spans += e.get("ph") == "X"
        span_counts[pname] = n_spans
        if n_spans == 0 and pname != "client":
            fail(f"{pname}: zero spans — tracing was installed but "
                 "nothing recorded")
    stamped = [e["ts"] for e in events if "ts" in e]
    base = min(stamped) if stamped else 0.0
    if base < 0:
        for e in events:
            if "ts" in e:
                e["ts"] -= base
    events.sort(key=lambda e: (0 if e.get("ph") == "M" else 1,
                               e.get("ts", 0.0)))
    table = _stitch_rids(events)
    reconcile = reconcile_fleet(table, have_client, tol_abs_ms,
                                tol_rel, tol_clock_ms)
    frac = reconcile.get("fraction")
    if frac is not None and frac < 1.0:
        bad = [r for r in sorted(table)
               if table[r].get("reconciled") is False]
        print(f"merge_traces: WARNING: {len(bad)} request(s) fail the "
              f"phase-sum reconcile (fraction {frac}): e.g. "
              f"{ {r: table[r] for r in bad[:3]} }", file=sys.stderr)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "clock": {"source": "synced"},
        "fleet": {
            "processes": {n: {"pid": p, "spans": span_counts[n]}
                          for n, p, _ in procs},
            "clock_offsets_us": {n: round(o, 1)
                                 for n, o in offsets.items()},
            "requests": table,
            "reconcile": reconcile,
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace_dir", help="directory holding trace-rank*.json")
    ap.add_argument("-o", "--out", default=None,
                    help="merged output path (default DIR/trace-merged.json)")
    ap.add_argument("--no-align", action="store_true",
                    help="keep each rank's raw clock (skip the "
                         "clock-sync offset alignment)")
    ap.add_argument("--straggler-threshold", type=float, default=1.5,
                    help="flag ranks whose dist.solve time exceeds this "
                         "multiple of the across-rank median")
    ap.add_argument("--fleet", action="store_true",
                    help="merge a serving fleet's rid-tagged traces "
                         "(trace-router.json + trace-replicaNN.json "
                         "[+ trace-client.json]) instead of dist ranks")
    ap.add_argument("--tol-abs-ms", type=float, default=75.0,
                    help="fleet reconcile: absolute residual budget "
                         "(connect/parse/relay overhead per request)")
    ap.add_argument("--tol-rel", type=float, default=0.25,
                    help="fleet reconcile: residual budget as a "
                         "fraction of the client-measured latency")
    ap.add_argument("--tol-clock-ms", type=float, default=10.0,
                    help="fleet reconcile: allowed NEGATIVE residual "
                         "(perf_counter rate noise across processes)")
    args = ap.parse_args(argv)

    if args.fleet:
        out_path = args.out or os.path.join(args.trace_dir,
                                            "trace-fleet-merged.json")
        doc = merge_fleet(args.trace_dir, tol_abs_ms=args.tol_abs_ms,
                          tol_rel=args.tol_rel,
                          tol_clock_ms=args.tol_clock_ms)
    else:
        out_path = args.out or os.path.join(args.trace_dir,
                                            "trace-merged.json")
        doc = merge(args.trace_dir, align=not args.no_align,
                    straggler_threshold=args.straggler_threshold)
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, out_path)
    if args.fleet:
        fb = doc["fleet"]
        rec = fb["reconcile"]
        print(f"merge_traces: merged fleet "
              f"{sorted(fb['processes'])} -> {out_path} "
              f"({len(fb['requests'])} rid(s), offsets us: "
              f"{fb['clock_offsets_us']}, reconciled: "
              f"{rec.get('n_reconciled')}/{rec.get('n_requests')})")
        return 0
    d = doc["dist"]
    print(f"merge_traces: merged {d['num_ranks']} ranks -> {out_path} "
          f"(spans per rank: {d['span_counts']}, offsets us: "
          f"{ {k: round(v, 1) for k, v in d['clock_offsets_us'].items()} })")
    return 0


if __name__ == "__main__":
    sys.exit(main())
