#!/usr/bin/env python
"""Tail-latency attribution over a merged fleet trace.

Decomposes the client-observed latency quantiles (p50/p95/p99) at each
offered-load level into per-phase contributions, names the dominant
phase per level (queue-bound vs solve-bound vs coalesce-bound ...),
and emits one ``kind="tailattrib"`` RunRecord per level (a queue-phase
p99 creeping up is the predictive-autoscaling signal BEFORE the
end-to-end SLO slips).

Method: the quantiles of a sum are not the sum of quantiles, so naive
"p99 of each phase" double-counts. Instead, for each quantile q the
tool takes the TAIL COHORT — the requests whose client-measured
latency is >= the q-th latency — and reports each phase's MEAN
duration over that cohort. The cohort means sum (plus the un-phased
residual) to roughly the cohort's mean latency, so the decomposition
is additive and the argmax phase is a meaningful "what is the tail
waiting on".

Input is ``tools/merge_traces.py --fleet`` output: the per-rid table
(client_ms, lag_ms, per-phase ms, level) is read from the embedded
``fleet.requests`` block. Levels come from the ``client.request``
spans' ``level`` arg (``serve.client.replay_open_loop(level=...)``);
rids without a level fall into the ``all`` pseudo-level.

Usage: python tools/tail_attrib.py MERGED.json [--record FILE]
       [--json] [--round N]
``--json`` prints ONE machine-readable verdict document on stdout
(narration to stderr). Exit 0 when every level attributes; 1 when no
level has an attributable (ok + fully-phased) request.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.merge_traces import FLEET_PHASES                     # noqa: E402

QUANTILES = (("p50", 0.5), ("p95", 0.95), ("p99", 0.99))


def _quantile(sorted_vals, q: float) -> float:
    """Nearest-rank-with-interpolation quantile of a sorted list."""
    if not sorted_vals:
        return float("nan")
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (pos - lo)


def attribute_level(reqs) -> dict:
    """Per-quantile per-phase tail decomposition of one level's
    requests: ``reqs`` is a list of (client_ms, {phase: ms}) pairs."""
    lats = sorted(c for c, _ in reqs)
    out = {"n": len(reqs), "quantiles": {}}
    for qt, q in QUANTILES:
        thresh = _quantile(lats, q)
        cohort = [(c, ph) for c, ph in reqs if c >= thresh]
        entry = {"client_ms": round(thresh, 3),
                 "cohort_n": len(cohort), "phases": {}}
        for p in FLEET_PHASES:
            entry["phases"][p] = round(
                sum(ph.get(p, 0.0) for _, ph in cohort) / len(cohort), 3)
        entry["residual_ms"] = round(
            sum(c - sum(ph.get(p, 0.0) for p in FLEET_PHASES)
                for c, ph in cohort) / len(cohort), 3)
        entry["dominant"] = max(entry["phases"],
                                key=lambda p: entry["phases"][p])
        out["quantiles"][qt] = entry
    out["dominant_p99"] = out["quantiles"]["p99"]["dominant"]
    return out


def attribute(merged: dict) -> dict:
    """-> {level_tag: attribution} over the merged fleet trace doc."""
    table = (merged.get("fleet") or {}).get("requests")
    if table is None:
        raise SystemExit("tail_attrib: FAIL: input has no fleet.requests "
                         "block — is it merge_traces --fleet output?")
    from dmlp_tpu.fleet.loadgen import level_tag
    by_level: dict = {}
    for rid in sorted(table):
        ent = table[rid]
        cl = ent.get("client")
        if cl is None or not cl.get("ok"):
            continue
        phases = ent.get("phases", {})
        if not all(p in phases for p in FLEET_PHASES):
            continue          # unphased requests cannot be attributed
        lvl = (level_tag(float(cl["level"])) if "level" in cl else "all")
        # Attribution decomposes time spent IN the fleet, so the
        # client's pre-send pacing lag is excluded up front.
        by_level.setdefault(lvl, []).append(
            (cl["client_ms"] - cl.get("lag_ms", 0.0), phases))
    return {lvl: attribute_level(reqs)
            for lvl, reqs in sorted(by_level.items())}


def emit_records(levels: dict, record_path: str, trace_path: str,
                 round_: int = None, device: str = None) -> int:
    """``device`` is the kind the TRACED fleet ran on (its replicas'
    device stamp, handed in by whoever ran the fleet) — an offline pass
    over a trace file has no device of its own to report."""
    from dmlp_tpu.obs.run import RunRecord
    n = 0
    for lvl, att in levels.items():
        metrics = {"attributed_requests": att["n"]}
        for qt, entry in att["quantiles"].items():
            metrics[f"client_{qt}_ms"] = entry["client_ms"]
            metrics[f"residual_{qt}_ms"] = entry["residual_ms"]
            for p, v in entry["phases"].items():
                metrics[f"{p}_{qt}_ms"] = v
        RunRecord(kind="tailattrib", tool="tools.tail_attrib",
                  config={"level": lvl, "dominant_p99":
                          att["dominant_p99"],
                          "trace": os.path.basename(trace_path),
                          "quantiles": [qt for qt, _ in QUANTILES]},
                  metrics=metrics, round=round_,
                  device=device).append_jsonl(record_path)
        n += 1
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("merged", help="merge_traces --fleet output JSON")
    ap.add_argument("--record", metavar="FILE", default=None,
                    help="append one kind='tailattrib' RunRecord per "
                         "level here")
    ap.add_argument("--json", action="store_true",
                    help="print the attribution document on stdout "
                         "(narration to stderr)")
    ap.add_argument("--round", type=int, default=None,
                    help="measurement round stamped into the records")
    ap.add_argument("--device", default=None,
                    help="device kind the traced fleet ran on (its "
                         "replicas' device stamp), stamped into the "
                         "records")
    args = ap.parse_args(argv)

    def say(msg):
        print(msg, file=sys.stderr if args.json else sys.stdout)

    with open(args.merged) as f:
        merged = json.load(f)
    levels = attribute(merged)
    if not levels:
        print("tail_attrib: FAIL: no attributable request (none ok "
              "with a complete phase set) in the merged trace",
              file=sys.stderr)
        return 1
    for lvl, att in levels.items():
        p99 = att["quantiles"]["p99"]
        say(f"tail_attrib: {lvl}: n={att['n']} p99={p99['client_ms']}ms "
            f"dominant={att['dominant_p99']} "
            f"(phases ms: {p99['phases']}, "
            f"residual {p99['residual_ms']}ms)")
    if args.record:
        n = emit_records(levels, args.record, args.merged,
                         round_=args.round, device=args.device)
        say(f"tail_attrib: appended {n} tailattrib record(s) -> "
            f"{args.record}")
    if args.json:
        json.dump({"levels": levels, "phases": list(FLEET_PHASES)},
                  sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
