"""Find the knee of an open-loop cell once, on the chip, in one process.

``python -m benchmark.sweep --workload W --seed N --start R [--step 1.25]
[--seconds 15] [--max-steps 10]``: builds the cell's daemon as
``benchmark.run`` does, then offers the cell's mix at R, R*step, ...
requests/s, ``--seconds`` each, and prints a line a rate: completed over
offered, the generator's lag, the latencies. A rate is sustained when
completed (answered ``ok``, the drain after the last arrival included)
>= 0.98 x offered, the generator's lag stays under 5 ms at its 95th
percentile, and the backlog does not grow: the median latency of the
step's last third is at most 1.5 x that of its first third. The knee is
the highest sustained rate; the cell's file then fixes ``rate_per_s``
at 0.8 x the knee. Stops two steps after the first rate
that fails. Not part of a benchmark run; its output goes into PERF.md.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import data, spec
from benchmark.readers import percentile
from benchmark.run import LoadGen, _check_stamp, build_daemon


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--step", type=float, default=1.25)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--max-steps", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload, rehearse=args.rehearse)
    labels, rows = data.corpus(cell.config, args.seed)
    daemon = build_daemon(cell, labels, rows)
    failing = 0
    try:
        daemon.start()
        _check_stamp(daemon.stats()["device"], cell)
        rate = args.start
        for step in range(args.max_steps):
            params = dict(cell.params, rate_per_s=rate)
            gen = LoadGen(cell, params, args.seed + step, args.seconds)
            try:
                gen.wait_ready()
                batches = daemon.stats()["batches"]
                gen.go(daemon.port)
                res = gen.result()
                batches = daemon.stats()["batches"] - batches
            finally:
                gen.close()
            recs = res["requests"]
            ok = [r for r in recs if r["ok"]]
            lat = sorted(r["latency_ms"] if r["ok"] else res["timeout_ms"]
                         for r in recs)
            lag = sorted(r["lag_ms"] for r in recs)
            last = max((r["done_s"] for r in recs), default=0.0)
            done_in = sum(1 for r in ok if r["done_s"] <= args.seconds)
            line = {"rate_per_s": rate, "offered": len(recs),
                    "completed_ok": len(ok), "batches": batches,
                    "completed_in_window": done_in,
                    "completed_share": done_in / max(len(recs), 1),
                    "drain_s": last - res["offered_s"],
                    "lag_p95_ms": percentile(lag, 95),
                    "p50_ms": percentile(lat, 50),
                    "p95_ms": percentile(lat, 95),
                    "p99_ms": percentile(lat, 99)}
            third = max(len(recs) // 3, 1)
            by_due = sorted(recs, key=lambda r: r["due_s"])

            def med(part):
                return percentile(sorted(
                    r["latency_ms"] if r["ok"] else res["timeout_ms"]
                    for r in part), 50)
            line["p50_first_third_ms"] = med(by_due[:third])
            line["p50_last_third_ms"] = med(by_due[-third:])
            line["sustained"] = (
                len(ok) >= 0.98 * len(recs) and line["lag_p95_ms"] < 5.0
                and line["p50_last_third_ms"]
                <= 1.5 * line["p50_first_third_ms"])
            print(json.dumps(line), flush=True)
            failing = 0 if line["sustained"] else failing + 1
            if failing >= 2:
                break
            rate *= args.step
    finally:
        daemon.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
