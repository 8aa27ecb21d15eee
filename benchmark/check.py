"""Deciding ``correct``: the window's own answers against the reference.

Three numbers, each beside its limit in every run's output:

- ``checksum_mismatches``: sampled queries whose label or FNV-1a
  checksum differs from the float64 brute force. Limit 0: the
  configurations guarantee exact answers.
- ``dist_rel_err_max``: over the sampled answers that carry distances
  (every batch answer; served answers of ``debug`` requests), the
  largest |served - reference| / scale, the scale the reference's to
  state (``dist_scale``; ``max(|reference|, tiny)`` where it states
  none). An ordering taken from a float32 pass is right on almost every
  query at these sizes, so the checksums alone would let it through;
  its distances are off by 1e-7.
- ``reference_plain_mismatches``: the reference the other two are held
  to is its ``knn_exact``, a screened search; a few of the sampled
  queries, drawn from the seed, are searched again by its plain brute
  force ``knn_plain`` at the cell's own size, and the two must give the
  same label, ids, checksum and distances. Limit 0.

The reference is the module the cell's configuration names
(``spec.Cell.reference``: ``references/<name>.py``, or
``benchmark/reference.py`` where it names none); nothing here imports
one. Samples are drawn from the seed once the window has closed, from
what the window itself produced, the longest request among them.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmark import data


def sample_requests(records: Sequence[Dict[str, Any]], requests: int,
                    per_request: int, seed: int
                    ) -> List[Tuple[Dict[str, Any], np.ndarray]]:
    """(record, query positions) pairs: the longest ``debug`` request
    and the longest plain one first, then alternately of each kind in
    seeded order, ``requests`` in all; of a request longer than
    ``per_request`` a seeded subset of its queries."""
    rng = np.random.default_rng([int(seed), 22])
    pools = []
    for flag in (True, False):
        pool = [r for r in records if r["ok"] and bool(r["debug"]) == flag]
        pool = [pool[i] for i in rng.permutation(len(pool))]
        pool.sort(key=lambda r: -r["nq"])       # stable: seeded among ties
        head, rest = pool[:1], pool[1:]
        pools.append(head + [rest[i] for i in rng.permutation(len(rest))])
    picks: List[Dict[str, Any]] = []
    while len(picks) < requests and any(pools):
        for pool in pools:
            if pool and len(picks) < requests:
                picks.append(pool.pop(0))
    out = []
    for r in picks:
        pos = np.arange(r["nq"])
        if r["nq"] > per_request:
            pos = np.sort(rng.choice(r["nq"], per_request, replace=False))
        out.append((r, pos))
    return out


def dist_scale(want: np.ndarray) -> np.ndarray:
    """The denominator of ``dist_rel_err_max`` under a reference that
    states none: the reference's own distance, which squared L2 keeps
    above zero wherever the query is not a row."""
    return np.maximum(np.abs(want), np.finfo(np.float64).tiny)


def _rel_err(got: np.ndarray, want: np.ndarray, scale=dist_scale) -> float:
    both = np.isfinite(want) & np.isfinite(got)
    if (np.isfinite(want) != np.isfinite(got)).any():
        return float("inf")
    if not both.any():
        return 0.0
    return float((np.abs(got[both] - want[both])
                  / scale(want[both])).max())


class Verdict:
    """Accumulates the compared numbers; ``lines()`` prints each beside
    its limit, ``correct`` holds them all. ``scale`` is the reference
    module's ``dist_scale`` where it has one."""

    def __init__(self, limits: Dict[str, float], scale=dist_scale):
        self.limits = limits
        self.scale = scale
        self.queries = self.mismatches = self.distances = 0
        self.plain = self.plain_mismatches = 0
        self.rel_err = 0.0

    def add(self, ref, label: int, checksum: int, dists=None) -> None:
        """One served answer against the reference's ``Answer``."""
        self.queries += 1
        if int(label) != ref.label or int(checksum) != ref.checksum:
            self.mismatches += 1
        if dists is not None:
            got = np.asarray(dists, np.float64)
            self.distances += got.size
            if got.shape != ref.dists.shape:
                self.rel_err = float("inf")
            else:
                self.rel_err = max(self.rel_err,
                                   _rel_err(got, ref.dists, self.scale))

    def add_plain(self, ref, plain) -> None:
        """One query of the screened reference, held to the plain one."""
        self.plain += 1
        if (ref.label != plain.label or ref.checksum != plain.checksum
                or not np.array_equal(ref.ids, plain.ids)
                or _rel_err(ref.dists, plain.dists, self.scale)
                > self.limits["dist_rel_err_max"]):
            self.plain_mismatches += 1

    @property
    def numbers(self) -> Dict[str, float]:
        return {"checksum_mismatches": self.mismatches,
                "dist_rel_err_max": self.rel_err,
                "reference_plain_mismatches": self.plain_mismatches}

    @property
    def correct(self) -> bool:
        return (self.queries > 0 and self.distances > 0 and self.plain > 0
                and all(self.numbers[k] <= self.limits[k]
                        for k in self.numbers))

    def lines(self) -> List[Dict[str, Any]]:
        n = {"checksum_mismatches": self.queries,
             "dist_rel_err_max": self.distances,
             "reference_plain_mismatches": self.plain}
        return [{"event": "check", "number": k, "value": v,
                 "limit": self.limits[k], "compared": n[k],
                 "within": v <= self.limits[k]}
                for k, v in self.numbers.items()]


def check_served(reference, cfg, rows, labels, records, answers, k,
                 requests, per_request, plain_queries, seed,
                 limits) -> Verdict:
    """The window's sampled answers against ``reference``, the module
    the cell's configuration names."""
    picks = sample_requests(records, requests, per_request, seed)
    v = Verdict(limits, getattr(reference, "dist_scale", dist_scale))
    if not picks:
        return v
    qs = np.concatenate([
        data.request_queries(cfg, seed, r["payload"], r["nq"])[pos]
        for r, pos in picks])
    refs = reference.knn_exact(rows, labels, qs, np.full(len(qs), int(k)))
    rng = np.random.default_rng([int(seed), 23])
    for j in rng.choice(len(qs), min(int(plain_queries), len(qs)),
                        replace=False):
        v.add_plain(refs[j], reference.knn_plain(
            rows, labels, qs[j:j + 1], [int(k)])[0])
    it = iter(refs)
    for r, pos in picks:
        ans = answers[str(r["seq"])]
        for p in pos:
            dists = ans["dists"][int(p)] if "dists" in ans else None
            v.add(next(it), ans["labels"][int(p)],
                  ans["checksums"][int(p)], dists)
    return v
