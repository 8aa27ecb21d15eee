"""Static per-dispatch counters from XLA cost analysis + roofline summary.

XLA knows, at compile time, how many model FLOPs and HBM bytes each
compiled program touches: ``jitted.lower(...).compile().cost_analysis()``.
This module turns that into run-level counters without perturbing the hot
path: engines *record* their dispatches into an installed
:class:`CostProbe` (shapes only — arguments are reduced to
``jax.ShapeDtypeStruct`` specs immediately, so no device buffer is kept
alive), and the probe *collects* after the timed region by re-lowering
each unique (function, shapes, statics) signature once and multiplying by
its dispatch count.

Cost analysis is best-effort across backends and program kinds: every
per-entry failure is swallowed and counted as ``skipped``; a collection
where nothing was analyzable returns ``{"counters_unavailable": True}``
— the explicit marker the CLI metrics contract requires instead of
silence. Pallas kernels expose no XLA cost model at all, so the flagship
extract/distance kernels resolve through the analytic per-kernel models
in :mod:`dmlp_tpu.obs.kernel_cost` instead (consulted first — XLA's
numbers for an interpret-mode Pallas program would measure the
emulation); analytically-resolved dispatch counts are reported
separately as ``dispatches_analytic_model``.

The roofline summary reuses the training side's per-chip peak table
(train.metrics.PEAK_FLOPS_BY_KIND) so KNN solves and train steps report
achieved-vs-peak on the same scale.

Import-light: jax is imported lazily, only when a probe is actually used.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

__all__ = ["CostProbe", "normalize_cost", "lowered_cost", "roofline",
           "install", "uninstall", "active", "record_dispatch",
           "record_measured_iters"]


# cost_analysis() shapes normalize_cost could not use, deduplicated and
# bounded — attached to the counters_unavailable marker so the next JAX
# API drift (a renamed key, a new container type) is diagnosable from a
# run record instead of a repro session.
_UNRECOGNIZED_MAX = 4
_unrecognized_shapes: list = []


def _note_unrecognized(raw) -> None:
    desc: Dict[str, Any] = {"type": type(raw).__name__}
    if isinstance(raw, dict):
        desc["keys"] = sorted(str(k) for k in raw)[:16]
    if desc not in _unrecognized_shapes and \
            len(_unrecognized_shapes) < _UNRECOGNIZED_MAX:
        _unrecognized_shapes.append(desc)


def normalize_cost(raw) -> Optional[Dict[str, float]]:
    """Normalize ``cost_analysis()`` output across JAX versions: a dict,
    a one-element list of dicts, or None. Returns {flops, bytes_accessed}
    (floats; absent keys -> 0.0), or None when there is nothing usable —
    noting the raw shape it could not use (see ``_note_unrecognized``)."""
    if raw is None:
        return None
    if isinstance(raw, (list, tuple)):
        if not raw:
            _note_unrecognized(raw)
            return None
        raw = raw[0]
    if not isinstance(raw, dict):
        _note_unrecognized(raw)
        return None
    flops = float(raw.get("flops", 0.0) or 0.0)
    byts = float(raw.get("bytes accessed", 0.0) or 0.0)
    if flops == 0.0 and byts == 0.0:
        if "flops" not in raw and "bytes accessed" not in raw:
            # a dict that carries NEITHER expected key is shape drift,
            # not a genuinely zero-cost program — record its keys
            _note_unrecognized(raw)
        return None
    return {"flops": flops, "bytes_accessed": byts}


def lowered_cost(fn, *args, **kwargs) -> Optional[Dict[str, float]]:
    """Cost analysis of one jitted signature; None when unavailable
    (non-jitted callable, backend without a cost model, lowering error)."""
    try:
        return normalize_cost(fn.lower(*args, **kwargs).compile()
                              .cost_analysis())
    except Exception:
        return None


class CostProbe:
    """Accumulates dispatch records (shape specs, not buffers) keyed by
    signature; ``collect()`` resolves them into summed counters."""

    def __init__(self) -> None:
        # key -> [fn, spec_args, static_kwargs, count, site]
        self._entries: Dict[Tuple, list] = {}
        # (site, (qb, b, a, kc)) -> [iters_total, wide_iters] — measured
        # extract-loop iteration counts (and how many of them ran at
        # full width) the engines read back post-fence, keyed by
        # dispatch shape like the dispatch records themselves (two
        # solves at different shapes under one site must cost their
        # iterations at their own tiles, not the first shape's)
        self._measured_iters: Dict[Tuple, List[int]] = {}

    def reset(self) -> None:
        """Drop recorded dispatches — callers bracket untimed work (e.g.
        a warmup solve) so counters match the timed region only."""
        self._entries.clear()
        self._measured_iters.clear()

    def record(self, fn, args: tuple, statics: Optional[dict] = None,
               count: int = 1, site: str = "") -> None:
        """Note ``count`` dispatches of ``fn(*args, **statics)``. ``args``
        are reduced to ShapeDtypeStructs here — nothing stays alive."""
        try:
            import jax
            specs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        except Exception:
            return  # non-array leaves etc. — observability must not raise
        statics = dict(statics or {})
        key = (id(fn), site,
               str(jax.tree_util.tree_structure(specs)),
               str(jax.tree_util.tree_leaves(specs)),
               tuple(sorted((k, str(v)) for k, v in statics.items())))
        entry = self._entries.get(key)
        if entry is not None:
            entry[3] += count
        else:
            self._entries[key] = [fn, specs, statics, count, site]

    def dispatches(self) -> list:
        """Read-only view of the recorded signatures —
        ``[(fn, spec_args, static_kwargs, count, site), ...]`` — for
        downstream introspection (obs.hlo lowers each unique signature
        once to read its compiled collective schedule and memory)."""
        return [tuple(e) for e in self._entries.values()]

    def record_measured_iters(self, site: str, iters_total: int,
                              shape: Tuple[int, int, int, int],
                              wide_iters: int = 0) -> None:
        """Attach MEASURED extraction-loop iteration counts to ``site``
        (summed over the kernel's iters output across that site's
        dispatches at this shape; ``wide_iters`` of them ran at full
        width under the kernel's two-level selection, where the caller
        read that back too). ``shape`` is the per-dispatch
        (qb, b, a, kc): the collect pass costs each (site, shape)'s
        count at that shape's resolved tiles
        (obs.kernel_cost.extract_loop_cost) and the site's total is no
        longer just the deterministic lower bound."""
        got = self._measured_iters.setdefault((site, tuple(shape)), [0, 0])
        got[0] += int(iters_total)
        got[1] += int(wide_iters)

    def collect(self) -> Dict[str, Any]:
        """Resolve every recorded signature through cost analysis.

        Returns summed ``flops`` / ``bytes_accessed`` with per-site
        breakdown, or ``{"counters_unavailable": True, ...}`` when no
        signature was analyzable (e.g. a backend with no cost model).
        Functions with a registered analytic model (the Pallas kernels,
        obs.kernel_cost) resolve through it instead of XLA."""
        from dmlp_tpu.obs import kernel_cost

        flops = byts = 0.0
        analyzed = skipped = dispatches = analytic = 0
        per_site: Dict[str, Dict[str, float]] = {}
        for fn, specs, statics, count, site in self._entries.values():
            dispatches += count
            cost = kernel_cost.analytic_cost(fn, specs, statics)
            if cost is not None:
                analytic += count
            else:
                cost = lowered_cost(fn, *specs, **statics)
            if cost is None:
                skipped += count
                continue
            analyzed += count
            flops += cost["flops"] * count
            byts += cost["bytes_accessed"] * count
            if site:
                agg = per_site.setdefault(
                    site, {"flops": 0.0, "bytes_accessed": 0.0,
                           "dispatches": 0})
                agg["flops"] += cost["flops"] * count
                agg["bytes_accessed"] += cost["bytes_accessed"] * count
                agg["dispatches"] += count
        if analyzed == 0:
            out = {"counters_unavailable": True,
                   "dispatches_recorded": dispatches}
            if _unrecognized_shapes:
                out["unrecognized_cost_shapes"] = \
                    [dict(d) for d in _unrecognized_shapes]
                try:
                    import jax
                    out["jax_version"] = jax.__version__
                except Exception:
                    pass
            return out
        # Measured extraction terms: fold each (site, shape)'s
        # read-back iters count into the totals (count-independent — the
        # engines already summed across that site's dispatches at the
        # shape).
        iters_all = 0
        for (site, shape), (iters_total, wide_iters) \
                in self._measured_iters.items():
            try:
                loop_flops = kernel_cost.extract_loop_cost(
                    *shape, iters_total=iters_total, wide_iters=wide_iters)
            except Exception:
                continue
            flops += loop_flops
            iters_all += iters_total
            if site in per_site:
                per_site[site]["flops"] += loop_flops
                per_site[site]["extraction_term"] = "measured"
                per_site[site]["extract_iters_total"] = \
                    per_site[site].get("extract_iters_total", 0) \
                    + iters_total
        out: Dict[str, Any] = {
            "flops": flops, "bytes_accessed": byts,
            "dispatches_recorded": dispatches,
            "dispatches_analyzed": analyzed,
        }
        if iters_all:
            out["extract_iters_total"] = iters_all
            out["extraction_term"] = "measured"
        if analytic:
            # Name the modeled share: these dispatches carry analytic
            # (obs.kernel_cost) numbers, not XLA cost analysis.
            out["dispatches_analytic_model"] = analytic
        if skipped:
            # No silent caps: name what the totals do NOT cover.
            out["dispatches_skipped_no_cost_model"] = skipped
        if per_site:
            out["per_site"] = per_site
        return out


def roofline(flops: float, bytes_accessed: float, elapsed_s: float,
             n_chips: int = 1) -> Dict[str, float]:
    """Achieved-vs-peak summary for a solve that took ``elapsed_s``.

    Peak comes from the training side's per-chip table
    (train.metrics.peak_flops_per_chip), so 'utilization_vs_peak' is
    directly comparable to the train loop's MFU. On a device kind the
    table does not know, the achieved rates stand alone: no peak, no
    utilisation."""
    out = {"flops": flops, "bytes_accessed": bytes_accessed,
           "elapsed_s": elapsed_s}
    if elapsed_s > 0:
        out["achieved_flops_per_s"] = flops / elapsed_s
        out["achieved_bytes_per_s"] = bytes_accessed / elapsed_s
    if bytes_accessed > 0:
        out["arithmetic_intensity"] = flops / bytes_accessed
    from dmlp_tpu.train.metrics import (UnknownDeviceKind,
                                        peak_flops_per_chip)
    try:
        peak = peak_flops_per_chip()
    except UnknownDeviceKind:
        return out
    out["peak_flops_per_chip"] = peak
    if elapsed_s > 0:
        out["utilization_vs_peak"] = flops / (elapsed_s * n_chips * peak)
    return out


# -- process-wide hook (mirrors obs.trace) -----------------------------------
_active: Optional[CostProbe] = None


def install(probe: Optional[CostProbe] = None) -> CostProbe:
    global _active
    _active = probe if probe is not None else CostProbe()
    return _active


def uninstall() -> None:
    global _active
    _active = None


def active() -> Optional[CostProbe]:
    return _active


def record_dispatch(fn, args: tuple, statics: Optional[dict] = None,
                    count: int = 1, site: str = "") -> None:
    """Hot-path hook: records into the installed probe, no-op otherwise."""
    p = _active
    if p is not None:
        p.record(fn, args, statics=statics, count=count, site=site)


def record_measured_iters(site: str, iters_total: int,
                          shape: Tuple[int, int, int, int],
                          wide_iters: int = 0) -> None:
    """Post-fence hook: measured extract-loop iters for ``site``
    (see CostProbe.record_measured_iters); no-op without a probe."""
    p = _active
    if p is not None:
        p.record_measured_iters(site, iters_total, shape, wide_iters)
