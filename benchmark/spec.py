"""Finding a cell's data files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own; the harness holds no table of
them. A later PR adds entries and files and edits none:

    configs/<config>.json          sizes, program options, guarantees, control,
                                   and (``modules``) its reference and generator
    references/<name>.py           a plain reference a configuration names
    generators/<name>.py           a value generator a configuration names
    traffic/<mix>.json             a traffic kind and its parameters
    traffic/<kind>.py              the generator of that kind
    workloads/<cell>.json          config + mix + what is particular to the pair
    layer_metrics/<metric>.json    layer, reader and its arguments, cells
    readers/<reader>.py            one reduction from spans/counters/trace
"""

from __future__ import annotations

import copy
import importlib
import json
import os
import pkgutil
import re
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """A data file is missing, malformed or disagrees with BENCHMARK.json."""


def _load(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        raise SpecError(f"cannot read {path}: {e}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not JSON: {e}") from None
    if not isinstance(doc, dict):
        raise SpecError(f"{path} must hold a JSON object")
    return doc


def merge(base: Dict[str, Any], over: Optional[Dict[str, Any]]
          ) -> Dict[str, Any]:
    """``over`` laid on ``base``, objects merged key by key."""
    out = copy.deepcopy(base)
    for key, val in (over or {}).items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = merge(out[key], val)
        else:
            out[key] = copy.deepcopy(val)
    return out


def benchmark() -> Dict[str, Any]:
    """``BENCHMARK.json`` at the checkout's root."""
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def peaks(device_kind: str) -> Dict[str, Any]:
    table = _load(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise SpecError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); a roofline share needs its peaks")
    return table[device_kind]


def _toy(doc: Dict[str, Any], path: str) -> Dict[str, Any]:
    """The file's own ``rehearse`` object: the toy sizes ``--rehearse``
    lays over it. A file without one cannot be rehearsed, so that a cell
    never runs at its real size by an oversight."""
    toy = doc.get("rehearse")
    if not isinstance(toy, dict):
        raise SpecError(f"{path} has no \"rehearse\" object: state the "
                        "toy sizes --rehearse lays over this file")
    return toy


def _named(package: str, name: str, what: str):
    """Module ``benchmark.<package>.<name>``; an unknown name lists the
    package's modules."""
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"illegal {what} {name!r}")
    pkg = importlib.import_module(f"benchmark.{package}")
    try:
        return importlib.import_module(f"{pkg.__name__}.{name}")
    except ImportError as e:
        has = sorted(m.name for m in pkgutil.iter_modules(pkg.__path__))
        raise SpecError(f"no {what} {name!r} under benchmark/{package}/ "
                        f"(has: {has}): {e}") from None


def traffic_kind(kind: str):
    return _named("traffic", kind, "traffic kind")


def reader(name: str):
    return _named("readers", name, "reader")


def reference(name: Optional[str] = None):
    """The plain reference a configuration names under ``modules``
    (``references/<name>.py``); unnamed, ``benchmark/reference.py``:
    float64 brute force under squared L2. The contract is what
    ``check.py`` uses: ``Answer``, ``knn_exact(rows, labels, queries,
    ks)``, ``knn_plain(...)`` and, optionally, ``dist_scale(want)``,
    the denominator of ``dist_rel_err_max``."""
    if name is None:
        return importlib.import_module("benchmark.reference")
    return _named("references", name, "reference")


def generator(name: str):
    """The value generator a configuration names under ``modules``
    (``generators/<name>.py``: ``draw(rng, shape, values, seed)``,
    NumPy only); a configuration that names none gets ``data.draw``."""
    return _named("generators", name, "generator")


class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    def __init__(self, name: str, rehearse: bool = False,
                 control: bool = False):
        bench = benchmark()
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SpecError(
                f"no workload {name!r} in BENCHMARK.json (has: "
                f"{[w['name'] for w in bench['workloads']]})")
        self.name = name
        self.chips = int(entry["chips"])
        self.bench = bench
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == entry["config"])
        cfg_path = os.path.join(ROOT, cfg_entry["file"])
        wl_path = os.path.join(HERE, "workloads", f"{name}.json")
        self.config = _load(cfg_path)
        self.workload = _load(wl_path)
        for key, want in (("config", entry["config"]),
                          ("traffic", entry["traffic"])):
            if self.workload.get(key) != want:
                raise SpecError(
                    f"workloads/{name}.json says {key}="
                    f"{self.workload.get(key)!r}, BENCHMARK.json {want!r}")
        mix = _load(os.path.join(HERE, "traffic",
                                 f"{entry['traffic']}.json"))
        self.kind_name = mix["kind"]
        self.params = merge(mix.get("params", {}),
                            self.workload.get("params"))
        self.rehearse = rehearse
        self.control = control
        if rehearse:
            self.config = merge(self.config, _toy(self.config, cfg_path))
            over = _toy(self.workload, wl_path)
            self.params = merge(self.params, over.get("params"))
            self.workload = merge(self.workload,
                                  {k: v for k, v in over.items()
                                   if k != "params"})
        if control:
            if "control" not in self.config:
                raise SpecError(f"config {entry['config']!r} states no "
                                "control")
            self.config = merge(self.config, self.config["control"]["set"])
        self.kind = traffic_kind(self.kind_name)
        # an unknown name is refused here, before any set-up
        modules = self.config.get("modules", {})
        self.reference = reference(modules.get("reference"))
        if "generator" in modules:
            generator(modules["generator"])

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]

    def per_layer(self) -> List[Dict[str, Any]]:
        """This cell's per-layer metrics, each with its own file's
        reader and arguments merged in."""
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            doc = _load(os.path.join(HERE, "layer_metrics",
                                     f"{m['name']}.json"))
            for key in ("unit", "layer", "moves", "source"):
                if doc.get(key) != m[key]:
                    raise SpecError(
                        f"layer_metrics/{m['name']}.json disagrees with "
                        f"BENCHMARK.json on {key!r}")
            out.append(doc)
        return out
