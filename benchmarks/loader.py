"""Finds everything the benchmark runs by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: its ``config`` names
``benchmarks/configs/<config>.json`` (through the manifest's ``file``), its
``traffic`` names ``benchmarks/traffic/<traffic>.json``, the cell's own
settings sit in ``benchmarks/workloads/<cell>.json`` and each per-layer
metric has a reader ``benchmarks/layer_metrics/<metric>.py`` with one
function ``read(facts)``. The configuration file names its model family
under ``family``: ``benchmarks/families/<family>/`` holds the family's four
pieces (``PIECES``), found by path under the manifest's own root as a reader
is. A later PR adds files and manifest entries; this module names none of
them.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import sys
import types
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# A family's pieces, each a file ``<piece>.py`` of its directory, and what
# the drivers and tools call of each. Beyond these the files are the family's
# own: how many kinds of layers its tree has, what its cache holds.
PIECES = {
    "weights": ("seed_keys", "make_params", "make_params_on_device"),
    "reference": ("teacher_forced_logits", "TrainReference"),
    "counts": ("train_flops_per_token", "forward_flops", "weight_bytes",
               "cache_bytes_per_token", "decode_tick_bytes"),
    "program": ("model_config", "make_module", "engine_params"),
}


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the contract."""


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: Optional[tuple]  # None: every cell that reports ``moves``
    bound: Optional[float] = None  # end-to-end only
    layer: Optional[str] = None  # per-layer only
    moves: Optional[str] = None  # per-layer only

    def in_cell(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass(frozen=True)
class Family:
    """The four pieces of ``benchmarks/families/<name>/``, as modules. Only
    ``program`` imports the program under test."""
    name: str
    weights: Any  # the seeded weights, whole and a layer at a time
    reference: Any  # the plain reference, float32 at ``highest``
    counts: Any  # operations and bytes from the configuration's shapes
    program: Any  # the program's config object, module and engine parameters


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # the configuration file as it is run
    family: Family  # the one the configuration file names
    traffic: Dict[str, Any]  # the traffic mix's parameters
    settings: Dict[str, Any]  # benchmarks/workloads/<cell>.json
    end_to_end: tuple  # Metric, those this cell reports
    per_layer: tuple


def _read_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing file: {path}") from None
    except ValueError as err:
        raise ManifestError(f"{path}: not JSON: {err}") from None


def _name(value: Any, what: str) -> str:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise ManifestError(f"{what}: {value!r} is not a name")
    return value


def _metric(entry: Dict[str, Any], per_layer: bool, cells: List[str]) -> Metric:
    name = _name(entry.get("name"), "metric name")
    if entry.get("better") not in ("lower", "higher"):
        raise ManifestError(f"{name}: better must be lower or higher")
    if entry.get("source") not in SOURCES:
        raise ManifestError(f"{name}: source {entry.get('source')!r}")
    if not per_layer and entry["source"] not in ("host_clock", "device_trace"):
        raise ManifestError(f"{name}: an end-to-end metric is taken by the "
                            "benchmark itself (host_clock or device_trace)")
    listed = entry.get("workloads")
    if listed is not None:
        for cell in listed:
            if cell not in cells:
                raise ManifestError(f"{name}: no cell named {cell!r}")
    if per_layer:
        for key in ("layer", "moves"):
            if not isinstance(entry.get(key), str):
                raise ManifestError(f"{name}: per-layer metric without {key}")
    elif not isinstance(entry.get("bound"), (int, float)):
        raise ManifestError(f"{name}: end-to-end metric without bound")
    return Metric(
        name=name, unit=str(entry.get("unit")), better=entry["better"],
        source=entry["source"],
        workloads=None if listed is None else tuple(listed),
        bound=entry.get("bound"), layer=entry.get("layer"),
        moves=entry.get("moves"),
    )


class Manifest:
    """``BENCHMARK.json`` of the checkout at ``root``, checked as far as
    the harness depends on it (the driver checks the rest of the
    contract)."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self._families: Dict[str, Family] = {}
        self.raw = _read_json(os.path.join(root, "BENCHMARK.json"))
        for key in ("command", "paths", "run_seconds", "configs", "workloads",
                    "end_to_end", "per_layer"):
            if key not in self.raw:
                raise ManifestError(f"BENCHMARK.json lacks {key!r}")
        self.run_seconds = int(self.raw["run_seconds"])
        self.configs = {
            _name(c.get("name"), "config name"): c for c in self.raw["configs"]
        }
        self.cells = {
            _name(w.get("name"), "cell name"): w for w in self.raw["workloads"]
        }
        if len(self.cells) != len(self.raw["workloads"]):
            raise ManifestError("two cells share a name")
        names = list(self.cells)
        self.end_to_end = [_metric(m, False, names) for m in self.raw["end_to_end"]]
        self.per_layer = [_metric(m, True, names) for m in self.raw["per_layer"]]
        every = [m.name for m in self.end_to_end + self.per_layer]
        if len(set(every)) != len(every):
            raise ManifestError("two metrics share a name")
        if "setup_s" not in every:
            raise ManifestError("no setup_s among the end-to-end metrics")
        e2e = {m.name: m for m in self.end_to_end}
        for m in self.per_layer:
            if m.moves not in e2e:
                raise ManifestError(f"{m.name}: moves {m.moves!r}, which is "
                                    "no end-to-end metric")
            for cell in (m.workloads or ()):
                if not e2e[m.moves].in_cell(cell):
                    raise ManifestError(
                        f"{m.name}: cell {cell!r} does not report {m.moves}")
        for name, w in self.cells.items():
            if w.get("config") not in self.configs:
                raise ManifestError(f"{name}: no config {w.get('config')!r}")
            if w.get("chips") not in (1, 4):
                raise ManifestError(f"{name}: chips must be 1 or 4")

    def _under_paths(self, rel: str) -> str:
        rel = os.path.normpath(rel)
        if not any(rel == p or rel.startswith(p.rstrip("/") + "/")
                   for p in self.raw["paths"]):
            raise ManifestError(f"{rel} lies outside paths")
        return os.path.join(self.root, rel)

    def cell(self, name: str) -> Cell:
        if name not in self.cells:
            raise ManifestError(
                f"no cell {name!r}; BENCHMARK.json has {sorted(self.cells)}")
        w = self.cells[name]
        path = self._under_paths(self.configs[w["config"]]["file"])
        config = _read_json(path)
        if "family" not in config:
            raise ManifestError(f"{path} names no family: there is no default")
        bench = os.path.join(self.root, "benchmarks")
        traffic = _read_json(
            os.path.join(bench, "traffic", _name(w.get("traffic"), "traffic") + ".json"))
        settings = _read_json(os.path.join(bench, "workloads", name + ".json"))
        e2e = tuple(m for m in self.end_to_end if m.in_cell(name))
        reported = {m.name for m in e2e}
        layer = tuple(
            m for m in self.per_layer
            if (m.workloads is None and m.moves in reported) or
               (m.workloads is not None and name in m.workloads)
        )
        return Cell(
            name=name, chips=w["chips"], config=config,
            family=self.family(config["family"]),
            traffic=traffic, settings=settings, end_to_end=e2e, per_layer=layer,
        )

    def family(self, name: str) -> Family:
        """The pieces of ``benchmarks/families/<name>/`` under this root,
        imported as one package of a name of this root's own, so that a
        family's files import each other by relative imports and two roots'
        families of one name stay apart."""
        if name in self._families:
            return self._families[name]
        where = os.path.join(self.root, "benchmarks", "families", _name(name, "family"))
        if not os.path.isdir(where):
            raise ManifestError(f"no family {name!r}: {where} is missing")
        package = "benchmarks_family_%s_%08x" % (
            re.sub(r"[^A-Za-z0-9_]", "_", name), zlib.crc32(where.encode()))
        for loaded in [m for m in sys.modules if m == package or m.startswith(package + ".")]:
            del sys.modules[loaded]
        sys.modules[package] = types.ModuleType(package)
        sys.modules[package].__path__ = [where]
        importlib.invalidate_caches()
        pieces = {}
        for piece, needs in PIECES.items():
            path = os.path.join(where, piece + ".py")
            if not os.path.exists(path):
                raise ManifestError(f"family {name!r} lacks its {piece}: {path} is missing")
            pieces[piece] = importlib.import_module(f"{package}.{piece}")
            for fn in needs:
                if not callable(getattr(pieces[piece], fn, None)):
                    raise ManifestError(f"{path} has no {fn}")
        self._families[name] = Family(name=name, **pieces)
        return self._families[name]

    def reader(self, metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
        """The ``read`` function of ``layer_metrics/<metric>.py``."""
        path = os.path.join(self.root, "benchmarks", "layer_metrics",
                            _name(metric, "metric") + ".py")
        if not os.path.exists(path):
            raise ManifestError(f"no reader for {metric}: {path}")
        spec = importlib.util.spec_from_file_location(
            "benchmarks.layer_metrics." + re.sub(r"[^A-Za-z0-9_]", "_", metric),
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        if not callable(getattr(module, "read", None)):
            raise ManifestError(f"{path} has no read(facts)")
        return module.read

    def driver(self, name: str):
        """The module ``benchmarks/drivers/<name>.py`` (a cell's settings
        name the driver that runs it)."""
        return importlib.import_module("benchmarks.drivers." + _name(name, "driver"))


def peaks(device_kind: str, root: str = ROOT) -> Dict[str, float]:
    """Published peaks of one chip; a device not in the table is an error."""
    table = _read_json(os.path.join(root, "benchmarks", "peaks.json"))["devices"]
    if device_kind not in table:
        raise ManifestError(
            f"device {device_kind!r} is not in benchmarks/peaks.json "
            f"({sorted(table)}): no peak, no utilisation")
    return table[device_kind]
