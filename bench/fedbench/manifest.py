"""The benchmark's manifest and the data files it names.

``BENCHMARK.json`` at the checkout's root lists configurations, cells
(``workloads``) and metrics.  Every piece that belongs to one of them sits
in a file of its own, found by its name:

    bench/configs/<config>.json      model and dataset shape
    bench/traffic/<traffic>.json     the federation and optimizer settings
    bench/workloads/<cell>.json      config + traffic + client size + limits
    bench/metrics/<metric>.py        one reader per metric
    bench/families/<family>.py       weights, plain reference, FLOP count
    bench/algorithms/<algorithm>.py  plain reference of the round

so a later change adds a cell, a configuration or a metric by adding
files and manifest entries, and edits none.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(f"not a valid name: {name!r}")
    return name


@functools.lru_cache(maxsize=None)
def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by its path (readers, families,
    algorithms), once per process, so that its jitted functions compile
    once: nothing is imported by a package name a later file could
    shadow."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str                      # "end_to_end" | "per_layer"
    workloads: list | None = None  # None: every cell that reports `moves`
    moves: str | None = None

    def reader(self) -> ModuleType:
        return load_module(BENCH_DIR / "metrics" / f"{check_name(self.name)}.py")


@dataclass
class Cell:
    """One cell, resolved to its files."""
    name: str
    workload: dict
    config: dict
    traffic: dict
    metrics: list = field(default_factory=list)   # every Metric it reports

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def family(self) -> ModuleType:
        return load_module(
            BENCH_DIR / "families" / f"{check_name(self.config['family'])}.py")

    def algorithm(self) -> ModuleType:
        return load_module(
            BENCH_DIR / "algorithms"
            / f"{check_name(self.traffic['algorithm'])}.py")

    def end_to_end(self) -> list:
        return [m for m in self.metrics if m.kind == "end_to_end"]

    def per_layer(self) -> list:
        return [m for m in self.metrics if m.kind == "per_layer"]


def metrics_of(manifest: dict) -> list:
    out = []
    for kind in ("end_to_end", "per_layer"):
        for m in manifest.get(kind, []):
            out.append(Metric(name=check_name(m["name"]), unit=m["unit"],
                              better=m["better"], source=m["source"],
                              kind=kind, workloads=m.get("workloads"),
                              moves=m.get("moves")))
    return out


def cell_metrics(metrics: list, cell: str) -> list:
    """The metrics one cell reports: an end-to-end metric where it lists
    the cell or lists none; a per-layer metric where it lists the cell,
    or lists none and the cell reports the metric it moves."""
    e2e = [m for m in metrics if m.kind == "end_to_end"
           and (m.workloads is None or cell in m.workloads)]
    names = {m.name for m in e2e}
    layer = [m for m in metrics if m.kind == "per_layer"
             and (cell in m.workloads if m.workloads is not None
                  else m.moves in names)]
    return e2e + layer


def load_cell(name: str) -> Cell:
    manifest = load_json(MANIFEST)
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise KeyError(f"no cell {name!r} in {MANIFEST.name}; "
                       f"known: {sorted(entries)}")
    workload = load_json(BENCH_DIR / "workloads" / f"{check_name(name)}.json")
    entry = entries[name]
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise ValueError(f"{name}: {key} is {workload[key]!r} in its "
                             f"file and {entry[key]!r} in the manifest")
    config = load_json(BENCH_DIR / "configs"
                       / f"{check_name(workload['config'])}.json")
    traffic = load_json(BENCH_DIR / "traffic"
                        / f"{check_name(workload['traffic'])}.json")
    return Cell(name=name, workload=workload, config=config,
                traffic=traffic,
                metrics=cell_metrics(metrics_of(manifest), name))
