"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix.
Each is one JSON file, found by its name:

    configs/<config>.json     sizes as run, source, reduced, assumed
    traffic/<traffic>.json    driver, batch and lengths, their source
    cells/<cell>.json         the correctness limit
    metrics/<metric>.py       ``read(ctx)`` for one per-layer metric
    drivers/<driver>.py       ``run_window(...)`` for one serving loop
    families/<family>.py      a model family's sizes, weight leaves,
                              reference layer and step work, named by
                              the configuration's ``family``

Adding a cell adds files and entries; nothing here is edited.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _load_json(path: Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path."""
    if name in sys.modules:
        return sys.modules[name]
    path = Path(path)
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str]
    moves: Optional[str]
    workloads: Optional[List[str]]

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]

    def driver(self):
        drv = self.traffic["driver"]
        return load_module(HERE / "drivers" / f"{drv}.py",
                           f"bench_driver_{drv}")

    def reader(self, metric: str):
        return load_module(HERE / "metrics" / f"{metric}.py",
                           "bench_metric_" + metric.replace(".", "_"))


def family(name: str):
    """The module of a model family, ``families/<name>.py``."""
    return load_module(HERE / "families" / f"{name}.py",
                       f"bench_family_{name}")


def _metrics(entries) -> List[Metric]:
    return [Metric(e["name"], e["unit"], e["better"], e["source"],
                   e.get("layer"), e.get("moves"), e.get("workloads"))
            for e in entries]


def load_bench(path: Path = REPO / "BENCHMARK.json") -> Dict[str, Any]:
    return _load_json(path)


def cell(name: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    """Resolve one ``workloads`` entry to its files."""
    bench = bench if bench is not None else load_bench()
    entry = next((w for w in bench["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    conf = next((c for c in bench["configs"]
                 if c["name"] == entry["config"]), None)
    if conf is None:
        raise SpecError(f"{name}: no config {entry['config']!r}")
    config = _load_json(REPO / conf["file"])
    traffic = _load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    limits = _load_json(HERE / "cells" / f"{name}.json")
    e2e = [m for m in _metrics(bench["end_to_end"]) if m.applies_to(name)]
    layer = [m for m in _metrics(bench["per_layer"]) if m.applies_to(name)]
    family(config["family"])
    if int(config["chips"]) != int(entry["chips"]):
        raise SpecError(f"{name}: config asks {config['chips']} chips, "
                        f"the cell {entry['chips']}")
    return Cell(name, int(entry["chips"]), config, traffic, limits, e2e,
                layer)
