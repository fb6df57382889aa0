"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix.
Each is one JSON file, found by its name:

    configs/<config>.json     sizes as run, source, reduced, assumed;
                              where cut to one chip's share, each cut
                              key's published value and the deployment
    traffic/<traffic>.json    driver, batch and lengths, their source
    cells/<cell>.json         the correctness limit
    metrics/<metric>.py       ``read(ctx)`` for one per-layer metric
    drivers/<driver>.py       ``run_window(...)`` for one serving loop
    families/<family>.py      a model family's sizes, weight leaves,
                              reference layer, step work and, where
                              its layers are not all of one kind, its
                              layer stacks and the keys it may cut,
                              named by the configuration's ``family``

Adding a cell adds files and entries; nothing here is edited.
"""
from __future__ import annotations

import importlib.util
import json
import re
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


#: names of widths, which no cut touches, whatever a family allows
WIDTH = re.compile(r"^d_|_(dim|rank|ff|state|expand|conv)$|^hidden_size$"
                   r"|intermediate|top_?k|per_tok")


def published_weight_bytes(config: Dict[str, Any]) -> float:
    """Bytes of every weight of the published model: the configuration
    with each cut key set back to its ``published`` value, and every row
    of the embedding."""
    from .counts import weight_bytes
    pub = dict(config, **config.get("published", {}))
    return weight_bytes(pub, pub["vocab"])


def check_cut(config: Dict[str, Any]) -> None:
    """Refuses a configuration cut in any other way than to one chip's
    share of its published model.

    ``reduced`` names the cut keys, ``published`` gives each its
    published value, and ``deployment`` says over how many chips each
    layer is divided, and how.  A cut is refused where the published
    model's weights fit the configuration's ``chips``: only a model too
    large for them is cut.  Only ``n_layers``, ``vocab`` and the keys of
    the family's ``cuts(config) -> {key: least}`` may be cut, none a
    width, each to no less than its floor and no more than its published
    value.  The floors: the depth keeps every layer outside the last of
    the family's stacks and four of that stack, the vocabulary an
    eighth, each raised to the family's least."""
    fam = family(config["family"])
    cut = config["reduced"]
    published = config.get("published", {})
    for k in cut:
        if k not in config:
            raise SpecError(f"reduced names {k!r}, which the configuration "
                            f"does not state")
        if k not in published:
            raise SpecError(f"the cut key {k!r} has no published value")
    for k in published:
        if k not in cut:
            raise SpecError(f"{k!r} is stated as cut but reduced does not "
                            f"name it")
    if not cut:
        return
    if not config.get("deployment"):
        raise SpecError("a cut configuration states no deployment")
    from .peaks import PEAKS
    from .weights import stacks
    least = dict(fam.cuts(config)) if hasattr(fam, "cuts") else {}
    segs = stacks(config)
    outside = sum(n for s, n in segs if s != segs[-1][0])
    floors = {"n_layers": lambda pub: min(pub, outside + 4),
              "vocab": lambda pub: -(-pub // 8)}
    for k in cut:
        if WIDTH.search(k):
            raise SpecError(f"{k!r} is a width, which no cut touches")
        if k not in floors and k not in least:
            raise SpecError(f"{k!r} may not be cut: only "
                            f"{sorted(set(floors) | set(least))}")
        v, pub = config[k], published[k]
        if not (isinstance(v, int) and isinstance(pub, int)):
            raise SpecError(f"{k!r}: a cut and its published value are "
                            f"counts, not {v!r} and {pub!r}")
        floor = max(floors[k](pub) if k in floors else 0, least.get(k, 0))
        if v < floor:
            raise SpecError(f"{k!r} = {v} is under its floor {floor}")
        if v > pub:
            raise SpecError(f"{k!r} = {v} is above its published {pub}")
    held = int(config["chips"]) * max(p.hbm_bytes for p in PEAKS.values())
    need = published_weight_bytes(config)
    if need <= held:
        raise SpecError(f"the published model's weights, {need:.4g} bytes, "
                        f"fit its {config['chips']} chip(s), {held:.4g} "
                        f"bytes: nothing forces the cut {cut}")


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
    check_cut(config)
    if int(config["chips"]) != int(entry["chips"]):
        raise SpecError(f"{name}: config asks {config['chips']} chips, "
                        f"the cell {entry['chips']}")
    return Cell(name, int(entry["chips"]), config, traffic, limits, e2e,
                layer)
