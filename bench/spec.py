"""Find a cell's parts by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
metric lives in a file of its own, found by name:

* ``bench/configs/<config>.json`` -- the configuration as it is run;
  ``bench/configs/<config>.py`` -- its builder (``build``) and its work
  count from the published layer table (``layers``).
* ``bench/traffic/<traffic>.json`` -- the traffic mix's parameters.
* ``bench/metrics/<metric>.py`` -- one reader per metric (``read``).

Adding a cell, a configuration or a metric therefore adds files and
entries; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path
from types import ModuleType


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    reader: ModuleType      #: bench/metrics/<name>.py


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            #: bench/configs/<config>.json
    builder: ModuleType     #: bench/configs/<config>.py
    traffic: dict           #: bench/traffic/<traffic>.json
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _metric(entry: dict) -> Metric:
    reader = importlib.import_module(f"bench.metrics.{entry['name']}")
    return Metric(entry["name"], entry["unit"], reader)


def load_cell(root: Path, name: str) -> Cell:
    """Resolve workload ``name`` of ``root/BENCHMARK.json``.

    ``root`` must be the directory whose ``bench`` package is imported
    (``bench/run.py`` puts it first on ``sys.path``)."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / cfg_entry["file"]),
        builder=importlib.import_module(f"bench.configs.{w['config']}"),
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=tuple(_metric(m) for m in spec["end_to_end"]
                         if _applies(m, name)),
        per_layer=tuple(_metric(m) for m in spec["per_layer"]
                        if _applies(m, name)),
    )
