"""Finds a cell's pieces by name: nothing here knows a cell, mix or metric.

``BENCHMARK.json`` names the cells. For a cell ``<config>.<traffic>``:

- ``bench/configs/<config>.json`` is the deployment (the file the manifest's
  ``configs[].file`` names);
- ``bench/traffic/<traffic>.json`` is the mix: parameters, and the name of
  the general generator in ``bench/generators/`` that reads them;
- ``bench/limits/<config>.<traffic>.json`` holds the limits of the numbers
  that decide ``correct``, with the readings each was set from;
- ``bench/metrics/<metric>.py`` is the reader of one per-layer metric; it
  defines ``read(run) -> float | None``.

A cell, mix or metric added later is new files and new manifest entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, dict]
    end_to_end: List[dict]  # manifest entries this cell reports
    per_layer: List[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(workload: str, root: Optional[str] = None) -> Cell:
    """The cell ``workload`` of the manifest at ``root`` (the checkout)."""
    root = root or os.path.dirname(BENCH_DIR)
    bench = os.path.join(root, "bench")
    manifest = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = _load_json(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = _load_json(os.path.join(bench, "traffic", entry["traffic"] + ".json"))
    limits_path = os.path.join(bench, "limits", workload + ".json")
    limits = _load_json(limits_path)["limits"] if os.path.exists(limits_path) else {}
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=config,
        traffic=traffic,
        limits=limits,
        end_to_end=[m for m in manifest["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in manifest["per_layer"] if _reports(m, workload)],
    )


def generator(cell: Cell, root: Optional[str] = None) -> ModuleType:
    """The general generator module that the cell's traffic file names."""
    bench = os.path.join(root or os.path.dirname(BENCH_DIR), "bench")
    name = cell.traffic["generator"]
    return load_module(os.path.join(bench, "generators", name + ".py"), f"bench_generator_{name}")


def metric_reader(name: str, root: Optional[str] = None) -> ModuleType:
    """The reader module of per-layer metric ``name``."""
    bench = os.path.join(root or os.path.dirname(BENCH_DIR), "bench")
    return load_module(os.path.join(bench, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_").replace("-", "_"))
