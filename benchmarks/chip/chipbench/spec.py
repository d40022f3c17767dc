"""Finds what a run needs by the names in ``BENCHMARK.json``.

Nothing here knows a configuration, traffic mix or metric by name: a
cell names its configuration and traffic, and each is a file of its own
under this benchmark's directory (``configs/<name>.json``,
``traffic/<name>.json``, ``metrics/<name>.py``,
``references/<name>.py``).  A later cell, configuration or metric is a
new file and a new entry, never an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional

#: ``benchmarks/chip`` — the directory that holds every file found here
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    end_to_end: bool
    moves: Optional[str] = None
    layer: Optional[str] = None
    bound: Optional[float] = None
    workloads: Optional[List[str]] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def find_benchmark(root: str) -> Dict[str, Any]:
    """``BENCHMARK.json`` at the root of the checkout."""
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _metrics(entries, end_to_end: bool) -> List[Metric]:
    return [Metric(name=e["name"], unit=e["unit"], better=e["better"],
                   source=e["source"], end_to_end=end_to_end,
                   moves=e.get("moves"), layer=e.get("layer"),
                   bound=e.get("bound"), workloads=e.get("workloads"))
            for e in entries]


def load_cell(bench: Dict[str, Any], name: str,
              bench_dir: str = BENCH_DIR) -> Cell:
    """The cell called ``name``, its configuration and traffic files
    read, and the metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[name]
    if w["config"] not in {c["name"] for c in bench["configs"]}:
        raise KeyError(f"workload {name!r} names configuration "
                       f"{w['config']!r}, which BENCHMARK.json lacks")
    config = load_json(os.path.join(bench_dir, "configs",
                                    w["config"] + ".json"))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in _metrics(bench["end_to_end"], True)
           if m.applies_to(name)]
    per_layer = [m for m in _metrics(bench["per_layer"], False)
                 if m.applies_to(name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def _load_module(path: str, mod_name: str):
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(run) -> float | None`` of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    mod = _load_module(path, "chipbench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))
    return mod.read


def reference_module(name: str, bench_dir: str = BENCH_DIR):
    """The plain reference ``references/<name>.py`` that a configuration
    names under ``"reference"``."""
    path = os.path.join(bench_dir, "references", name + ".py")
    return _load_module(path, "chipbench_reference_" + name)
