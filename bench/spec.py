"""Find a cell's pieces by name, as ``BENCHMARK.json`` lists them.

Every piece lives in a file of its own under the benchmark's directory,
so a later change adds files and edits none:

  configs/<config>.json     the model configuration as it is run
  traffic/<traffic>.json    the traffic mix and its engine deployment
  metrics/<metric>.py       the reader of one per-layer metric
  reference/<name>.py       the plain reference a configuration names
  limits/<workload>.json    each number the correctness check compares,
                            with its limit and the readings it was set from
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path

    def reader(self, metric: str) -> Callable:
        return load_module(self.bench_dir / "metrics" / f"{metric}.py").read

    def reference(self) -> ModuleType:
        return load_module(self.bench_dir / "reference"
                           / f"{self.config['reference']}.py")


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_piece_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: Path = BENCH_DIR,
              benchmark: Optional[Dict] = None) -> Cell:
    """The cell ``name`` of ``benchmark`` (default: the ``BENCHMARK.json``
    beside ``bench_dir``), with its configuration and traffic read."""
    if benchmark is None:
        benchmark = json.loads(
            (bench_dir.parent / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = json.loads(
        (bench_dir / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in benchmark["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in benchmark["per_layer"]
                           if _applies(m, name)],
                bench_dir=bench_dir)


def limits(cell: Cell) -> Dict[str, float]:
    """Each number the correctness check of ``cell`` compares, with its
    limit."""
    path = cell.bench_dir / "limits" / f"{cell.name}.json"
    return {k: float(v["limit"])
            for k, v in json.loads(path.read_text()).items()}
