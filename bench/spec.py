"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

Nothing here knows a particular configuration, traffic mix, metric or
kernel: a file added beside the others is found without an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]      # the cell's end-to-end metric entries
    per_layer: list[dict]       # the cell's per-layer metric entries
    layout: dict                # implementation choices (bench/layout.json)

    @property
    def bench(self) -> Path:
        return self.root / "bench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        root=root, name=workload, chips=int(w["chips"]),
        config_name=cfg_entry["name"],
        config=load_json(root / cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
        layout=load_json(root / "bench" / "layout.json"))


def metric_reader(cell: Cell, name: str) -> ModuleType:
    return load_module(cell.bench / "metrics" / f"{name}.py")


def kernel(cell: Cell, name: str) -> ModuleType:
    return load_module(cell.bench / "kernels" / f"{name}.py")


def limits(cell: Cell) -> dict:
    """The cell's correctness limits: {number: {"limit": ..., ...}}."""
    return load_json(cell.bench / "limits" / f"{cell.name}.json")["numbers"]


def operator(cell: Cell) -> ModuleType:
    """The configuration's plain reference operator."""
    return load_module(cell.bench / "operators"
                       / f"{cell.config['operator']}.py")
