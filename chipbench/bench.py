"""Resolve a cell of ``BENCHMARK.json`` to its files, by name."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(path: Path) -> ModuleType:
    """Import one file by path (metric files carry dots in their names,
    so they cannot be imported as package modules)."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    """One workload with everything the harness looks up for it."""
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: ModuleType
    generator: ModuleType
    reference: ModuleType
    end_to_end: list[dict]    # this cell's end-to-end metrics
    per_layer: list[dict]     # this cell's per-layer metrics
    readers: dict[str, ModuleType]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file.name}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    readers = {m["name"]: load_module(HERE / "metrics" / f"{m['name']}.py")
               for m in e2e + layer}
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        driver=load_module(HERE / "drivers" / f"{traffic['driver']}.py"),
        generator=load_module(HERE / "generators" / f"{config['generator']}.py"),
        reference=load_module(HERE / "references" / f"{config['reference']}.py"),
        end_to_end=e2e, per_layer=layer, readers=readers)
