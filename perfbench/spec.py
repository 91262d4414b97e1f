"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix; each is
a JSON file found by its name (``configs/<config>.json``,
``traffic/<traffic>.json``), and the cell's correctness limits sit in
``checks/<cell>.json``.  Adding a cell adds files; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent                      # the checkout's root


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    checks: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]    # the metrics this cell reports
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path.name}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(HERE / "configs" / f"{w['config']}.json"),
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        checks=load_json(HERE / "checks" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def model_configs(config: Dict[str, Any]):
    """(target, draft) ``ModelConfig`` of the program, as the file runs them."""
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    target = ModelConfig(**config["model"])
    draft = ModelConfig(**{k: v for k, v in config["draft"].items()
                           if k in fields})
    return target, draft
