"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix; each is
a JSON file found by its name (``configs/<config>.json``,
``traffic/<traffic>.json``), and the cell's correctness limits sit in
``checks/<cell>.json``.  A configuration names its plain reference module
under ``"reference"`` (``<module>.py`` beside this file): the forward pass
the check compares against, and the rules for drawing its weights.  Adding
a cell, or a configuration of another architecture, adds files; nothing
here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent                      # the checkout's root
MODULE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Execute one file of the benchmark as a module of its own."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    reference: Path                     # the configuration's reference module
    traffic: Dict[str, Any]
    checks: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]    # the metrics this cell reports
    per_layer: List[Dict[str, Any]]


def reference_path(config: Dict[str, Any], origin: str) -> Path:
    """The file of the reference module that ``config`` names; ``origin``
    names the configuration's file in the error.  There is no default."""
    name = config.get("reference")
    if not isinstance(name, str) or not MODULE.fullmatch(name):
        raise ValueError(f"{origin}: \"reference\" must name a module of "
                         f"perfbench/ (a Python identifier), not {name!r}")
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{origin} names the reference module "
                                f"{name!r}, but there is no {path}")
    return path


def load_reference(path: Path):
    """The reference module in ``path``: ``forward(w, cfg, tokens, rows,
    quant)``, ``readings(w, cfg, prompt, served, controls)`` and, optionally,
    ``FAN_IN``, the fan-in of weight leaves that ``weights.make``'s own
    table does not know (leaf name -> a ``ModelConfig`` field or a product
    of fields, ``"num_heads*v_head_dim"``)."""
    mod = load_module(path, "perfbench." + path.stem)
    for fn in ("forward", "readings"):
        if not callable(getattr(mod, fn, None)):
            raise ImportError(f"reference module {path} has no {fn}()")
    return mod


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path.name}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    config_file = HERE / "configs" / f"{w['config']}.json"
    config = load_json(config_file)
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        reference=reference_path(config, str(config_file)),
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
