"""Find a cell's pieces by name: BENCHMARK.json, then files under chipbench/.

Data lives in ``configs/<config>.json``, ``traffic/<mix>.json`` and
``cells/<cell>.json``; code in one module per name, loaded from its path:

  drivers/<driver>.py      runs a cell (the cell file names its driver)
  generators/<kind>.py     makes a mix's work and offers it (the mix file
                           names its kind)
  end_to_end/<metric>.py   reads an end-to-end metric from the window
  metrics/<metric>.py      reads a per-layer metric from a traced window

Nothing here imports JAX or the program, so tests can load a benchmark
written into a temporary directory.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

PKG = "chipbench"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<mix>.json
    deploy: dict            # cells/<cell>.json: "driver" and its values
    end_to_end: List[dict]  # the end-to-end metrics this cell reports
    per_layer: List[dict]   # the per-layer metrics this cell reports


def load_benchmark(root: Path) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` with its config, mix, deployment and metrics."""
    root = Path(root)
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                       f"{[w['name'] for w in bench['workloads']]})")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and _reports(m, name)]
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=_read_json(root / conf["file"]),
        traffic=_read_json(root / PKG / "traffic" / f"{entry['traffic']}.json"),
        deploy=_read_json(root / PKG / "cells" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer)


def load(root: Path, group: str, name: str) -> ModuleType:
    """The module chipbench/<group>/<name>.py, loaded from its path."""
    path = Path(root) / PKG / group / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {group} module {name!r} ({path})")
    ident = f"{PKG}_{group}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(ident, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, metric: str) -> Callable:
    """``read(ctx) -> float | None`` from chipbench/metrics/<metric>.py."""
    return load(root, "metrics", metric).read


def read_per_layer(root: Path, cell: Cell, ctx) -> Dict[str, dict]:
    """Every per-layer metric of ``cell`` whose reader finds something."""
    out = {}
    for m in cell.per_layer:
        value: Optional[float] = metric_reader(root, m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
