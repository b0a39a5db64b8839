"""Where the pieces of a cell live, found by name from ``BENCHMARK.json``.

``BENCHMARK.json`` names the cells (``workloads``), the configurations and
the metrics.  Everything that belongs to one of them sits in a file of its
own, which this module finds by the name:

* ``configs[].file`` (``chipbench/configs/<config>.json``): the sizes of one
  configuration as it is run, and the limits of its correctness check;
* ``chipbench/traffic/<traffic>.json``: the parameters of one traffic mix;
  its ``"driver"`` names the general driver that reads it,
  ``chipbench/drivers/<driver>.py``;
* ``chipbench/generators/<generator>.py``: what makes the matrix a
  configuration's ``"generator"`` names;
* ``chipbench/metrics/<metric>.py``: the reader of one per-layer metric.

A later cell, configuration or per-layer metric adds files and entries;
no existing file needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

PKG = Path(__file__).resolve().parent          # chipbench/
REPO = PKG.parent                              # root of the checkout
CACHE = REPO / ".chipbench_cache"              # compile cache, traces


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    repo: Path

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def load_benchmark(repo: Path = REPO) -> dict:
    with open(Path(repo) / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, repo: Path = REPO) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    repo = Path(repo)
    bench = load_benchmark(repo)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(repo / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(repo / "chipbench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        repo=repo)


def load_module(path: Path) -> ModuleType:
    """Import the file at ``path`` (its name may hold dots, as a metric's
    does) as a module of its own."""
    path = Path(path)
    mod_name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(path.parents[1])))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def driver(cell: Cell) -> ModuleType:
    return load_module(cell.repo / "chipbench" / "drivers" /
                       f"{cell.driver}.py")


def generator(cell: Cell) -> ModuleType:
    return load_module(cell.repo / "chipbench" / "generators" /
                       f"{cell.config['generator']}.py")


def metric_readers(cell: Cell) -> Dict[str, ModuleType]:
    """``{metric name: reader module}`` for the cell's per-layer metrics."""
    return {m["name"]: load_module(cell.repo / "chipbench" / "metrics" /
                                   f"{m['name']}.py")
            for m in cell.per_layer}


def read_metrics(cell: Cell, ctx) -> Dict[str, Optional[float]]:
    """Each per-layer reader's value; a reader that finds nothing to read
    returns None, and the metric is left out of the result."""
    out = {}
    for name, reader in metric_readers(cell).items():
        value = reader.read(ctx)
        if value is not None:
            out[name] = float(value)
    return out
