"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, so a cell or metric is added with new
files and entries and no edit:

* ``configs/<config>.json`` — the configuration's sizes (the cell's
  ``config`` names its ``file``);
* ``traffic/<traffic>.json`` — the traffic mix's parameters; its
  ``kind`` names its module ``drivers/<kind>.py``;
* ``metrics/<metric>.py`` — a per-layer metric's reader, a function
  ``read(m)`` that returns a number, or None when it finds nothing.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]    # this cell's end-to-end metric entries
    per_layer: List[dict]     # this cell's per-layer metric entries


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(benchmark: dict, name: str, root: str = ROOT,
              traffic_dir: str = os.path.join(HERE, "traffic")) -> Cell:
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in benchmark["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(traffic_dir, w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in benchmark["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in benchmark["per_layer"] if _applies(m, name)])


def _module(path: str):
    name = "lutbench_" + os.path.relpath(path, HERE)[:-3].replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def driver(kind: str):
    """The traffic kind's driver module (``drivers/<kind>.py``)."""
    return _module(os.path.join(HERE, "drivers", kind + ".py"))


def readers(metrics: List[dict]) -> Dict[str, Callable]:
    """``name -> read`` for each per-layer metric (``metrics/<name>.py``)."""
    return {m["name"]: _module(os.path.join(HERE, "metrics",
                                            m["name"] + ".py")).read
            for m in metrics}
