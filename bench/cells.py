"""Finding the benchmark's parts by name.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own, so adding one is adding files:

* ``bench/configs/<config>.json`` — a deployment: dataset, distance,
  execution policy, the precision and guarantees it states;
* ``bench/workloads/<cell>.json`` — a cell: its configuration, traffic
  kind, chips and traffic parameters;
* ``bench/traffic/<kind>.py`` — a traffic driver (``warmup``, ``window``,
  ``check``), shared by every cell of that kind;
* ``bench/metrics/<metric>.py`` — a reader with ``read(run)`` that returns
  the metric's value, or ``None`` where the run holds nothing to read.

``BENCHMARK.json`` names which metrics each cell reports, with their units.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic: str
    chips: int
    params: dict


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _file(kind: str, name: str, ext: str) -> str:
    path = os.path.join(BENCH, kind, name + ext)
    if not os.path.isfile(path):
        raise KeyError(f"no {kind[:-1]} named {name!r} ({path} not found)")
    return path


def load_cell(name: str) -> Cell:
    """The cell ``name`` with its configuration."""
    spec = _read_json(_file("workloads", name, ".json"))
    config = _read_json(_file("configs", spec["config"], ".json"))
    return Cell(name=name, config_name=spec["config"], config=config,
                traffic=spec["traffic"], chips=int(spec.get("chips", 1)),
                params=dict(spec.get("params", {})))


def _module(kind: str, name: str):
    path = _file(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic(kind: str):
    """The traffic driver module of ``kind``."""
    return _module("traffic", kind)


def reader(metric: str):
    """The ``read(run)`` function of ``metric``."""
    return _module("metrics", metric).read


def benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def metrics_for(cell: str, section: str, root: str = ROOT) -> list[dict]:
    """The ``section`` ("end_to_end" or "per_layer") metrics that ``cell``
    reports: those that list it, and those that list no cells."""
    return [m for m in benchmark(root)[section]
            if "workloads" not in m or cell in m["workloads"]]
