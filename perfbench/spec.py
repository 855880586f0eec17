"""Find a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic; the
files follow from the names alone, so a later change adds a cell, a
configuration, a traffic mix or a per-layer metric by adding files and
entries, with no edit to this code:

* configuration: the ``file`` its entry in ``configs`` gives;
* algorithm family: ``perfbench/algorithms/<algorithm>.py``, by the
                 configuration's ``"algorithm"`` (``algorithms/__init__.py``
                 lists what the module holds);
* traffic:       ``perfbench/traffic/<traffic>.json``;
* limits:        ``perfbench/limits/<workload>.json`` (the comparison
                 that decides ``correct``);
* per-layer metric: ``perfbench/metrics/<name>.py``, whose
  ``read(ctx)`` returns the value or None.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

from perfbench import algorithms

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable]
    root: str


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(path: str) -> Callable:
    """``read`` of the metric module at ``path``."""
    name = "perfbench_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = _load_json(os.path.join(root, entry["file"]))
    algorithms.load(config["algorithm"])
    pb = os.path.join(root, "perfbench")
    readers = {
        m["name"]: load_reader(os.path.join(pb, "metrics", m["name"] + ".py"))
        for m in bench["per_layer"] if _applies(m, workload)}
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"],
        config=config,
        traffic_name=w["traffic"],
        traffic=_load_json(os.path.join(pb, "traffic", w["traffic"] + ".json")),
        limits=_load_json(os.path.join(pb, "limits", workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        readers=readers, root=root)
