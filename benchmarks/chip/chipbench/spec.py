"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs[].file``) and a traffic mix
(``traffic/<mix>.json``); each metric the cell reports is a reader
``metrics/<metric>.py`` with a ``read(run)`` function. Adding a cell, a
configuration, a mix or a metric adds files and entries; nothing here
changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List, Tuple

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Tuple[str, str]]    # (metric, unit) the cell reports
    per_layer: List[Tuple[str, str]]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[(m["name"], m["unit"]) for m in bench["end_to_end"]
                    if _reports(m, workload)],
        per_layer=[(m["name"], m["unit"]) for m in bench["per_layer"]
                   if _reports(m, workload)])


def load_metric(name: str):
    """The module ``metrics/<name>.py``; its ``read(run)`` gives the value."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(names: List[Tuple[str, str]], run) -> Dict[str, dict]:
    """Each metric whose reader finds something to read, in order."""
    out = {}
    for name, unit in names:
        value = load_metric(name).read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out
