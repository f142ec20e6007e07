"""Finds everything a run needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; a traffic mix names a query
mix; a metric is a reader module named after the metric.  Each lives in a
file of its own under the benchmark directory, so a later change adds a
cell, a configuration, a mix or a metric by adding files and entries:

    BENCHMARK.json                      cells, configurations, metrics
    <bench>/configs/<config>.json       (the path the config entry gives)
    <bench>/traffic/<traffic>.json      arrivals, tenants, writes
    <bench>/queries/<mix>.json          query classes and their parameters
    <bench>/metrics/<metric>.py         ``read(rec) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    queries: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]     # metric entries this cell reports
    per_layer: List[Dict[str, Any]]
    bench_dir: str
    run_seconds: int


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``; raises
    ``KeyError`` for a name the file does not have."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    queries = _load_json(os.path.join(bench_dir, "queries",
                                      traffic["queries"] + ".json"))
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic=traffic, queries=queries,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        bench_dir=bench_dir, run_seconds=int(bench["run_seconds"]))


def metric_reader(bench_dir: str, name: str) -> Callable[[Any],
                                                         Optional[float]]:
    """``read`` of ``<bench>/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
