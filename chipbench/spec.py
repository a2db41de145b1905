"""What a cell is: its entry in BENCHMARK.json and the files it names.

A cell pairs a configuration (``chipbench/configs/<config>.json``) with a
traffic mix (``chipbench/traffic/<traffic>.json``), whose arrival kind is
``chipbench/arrivals/<kind>.py``.  Every metric, end-to-end or per-layer,
is read by ``chipbench/metrics/<metric>.py``, or, for a metric whose name
has a suffix after a dot (``reqs_per_dispatch.lat``), by the reader of
the part before the first dot.  Nothing here knows any cell by name.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """The benchmark's files do not define the cell asked for."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]       # the metrics this cell reports, in order
    per_layer: List[dict]


def benchmark() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str) -> Cell:
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    config = _load_json(os.path.join(BENCH_DIR, "configs",
                                     entry["config"] + ".json"))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      entry["traffic"] + ".json"))
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reported_in(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reported_in(m, name)])


def peaks(device_kind: str) -> dict:
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"chipbench/peaks.json; add its published peaks")
    return table["devices"][device_kind]


def arrival_kind(kind: str):
    """The module of an arrival kind (``chipbench/arrivals/<kind>.py``)."""
    path = os.path.join(BENCH_DIR, "arrivals", f"{kind}.py")
    if not re.fullmatch(r"[a-z][a-z0-9_]*", kind) or not os.path.exists(path):
        raise SpecError(f"no arrival kind chipbench/arrivals/{kind}.py")
    return importlib.import_module(f"chipbench.arrivals.{kind}")


def reader(metric: str) -> Optional[Callable]:
    """The ``read(run)`` function of a per-layer metric, or None."""
    for stem in (metric, metric.split(".", 1)[0]):
        path = os.path.join(BENCH_DIR, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "chipbench_metric_" + stem.replace(".", "_"), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read
    return None


def readers(metrics: List[dict]) -> Dict[str, Callable]:
    out = {}
    for m in metrics:
        fn = reader(m["name"])
        if fn is None:
            raise SpecError(f"no reader chipbench/metrics/{m['name']}.py")
        out[m["name"]] = fn
    return out
