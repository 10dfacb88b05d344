"""What a run measures, resolved by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a data file found
by its name (``configs/<config>.json``, ``traffic/<traffic>.json``). A
per-layer metric is a reader found by its name: ``metrics/<name>.py``, or,
for a quantity split by the end-to-end metric it moves
(``step_ms.chat``, ``step_ms.prefill``), ``metrics/<name up to the first
dot>.py``. Adding a cell, a configuration or a metric is adding files and
entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[tuple]  # None: every cell that reports ``moves``
    moves: Optional[str] = None  # per-layer metrics only


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def config_file(name: str) -> str:
    return os.path.join(HERE, "configs", f"{name}.json")


def traffic_file(name: str) -> str:
    return os.path.join(HERE, "traffic", f"{name}.json")


def reader_file(name: str) -> str:
    """The reader of per-layer metric ``name``."""
    own = os.path.join(HERE, "metrics", f"{name}.py")
    if os.path.exists(own):
        return own
    return os.path.join(HERE, "metrics", f"{name.split('.')[0]}.py")


def load_reader(name: str) -> Callable:
    """``read(ctx)`` of the metric's reader module: a number, or None where
    the run gave it nothing to read."""
    path = reader_file(name)
    mod_name = "chipbench_metric_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metric(m: Dict) -> Metric:
    wl = m.get("workloads")
    return Metric(name=m["name"], unit=m["unit"],
                  workloads=None if wl is None else tuple(wl),
                  moves=m.get("moves"))


def _reports(metric: Metric, cell: str, e2e_names) -> bool:
    if metric.workloads is not None:
        return cell in metric.workloads
    return metric.moves is None or metric.moves in e2e_names


def cell(name: str, bench: Optional[Dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[name]
    e2e = [m for m in map(_metric, bench["end_to_end"])
           if _reports(m, name, ())]
    names = {m.name for m in e2e}
    per_layer = [m for m in map(_metric, bench["per_layer"])
                 if _reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(config_file(w["config"])),
                traffic=_json(traffic_file(w["traffic"])),
                end_to_end=e2e, per_layer=per_layer)
