"""Finds everything that belongs to one cell by name.

``BENCHMARK.json`` names the cell, its configuration and its metrics;
the files are found from those names, so adding a cell, a configuration,
a traffic mix or a per-layer metric adds files and entries, and edits
none:

- configuration ``<c>``: the ``file`` that ``BENCHMARK.json`` gives it,
  a JSON object whose ``task`` names ``benchkit/tasks/<task>.py`` (the
  data generator and the plain reference of that kind of model);
- traffic mix of cell ``<w>``: ``traffic/<w>.json``, whose ``kind``
  names the generic driver ``benchkit/drivers/<kind>.py``;
- limits of cell ``<w>``'s correctness check: ``limits/<w>.json``;
- per-layer metric ``<m>``: the reader ``metrics/<m>.py``, a function
  ``read(ctx)`` returning a number, or None where it finds nothing.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Metric:
    name: str
    unit: str
    kind: str                    # "end_to_end" | "per_layer"
    entry: dict


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)

    @property
    def task(self):
        return importlib.import_module(
            f"benchkit.tasks.{self.config['task']}")

    @property
    def driver(self):
        return importlib.import_module(
            f"benchkit.drivers.{self.traffic['kind']}")


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, workload: str, reported: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(wl)}")
    w = wl[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / cfgs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{name}.json")
                         .read_text())
    limits_file = root / "bench" / "limits" / f"{name}.json"
    limits = json.loads(limits_file.read_text()) if limits_file.exists() \
        else {}
    cell = Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits)
    for m in bench["end_to_end"]:
        if _applies(m, name, set()):
            cell.end_to_end.append(Metric(m["name"], m["unit"],
                                          "end_to_end", m))
    reported = {m.name for m in cell.end_to_end}
    for m in bench["per_layer"]:
        if _applies(m, name, reported):
            cell.per_layer.append(Metric(m["name"], m["unit"],
                                         "per_layer", m))
    return cell


def reader(metric: str, root: Path = ROOT) -> Callable[[dict], object]:
    """The ``read(ctx)`` function of per-layer metric ``metric``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_per_layer(cell: Cell, ctx: dict,
                   root: Path = ROOT) -> Dict[str, dict]:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in cell.per_layer:
        value = reader(m.name, root)(ctx)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out
