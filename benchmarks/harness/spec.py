"""Reads BENCHMARK.json and finds a cell's files by the names it gives.

Nothing here knows a cell, a configuration, a family, a traffic mix or a
metric by name: a workload names its ``config`` and ``traffic``; the
configuration's ``file`` is in BENCHMARK.json and names its family under
``entry`` (``benchmarks/families/<family>/`` holds the model's binding,
weights, reference and work count, ``benchmarks/entries/<family>_<kind>.py``
drives it); the traffic mix is ``benchmarks/traffic/<traffic>.json``; a
metric is ``benchmarks/end_to_end/<name>.py`` or
``benchmarks/layer_metrics/<name>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]   # BENCHMARK.json entries that apply
    per_layer: List[Dict[str, Any]]

    @property
    def family(self) -> str:
        """Name of the package under benchmarks/families/ that holds what is
        this model's alone."""
        return self.config["entry"]

    @property
    def entry(self) -> str:
        """Name of the module under benchmarks/entries/ that builds and
        drives this cell: ``<the configuration's entry>_<the traffic's
        kind>``, so a new kind of traffic brings its module and edits no
        configuration, and a new model family brings its own."""
        return f"{self.family}_{self.traffic['kind']}"


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell_name: str) -> bool:
    """A metric with no ``workloads`` list is reported by every cell."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, bench: Optional[Dict[str, Any]] = None,
              root: str = ROOT) -> Cell:
    """The cell ``workload`` of BENCHMARK.json (or of ``bench``, a dict of
    the same shape: the tests pass tiny ones)."""
    bench = bench if bench is not None else _read_json(
        os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(
        root, bench["paths"][0], "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(name=workload, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def load_reader(kind: str, name: str):
    """The module benchmarks/<kind>/<name>.py (metric names may hold dots,
    so it is loaded by path)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_entry(name: str):
    return importlib.import_module(f"benchmarks.entries.{name}")


def load_family(name: str):
    """The package benchmarks/families/<name> (families/__init__.py says
    what it provides)."""
    return importlib.import_module(f"benchmarks.families.{name}")
