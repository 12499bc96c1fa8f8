"""Finding a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own; nothing here lists them. A later PR adds a cell by
adding files and entries, never by editing this module:

  configs[].file                          the configuration as it is run
  <dir of the config file>/../traffic/<traffic>.json   the traffic mix
  <any path of "paths">/metrics/<metric>.py            the metric's reader

One metric name is one file, because the file is found by the name. Where a
quantity is split by the end-to-end metric it moves (`device_idle_pct.serve`
and `.train`: the contract wants `moves` reported in every cell the metric
is), each name has its file, and each is one call into harness/readers.py.

A config names its `builder`, a traffic file its `driver`, both as
"package.module:function", so an architecture or a kind of traffic the
harness has never seen lives in files the PR brings.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

DRIVERS = {
    "closed": "benchmarks.harness.serve_cell:run",
    "open": "benchmarks.harness.serve_cell:run",
    "train": "benchmarks.harness.train_cell:run",
}


class SpecError(ValueError):
    pass


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def resolve(dotted: str) -> Callable:
    """"package.module:function" -> the function."""
    module, _, name = dotted.partition(":")
    if not name:
        raise SpecError(f"{dotted!r} is not 'module:function'")
    return getattr(importlib.import_module(module), name)


class Cell:
    """One entry of `workloads` with its configuration and traffic files."""

    def __init__(self, root: str, name: str,
                 benchmark: Optional[Dict[str, Any]] = None):
        self.root = os.path.abspath(root)
        self.benchmark = benchmark or load_json(
            os.path.join(self.root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r}; BENCHMARK.json has "
                            f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        self.config_path = os.path.join(
            self.root, configs[self.entry["config"]]["file"])
        self.config = load_json(self.config_path)
        self.traffic_path = self._find(
            "traffic", self.entry["traffic"], (".json",))
        self.traffic = load_json(self.traffic_path)

    def _find(self, kind: str, name: str, endings) -> str:
        for path in self.benchmark["paths"]:
            for ending in endings:
                candidate = os.path.join(self.root, path, kind,
                                         name + ending)
                if os.path.isfile(candidate):
                    return candidate
        raise SpecError(f"no {kind} file named {name!r} under "
                        f"{self.benchmark['paths']}")

    def driver(self) -> Callable:
        dotted = self.traffic.get("driver") \
            or DRIVERS.get(self.traffic.get("kind"))
        if not dotted:
            raise SpecError(f"{self.traffic_path}: no 'driver' and no "
                            f"known 'kind'")
        return resolve(dotted)

    def metrics(self, traced: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports in this kind of run."""
        group = "per_layer" if traced else "end_to_end"
        return [m for m in self.benchmark[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, metric: str) -> Callable:
        """`read(record)` of benchmarks/metrics/<metric>.py (loaded by
        path: a metric's name may hold dots)."""
        path = self._find("metrics", metric, (".py",))
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
