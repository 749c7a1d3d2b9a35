"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

* configuration  the ``file`` of its ``configs`` entry;
* traffic        ``<home>/traffic/<traffic>.json``;
* check          ``<home>/checks/<cell>.json``: the sample size and the
                 limit of each compared number, with the readings each
                 limit was set from;
* metric         ``<home>/metrics/<metric>.py``: a reader with
                 ``read(run) -> float | None``.

A later cell, mix, configuration or metric is new files and new entries;
nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, List

HOME = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HOME)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` under ``root`` and the data files under
    ``home``."""

    def __init__(self, root: str = ROOT, home: str = HOME):
        self.root, self.home = root, home
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _load_json(os.path.join(self.home, "traffic", name + ".json"))

    def check(self, cell: str) -> dict:
        return _load_json(os.path.join(self.home, "checks", cell + ".json"))

    def _metrics(self, kind: str, cell: str) -> List[dict]:
        return [m for m in self.spec[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def end_to_end(self, cell: str) -> List[dict]:
        """End-to-end metrics the cell reports (``--trace 0``)."""
        return self._metrics("end_to_end", cell)

    def per_layer(self, cell: str) -> List[dict]:
        """Per-layer metrics the cell reports (``--trace 1``)."""
        return self._metrics("per_layer", cell)

    def reader(self, metric: str) -> Callable:
        path = os.path.join(self.home, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
