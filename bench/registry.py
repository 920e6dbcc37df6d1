"""Find a cell's pieces by name: ``BENCHMARK.json`` entries, configuration
and traffic data files, system modules and per-layer metric readers.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own under ``bench/`` (``configs/``, ``traffic/<name>.json``,
``metrics/<name>.py``, ``systems/<system>.py``), so a later cell is added by
adding files and ``BENCHMARK.json`` entries."""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import List

from bench import ROOT


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names loaded:
    ``config`` and ``traffic`` (dicts), the ``system`` module, and the
    end-to-end and per-layer metric entries that this cell reports."""

    def __init__(self, spec: dict, workload: str, root: str = ROOT):
        self.spec, self.name, self.root = spec, workload, root
        self.workload = _by_name(spec["workloads"], workload, "workload")
        entry = _by_name(spec["configs"], self.workload["config"], "config")
        self.config = _json(os.path.join(root, entry["file"]))
        self.traffic = _json(os.path.join(
            root, "bench", "traffic", self.workload["traffic"] + ".json"))
        system = self.config["system"]
        self.system = load_module(
            os.path.join(root, "bench", "systems", system + ".py"),
            "bench_system_" + system)
        self.end_to_end = [m for m in spec["end_to_end"] if self._reports(m)]
        self.per_layer = [m for m in spec["per_layer"] if self._reports(m)]

    def _reports(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        if cells is None and "moves" in metric:
            # a per-layer metric without a list is read wherever the
            # end-to-end metric it moves is reported
            return self._reports(_by_name(self.spec["end_to_end"],
                                          metric["moves"], "metric"))
        return cells is None or self.name in cells

    def reader(self, metric: str) -> ModuleType:
        return load_module(
            os.path.join(self.root, "bench", "metrics", metric + ".py"),
            "bench_metric_" + metric.replace(".", "_"))
