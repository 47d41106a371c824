"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A configuration is ``bench/configs/<config>.json`` (the ``file`` its entry
names), a traffic mix is ``bench/traffic/<mix>.json`` and a per-layer
metric is the reader ``bench/metrics/<metric>.py``. Adding any of them
takes new files and new entries in ``BENCHMARK.json``, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib


class Registry:
    """The benchmark spec of one checkout, rooted at ``root``."""

    def __init__(self, root):
        self.root = pathlib.Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._readers: dict = {}

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads(
            (self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def end_to_end(self, workload: str) -> list[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.spec["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list[dict]:
        """The per-layer metrics this cell reports: those that list it, or
        that list no cells and move an end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.spec["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str):
        """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
        if metric not in self._readers:
            path = self.root / "bench" / "metrics" / f"{metric}.py"
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._readers[metric] = mod.read
        return self._readers[metric]
