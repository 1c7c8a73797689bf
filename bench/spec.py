"""BENCHMARK.json and the files it names, found by name.

A configuration is the file its entry names. A traffic mix is
``bench/traffic/<traffic>.json``, a cell's limits for ``correct`` are
``bench/limits/<workload>.json``, and a per-layer metric is read by
``bench/metrics/<metric>.py``. A later change adds a configuration, a
traffic mix, a cell or a metric by adding files and entries, without
editing any file here.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Spec:
    def __init__(self, root: str = ROOT):
        self.root = root
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            self.bench = json.load(f)

    def _json(self, *parts) -> Dict:
        with open(os.path.join(self.root, *parts)) as f:
            return json.load(f)

    def workload(self, name: str) -> Dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.bench['workloads']]}")

    def config(self, name: str) -> Dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                cfg = self._json(c["file"])
                if sorted(cfg["reduced"]) != sorted(c["reduced"]):
                    raise ValueError(f"{c['file']}: reduced {cfg['reduced']}"
                                     f" but BENCHMARK.json says "
                                     f"{c['reduced']}")
                return cfg
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        return self._json("bench", "traffic", f"{name}.json")

    def limits(self, workload: str) -> Dict[str, float]:
        return self._json("bench", "limits", f"{workload}.json")["limits"]

    def end_to_end(self, workload: str) -> List[Dict]:
        return [m for m in self.bench["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> List[Dict]:
        """The per-layer metrics this cell reports: those that list it,
        and those without a list whose ``moves`` metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.bench["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str) -> Callable:
        path = os.path.join(self.root, "bench", "metrics", f"{metric}.py")
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read
