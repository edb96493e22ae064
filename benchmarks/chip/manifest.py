"""`BENCHMARK.json` and the files it names. Everything that belongs to one
configuration, traffic mix or per-layer metric is a file of its own,
found by its name:

- a configuration: the `file` that its entry names (JSON);
- a traffic mix: `traffic/<traffic>.json` beside this file;
- a per-layer metric: `metrics/<name>.py`, with `read(view) -> float`.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class Manifest:
    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.here = self.root / "benchmarks" / "chip"
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for wl in self.data["workloads"]:
            if wl["name"] == name:
                return wl
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; there are "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic_path(self, traffic: str) -> pathlib.Path:
        return self.here / "traffic" / f"{traffic}.json"

    def traffic(self, traffic: str) -> dict:
        return json.loads(self.traffic_path(traffic).read_text())

    def reader_path(self, metric: str) -> pathlib.Path:
        return self.here / "metrics" / f"{metric}.py"

    def end_to_end(self, workload: str) -> list:
        return [m for m in self.data["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list:
        return [m for m in self.data["per_layer"]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        """The `read` function of a per-layer metric's own file."""
        path = self.reader_path(metric)
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
