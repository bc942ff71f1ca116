"""`BENCHMARK.json` and the files it names, found by name.

A cell names a configuration and a traffic mix; the configuration's
`file` is a JSON file of sizes whose `simulator` key names the adapter
`bench/adapters/<simulator>.py`; the mix is `bench/traffic/<traffic>.json`;
a per-layer metric `<name>` is the reader `bench/metrics/<name>.py`.
Adding a cell, a configuration or a metric is adding files and entries:
nothing here or in the harness changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: str):
    """Import the Python file at `path` as a module named `name`."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file's contents
    traffic: dict          # the traffic file's contents
    config_name: str
    traffic_name: str
    end_to_end: list       # the end-to-end metric entries this cell reports
    per_layer: list        # the per-layer metric entries this cell reports


class Spec:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = os.path.join(root, "bench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.data = json.load(f)
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.workloads = {w["name"]: w for w in self.data["workloads"]}

    def config_path(self, name: str) -> str:
        return os.path.join(self.root, self.configs[name]["file"])

    def traffic_path(self, name: str) -> str:
        return os.path.join(self.bench, "traffic", name + ".json")

    def adapter_path(self, simulator: str) -> str:
        return os.path.join(self.bench, "adapters", simulator + ".py")

    def metric_path(self, name: str) -> str:
        return os.path.join(self.bench, "metrics", name + ".py")

    def cell(self, name: str) -> Cell:
        if name not in self.workloads:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{sorted(self.workloads)}")
        w = self.workloads[name]
        with open(self.config_path(w["config"])) as f:
            config = json.load(f)
        with open(self.traffic_path(w["traffic"])) as f:
            traffic = json.load(f)
        e2e = [m for m in self.data["end_to_end"]
               if name in m.get("workloads", [name])]
        moved = {m["name"] for m in e2e}
        layer = [m for m in self.data["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
        return Cell(name, int(w["chips"]), config, traffic, w["config"],
                    w["traffic"], e2e, layer)

    def adapter(self, cell: Cell):
        sim = cell.config["simulator"]
        return load_module(self.adapter_path(sim), f"bench_adapter_{sim}")

    def metric(self, name: str):
        return load_module(self.metric_path(name),
                           "bench_metric_" + name.replace(".", "_")
                           .replace("-", "_"))
