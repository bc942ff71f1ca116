"""`BENCHMARK.json` against the benchmark contract, and every entry
resolving its files by name — also entries a later change adds."""
from __future__ import annotations

import json
import os
import re

import pytest

import bench_tiny
from bench.spec import Spec

REPO = bench_tiny.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return Spec(REPO)


def test_top_level_keys_and_limits(spec):
    d = spec.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert d["paths"] == ["bench"] and d["command"][1] == "bench/run.py"
    assert 1 <= d["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in d[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def test_metrics(spec):
    d = spec.data
    e2e = {m["name"] for m in d["end_to_end"]}
    assert "setup_s" in e2e
    for m in d["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in d["per_layer"]:
        assert UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        layers.setdefault(m["layer"], []).append(m["name"])
        if "_roofline" in m["name"]:
            assert m["unit"] == "%"


def test_every_entry_resolves_its_files(spec):
    for c in spec.data["configs"]:
        path = os.path.join(REPO, c["file"])
        assert path.startswith(os.path.join(REPO, "bench") + os.sep)
        with open(path) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.isfile(spec.adapter_path(cfg["simulator"]))
    for w in spec.data["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = spec.cell(w["name"])
        assert os.path.isfile(spec.traffic_path(w["traffic"]))
        assert hasattr(spec.adapter(cell), "make")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert set(cell.traffic["check"]["limits"])
    for m in spec.data["per_layer"]:
        assert callable(spec.metric(m["name"]).read)
    four = sum(w["chips"] == 4 for w in spec.data["workloads"])
    assert four <= max(1, len(spec.data["workloads"]) // 2)


def test_a_new_cell_config_and_metric_need_only_new_files(tmp_path):
    """A later change adds a configuration, a traffic mix and a per-layer
    metric as files plus entries; the harness finds them by name."""
    root = bench_tiny.tiny_root(str(tmp_path))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "fig13_fleet.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "fig13_fleet_high"
    cfg["designs"] = {k: cfg["designs"][k] for k in ("10N/8", "8+2")}
    with open(os.path.join(b, "configs", "fig13_fleet_high.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "fig13.json")) as f:
        traffic = json.load(f)
    traffic["grid"].update(designs=["10N/8", "8+2"], gpu_scenarios=["high"],
                           replicas=2)
    with open(os.path.join(b, "traffic", "fig13-high.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(b, "metrics", "calls_per_window.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.window['calls'])\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "fig13_fleet_high", "source": "x",
                            "file": "bench/configs/fig13_fleet_high.json",
                            "reduced": ["demand_scale"], "why": "x"})
    data["workloads"].append({"name": "fleet.fig13-high",
                              "config": "fig13_fleet_high",
                              "traffic": "fig13-high", "chips": 1,
                              "why": "x"})
    data["per_layer"].append({"name": "calls_per_window", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "harness",
                              "moves": "fleet_lifecycles_per_s"})
    data["end_to_end"][0]["workloads"].append("fleet.fig13-high")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)

    cell = Spec(root).cell("fleet.fig13-high")
    assert "calls_per_window" in {m["name"] for m in cell.per_layer}
    rc, res, out = bench_tiny.run(root, [
        "--workload", "fleet.fig13-high", "--seed", "31", "--seconds",
        "0.1", "--trace", "1"])
    assert rc == 0 and res["correct"] is True, out
    assert res["metrics"]["calls_per_window"]["value"] >= 1
