"""The pod cell `hall.fig7-pod7-high`: its configuration states the pod
size and TDP scenario that its traffic runs, its adapter warms one grid
and refuses a program that compiles per grid, and `pod_step_fill.hall`
reads the pod window's counters."""
from __future__ import annotations

import contextlib
import json
import os

import jax
import pytest

import bench_tiny
from bench.spec import Spec
from repro.runtime import spans

CELL = "hall.fig7-pod7-high"
MS = 1_000_000      # ns


@pytest.fixture(scope="module")
def spec():
    return Spec(bench_tiny.REPO)


def test_config_states_what_the_traffic_runs(spec):
    cell = spec.cell(CELL)
    grid = cell.traffic["grid"]
    assert cell.config["pod_racks"] == grid["pod_racks"] == 7
    assert cell.config["scenario"] == grid["scenario"] == "high"
    assert list(cell.config["designs"]) == grid["designs"] == ["10N/8"]
    # the rest of the hall is Fig. 7's, as `fig7_hall` states it
    with open(spec.config_path("fig7_hall")) as f:
        fig7 = json.load(f)
    assert cell.config["designs"]["10N/8"] == fig7["designs"]["10N/8"]
    for k in ("year", "gpu_power_share", "quantum_racks", "harvest",
              "precision"):
        assert cell.config[k] == fig7[k], k
    assert cell.config["reduced"] == {}
    assert os.path.basename(spec.config_path(cell.config_name)) \
        == cell.config["name"] + ".json"


def _records(counts, root="repro.mc_sweep"):
    return (spans.Record(0, root, 0, 1000 * MS, None, 0, {}),
            spans.Record(1, "repro.mc_sweep.prepare", 0, 100 * MS, 0, 0,
                         counts))


@pytest.mark.parametrize("counts,value", [
    ({"pod_events": 3072, "pod_steps": 4096, "pod_racks": 21504}, 75.0),
    ({"pod_events": 0, "pod_steps": 512}, 0.0),
    ({"events": 9600}, None),                # a pod-free grid
    ({"pod_events": 12, "pod_steps": 0}, None)])
def test_pod_step_fill_on_synthetic_records(monkeypatch, spec, counts,
                                            value):
    read = spec.metric("pod_step_fill.hall").read

    class Ctx:
        traced = {"seconds": 2.0}

    def with_records(recs):
        monkeypatch.setattr(spans, "records", lambda: tuple(recs))
        return read(Ctx())

    recs = _records(counts)
    assert with_records(recs) == (None if value is None
                                  else pytest.approx(value))
    # no root of the engine, or another engine's: nothing to read
    assert with_records(recs[1:]) is None
    assert with_records(_records(counts, root="repro.sweep")) is None
    # no traced call: nothing to read
    Ctx.traced = None
    assert with_records(recs) is None


@pytest.fixture(scope="module")
def pod_root(tmp_path_factory):
    """The tiny benchmark with the pod cell on its own configuration and
    adapter, at the tiny hall sizes (`bench_tiny` also appends a stand-in
    of the cell on the rack-scale configuration; that one goes)."""
    root = bench_tiny.tiny_root(str(tmp_path_factory.mktemp("pods")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    data["workloads"] = [w for w in data["workloads"]
                         if (w["name"], w["config"]) != (CELL, "fig7_hall")]
    with open(path, "w") as f:
        json.dump(data, f)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_pod_cell_warms_one_grid_and_compiles_nothing_after(pod_root,
                                                            trace):
    rc, res, out = bench_tiny.run(pod_root, [
        "--workload", CELL, "--seed", str(2 ** 32 + 17), "--seconds", "0.5",
        "--trace", str(trace)])
    assert rc == 0 and res is not None, out
    assert res["correct"] is True, res["checks"]
    assert "set-up: 1 warm-up grid call(s)" in out
    assert "compilations inside the window: 0" in out
    if trace:
        assert 0 < res["metrics"]["pod_step_fill.hall"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {"hall_trials_per_s", "setup_s"}


def test_pod_adapter_refuses_a_program_that_compiles_per_grid(pod_root):
    spec = Spec(pod_root)
    cell = spec.cell(CELL)
    sim = spec.adapter(cell).make(cell, 5, jax.devices()[:1],
                                  lambda name: contextlib.nullcontext())
    assert sim.warm_each_call is False
    # a program of its own for every grid
    sim._run = lambda i: jax.jit(lambda x: x + i)(1.0)
    with pytest.raises(RuntimeError, match="compiles a trial per pod grid"):
        sim.warm(-1)
