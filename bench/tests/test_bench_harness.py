"""The harness end to end on the CPU, at sizes a test can hold.

A run without a TPU exits non-zero with no result line.  With the CPU
standing in for the chip, a tiny run of each simulator's cell comes out
`correct`, and comes out not correct when the timed path is broken
underneath: an answer altered where it is produced, a placement step
that returns its state unchanged, half of the batch left out.  (No cell
exchanges data between chips, so that fault has no case here.)
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

import bench_tiny

REPO = bench_tiny.REPO


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hall.fig7",
         "--seed", str(2 ** 31 + 12345), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_run_outside_a_checkout_of_the_program_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and bench/ has no simulator."""
    root = bench_tiny.tiny_root(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = ("import sys, jax; sys.path.insert(0, sys.argv[1]); "
            "sys.path = [p for p in sys.path if not p.endswith('/src')]; "
            "from bench import run; sys.exit(run.main(sys.argv[2:], "
            "root=sys.argv[1], find_devices=lambda n: jax.devices()[:n]))")
    p = subprocess.run(
        [sys.executable, "-c", code, root, "--workload", "hall.fig7",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.tiny_root(str(tmp_path_factory.mktemp("bench")))


RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("workload,rate", [
    ("fleet.fig13", "fleet_lifecycles_per_s"),
    ("hall.fig7-pod7-high", "hall_trials_per_s")])
def test_tiny_run_is_correct(root, workload, rate):
    rc, res, out = bench_tiny.run(root, [
        "--workload", workload, "--seed", str(2 ** 32 + 7),
        "--seconds", "0.5", "--trace", "0"])
    assert rc == 0 and res is not None, out
    assert list(res)[:5] == RESULT_KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {rate, "setup_s"}
    assert res["metrics"][rate]["value"] > 0
    assert "compilations inside the window: 0" in out


def test_tiny_traced_run_reads_the_trace(root):
    rc, res, out = bench_tiny.run(root, [
        "--workload", "hall.fig7", "--seed", "11", "--seconds", "0.2",
        "--trace", "1"])
    assert rc == 0 and res["correct"] is True
    assert "window_s" in res["device"] and "busy_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU writes no TPU plane, so no device metric may be reported
    assert not any(k.startswith(("device_idle", "placement_score",
                                 "scan_us")) for k in res["metrics"])


def test_fleet_grids_have_the_stated_busiest_month(root):
    """Every grid of a fleet run has the mix's busiest month, its seeds
    follow from the run's seed alone, and no two calls share one."""
    import contextlib
    from bench.spec import Spec
    spec = Spec(root)
    cell = spec.cell("fleet.fig13")
    make = lambda seed: spec.adapter(cell).make(
        cell, seed, None, lambda name: contextlib.nullcontext())
    sim = make(2 ** 31 + 99)
    seeds = [sim._seeds(i)[0] for i in range(6)]
    assert len(set(seeds)) == 6
    assert {sim.busiest_month(s) for s in seeds} == {3}
    assert [make(2 ** 31 + 99)._seeds(i)[0] for i in range(6)] == seeds
    assert make(2 ** 31 + 100)._seeds(0) != seeds[:1]


def _alter_answer(monkeypatch):
    from repro.core import placement
    real = placement.deployed_kw
    monkeypatch.setattr(placement, "deployed_kw",
                        lambda state: real(state) * 1.01)


def _unchanged_state(monkeypatch):
    from repro.core import placement
    monkeypatch.setattr(placement, "_apply_to_row",
                        lambda jt, state, dep, n, row: state)


def _half_batch(monkeypatch):
    """The sweep computes the first half of its batch and hands the rest
    back as copies of it."""
    import jax
    from repro.core import mc_sweep, sweep
    real_fleet, real_hall = sweep.sharded_sweep, mc_sweep.sharded_mc_sweep

    def fleet(axes, traces, **kw):
        h = len(axes) // 2
        sub = sweep.SweepAxes.zip(axes.designs[:h], axes.envs[:h],
                                  axes.policies[:h], axes.seeds[:h])
        res = real_fleet(sub, traces=traces[:h], **kw)
        for f in ("halls_active", "deployed_mw", "p50_stranding",
                  "p90_stranding", "n_halls_built", "placed_fraction"):
            a = getattr(res, f)
            setattr(res, f, np.concatenate([a, a[:len(axes) - h]]))
        return res

    def hall(axes, n_trials, **kw):
        res = real_hall(axes, n_trials=n_trials // 2, **kw)
        for f in ("deployed_kw", "hall_stranding", "lineup_stranding",
                  "saturated", "placed_a", "placed_b"):
            a = getattr(res, f)
            setattr(res, f, np.concatenate([a, a], axis=1))
        return res

    monkeypatch.setattr(sweep, "sharded_sweep", fleet)
    monkeypatch.setattr(mc_sweep, "sharded_mc_sweep", hall)
    jax.clear_caches()


FAULTS = {"answer_altered": _alter_answer,
          "state_unchanged": _unchanged_state,
          "half_batch": _half_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["fleet.fig13", "hall.fig7"])
def test_broken_timed_path_is_not_correct(root, monkeypatch, workload,
                                          fault):
    import jax
    FAULTS[fault](monkeypatch)
    jax.clear_caches()
    try:
        rc, res, out = bench_tiny.run(root, [
            "--workload", workload, "--seed", "2024", "--seconds", "0.2",
            "--trace", "0"])
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert rc == 0 and res is not None, out
    assert res["correct"] is False, res["checks"]
