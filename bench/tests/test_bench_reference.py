"""The benchmark's plain references against the simulator, on the CPU.

The frozen arrival generators must draw the simulator's traces bit for
bit; the numpy fleet lifecycle and Monte Carlo trial must reproduce
`sweep` and `mc_sweep` at a small size, policy by policy.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench.reference import arrivals as ra
from bench.reference import engine as re_
from bench.reference import fleet as rf
from bench.reference import hall as rh

SEED = 2 ** 31 - 5


def ref_design(d):
    return re_.Design(**{f.name: getattr(d, f.name)
                         for f in dataclasses.fields(re_.Design)})


def ref_env(e):
    return ra.Envelope(**{f.name: getattr(e, f.name)
                          for f in dataclasses.fields(ra.Envelope)})


@pytest.mark.parametrize("scenario,pod_racks", [("low", 1), ("high", 5)])
def test_frozen_fleet_generator_matches(scenario, pod_racks):
    from repro.core.arrivals import EnvelopeSpec, generate_fleet_trace
    env = EnvelopeSpec(demand_scale=0.01, gpu_scenario=scenario,
                       pod_racks=pod_racks)
    got = generate_fleet_trace(env, SEED)
    ref = ra.fleet_trace(ref_env(env), SEED)
    for f in ra.FIELDS:
        a = np.asarray(getattr(got, f))
        assert a.dtype == ref[f].dtype and np.array_equal(a, ref[f]), f


@pytest.mark.parametrize("phase,pod_racks", [(0, 1), (1, 7)])
def test_frozen_mixed_generator_matches(phase, pod_racks):
    from repro.core.arrivals import sample_mixed_traces
    got = sample_mixed_traces(4, 300, year=2028, scenario="high", seed=SEED,
                              pod_racks=pod_racks, phase=phase)
    ref = ra.mixed_traces(4, 300, 2028, "high", SEED, phase, 0.6,
                          pod_racks, 10)
    for f in ra.FIELDS:
        assert np.array_equal(np.asarray(getattr(got, f)), ref[f]), f


@pytest.mark.parametrize("policy", [0, 1, 2, 3])
def test_hall_reference_matches_mc_sweep(policy):
    from repro.core import hierarchy
    from repro.core.mc_sweep import MCAxes, mc_sweep
    names = ("10N/8", "8+2")
    T, E, Eb = 2, 250, 80
    designs = [hierarchy.get_design(n) for n in names]
    ax = MCAxes.zip(designs=designs, policies=[policy], seeds=[SEED])
    res = mc_sweep(ax, n_trials=T, n_events=E, refill_events=Eb,
                   models=())
    ta = ra.mixed_traces(T, E, 2028, "med", SEED, 0, 0.6, 1, 10)
    tb = ra.mixed_traces(T, Eb, 2028, "med", SEED, 1, 0.6, 1, 10)
    keys = rh.trial_keys(SEED, T)
    for b, d in enumerate(designs):
        eng = re_.Engine(re_.Topology(ref_design(d), 1, 100, 10),
                         np.float32)
        for t in range(T):
            pick = lambda tr: {k: v[t] for k, v in tr.items()}
            out = rh.run_trial(eng, pick(ta), pick(tb), policy, keys[t],
                               True)
            assert np.array_equal(out["placed_a"], res.placed_a[b, t])
            assert np.array_equal(out["placed_b"], res.placed_b[b, t])
            assert out["deployed_kw"] == pytest.approx(
                float(res.deployed_kw[b, t]), rel=1e-6)
            assert out["hall_stranding"] == pytest.approx(
                float(res.hall_stranding[b, t]), abs=1e-6)


def test_hall_reference_matches_mc_sweep_with_pods():
    from repro.core import hierarchy
    from repro.core.mc_sweep import MCAxes, mc_sweep
    T, E, Eb = 2, 200, 60
    d = hierarchy.get_design("10N/8")
    ax = MCAxes.zip(designs=[d], policies=range(4), seeds=[SEED])
    res = mc_sweep(ax, n_trials=T, n_events=E, refill_events=Eb,
                   pod_racks=7, scenario="high", models=())
    ta = ra.mixed_traces(T, E, 2028, "high", SEED, 0, 0.6, 7, 10)
    tb = ra.mixed_traces(T, Eb, 2028, "high", SEED, 1, 0.6, 7, 10)
    keys = rh.trial_keys(SEED, T)
    eng = re_.Engine(re_.Topology(ref_design(d), 1, 100, 10), np.float32)
    for p in range(4):
        for t in range(T):
            pick = lambda tr: {k: v[t] for k, v in tr.items()}
            out = rh.run_trial(eng, pick(ta), pick(tb), p, keys[t], True)
            assert np.array_equal(out["placed_a"], res.placed_a[p, t])
            assert np.array_equal(out["placed_b"], res.placed_b[p, t])
            assert out["deployed_kw"] == pytest.approx(
                float(res.deployed_kw[p, t]), rel=1e-6)


@pytest.mark.parametrize("policy,pod_racks,scale,names", [
    (3, 1, 0.004, ("4N/3", "3+1", "10N/8", "8+2")),
    (2, 1, 0.004, ("4N/3", "3+1", "10N/8", "8+2")),
    (0, 5, 0.002, ("10N/8",))])
def test_fleet_reference_matches_sweep(policy, pod_racks, scale, names):
    from repro.core import hierarchy
    from repro.core.arrivals import EnvelopeSpec, generate_fleet_trace
    from repro.core.sweep import SweepAxes, sweep
    env = EnvelopeSpec(demand_scale=scale, gpu_scenario="high",
                       pod_racks=pod_racks)
    designs = [hierarchy.get_design(n) for n in names]
    ax = SweepAxes.zip(designs=designs, envs=[env], policies=[policy],
                       seeds=[SEED])
    traces = [generate_fleet_trace(env, SEED)] * len(names)
    res = sweep(ax, traces=traces, models=())
    renv = ref_env(env)
    from bench.adapters import fleet as fleet_adapter
    caps = [fleet_adapter.hall_cap(ref_design(d), renv) for d in designs]
    H = -(-max(caps) // 4) * 4
    tr = ra.fleet_trace(renv, SEED)
    for i, d in enumerate(designs):
        eng = re_.Engine(re_.Topology(ref_design(d), H, 100, 10),
                         np.float32)
        out = rf.lifecycle(eng, tr, env.n_months, policy, SEED, caps[i])
        assert np.array_equal(out["halls_active"], res.halls_active[i])
        assert out["n_halls_built"] == res.n_halls_built[i]
        np.testing.assert_allclose(out["deployed_kw"] / 1e3,
                                   res.deployed_mw[i], rtol=1e-5)
        np.testing.assert_allclose(out["p90"], res.p90_stranding[i],
                                   atol=1e-6)
        assert out["placed_fraction"] == pytest.approx(
            float(res.placed_fraction[i]))
