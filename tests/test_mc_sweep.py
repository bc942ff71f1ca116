"""Batched single-hall MC engine + split-trace fleet scan (ISSUE 4).

Three equivalence obligations:

* `mc_sweep` over a configuration grid must reproduce the sequential
  one-configuration `singlehall.monte_carlo` wrapper per config — the
  same `sample_mixed_traces` batch is generated either way, and topology
  padding is inert, so results are bitwise-equal up to float tolerance.
* the fleet engine's split-trace pod scan must reproduce the
  pre-refactor `lax.cond(is_pod, …)`+retry path exactly
  (`legacy_pod_cond=True` keeps that path compilable as the reference)
  and the pre-refactor golden pod-grid numbers.
* `sharded_mc_sweep` over ≥2 devices must match single-device `mc_sweep`.

Multi-device cases force simulated host devices BEFORE jax initializes
(the test_sharded_sweep.py pattern); in-suite they rely on CI exporting
``XLA_FLAGS=--xla_force_host_platform_device_count=2``.
"""
import os
import sys

if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=2").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.core import arrivals, hierarchy as h  # noqa: E402
from repro.core import placement as pl, projections as proj  # noqa: E402
from repro.core.arrivals import (EnvelopeSpec,  # noqa: E402
                                 generate_fleet_trace, sample_mixed_traces)
from repro.core.fleet import FleetConfig, run_fleet  # noqa: E402
from repro.core.mc_sweep import (MCAxes, mc_sweep,  # noqa: E402
                                 sharded_mc_sweep)
from repro.core.singlehall import monte_carlo  # noqa: E402
from repro.core.sweep import SweepAxes, sweep  # noqa: E402

needs_devices = pytest.mark.skipif(
    jax.device_count() < 2, reason="needs >=2 host devices")

MC_KW = dict(n_trials=4, n_events=150, year=2030, scenario=proj.HIGH)


def _assert_mc_equal(batch_res, wrapper_res):
    for key in ("saturated", "placed_a", "placed_b"):
        np.testing.assert_array_equal(batch_res[key], wrapper_res[key],
                                      err_msg=key)
    for key in ("lineup_stranding", "hall_stranding", "deployed_kw"):
        np.testing.assert_allclose(batch_res[key], wrapper_res[key],
                                   rtol=1e-6, atol=1e-5, err_msg=key)
    assert batch_res["ha_capacity_kw"] == wrapper_res["ha_capacity_kw"]


# ---------------------------------------------------------------------------
# mc_sweep ≡ sequential monte_carlo
# ---------------------------------------------------------------------------

def test_mc_sweep_matches_sequential():
    """Heterogeneous (design, policy, seed) batch: every configuration
    must match its sequential `monte_carlo` call — identical trace batch,
    inert topology padding (10N/8 forces padding on the small halls)."""
    axes = MCAxes.zip(
        designs=[h.get_design(n) for n in ("4N/3", "3+1", "10N/8")],
        policies=[pl.POLICY_VAR_MIN, pl.POLICY_MIN_WASTE,
                  pl.POLICY_VAR_MIN],
        seeds=[11, 11, 13])
    res = mc_sweep(axes, **MC_KW)
    assert len(res) == 3 and res.n_trials == MC_KW["n_trials"]
    for i in range(len(axes)):
        w = monte_carlo(axes.designs[i], policy=axes.policies[i],
                        seed=axes.seeds[i], **MC_KW)
        _assert_mc_equal(res.result(i), w)
        # padding stripped: per-config line-up axis is the design's own
        assert res.result(i)["lineup_stranding"].shape == \
            (MC_KW["n_trials"], axes.designs[i].n_lineups)


def test_mc_sweep_fig6_single_sku_mode():
    """`single_sku_gpu` + per-config `sku_kw` as generator arguments:
    batched grid ≡ sequential wrapper, and every event is a GPU rack at
    the override power."""
    axes = MCAxes.product(designs=[h.get_design("4N/3"),
                                   h.get_design("3+1")],
                          sku_kw=(400.0, 900.0), seeds=(6,))
    res = mc_sweep(axes, n_trials=3, n_events=120, harvest=False,
                   single_sku_gpu=True)
    for i in range(len(axes)):
        w = monte_carlo(axes.designs[i], n_trials=3, n_events=120,
                        harvest=False, single_sku_gpu=True,
                        sku_kw_override=axes.sku_kw[i], seed=6)
        _assert_mc_equal(res.result(i), w)

    t = sample_mixed_traces(3, 120, seed=6, sku_kw_override=700.0,
                            single_sku_gpu=True)
    assert (t.is_gpu.all() and (t.rack_kw == 700.0).all()
            and (t.class_id == 0).all())


def test_sample_mixed_traces_semantics():
    """One vectorized pass: reproducible per (args, seed), distinct across
    seeds, and mix parameters land in the right columns."""
    a = sample_mixed_traces(4, 200, seed=3)
    b = sample_mixed_traces(4, 200, seed=3)
    c = sample_mixed_traces(4, 200, seed=4)
    for f in ("class_id", "rack_kw", "lifetime_m", "tier"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.rack_kw, c.rack_kw)
    assert a.rack_kw.shape == (4, 200) and len(a) == 4
    assert a.trial(2).rack_kw.shape == (200,)

    t = sample_mixed_traces(2, 300, seed=5, pod_racks=5, la_fraction=1.0)
    gpu = t.is_gpu
    assert (t.is_pod == gpu).all() and (t.n_racks[gpu] == 5).all()
    assert (t.tier == 1).all()          # TIER_LA everywhere
    assert (t.lifetime_m >= 12).all()
    # realized GPU power share tracks the requested 0.6 calibration
    p = t.rack_kw.astype(float) * t.n_racks
    share = p[gpu].sum() / p.sum()
    assert 0.4 < share < 0.8


def test_monte_carlo_topology_cache():
    """Repeated wrapper calls stage each (design, padding) topology once."""
    from repro.core.mc_sweep import _TOPO_CACHE, _staged_topology
    d = h.get_design("4N/3")
    key = (d, d.n_rows, d.n_lineups)
    _TOPO_CACHE.pop(key, None)
    e1 = _staged_topology(d, d.n_rows, d.n_lineups)
    e2 = _staged_topology(d, d.n_rows, d.n_lineups)
    assert e1 is e2 and key in _TOPO_CACHE


def test_mc_axes_product_tags():
    """`product` carries `tags` (aligned with designs, like
    `SweepAxes.product(env_tags=…)`) instead of dropping them."""
    axes = MCAxes.product(designs=[h.get_design("4N/3"),
                                   h.get_design("3+1")],
                          sku_kw=(400.0, 900.0), seeds=(1, 2),
                          tags=("dist", "block"))
    assert axes.tags == ["dist"] * 4 + ["block"] * 4
    assert MCAxes.product(designs=[h.get_design("4N/3")],
                          seeds=(1, 2)).tags == ["", ""]
    with pytest.raises(ValueError):
        MCAxes.product(designs=[h.get_design("4N/3")], tags=("a", "b"))


# ---------------------------------------------------------------------------
# single-hall split-pods fast path ≡ legacy per-event cond
# ---------------------------------------------------------------------------

def test_sample_mixed_traces_pods_first():
    """With `pod_racks > 1` every trial's pod events precede its cluster
    events (stable reorder: in-group order, marginals and the realized
    power mix are untouched) and the batch exposes its window geometry."""
    t = sample_mixed_traces(4, 200, seed=9, pod_racks=5)
    ip = t.is_pod
    assert not np.any(ip[:, 1:] & ~ip[:, :-1])      # never False → True
    np.testing.assert_array_equal(t.n_pods, ip.sum(axis=1))
    assert t.max_pod_racks == 5
    assert (t.n_racks[ip] == 5).all() and (t.is_gpu == ip).all()
    # pod_racks=1 skips the reorder entirely and reports the sentinel
    # pod size (the pod-free placement mode's contract)
    assert sample_mixed_traces(4, 200, seed=9, pod_racks=1).max_pod_racks == 1

    from repro.core.mc_sweep import _pod_geometry
    wa, sa = _pod_geometry([t])
    assert wa == int(t.n_pods.max()) and sa == int(t.n_pods.min())
    bad = sample_mixed_traces(2, 50, seed=9, pod_racks=3)
    bad.is_pod = np.zeros_like(bad.is_pod)
    bad.is_pod[:, 1] = True                          # a cluster before a pod
    with pytest.raises(ValueError, match="precede"):
        _pod_geometry([bad])


@pytest.mark.parametrize("pod_racks", [3, 7])
def test_mc_split_pods_matches_legacy_cond(pod_racks):
    """The split-pods fast path (pods-first windows, trimmed rack scan,
    HD-compacted row view) must be bit-identical to the legacy per-event
    `lax.cond(is_pod, …)` path on the same traces."""
    axes = MCAxes.zip(designs=[h.get_design("10N/8"), h.get_design("3+1")],
                      seeds=[11, 12])
    kw = dict(n_trials=2, n_events=100, year=2030, scenario=proj.HIGH,
              pod_racks=pod_racks)
    res_split = mc_sweep(axes, **kw)
    res_legacy = mc_sweep(axes, legacy_pod_cond=True, **kw)
    for f in ("lineup_stranding", "hall_stranding", "deployed_kw",
              "saturated", "placed_a", "placed_b"):
        np.testing.assert_array_equal(getattr(res_split, f),
                                      getattr(res_legacy, f), err_msg=f)


# Two one-configuration pod grids of equal shapes whose exact windows
# (W_a, W_b, S_a, S_b) are (9, 4, 7, 2) and (14, 6, 4, 0): rounded to
# multiples of 4 they would still differ, so window sizes baked into the
# program as constants would compile each grid anew.
POD_GRID_SEEDS = (1, 4)
POD_GRID_KW = dict(n_trials=4, n_events=80, refill_events=30, year=2030,
                   scenario=proj.HIGH, pod_racks=3)

_ONE_COMPILE_CHILD = """
import functools, json, sys
import jax
from repro.core import hierarchy as h, mc_sweep as mcs
path, kw, seeds = sys.argv[1], json.loads(sys.argv[2]), json.loads(sys.argv[3])
if path == "mc_sweep":
    run, jitted = mcs.mc_sweep, mcs._mc_sweep_jit
else:
    run = functools.partial(mcs.sharded_mc_sweep, devices=jax.devices()[:2],
                            mesh_shape=(1, 2))
    jitted = mcs._mc_sharded2d_jit
out = {"compiled": [], "windows": [], "placed": []}
for s in seeds:
    axes = mcs.MCAxes.zip(designs=[h.get_design("4N/3")], seeds=[s])
    prep_kw = {k: kw[k] for k in ("year", "scenario", "pod_racks")}
    _, trial_kw = mcs._mc_prepare(
        axes, kw["n_trials"], kw["n_events"], gpu_power_share=0.6,
        quantum_racks=10, la_fraction=0.0, single_sku_gpu=False,
        refill_events=kw["refill_events"], **prep_kw)
    res = run(axes, **kw)
    out["compiled"].append(jitted._cache_size())
    out["windows"].append(trial_kw["windows"].tolist())
    out["placed"].append(int(res.placed_a.sum() + res.placed_b.sum()))
print(json.dumps(out))
"""


@pytest.mark.parametrize("path", ["mc_sweep", "sharded_2d"])
def test_pod_grids_share_one_executable(path):
    """The split-pods windows are data, not constants of the program: two
    pod grids of one mix and equal shapes whose windows differ run one
    compiled hall program, unsharded and on a (1, 2) (config × trial)
    mesh of two host devices (a child process, so the device count holds
    whatever jax this process already started)."""
    import json
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-c", _ONE_COMPILE_CHILD, path,
         json.dumps(POD_GRID_KW), json.dumps(POD_GRID_SEEDS)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["windows"] == [[9, 4, 7, 2], [14, 6, 4, 0]]
    assert out["compiled"] == [1, 1]
    assert all(p > 0 for p in out["placed"])


def test_refill_stream_decorrelated_from_adjacent_seed():
    """Refill traces draw from the phase-1 stream of the same seed; the
    old `seed + 1` refill was bitwise the next configuration's fill
    trace, correlating trials across adjacent-seed grid points."""
    refill = sample_mixed_traces(3, 120, seed=7, phase=1)
    next_fill = sample_mixed_traces(3, 120, seed=8)
    own_fill = sample_mixed_traces(3, 120, seed=7)
    assert not np.array_equal(refill.rack_kw, next_fill.rack_kw)
    assert not np.array_equal(refill.rack_kw, own_fill.rack_kw)
    # still deterministic per (seed, phase)
    np.testing.assert_array_equal(
        refill.rack_kw, sample_mixed_traces(3, 120, seed=7, phase=1).rack_kw)


# ---------------------------------------------------------------------------
# split-trace fleet scan ≡ pre-refactor pod path
# ---------------------------------------------------------------------------

def _pod_env(pod, scale=0.01):
    return EnvelopeSpec(demand_scale=scale, gpu_scenario=proj.HIGH,
                        pod_racks=pod, pod_scale_arch=True)


def test_split_trace_matches_legacy_pod_cond():
    """The split-trace scan and the pre-refactor `lax.cond` path must be
    exactly equivalent on a shared-trace pod grid (same RNG keys via the
    per-month pod-count offset)."""
    axes = SweepAxes.zip(
        designs=[h.get_design("10N/8"), h.get_design("8+2")],
        envs=[_pod_env(3, 0.005), _pod_env(5, 0.005)],
        seeds=[3, 4])
    traces = [generate_fleet_trace(e, s)
              for e, s in zip(axes.envs, axes.seeds)]
    res_split = sweep(axes, traces=traces)
    res_legacy = sweep(axes, traces=traces, legacy_pod_cond=True)
    np.testing.assert_array_equal(res_split.n_halls_built,
                                  res_legacy.n_halls_built)
    for f in ("final_deployed_mw", "placed_fraction", "p50_stranding",
              "p90_stranding", "halls_active", "final_lineup_stranding"):
        np.testing.assert_allclose(getattr(res_split, f),
                                   getattr(res_legacy, f), atol=1e-6,
                                   err_msg=f)


def test_pod_golden_regression():
    """Fixed-seed pod-grid numbers captured from the PRE-refactor
    `lax.cond` engine (100 MW, High TDP): the split-trace scan must
    reproduce them — guards ordering, RNG alignment, and the
    `pod_scan_len` trim against silent drift."""
    golden = {
        ("10N/8", 5, 3): (8, 60.0096, 0.990950, 0.6386),
        ("3+1", 5, 9): (11, 35.8188, 0.978448, 0.6239),
    }
    for (dname, pod, seed), (halls, dep, pf, p90) in golden.items():
        r = run_fleet(FleetConfig(h.get_design(dname), _pod_env(pod),
                                  seed=seed))
        assert r.n_halls_built == halls, (dname, r.n_halls_built)
        np.testing.assert_allclose(r.final_deployed_mw, dep, atol=0.01)
        np.testing.assert_allclose(r.placed_fraction, pf, atol=1e-4)
        np.testing.assert_allclose(float(r.p90_stranding[-1]), p90,
                                   atol=2e-3)


# ---------------------------------------------------------------------------
# sharded mc_sweep (2 forced host devices)
# ---------------------------------------------------------------------------

@needs_devices
def test_sharded_mc_sweep_matches_single_device():
    axes = MCAxes.zip(designs=[h.get_design("4N/3"), h.get_design("3+1")],
                      seeds=[21, 22])
    res_1 = mc_sweep(axes, **MC_KW)
    res_d = sharded_mc_sweep(axes, **MC_KW)
    np.testing.assert_array_equal(res_1.saturated, res_d.saturated)
    for f in ("lineup_stranding", "hall_stranding", "deployed_kw"):
        np.testing.assert_allclose(getattr(res_1, f), getattr(res_d, f),
                                   rtol=1e-6, atol=1e-5, err_msg=f)


@needs_devices
def test_sharded_mc_sweep_remainder_grid():
    """3 configurations over 2 devices: pad-with-config-0 then drop."""
    axes = MCAxes.zip(designs=[h.get_design("4N/3")], seeds=[31, 32, 33])
    res_1 = mc_sweep(axes, n_trials=3, n_events=100)
    res_d = sharded_mc_sweep(axes, n_trials=3, n_events=100)
    assert len(res_d) == 3
    np.testing.assert_allclose(res_1.deployed_kw, res_d.deployed_kw,
                               rtol=1e-6, atol=1e-5)


def test_sharded_mc_sweep_passthrough_single_device():
    axes = MCAxes.zip(designs=[h.get_design("4N/3")], seeds=[41])
    res_d = sharded_mc_sweep(axes, n_trials=2, n_events=80,
                             devices=jax.devices()[:1])
    res_1 = mc_sweep(axes, n_trials=2, n_events=80)
    np.testing.assert_array_equal(res_1.deployed_kw, res_d.deployed_kw)
