"""Benchmark harness — one function per paper table/figure.

Each benchmark prints CSV rows:  name,us_per_call,derived
where `us_per_call` is the wall-time of one underlying simulator/model
call and `derived` is the figure's headline quantity, so the paper's
claims are checkable from the output.

    PYTHONPATH=src python -m benchmarks.run                 # all, reduced scale
    PYTHONPATH=src python -m benchmarks.run --only fig13
    PYTHONPATH=src python -m benchmarks.run --scale 1.0     # full 10 GW study
    PYTHONPATH=src python -m benchmarks.run --json BENCH.json  # + JSON rows

The 10 GW headline study (--scale 1.0) takes hours on this 1-core
container; the default 0.04 (400 MW) preserves every qualitative ranking
(fractions are scale-stable — see tests/test_fleet.py).

Fleet lifecycles are served from `_FLEET_CACHE`, which the fig
benchmarks fill in batches via the sweep engine (`repro.core.sweep`):
each fig prefetches its whole configuration grid as one vmapped call,
sharded across all visible devices (`sharded_sweep`).  The single-hall
figs (5–7) run the same way through `repro.core.mc_sweep` — one batched
call per figure grid.  See benchmarks/README.md for the CSV schema, the
`--json` perf-trajectory dump, and the `sweep_speedup` / `mc_speedup` /
`pod_sweep_speedup` / `placement_kernel_speedup` acceptance modes.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time
from dataclasses import replace
from typing import Dict

import numpy as np

from repro.core import (arrivals, cost, fleet, hierarchy, payoff,
                        placement, projections as proj, quantiles as qt,
                        singlehall, throughput as tp)
from repro.core.arrivals import EnvelopeSpec, generate_fleet_trace
from repro.core.fleet import FleetConfig, run_fleet
from repro.core.mc_sweep import MCAxes, sharded_mc_sweep
from repro.core.sweep import SweepAxes, sharded_sweep, sweep
from repro.runtime.compile_cache import enable_compile_cache

REGISTRY = {}
_FLEET_CACHE: Dict[tuple, fleet.FleetResult] = {}
_ROWS: Dict[str, dict] = {}
SCALE = 0.04
SMOKE = False


def bench(fn):
    REGISTRY[fn.__name__] = fn
    return fn


def emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)
    _ROWS[name] = {"us_per_call": float(f"{us:.1f}"),
                   "derived": str(derived)}


def _req(design_name, scenario=proj.MED, pod_racks=1, quantum=10,
         harvest=True, seed=0, scale=None):
    """Normalized fleet-configuration request (also the cache key)."""
    return dict(design_name=design_name, scenario=scenario,
                pod_racks=pod_racks, quantum=quantum, harvest=harvest,
                seed=seed, scale=scale or SCALE)


def _env_of(r):
    return EnvelopeSpec(demand_scale=r["scale"], gpu_scenario=r["scenario"],
                        pod_racks=r["pod_racks"], quantum_racks=r["quantum"],
                        pod_scale_arch=r["pod_racks"] > 1)


def _prefetch(reqs):
    """Batch-evaluate all not-yet-cached fleet configurations through the
    sweep engine: one vmapped lifecycle call per (harvest, pods) group
    instead of one host-driven run per configuration, sharded across all
    visible devices (`sharded_sweep`; single-device passthrough on this
    1-core container).  Pod-free groups stay separate so they compile the
    cheap biased-placement path."""
    seen, miss = set(), []
    for r in reqs:
        k = tuple(sorted(r.items()))
        if k not in _FLEET_CACHE and k not in seen:
            seen.add(k)
            miss.append(r)
    groups = {}
    for r in miss:
        groups.setdefault((r["harvest"], r["pod_racks"] > 1), []).append(r)
    for (hv, _), grp in groups.items():
        axes = SweepAxes.zip(
            designs=[hierarchy.get_design(r["design_name"]) for r in grp],
            envs=[_env_of(r) for r in grp],
            seeds=[r["seed"] for r in grp])
        t0 = time.time()
        res = sharded_sweep(axes, harvest=hv)
        wall = (time.time() - t0) / len(grp)   # amortized per configuration
        for i, r in enumerate(grp):
            fr = res.result(i)
            fr._wall = wall
            _FLEET_CACHE[tuple(sorted(r.items()))] = fr


def _fleet(design_name, scenario=proj.MED, pod_racks=1, quantum=10,
           harvest=True, seed=0, scale=None):
    r = _req(design_name, scenario, pod_racks, quantum, harvest, seed, scale)
    key = tuple(sorted(r.items()))
    if key not in _FLEET_CACHE:
        _prefetch([r])
    return _FLEET_CACHE[key]


# ---------------------------------------------------------------------------


@bench
def fig5_stranding_cdf():
    """CDF of UPS stranding: single-hall MC vs fleet lifecycle (Fig. 5).
    Both designs' MC trials run as ONE batched `mc_sweep` call."""
    dnames = ("4N/3", "3+1")
    t0 = time.time()
    mc = sharded_mc_sweep(
        MCAxes.zip(designs=[hierarchy.get_design(d) for d in dnames],
                   seeds=[5]),
        n_trials=16, n_events=500, year=2030, scenario=proj.HIGH)
    us = (time.time() - t0) / (len(dnames) * 16) * 1e6   # per trial
    for i, dname in enumerate(dnames):
        s = mc.result(i)["lineup_stranding"].flatten()
        emit(f"fig5.mc.{dname}", us,
             f"p50={np.percentile(s, 50):.3f};p99={np.percentile(s, 99):.3f}")
    _prefetch([_req(d, proj.HIGH) for d in dnames])
    for dname in dnames:
        r = _fleet(dname, proj.HIGH)
        s = r.final_lineup_stranding
        emit(f"fig5.lifecycle.{dname}", r._wall * 1e6,
             f"p50={np.percentile(s, 50):.3f};p99={np.percentile(s, 99):.3f};"
             f"halls={r.n_halls_built}")


def _fig6_axes(seed=6):
    """The Fig. 6 grid: 21-point SKU-kW sweep × 2 designs, designs-major."""
    kws = np.arange(200, 2501, 115)
    designs = [hierarchy.get_design(d) for d in ("4N/3", "3+1")]
    return kws, MCAxes.product(designs=designs,
                               sku_kw=[float(k) for k in kws], seeds=(seed,))


@bench
def fig6_single_sku_sweep():
    """Single-hall single-SKU stranding vs deployment power (Fig. 6).
    The whole per-kW loop — 21 kW points × 2 designs — is ONE batched
    `mc_sweep` call over the grid."""
    kws, axes = _fig6_axes()
    t0 = time.time()
    res = sharded_mc_sweep(axes, n_trials=4, n_events=300, harvest=False,
                           single_sku_gpu=True)
    us = (time.time() - t0) / len(axes) * 1e6   # amortized per grid point
    for di, dname in enumerate(("4N/3", "3+1")):
        vals = []
        for ki in range(len(kws)):
            r = res.result(di * len(kws) + ki)
            vals.append(1.0 - r["deployed_kw"].mean() / r["ha_capacity_kw"])
        tops = ",".join(f"{k}:{v:.2f}" for k, v in
                        zip(kws.tolist(), vals) if v > 0.15)
        emit(f"fig6.{dname}", us, f"max_strand={max(vals):.3f};spikes>{{0.15}}=[{tops}]")


@bench
def fig7_placement_policies():
    """Placement-policy comparison (Fig. 7): variance-min lowest.
    All 4 policies × 2 designs run as ONE batched `mc_sweep` call."""
    dnames = ("10N/8", "8+2")
    t0 = time.time()
    res = sharded_mc_sweep(
        MCAxes.product(designs=[hierarchy.get_design(d) for d in dnames],
                       policies=range(4), seeds=(7,)),
        n_trials=8, n_events=900)
    us = (time.time() - t0) / (len(res) * 8) * 1e6   # per trial
    results = {}
    for pol in range(4):
        agg = [res.result(di * 4 + pol)["lineup_stranding"].mean()
               for di in range(len(dnames))]
        results[placement.POLICY_NAMES[pol]] = float(np.mean(agg))
        emit(f"fig7.{placement.POLICY_NAMES[pol]}", us,
             f"mean_lineup_stranding={np.mean(agg):.4f}")
    best = min(results, key=results.get)
    emit("fig7.best_policy", 0, best)


@bench
def fig9_validation():
    """Simulator self-validation (Fig. 9): the paper validates against
    proprietary Azure traces; here a synthetic ground-truth harness —
    re-simulating a held-out seed must reproduce the unused-power
    distribution (median gap < 6%, the paper's own tolerance)."""
    t0 = time.time()
    _prefetch([_req("4N/3", proj.MED, seed=s) for s in (11, 12)])
    ra = _fleet("4N/3", proj.MED, seed=11)
    rb = _fleet("4N/3", proj.MED, seed=12)
    us = (time.time() - t0) * 1e6
    med_a = np.median(ra.final_hall_stranding)
    med_b = np.median(rb.final_hall_stranding)
    gap = abs(med_a - med_b) / max(med_a, 1e-3)
    emit("fig9.selfvalidation", us, f"median_gap={gap:.3f};pass={gap < 0.3}")


@bench
def table5_projections():
    """GPU rack power trajectories (Fig. 12 / Table 5)."""
    t0 = time.time()
    rows = []
    for year in (2026, 2030, 2034):
        rows.append(f"{year}:" + "/".join(
            f"{proj.gpu_rack_kw(year, s):.0f}" for s in proj.SCENARIOS))
    emit("table5.oberon", (time.time() - t0) * 1e6, ";".join(rows))
    rows = [f"{y}:" + "/".join(f"{proj.gpu_rack_kw(y, s, True):.0f}"
                               for s in proj.SCENARIOS)
            for y in (2027, 2030, 2034)]
    emit("table5.kyber", 0, ";".join(rows))


@bench
def fig13_tail_stranding():
    """P90 site stranding over the lifecycle per design × TDP (Fig. 13)."""
    final = {}
    _prefetch([_req(d, s) for s in (proj.LOW, proj.MED, proj.HIGH)
               for d in ("4N/3", "3+1", "10N/8", "8+2")])
    for scenario in (proj.LOW, proj.MED, proj.HIGH):
        for dname in ("4N/3", "3+1", "10N/8", "8+2"):
            r = _fleet(dname, scenario)
            p90 = r.p90_stranding[-1]
            final[(dname, scenario)] = p90
            emit(f"fig13.{dname}.{scenario}", r._wall * 1e6,
                 f"p90_final={p90:.3f};halls={r.n_halls_built};"
                 f"trajectory={','.join(f'{v:.2f}' for v in r.p90_stranding[::24])}")
    sep = final[("3+1", proj.HIGH)] - final[("4N/3", proj.HIGH)]
    emit("fig13.separation_high", 0,
         f"3+1_minus_4N/3={sep:.3f};paper_claims_positive={sep > 0}")


@bench
def fig14_cost_decomposition():
    """Effective-cost decomposition: reserve vs stranding (Fig. 14)."""
    _prefetch([_req(d, proj.HIGH) for d in ("4N/3", "3+1", "10N/8", "8+2")])
    for dname in ("4N/3", "3+1", "10N/8", "8+2"):
        d = hierarchy.get_design(dname)
        r = _fleet(dname, proj.HIGH)
        reserve = cost.reserve_cost_per_mw(d) / 1e6
        strand = cost.stranding_cost_per_mw(
            d, r.n_halls_built, r.final_deployed_mw) / 1e6
        emit(f"fig14.{dname}", r._wall * 1e6,
             f"base=${r.initial_dpm/1e6:.2f}M;reserve=${reserve:.2f}M;"
             f"stranding=${strand:.2f}M;effective=${r.effective_dpm/1e6:.2f}M")


@bench
def fig15_quantization_thresholds():
    """P90 stranding vs effective per-domain deployment power (Fig. 15)."""
    d = hierarchy.get_design("3+1")
    lineup = d.lineup_kw
    _prefetch([_req("3+1", s, pod_racks=p) for p in (1, 3, 5)
               for s in (proj.MED, proj.HIGH)])
    for pod in (1, 3, 5):
        for scenario in (proj.MED, proj.HIGH):
            r = _fleet("3+1", scenario, pod_racks=pod)
            rack = proj.gpu_rack_kw(2030, scenario, pod_scale=pod > 1)
            per_dom = rack * pod
            q = lineup / per_dom
            emit(f"fig15.3+1.pod{pod}.{scenario}", r._wall * 1e6,
                 f"per_domain_kw={per_dom:.0f};C_over_P={q:.2f};"
                 f"p90={r.p90_stranding[-1]:.3f}")


@bench
def fig16_operational_levers():
    """Operational levers vs baseline (Fig. 16)."""
    _prefetch([_req("3+1", proj.HIGH, quantum=q, harvest=hv)
               for q in (10, 5) for hv in (False, True)])
    base = _fleet("3+1", proj.HIGH, quantum=10, harvest=False)
    base_cost = base.total_capex
    for name, kw in (("smaller_quanta", dict(quantum=5, harvest=False)),
                     ("harvesting", dict(quantum=10, harvest=True)),
                     ("both", dict(quantum=5, harvest=True))):
        r = _fleet("3+1", proj.HIGH, **kw)
        delta = (r.total_capex - base_cost) / base_cost
        emit(f"fig16.{name}", r._wall * 1e6,
             f"cost_delta={delta:+.3%};halls={r.n_halls_built} vs "
             f"{base.n_halls_built}")


@bench
def fig17_pareto():
    """Effective fleet cost vs TPS/W for MoE-132T (Fig. 17)."""
    m = tp.MODELS["MoE-132T"]
    _prefetch([_req(d, proj.HIGH, pod_racks=p)
               for d in ("10N/8", "8+2") for p in (1, 3, 5, 7)])
    for dname in ("10N/8", "8+2"):
        for pod in (1, 3, 5, 7):
            r = _fleet(dname, proj.HIGH, pod_racks=pod)
            d = tp.Deployment(proj.KYBER, 2028, max(pod, 1), proj.HIGH)
            tw = tp.tps_per_watt(m, d)
            emit(f"fig17.{dname}.pod{pod}", r._wall * 1e6,
                 f"eff$/MW={r.effective_dpm/1e6:.2f}M;tps_per_w={tw:.3f}")


@bench
def fig18_pod_payoff():
    """Pod payoff across model sizes (Fig. 18)."""
    _prefetch([_req(d, proj.HIGH, pod_racks=p)
               for d in ("10N/8", "8+2") for p in (1, 5)])
    for dname in ("10N/8", "8+2"):
        cache = {p: _fleet(dname, proj.HIGH, pod_racks=p)
                 for p in (1, 5)}
        base_cost = cache[1].effective_dpm
        for mname in ("MoE-0.6T", "MoE-19T", "MoE-132T", "MoE-401T"):
            m = tp.MODELS[mname]
            _, d_tps = payoff.serving_gain(m, 5, 2028)
            d_cost = cache[5].effective_dpm / base_cost - 1
            po = (1 + d_tps) / (1 + d_cost) - 1
            emit(f"fig18.{dname}.{mname}", 0,
                 f"dTPS/W={d_tps:+.3f};dCost={d_cost:+.3f};payoff={po:+.3f}")


@bench
def table2_throughput():
    """Model-suite serving throughput (Table 2 / §5.4 model)."""
    d = tp.Deployment(proj.KYBER, 2028, 1, proj.MED)
    for m in tp.MODEL_SUITE:
        t0 = time.time()
        t = float(tp.tps_request(m, d))
        us = (time.time() - t0) * 1e6
        which, _ = tp.bottleneck(m, d, "dec")
        emit(f"table2.{m.name}", us,
             f"tps={t:,.0f};tps_per_w={tp.tps_per_watt(m, d):.3f};"
             f"n_dom={tp.n_domains(m, d)};bottleneck={which}")


def _speedup_grid(scale, seeds):
    """Fresh 8-configuration (design × scenario × seed) grid shared by the
    `sweep_speedup` legs; distinct seed pairs give distinct traces so the
    bucketed jit cache, not the trace, is what carries between grids."""
    combos = [(d, s, sd) for d in ("4N/3", "3+1")
              for s in (proj.MED, proj.HIGH) for sd in seeds]
    return combos, SweepAxes.zip(
        designs=[hierarchy.get_design(d) for d, _, _ in combos],
        envs=[EnvelopeSpec(demand_scale=scale, gpu_scenario=s)
              for _, s, _ in combos],
        seeds=[sd for _, _, sd in combos])


def _sharded_probe(scale):
    """Sharded-vs-single-device leg of `sweep_speedup`: requires ≥2
    (possibly simulated) devices in THIS process.  Warms both paths on
    one grid, then times a fresh grid each way and emits the ratio.
    Traces are generated once per grid and shared by both legs, so the
    (serial, host-side) trace synthesis cost does not dilute the
    device-execution ratio."""
    import jax

    D = jax.device_count()
    if D < 2:
        emit("sweep.sharded_speedup", 0,
             f"skipped=needs>=2_devices;n_devices={D}")
        return

    def traces_for(axes):
        return [arrivals.generate_fleet_trace(e, s)
                for e, s in zip(axes.envs, axes.seeds)]

    _, warm_axes = _speedup_grid(scale, (201, 202))
    warm_traces = traces_for(warm_axes)
    sweep(warm_axes, traces=warm_traces)
    sharded_sweep(warm_axes, traces=warm_traces)

    combos, axes = _speedup_grid(scale, (203, 204))
    traces = traces_for(axes)
    t0 = time.time()
    res_1 = sweep(axes, traces=traces)
    t_single = time.time() - t0
    t0 = time.time()
    res_d = sharded_sweep(axes, traces=traces)
    t_shard = time.time() - t0

    dev = max(abs(float(res_d.final_deployed_mw[i]) -
                  float(res_1.final_deployed_mw[i]))
              / max(float(res_1.final_deployed_mw[i]), 1e-9)
              for i in range(len(combos)))
    emit("sweep.single_device", t_single / len(combos) * 1e6,
         f"n_cfg={len(combos)};wall_s={t_single:.2f}")
    emit("sweep.sharded", t_shard / len(combos) * 1e6,
         f"n_cfg={len(combos)};n_devices={D};wall_s={t_shard:.2f}")
    emit("sweep.sharded_speedup", 0,
         f"single_over_sharded={t_single / t_shard:.2f}x;"
         f"n_devices={D};max_rel_dev={dev:.2e}")


@bench
def sweep_speedup():
    """Acceptance (ISSUE 1): one jitted/vmapped sweep call evaluates an
    8-configuration (design × scenario × seed) grid; per-configuration
    outputs must agree with sequential `run_fleet` and the wall-time
    ratio is emitted.  A warm-up grid with different seeds runs first so
    both paths are measured on a FRESH grid: the bucketed sweep hits the
    jit cache, while sequential lifecycles recompile per trace shape —
    exactly the workflow the sweep engine batches.

    It also emits the sharded-vs-single-device ratio
    (`sweep.sharded_speedup`) on ≥2 devices, in this process.  A CPU
    process that sees one device re-runs the sharded leg in a CPU child
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` (host
    devices are time-sliced cores there, so the ratio measures overhead,
    not speedup — real scaling needs real devices).  One accelerator
    device gives a ``skipped=single_device`` row: the chip belongs to
    this process, and no child may need it."""
    scale = min(SCALE, 0.01)

    _, warm_axes = _speedup_grid(scale, (101, 102))
    t0 = time.time()
    sweep(warm_axes)
    t_compile = time.time() - t0

    combos, axes = _speedup_grid(scale, (103, 104))
    t0 = time.time()
    res = sweep(axes)
    t_batched = time.time() - t0
    t0 = time.time()
    seq = [run_fleet(axes.config(i)) for i in range(len(combos))]
    t_seq = time.time() - t0

    dev = max(abs(float(res.final_deployed_mw[i]) - r.final_deployed_mw)
              / max(r.final_deployed_mw, 1e-9) for i, r in enumerate(seq))
    halls_ok = all(int(res.n_halls_built[i]) == r.n_halls_built
                   for i, r in enumerate(seq))
    emit("sweep.batched", t_batched / len(combos) * 1e6,
         f"n_cfg={len(combos)};wall_s={t_batched:.2f};"
         f"compile_s={t_compile:.2f}")
    emit("sweep.sequential", t_seq / len(combos) * 1e6,
         f"wall_s={t_seq:.2f}")
    emit("sweep.speedup", 0,
         f"seq_over_batched={t_seq / t_batched:.2f}x;"
         f"max_rel_dev={dev:.2e};halls_match={halls_ok}")

    import jax
    if jax.device_count() >= 2:
        _sharded_probe(scale)
    elif jax.default_backend() == "cpu":
        # a CPU parent holds no chip, so a child pinned to the CPU may
        # force two host devices; a child that needed this process's
        # accelerator would fail or hang behind it
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=2"
                            ).strip()
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--sharded-probe",
             "--scale", str(SCALE)], env=env)
        if r.returncode != 0:
            emit("sweep.sharded_speedup", 0,
                 f"error=probe_subprocess_rc{r.returncode}")
    else:
        emit("sweep.sharded_speedup", 0,
             f"skipped=single_device;backend={jax.default_backend()}")


_LEGACY_MC_JIT = None


def _legacy_monte_carlo_fig6(design, n_trials, n_events, seed, sku_kw):
    """Pre-refactor `singlehall.monte_carlo` reference, kept verbatim as
    the sequential baseline `mc_speedup` measures against: per-trial
    host-side Python-loop trace synthesis (`sample_mixed_trace`) with
    post-hoc single-SKU in-place mutation, then one per-point jitted
    trial batch.  Returns the mean deployed kW."""
    global _LEGACY_MC_JIT
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core import placement as pl
    from repro.core.singlehall import TraceArrays, run_trial

    if _LEGACY_MC_JIT is None:
        @functools.partial(jax.jit, static_argnames=("policy", "harvest"))
        def _run(jt, init, ta, tb, keys, policy, harvest):
            return jax.vmap(lambda a, b, k: run_trial(
                jt, init, a, b, policy, k, harvest))(ta, tb, keys)
        _LEGACY_MC_JIT = _run

    topo = hierarchy.build_topology(design)
    jt = pl.jax_topology(topo)
    init = pl.init_state(topo)
    tas, tbs = [], []
    for i in range(n_trials):
        t = arrivals.sample_mixed_trace(n_events, 2028, proj.MED,
                                        seed + 7919 * i, 1.0, 1, 10)
        t.rack_kw[:] = sku_kw
        t.class_id[:] = 0
        t.is_gpu[:] = True
        tas.append(t)
        tb = arrivals.sample_mixed_trace(max(200, n_events // 3), 2028,
                                         proj.MED, seed + 7919 * i + 1,
                                         1.0, 1, 10)
        tb.rack_kw[:] = sku_kw
        tb.is_gpu[:] = True
        tbs.append(tb)
    stack = lambda ts: jax.tree.map(lambda *xs: jnp.stack(xs),
                                    *[TraceArrays.from_trace(t) for t in ts])
    ta, tb = stack(tas), stack(tbs)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_trials)
    state, _, _ = _LEGACY_MC_JIT(jt, init, ta, tb, keys,
                                 placement.DEFAULT_POLICY, False)
    return float(jax.vmap(pl.deployed_kw)(state).mean())


@bench
def mc_speedup():
    """Acceptance (ISSUE 4): the Fig. 6 grid (21 SKU-kW points × 2
    designs) evaluated as ONE batched `mc_sweep` call vs the pre-refactor
    sequential path (one `monte_carlo`-style call per grid point, each
    synthesizing its trial traces in a host-side Python loop —
    `_legacy_monte_carlo_fig6`).  A warm-up grid with a different seed
    runs first so both legs are measured compiled; the batched outputs
    are additionally cross-checked against the new per-point
    `monte_carlo` wrapper (identical generator → deviation must be 0)."""
    from repro.core.mc_sweep import mc_sweep

    kw = dict(n_trials=4, n_events=300, harvest=False, single_sku_gpu=True)
    _, warm_axes = _fig6_axes(seed=60)
    mc_sweep(warm_axes, **kw)
    for d in (warm_axes.designs[0], warm_axes.designs[-1]):
        _legacy_monte_carlo_fig6(d, 4, 300, 60, 200.0)

    kws, axes = _fig6_axes(seed=61)
    t0 = time.time()
    res = mc_sweep(axes, **kw)
    t_batched = time.time() - t0
    t0 = time.time()
    seq = [_legacy_monte_carlo_fig6(axes.designs[i], 4, 300, 61,
                                    axes.sku_kw[i])
           for i in range(len(axes))]
    t_seq = time.time() - t0

    # exactness vs the new wrapper on sampled grid points (same batched
    # generator, so the deviation must be 0), and the statistical gap of
    # the legacy RNG's derived stranding (info only)
    wrap_dev = 0.0
    for i in (0, len(kws) - 1, len(kws), len(axes) - 1):
        w = singlehall.monte_carlo(axes.designs[i], n_trials=4,
                                   n_events=300,
                                   sku_kw_override=axes.sku_kw[i],
                                   single_sku_gpu=True, harvest=False,
                                   seed=axes.seeds[i])
        wrap_dev = max(wrap_dev,
                       float(np.abs(res.result(i)["deployed_kw"]
                                    - w["deployed_kw"]).max()))
    strand = lambda dep, i: 1.0 - dep / float(res.ha_capacity_kw[i])
    stat_gap = float(np.mean([abs(strand(res.deployed_kw[i].mean(), i)
                                  - strand(seq[i], i))
                              for i in range(len(axes))]))
    emit("mc.batched", t_batched / len(axes) * 1e6,
         f"n_cfg={len(axes)};n_trials=4;wall_s={t_batched:.2f}")
    emit("mc.sequential", t_seq / len(axes) * 1e6,
         f"wall_s={t_seq:.2f};reference=pre-refactor_python-loop_gen")
    emit("mc.speedup", 0,
         f"seq_over_batched={t_seq / t_batched:.2f}x;"
         f"wrapper_dev={wrap_dev:.2e};legacy_stat_gap={stat_gap:.3f}")


@bench
def pod_sweep_speedup():
    """Acceptance (ISSUE 4): batched pod-grid sweeps through the
    split-trace scan (pods and clusters in separate per-month windows)
    vs the pre-refactor `lax.cond(is_pod, …)` + retry path
    (`legacy_pod_cond=True`), on a fresh 8-configuration
    (design × pod size × seed) grid with shared traces.  The two paths
    are exactly equivalent, so max deviation must be 0."""
    scale = min(SCALE, 0.01)

    def grid(seeds):
        combos = [(d, p, sd) for d in ("10N/8", "8+2") for p in (3, 5)
                  for sd in seeds]
        return SweepAxes.zip(
            designs=[hierarchy.get_design(d) for d, _, _ in combos],
            envs=[EnvelopeSpec(demand_scale=scale, gpu_scenario=proj.HIGH,
                               pod_racks=p, pod_scale_arch=True)
                  for _, p, _ in combos],
            seeds=[sd for *_, sd in combos])

    warm = grid((301,))
    warm_traces = [generate_fleet_trace(e, s)
                   for e, s in zip(warm.envs, warm.seeds)]
    sweep(warm, traces=warm_traces)
    sweep(warm, traces=warm_traces, legacy_pod_cond=True)

    axes = grid((302, 303))
    traces = [generate_fleet_trace(e, s)
              for e, s in zip(axes.envs, axes.seeds)]

    def timed(**kw):
        t0 = time.time()
        res = sweep(axes, traces=traces, **kw)
        return res, time.time() - t0

    # two interleaved repetitions, min per leg (1-core wall times are
    # noisy; the compiled executables are cached so reps only re-execute)
    res_split, t_split = timed()
    res_legacy, t_legacy = timed(legacy_pod_cond=True)
    t_split = min(t_split, timed()[1])
    t_legacy = min(t_legacy, timed(legacy_pod_cond=True)[1])

    dev = float(np.max(np.abs(res_split.final_deployed_mw
                              - res_legacy.final_deployed_mw)))
    halls_ok = bool(np.array_equal(res_split.n_halls_built,
                                   res_legacy.n_halls_built))
    emit("pod_sweep.split", t_split / len(axes) * 1e6,
         f"n_cfg={len(axes)};wall_s={t_split:.2f}")
    emit("pod_sweep.legacy_cond", t_legacy / len(axes) * 1e6,
         f"wall_s={t_legacy:.2f}")
    emit("pod_sweep.speedup", 0,
         f"legacy_over_split={t_legacy / t_split:.2f}x;"
         f"max_dev={dev:.2e};halls_match={halls_ok}")


@bench
def mc_pod_speedup():
    """Acceptance (ISSUE 5): single-hall pod grids through the split-pods
    fast path (pods-first trace windows + HD-compacted row scan) vs the
    legacy per-event `lax.cond(is_pod, …)` path
    (`mc_sweep(..., legacy_pod_cond=True)`) — identical pods-first traces
    either way, so the two paths are exactly equivalent (max deviation
    must be 0).  The grid covers `pod_racks ∈ {3, 5, 7}` (each pod size
    is its own `mc_sweep` call: the pod quantum is a trace-stream
    parameter), 2 designs × 2 seeds per pod size; a warm-up seed
    compiles both paths first so the timed legs measure execution."""
    from repro.core.mc_sweep import MCAxes, mc_sweep

    pods = (3, 5, 7)
    designs = [hierarchy.get_design(d) for d in ("10N/8", "8+2")]
    kw = dict(n_trials=4, n_events=240, year=2030, scenario=proj.HIGH)
    axes = MCAxes.product(designs=designs, seeds=(51, 52))

    t_split = t_legacy = 0.0
    dev, n_cfg = 0.0, 0
    for p in pods:
        # first pair compiles both paths at the exact grid shape; the
        # timed reps (min of 2, interleaved — 1-core
        # wall times are noisy) then measure execution + staging only
        rs = mc_sweep(axes, pod_racks=p, **kw)
        rl = mc_sweep(axes, pod_racks=p, legacy_pod_cond=True, **kw)
        dev = max(dev, float(np.abs(rs.deployed_kw - rl.deployed_kw).max()),
                  float(np.abs(rs.lineup_stranding
                               - rl.lineup_stranding).max()))

        def timed(**mode):
            t0 = time.time()
            mc_sweep(axes, pod_racks=p, **mode, **kw)
            return time.time() - t0

        reps = [(timed(), timed(legacy_pod_cond=True)) for _ in range(2)]
        t_split += min(r[0] for r in reps)
        t_legacy += min(r[1] for r in reps)
        n_cfg += len(axes)
    emit("mc_pod.split", t_split / n_cfg * 1e6,
         f"n_cfg={n_cfg};pods={'/'.join(map(str, pods))};"
         f"wall_s={t_split:.2f}")
    emit("mc_pod.legacy_cond", t_legacy / n_cfg * 1e6,
         f"wall_s={t_legacy:.2f}")
    emit("mc_pod.speedup", 0,
         f"legacy_over_split={t_legacy / t_split:.2f}x;"
         f"max_dev={dev:.2e}")


@bench
def placement_kernel_speedup():
    """Acceptance: the Pallas placement-feasibility kernel
    behind `use_kernel=True`.

    Always runs the equivalence leg — a pod-heavy single-hall MC grid
    through the kernel path vs the jnp path, every output column compared
    (`max_dev` must be 0; on non-TPU hosts the kernel runs in interpret
    mode).  The timed kernel-vs-jnp ratio is only meaningful where the
    compiled kernel exists, so on non-TPU backends the ratio row is
    emitted as `skipped=` (which `tools/check_speedups.py` ignores)."""
    import jax
    from repro.core.mc_sweep import MCAxes, mc_sweep

    backend = jax.default_backend()
    axes = MCAxes.zip(designs=[hierarchy.get_design("10N/8")], seeds=[9])
    kw = dict(n_trials=2, n_events=60, pod_racks=3, models=())
    t0 = time.time()
    a = mc_sweep(axes, **kw)
    b = mc_sweep(axes, use_kernel=True,
                 kernel_interpret=backend != "tpu", **kw)
    dev = max(float(np.abs(np.asarray(getattr(a, f), np.float32)
                           - np.asarray(getattr(b, f), np.float32)).max())
              for f in ("lineup_stranding", "hall_stranding", "deployed_kw",
                        "saturated", "placed_a", "placed_b"))
    emit("placement_kernel.equivalence", (time.time() - t0) * 1e6,
         f"max_dev={dev:.2e};bitwise={dev == 0.0};backend={backend}")

    if backend != "tpu":
        emit("placement_kernel.speedup", 0,
             f"skipped=non_tpu_backend;backend={backend}")
        return

    kwt = dict(n_trials=8, n_events=400, pod_racks=3, models=())
    mc_sweep(axes, **kwt)
    mc_sweep(axes, use_kernel=True, **kwt)

    def timed(**mode):
        t0 = time.time()
        mc_sweep(axes, **mode, **kwt)
        return time.time() - t0

    reps = [(timed(), timed(use_kernel=True)) for _ in range(2)]
    t_jnp = min(r[0] for r in reps)
    t_k = min(r[1] for r in reps)
    emit("placement_kernel.jnp", t_jnp / kwt["n_trials"] * 1e6,
         f"wall_s={t_jnp:.2f}")
    emit("placement_kernel.kernel", t_k / kwt["n_trials"] * 1e6,
         f"wall_s={t_k:.2f}")
    emit("placement_kernel.speedup", 0,
         f"jnp_over_kernel={t_jnp / t_k:.2f}x;max_dev={dev:.2e}")


@bench
def giant_grid():
    """Acceptance (ISSUE 8): a planet-scale configuration grid — 10⁴
    lifecycles (512 under ``--smoke``) — through the streaming-quantile
    scan (`exact_quantiles=False`) with chunked sharded dispatch.

    The grid reuses a small (scenario × seed) trace pool across all
    configurations (`traces=`; traces depend only on the envelope and
    seed) and a shortened buildout horizon, so grid SIZE — not trace
    synthesis or horizon length — is what the run exercises.  Chunked
    dispatch (`chunk_size`) bounds live memory at one chunk whatever the
    grid size; every chunk shares one compiled executable.

    Rows:
    * ``giant_grid.stream`` — configs/s throughput and peak RSS of the
      streaming chunked run.
    * ``giant_grid.equivalence`` — streaming p50/p90 vs the exact
      post-hoc reduction on a sub-grid; must stay within one histogram
      bin (1/`quantiles.DEFAULT_BINS`).
    * ``giant_grid.mem_speedup`` — per-configuration XLA temp-buffer
      ratio exact/streaming from `compiled.memory_analysis()` (a
      deterministic compiler quantity, unlike 1-core wall-time ratios;
      gated ≥ 1.0 by tools/check_speedups.py, `skipped=` where the
      backend exposes no memory analysis).  The streaming scan carries
      no ``[M, H]`` stranding history, so its temp footprint is flat in
      the horizon while the exact path's grows with it.
    """
    n_cfg = 512 if SMOKE else 10_000
    chunk = 128 if SMOKE else 512
    pool = [(sc, sd) for sc in (proj.MED, proj.HIGH)
            for sd in (41, 42, 43, 44)]
    envs_pool = [EnvelopeSpec(demand_scale=0.01, gpu_scenario=sc,
                              end_year=2028) for sc, _ in pool]
    traces_pool = [generate_fleet_trace(e, sd)
                   for e, (_, sd) in zip(envs_pool, pool)]
    dnames = ("4N/3", "3+1")
    idx = [i % len(pool) for i in range(n_cfg)]
    axes = SweepAxes.zip(
        designs=[hierarchy.get_design(dnames[i % 2]) for i in range(n_cfg)],
        envs=[envs_pool[j] for j in idx],
        seeds=[pool[j][1] for j in idx])
    traces = [traces_pool[j] for j in idx]

    t0 = time.time()
    res = sharded_sweep(axes, traces=traces, exact_quantiles=False,
                        chunk_size=chunk)
    wall = time.time() - t0
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    emit("giant_grid.stream", wall / n_cfg * 1e6,
         f"n_cfg={n_cfg};chunk={chunk};wall_s={wall:.1f};"
         f"cfg_per_s={n_cfg / wall:.0f};peak_rss_mb={rss_mb:.0f}")

    # streaming vs exact on a sub-grid covering every (design, trace)
    # combination in the big grid
    n_sub = 16
    sub = SweepAxes.zip(designs=axes.designs[:n_sub],
                        envs=axes.envs[:n_sub], seeds=axes.seeds[:n_sub])
    exact = sweep(sub, traces=traces[:n_sub])
    tol = 1.0 / qt.DEFAULT_BINS + 1e-6
    dev = 0.0
    for attr in ("p50_stranding", "p90_stranding"):
        e = np.asarray(getattr(exact, attr))
        s = np.asarray(getattr(res, attr))[:n_sub]
        assert (np.isnan(e) == np.isnan(s)).all()
        ok = ~np.isnan(e)
        dev = max(dev, float(np.abs(s[ok] - e[ok]).max()))
    emit("giant_grid.equivalence", 0,
         f"n_sub={n_sub};max_dev={dev:.2e};"
         f"bin_width={1.0 / qt.DEFAULT_BINS:.2e};pass={dev <= tol}")

    # the temp-memory probe uses a small full-horizon grid with a
    # planet-scale static hall cap: the exact path's per-config [M, H]
    # stranding/activation histories are what the streaming scan
    # removes, and their temp-buffer cost shows up in the compiled
    # program's memory analysis (the measured exact−stream delta equals
    # the history bytes; the rest of the temp footprint is shared)
    probe_hmax = 128
    probe_env = EnvelopeSpec(demand_scale=0.01, gpu_scenario=proj.HIGH)
    probe = SweepAxes.zip(
        designs=[hierarchy.get_design(d) for d in dnames],
        envs=[probe_env], seeds=[41, 42])

    def memory(exact_q):
        """The compiled program's memory analysis; None where the
        backend has none.  A compile error raises."""
        from repro.core.sweep import _prepare, _sweep_jit
        args, *_, with_pods, pod_len, hd_scan = _prepare(
            probe, probe_hmax, None)
        return _sweep_jit.lower(
            *args, harvest=True, mature_months=12, with_pods=with_pods,
            legacy_pod_cond=False, pod_scan_len=pod_len, hd_scan=hd_scan,
            use_kernel=placement.resolve_use_kernel(None),
            kernel_interpret=False, exact_quantiles=exact_q,
            quantile_bins=None).compile().memory_analysis()

    m_ex, m_st = memory(True), memory(False)
    if m_ex is None or m_st is None:
        import jax
        emit("giant_grid.mem_speedup", 0,
             f"skipped=memory_analysis_unavailable;"
             f"backend={jax.default_backend()}")
        return
    b_ex, b_st = int(m_ex.temp_size_in_bytes), int(m_st.temp_size_in_bytes)
    emit("giant_grid.mem_speedup", 0,
         f"exact_over_stream_temp={b_ex / max(b_st, 1):.2f}x;"
         f"exact_temp_mb={b_ex / 1e6:.2f};"
         f"stream_temp_mb={b_st / 1e6:.2f};"
         f"history_mb={(b_ex - b_st) / 1e6:.2f};"
         f"n_cfg={len(probe)};n_halls_max={probe_hmax}")


def _resilience_grid(n_cfg):
    """The giant_grid --smoke geometry (trace pool × 2 designs, short
    horizon) shared by the `resilience_*` legs, so the resume leg and
    the overhead leg reuse one compiled chunk executable."""
    pool = [(sc, sd) for sc in (proj.MED, proj.HIGH)
            for sd in (41, 42, 43, 44)]
    envs_pool = [EnvelopeSpec(demand_scale=0.01, gpu_scenario=sc,
                              end_year=2028) for sc, _ in pool]
    traces_pool = [generate_fleet_trace(e, sd)
                   for e, (_, sd) in zip(envs_pool, pool)]
    idx = [i % len(pool) for i in range(n_cfg)]
    axes = SweepAxes.zip(
        designs=[hierarchy.get_design(("4N/3", "3+1")[i % 2])
                 for i in range(n_cfg)],
        envs=[envs_pool[j] for j in idx],
        seeds=[pool[j][1] for j in idx])
    return axes, [traces_pool[j] for j in idx]


@bench
def resilience_overhead():
    """Acceptance (ISSUE 9): per-chunk checkpointing must cost ≤ ~10%
    over the same chunked run without durability.  Both legs go through
    `resilient_sweep` on the giant_grid --smoke geometry (so the only
    delta is the atomic write-temp→rename→fsync commit per chunk), the
    ratio row carries its own `min=0.9` floor for
    tools/check_speedups.py, and the two results must be bitwise equal
    — durability cannot change a single bit of the output."""
    import shutil
    import tempfile

    from repro.core.resilience import resilient_sweep

    n_cfg, chunk = (128, 32) if SMOKE else (512, 128)
    axes, traces = _resilience_grid(n_cfg)
    kw = dict(chunk_size=chunk, traces=traces, exact_quantiles=False)

    resilient_sweep(axes, **kw)                     # compile warm-up
    t0 = time.time()
    res_off = resilient_sweep(axes, **kw)
    t_off = time.time() - t0

    ckdir = tempfile.mkdtemp(prefix="resilience_bench_")
    try:
        t0 = time.time()
        res_on = resilient_sweep(axes, checkpoint_dir=ckdir, **kw)
        t_on = time.time() - t0
        n_steps = len([n for n in os.listdir(ckdir)
                       if n.startswith("step_")])
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)

    bitwise = all(
        np.array_equal(np.asarray(getattr(res_off, f)),
                       np.asarray(getattr(res_on, f)))
        for f in ("final_deployed_mw", "deployed_mw", "p90_stranding",
                  "n_halls_built", "total_capex"))
    assert bitwise, "checkpointing changed the sweep output"
    emit("resilience.ckpt_off", t_off / n_cfg * 1e6,
         f"n_cfg={n_cfg};chunk={chunk};wall_s={t_off:.2f}")
    emit("resilience.ckpt_on", t_on / n_cfg * 1e6,
         f"wall_s={t_on:.2f};chunks_committed={n_steps}")
    emit("resilience.overhead_speedup", 0,
         f"ckpt_off_over_on={t_off / t_on:.2f}x;min=0.9;"
         f"bitwise={bitwise}")


@bench
def resilience_resume():
    """Acceptance (ISSUE 9): kill-and-resume on the 512-configuration
    giant_grid --smoke grid — crash injected after chunk 3 commits,
    the resumed run loads the 3 committed chunks, computes the rest and
    must be BITWISE identical to the uninterrupted `sweep()` result
    (asserted here, so the CI resume-smoke leg fails loudly on any
    drift; also exercised per-boundary in tests/test_resilience.py)."""
    import shutil
    import tempfile

    from repro.core.resilience import (FaultPlan, InjectedCrash,
                                       resilient_sweep)

    n_cfg, chunk = (512, 128) if SMOKE else (1024, 256)
    axes, traces = _resilience_grid(n_cfg)
    kw = dict(chunk_size=chunk, traces=traces, exact_quantiles=False)

    ref = sweep(axes, traces=traces, exact_quantiles=False)

    ckdir = tempfile.mkdtemp(prefix="resilience_resume_")
    try:
        t0 = time.time()
        crashed = False
        try:
            resilient_sweep(axes, checkpoint_dir=ckdir,
                            fault_plan=FaultPlan(crash_after=2), **kw)
        except InjectedCrash:
            crashed = True
        assert crashed, "injected crash did not fire"
        res = resilient_sweep(axes, checkpoint_dir=ckdir, **kw)
        wall = time.time() - t0
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)

    fields = ("halls_active", "deployed_mw", "p50_stranding",
              "p90_stranding", "n_halls_built", "final_deployed_mw",
              "placed_fraction", "total_capex", "dollars_per_tps")
    bitwise = all(np.array_equal(np.asarray(getattr(res, f)),
                                 np.asarray(getattr(ref, f)))
                  for f in fields)
    assert bitwise, "resumed sweep diverged from the uninterrupted run"
    r = res.report
    assert r.chunks_resumed == 3, r
    emit("resilience.resume", wall / n_cfg * 1e6,
         f"n_cfg={n_cfg};chunk={chunk};wall_s={wall:.1f};"
         f"chunks_resumed={r.chunks_resumed};"
         f"chunks_computed={r.chunks_computed};bitwise={bitwise}")


@bench
def scenario_sweep():
    """Beyond-the-paper scenario frontier (docs/scenarios.md): baseline +
    all four scenario families (demand shocks, correlated cohorts,
    mix/LA sweeps, refresh waves) on ONE sharded sweep grid; emits
    p50/p90 stranding and effective-capex deltas per scenario."""
    base = EnvelopeSpec(demand_scale=SCALE)
    t0 = time.time()
    pts = payoff.scenario_frontier(hierarchy.get_design("3+1"),
                                   base_env=base)
    us = (time.time() - t0) / len(pts) * 1e6    # amortized per scenario
    for p in pts:
        emit(f"scenario.{p.family}.{p.label}", us,
             f"p50={p.p50_stranding:.3f};p90={p.p90_stranding:.3f};"
             f"halls={p.n_halls};dP90={p.d_p90:+.3f};"
             f"dCapex={p.d_capex:+.3%};d$/MW={p.d_dpm:+.3%}")
    worst = max(pts, key=lambda p: p.p90_stranding)
    n_fam = len({p.family for p in pts}) - 1     # minus the baseline
    emit("scenario.frontier", 0,
         f"n_scenarios={len(pts)};n_families={n_fam};"
         f"worst_p90={worst.family}:{worst.label}={worst.p90_stranding:.3f}")


@bench
def metric_stack():
    """Acceptance (ISSUE 6): the batched $/performance metric stage.

    Times ONE jitted `tps_per_watt_grid` over a deployments × models grid
    (pod sizes × TDP scenarios × the Table 2 suite — the grid the sweep
    engines' metric stage evaluates per call) against the pre-refactor
    path: one eager scalar `tps_request` per (model, deployment) pair.
    Cross-checks the grid against the scalar loop (must agree to float
    tolerance) and smokes `payoff.design_frontier` on its default
    4-design × 2-pod-quanta grid."""
    deps = [tp.Deployment(proj.KYBER, 2028, n, s)
            for s in (proj.MED, proj.HIGH) for n in (1, 3, 5, 7)]
    models = tp.MODEL_SUITE
    tp.tps_per_watt_grid(models, deps).block_until_ready()   # compile
    [float(tp.tps_per_watt(m, d)) for m in models for d in deps[:1]]

    t0 = time.time()
    grid = np.asarray(tp.tps_per_watt_grid(models, deps))
    t_batched = time.time() - t0
    t0 = time.time()
    loop = np.array([[tp.tps_per_watt(m, d) for m in models] for d in deps])
    t_loop = time.time() - t0
    dev = float(np.abs(grid / loop - 1.0).max())
    n = grid.size
    emit("metric_stack.batched", t_batched / n * 1e6,
         f"n_pairs={n};wall_s={t_batched:.3f}")
    emit("metric_stack.loop", t_loop / n * 1e6,
         f"wall_s={t_loop:.3f};reference=eager_scalar_tps_request")
    emit("metric_stack.speedup", 0,
         f"loop_over_batched={t_loop / t_batched:.2f}x;grid_dev={dev:.2e}")

    env = EnvelopeSpec(demand_scale=min(SCALE, 0.01),
                       gpu_scenario=proj.HIGH)
    t0 = time.time()
    pts = payoff.design_frontier(base_env=env,
                                 models=[tp.MODELS["MoE-132T"]])
    us = (time.time() - t0) / len(pts) * 1e6
    front = sorted((p for p in pts if not p.dominated),
                   key=lambda p: p.total_capex)
    emit("metric_stack.frontier", us,
         f"n_points={len(pts)};n_pareto={len(front)};"
         f"best={front[0].design}:pod{front[0].pod_racks}"
         f"=${front[0].dollars_per_tps:.2f}/tps")


@bench
def fig2_overview():
    """Design × workload overview (Fig. 2): TPS/W vs effective $/W."""
    _prefetch([_req(d, proj.HIGH) for d in ("4N/3", "8+2")])
    for dname in ("4N/3", "8+2"):
        r = _fleet(dname, proj.HIGH)
        for mname in ("MoE-0.6T", "MoE-132T"):
            m = tp.MODELS[mname]
            d = tp.Deployment(proj.KYBER, 2028, 1, proj.HIGH)
            emit(f"fig2.{dname}.{mname}", 0,
                 f"tps_per_w={tp.tps_per_watt(m, d):.3f};"
                 f"eff$/W={r.effective_dpm/1e6:.2f}")


def main(argv=None):
    global SCALE, SMOKE
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--scale", type=float, default=0.04)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-size giant_grid (512 configs; the CI "
                         "acceptance gate) instead of the full 10^4")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write {name: {us_per_call, derived}} for "
                         "every emitted row to PATH (machine-readable "
                         "perf trajectory; see benchmarks/README.md)")
    ap.add_argument("--sharded-probe", action="store_true",
                    help="internal: run only the multi-device leg of "
                         "sweep_speedup (expects forced host devices)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    SCALE = args.scale
    SMOKE = args.smoke
    if args.sharded_probe:
        _sharded_probe(min(SCALE, 0.01))
        return
    print("name,us_per_call,derived")
    for name, fn in REGISTRY.items():
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        fn()
        print(f"# {name} total {time.time() - t0:.1f}s", file=sys.stderr,
              flush=True)
    if args.json:
        # rows emitted by the sweep_speedup sharded-probe *subprocess*
        # appear only in its own CSV stream, not here
        with open(args.json, "w") as f:
            json.dump(_ROWS, f, indent=2, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    main()
