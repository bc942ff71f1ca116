"""Quickstart: the paper's pipeline end-to-end in ~a minute.

Evaluates two power-delivery designs (4N/3 distributed vs 3+1 block) the
three ways the paper does: static commissioning metrics, single-hall
Monte Carlo, and a (reduced-scale) fleet lifecycle — then prices an
MoE serving deployment with the throughput model.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import cost, hierarchy, projections as proj
from repro.core import throughput as tp
from repro.core.arrivals import EnvelopeSpec
from repro.core.fleet import FleetConfig, run_fleet
from repro.core.mc_sweep import MCAxes, mc_sweep
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    d43, d31 = hierarchy.design_4n3(), hierarchy.design_3p1()

    print("== static commissioning metrics (paper §3.1) ==")
    for d in (d43, d31):
        print(f"  {d.name}: HA capacity {d.ha_capacity_kw/1e3:.1f} MW, "
              f"initial ${cost.initial_dollars_per_mw(d)/1e6:.2f}M/MW")

    print("\n== single-hall Monte Carlo (paper §4.4, Fig. 5a) ==")
    # both designs' trials as one batched mc_sweep call (the
    # single-configuration equivalent is singlehall.monte_carlo)
    res = mc_sweep(MCAxes.zip(designs=[d43, d31]), n_trials=8,
                   n_events=400, year=2030, scenario=proj.HIGH)
    for i, d in enumerate((d43, d31)):
        s = res.result(i)["lineup_stranding"]
        print(f"  {d.name}: median UPS stranding {np.median(s):.1%}, "
              f"P99 {np.percentile(s, 99):.1%}")

    print("\n== fleet lifecycle, 200 MW demand (Fig. 5b/13 reduced) ==")
    env = EnvelopeSpec(demand_scale=0.02, gpu_scenario=proj.HIGH)
    for d in (d43, d31):
        r = run_fleet(FleetConfig(d, env, seed=0))
        print(f"  {d.name}: {r.n_halls_built} halls, "
              f"P90 stranding {r.p90_stranding[-1]:.1%}, "
              f"effective ${r.effective_dpm/1e6:.2f}M/MW "
              f"(initial ${r.initial_dpm/1e6:.2f}M)")

    print("\n== MoE serving economics (paper §5.4/6.5) ==")
    m = tp.MODELS["MoE-132T"]
    for pod in (1, 4):
        d = tp.Deployment(proj.KYBER, 2028, pod, proj.HIGH)
        print(f"  {m.name} on {max(pod, d.n_units(m))}-rack "
              f"{'pod' if pod > 1 else 'rack-scale'}: "
              f"{tp.tps_request(m, d):,.0f} tok/s, "
              f"{tp.tps_per_watt(m, d):.2f} tok/s/W "
              f"(f_IB={tp.f_ib(m, d):.2f})")


if __name__ == "__main__":
    main()
