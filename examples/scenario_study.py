"""Beyond-the-paper scenario frontier (docs/scenarios.md).

Stresses one reference design across all four scenario families —
demand shocks, correlated-lifetime cohorts, workload-mix / LA-share
sweeps, decommission-wave refresh cycles — plus the paper baseline, as
ONE batched sweep call (device-sharded on a multi-device host), and
prints per-scenario stranding and effective-capex deltas.

    PYTHONPATH=src python examples/scenario_study.py --scale 0.01
    PYTHONPATH=src python examples/scenario_study.py --family shock
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
        PYTHONPATH=src python examples/scenario_study.py --scale 0.01
"""
import argparse
import time

import jax

from repro.core import hierarchy, payoff, scenarios as sc
from repro.core.arrivals import EnvelopeSpec
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.01,
                    help="EnvelopeSpec.demand_scale (1.0 = full 10 GW)")
    ap.add_argument("--design", default="3+1",
                    choices=("4N/3", "3+1", "10N/8", "8+2"))
    ap.add_argument("--family", default="all",
                    choices=("all",) + sc.FAMILIES,
                    help="restrict to one scenario family")
    ap.add_argument("--seeds", nargs="+", type=int, default=[0])
    args = ap.parse_args()
    enable_compile_cache()

    base = EnvelopeSpec(demand_scale=args.scale)
    families = sc.all_families(base)
    if args.family != "all":
        families = {args.family: families[args.family]}

    t0 = time.time()
    pts = payoff.scenario_frontier(hierarchy.get_design(args.design),
                                   base_env=base, seeds=tuple(args.seeds),
                                   families=families)
    wall = time.time() - t0

    print(f"{'family':8s} {'scenario':16s} {'seed':>4s} {'halls':>5s} "
          f"{'deploy':>7s} {'P50str':>7s} {'P90str':>7s} {'dP90':>7s} "
          f"{'dCapex':>7s} {'d$/MW':>7s}")
    last_family = None
    for p in pts:
        if p.family != last_family and last_family is not None:
            print()
        last_family = p.family
        print(f"{p.family:8s} {p.label:16s} {p.seed:4d} {p.n_halls:5d} "
              f"{p.deployed_mw:6.0f}M {p.p50_stranding:6.1%} "
              f"{p.p90_stranding:6.1%} {p.d_p90:+6.1%} {p.d_capex:+6.1%} "
              f"{p.d_dpm:+6.1%}")
    print(f"# {len(pts)} scenarios in one sweep call over "
          f"{jax.device_count()} device(s), {wall:.1f}s wall")


if __name__ == "__main__":
    main()
