"""Fleet lifecycle study (paper §6.2–6.3, Figs. 13–15).

Sweeps the four reference designs across GPU TDP scenarios as ONE
batched sweep call (design × scenario vmapped lifecycle) and prints the
lifecycle metrics that separate designs which look identical at
commissioning.  Use --scale 1.0 for the full 10 GW study (hours).

On a multi-device host the configuration grid is sharded across all
visible devices (`sharded_sweep`); on one device it runs as a plain
single-device sweep.  To simulate N CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
        PYTHONPATH=src python examples/fleet_study.py

    PYTHONPATH=src python examples/fleet_study.py [--scale 0.03]
"""
import argparse
import time

import jax

from repro.core import hierarchy, projections as proj
from repro.core.arrivals import EnvelopeSpec
from repro.core.sweep import SweepAxes, sharded_sweep
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.03)
    ap.add_argument("--scenarios", nargs="+",
                    default=[proj.LOW, proj.MED, proj.HIGH])
    args = ap.parse_args()
    enable_compile_cache()

    names = ("4N/3", "3+1", "10N/8", "8+2")
    combos = [(s, n) for s in args.scenarios for n in names]
    axes = SweepAxes.zip(
        designs=[hierarchy.get_design(n) for _, n in combos],
        envs=[EnvelopeSpec(demand_scale=args.scale, gpu_scenario=s)
              for s, _ in combos])
    t0 = time.time()
    res = sharded_sweep(axes)
    wall = time.time() - t0

    print(f"{'design':8s} {'tdp':5s} {'halls':>6s} {'deployed':>9s} "
          f"{'P90str':>7s} {'init$/MW':>9s} {'eff$/MW':>9s} {'gap':>6s}")
    for i, (scenario, name) in enumerate(combos):
        gap = res.effective_dpm[i] / res.initial_dpm[i] - 1
        print(f"{name:8s} {scenario:5s} {res.n_halls_built[i]:6d} "
              f"{res.final_deployed_mw[i]:8.0f}M "
              f"{res.p90_stranding[i, -1]:6.1%} "
              f"{res.initial_dpm[i]/1e6:8.2f}M "
              f"{res.effective_dpm[i]/1e6:8.2f}M {gap:6.1%}")
    print(f"# {len(combos)} configurations in one sweep call over "
          f"{jax.device_count()} device(s), {wall:.1f}s wall")


if __name__ == "__main__":
    main()
