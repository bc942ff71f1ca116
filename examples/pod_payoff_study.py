"""Pod payoff study (paper §6.5, Figs. 17–18): when do larger GPU pods'
serving gains survive their deployability cost?

    PYTHONPATH=src python examples/pod_payoff_study.py
"""
from repro.core import hierarchy, payoff, throughput as tp
from repro.core.arrivals import EnvelopeSpec
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    env = EnvelopeSpec(demand_scale=0.03, gpu_scenario="high",
                       pod_scale_arch=True)
    models = [tp.MODELS[n] for n in
              ("MoE-0.6T", "MoE-19T", "MoE-132T", "MoE-401T")]
    for dname in ("10N/8", "8+2"):
        print(f"== {dname} ==")
        pts = payoff.pod_payoff_study(hierarchy.get_design(dname), models,
                                      pod_sizes=(1, 3, 5, 7), env=env)
        print(f"{'model':10s} {'pod':>4s} {'dTPS/W':>8s} {'dCost':>8s} "
              f"{'payoff':>8s}")
        for p in pts:
            if p.pod_racks == 1:
                continue
            print(f"{p.model:10s} {p.pod_racks:4d} {p.d_tps_per_watt:+7.1%} "
                  f"{p.d_cost:+7.1%} {p.payoff:+7.1%}")


if __name__ == "__main__":
    main()
