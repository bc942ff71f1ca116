"""Design frontier: delivered tokens/s vs. effective capex (paper §6.6).

Evaluates every power-delivery design × pod placement quantum on ONE
batched sweep call (device-sharded on a multi-device host), prices each
configuration against the Table 2 model suite via the sweep engine's
metric stage, and prints the Pareto frontier — the paper's argument that
the planning objective is $/performance, not installed MW, in one table.

    PYTHONPATH=src python examples/frontier_study.py --scale 0.01
    PYTHONPATH=src python examples/frontier_study.py --model MoE-401T
    PYTHONPATH=src python examples/frontier_study.py --pods 1 3 5 7
    PYTHONPATH=src python examples/frontier_study.py --plot frontier.png

The --plot figure (delivered TPS vs. capex, frontier highlighted) needs
matplotlib; without it the flag degrades gracefully to the table.
"""
import argparse
import time

import jax

from repro.core import payoff, throughput as tp
from repro.core.arrivals import EnvelopeSpec
from repro.runtime.compile_cache import enable_compile_cache


def plot(pts, model, path):
    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except ImportError:
        print(f"# matplotlib unavailable, skipping {path}")
        return
    fig, ax = plt.subplots(figsize=(6, 4))
    dom = [p for p in pts if p.dominated]
    front = sorted((p for p in pts if not p.dominated),
                   key=lambda p: p.total_capex)
    ax.scatter([p.total_capex / 1e9 for p in dom],
               [p.delivered_tps / 1e6 for p in dom],
               c="lightgray", label="dominated")
    ax.plot([p.total_capex / 1e9 for p in front],
            [p.delivered_tps / 1e6 for p in front],
            "o-", c="tab:blue", label="Pareto frontier")
    for p in pts:
        ax.annotate(f"{p.design} p{p.pod_racks}",
                    (p.total_capex / 1e9, p.delivered_tps / 1e6),
                    fontsize=7, xytext=(3, 3), textcoords="offset points")
    ax.set_xlabel("effective capex [$B]")
    ax.set_ylabel(f"delivered tokens/s [M], {model}")
    ax.legend()
    fig.tight_layout()
    fig.savefig(path, dpi=150)
    print(f"# wrote {path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.01,
                    help="EnvelopeSpec.demand_scale (1.0 = full 10 GW)")
    ap.add_argument("--pods", nargs="+", type=int, default=[1, 5],
                    help="pod placement quanta (racks)")
    ap.add_argument("--model", default="MoE-132T",
                    choices=sorted(tp.MODELS),
                    help="Table 2 model the frontier table reports")
    ap.add_argument("--seeds", nargs="+", type=int, default=[0])
    ap.add_argument("--plot", default=None, metavar="PNG",
                    help="write the frontier figure (needs matplotlib)")
    args = ap.parse_args()
    enable_compile_cache()

    env = EnvelopeSpec(demand_scale=args.scale, gpu_scenario="high")
    t0 = time.time()
    pts = payoff.design_frontier(base_env=env, pod_sizes=tuple(args.pods),
                                 models=[tp.MODELS[args.model]],
                                 seeds=tuple(args.seeds))
    wall = time.time() - t0

    print(f"{'design':7s} {'pods':>4s} {'seed':>4s} {'halls':>5s} "
          f"{'deploy':>7s} {'P90str':>7s} {'TPS':>9s} {'TPS/MWbuilt':>11s} "
          f"{'capex':>7s} {'$/TPS':>8s}  frontier")
    for p in sorted(pts, key=lambda q: (q.dominated, q.total_capex)):
        print(f"{p.design:7s} {p.pod_racks:4d} {p.seed:4d} {p.n_halls:5d} "
              f"{p.deployed_mw:6.0f}M {p.p90_stranding:6.1%} "
              f"{p.delivered_tps:9.2e} {p.tps_per_provisioned_w * 1e6:11.0f} "
              f"{p.total_capex / 1e9:6.2f}B {p.dollars_per_tps:8.2f}"
              f"  {'-' if p.dominated else '*'}")
    print(f"# {len(pts)} configs ({args.model}) in one sweep call over "
          f"{jax.device_count()} device(s), {wall:.1f}s wall")
    if args.plot:
        plot(pts, args.model, args.plot)


if __name__ == "__main__":
    main()
