"""A copy of the benchmark's data at sizes a CPU test can run.

`tiny_root(dir)` copies `BENCHMARK.json` and `bench/` under `dir` and
shrinks the grids: the fleet to `demand_scale` 0.002 (busiest month 3
arrivals), the halls to 2 trials of 60 + 20 events.  It adds, as files and entries only, the cell
`hall.fig7-pod7-high` (the Fig. 7 policies on 10N/8 with pods of 7
racks under high TDP), which keeps the hall adapter's pod path tested.
`run(root, args)` drives `bench/run.py` there with the CPU standing in
for the chip.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _edit(path, fn):
    with open(path) as f:
        data = json.load(f)
    fn(data)
    with open(path, "w") as f:
        json.dump(data, f)


def tiny_root(dst: str) -> str:
    os.makedirs(dst, exist_ok=True)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"),
                    dirs_exist_ok=True)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    b = os.path.join(dst, "bench")
    _edit(os.path.join(b, "configs", "fig13_fleet.json"),
          lambda c: c["envelope"].update(demand_scale=0.002))

    def fleet(t):
        t["busiest_month_events"] = 3      # the commonest at 0.002
        t["check"]["sample"] = 1000

    _edit(os.path.join(b, "traffic", "fig13.json"), fleet)
    with open(os.path.join(b, "traffic", "fig7.json")) as f:
        pods = json.load(f)
    pods["grid"].update(designs=["10N/8"], scenario="high", pod_racks=7)
    with open(os.path.join(b, "traffic", "fig7-pod7-high.json"), "w") as f:
        json.dump(pods, f)

    def hall(t):
        t["grid"].update(n_trials=2, n_events=60, refill_events=20)
        t["check"]["per_config"] = 4

    for n in ("fig7", "fig7-pod7-high"):
        _edit(os.path.join(b, "traffic", n + ".json"), hall)

    def pod_cell(d):
        d["workloads"].append({"name": "hall.fig7-pod7-high",
                               "config": "fig7_hall",
                               "traffic": "fig7-pod7-high", "chips": 1,
                               "why": "pods of 7 racks"})
        for m in d["end_to_end"] + d["per_layer"]:
            if "hall.fig7" in m.get("workloads", ()):
                m["workloads"].append("hall.fig7-pod7-high")

    _edit(os.path.join(dst, "BENCHMARK.json"), pod_cell)
    return dst


def run(root: str, args) -> tuple:
    """(exit code, the result line as a dict or None, stdout) of one run
    with the CPU in the chip's place."""
    import jax
    from bench import run as bench_run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(args, root=root,
                            find_devices=lambda n: jax.devices()[:n])
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith(
        "{") else None
    return rc, result, out.getvalue()
