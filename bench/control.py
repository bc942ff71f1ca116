#!/usr/bin/env python3
"""Readings that set the limits of `correct`: the program's and the control's.

    python bench/control.py --workload hall.fig7 --seeds 1 2 3 ... \
        --control-seeds 1 2 3

For each seed, one grid call of the cell's timed path at its own size,
and the numbers a run compares, over a sample of that call drawn as a
run draws it: the program against the float32 reference (the lower
readings), and, for the control seeds, the reference computed in
bfloat16 put in the program's place (the upper readings).  One JSON
line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.run import tpu_devices  # noqa: E402
from bench.spec import Spec  # noqa: E402


def readings(root, workload, seeds, control_seeds, find_devices=tpu_devices,
             out=print):
    """One row per seed: the program's readings over the sample of one
    call, and for the control seeds the control's on the same sample."""
    spec = Spec(root)
    cell = spec.cell(workload)
    devices = find_devices(cell.chips)
    if devices is None:
        return None
    adapter = spec.adapter(cell)
    rows = []
    for seed in seeds:
        sim = adapter.make(cell, seed, devices,
                           lambda name: contextlib.nullcontext())
        t = time.perf_counter()
        sim.call(0)
        call_s = time.perf_counter() - t
        sample = sim.sample()
        row = {"seed": seed, "call_s": call_s,
               "program": sim.check(sample)}
        if seed in control_seeds:
            row["control"] = sim.control(sample)
        rows.append(row)
        out(json.dumps(row))
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    a = p.parse_args(argv)
    rows = readings(ROOT, a.workload, a.seeds, set(a.control_seeds),
                    out=lambda s: print(s, flush=True))
    return 3 if rows is None else 0


if __name__ == "__main__":
    sys.exit(main())
