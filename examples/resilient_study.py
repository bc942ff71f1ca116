"""Resilient sweep study: checkpoint/resume + fault quarantine demo
(docs/reliability.md).

Runs a design × scenario × seed grid through `resilient_sweep` with
per-chunk checkpointing, kills it after the second chunk commits
(injected crash — stand-in for preemption / OOM-kill), resumes from the
same checkpoint directory, and verifies the resumed result is bitwise
identical to an uninterrupted run.  A second pass injects one poisoned
configuration and shows the quarantine report: only that row is lost
(NaN sentinels), every other row is bitwise unchanged.

    PYTHONPATH=src python examples/resilient_study.py [--scale 0.01]
"""
import argparse
import shutil
import tempfile
import time

import numpy as np

from repro.core import hierarchy, projections as proj
from repro.core.arrivals import EnvelopeSpec
from repro.core.resilience import (FaultPlan, InjectedCrash,
                                   resilient_sweep)
from repro.core.sweep import SweepAxes, sweep
from repro.runtime.fault import Backoff
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--chunk", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    names = ("4N/3", "3+1")
    combos = [(n, s, sd) for n in names for s in (proj.MED, proj.HIGH)
              for sd in (0, 1, 2)]
    axes = SweepAxes.zip(
        designs=[hierarchy.get_design(n) for n, _, _ in combos],
        envs=[EnvelopeSpec(demand_scale=args.scale, gpu_scenario=s,
                           end_year=2028) for _, s, _ in combos],
        seeds=[sd for *_, sd in combos])
    print(f"{len(axes)} configurations, chunk_size={args.chunk}")

    ref = sweep(axes)

    # ---- kill-and-resume -------------------------------------------------
    ckdir = tempfile.mkdtemp(prefix="resilient_study_")
    try:
        try:
            resilient_sweep(axes, chunk_size=args.chunk,
                            checkpoint_dir=ckdir,
                            fault_plan=FaultPlan(crash_after=1))
        except InjectedCrash as e:
            print(f"crashed: {e}")
        t0 = time.time()
        res = resilient_sweep(axes, chunk_size=args.chunk,
                              checkpoint_dir=ckdir)
        r = res.report
        bitwise = np.array_equal(res.final_deployed_mw,
                                 ref.final_deployed_mw)
        print(f"resumed in {time.time() - t0:.1f}s: "
              f"{r.chunks_resumed} chunks loaded, "
              f"{r.chunks_computed} recomputed, "
              f"bitwise_equal={bitwise}")
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)

    # ---- quarantine ------------------------------------------------------
    res = resilient_sweep(axes, chunk_size=args.chunk,
                          fault_plan=FaultPlan(poison=(5,)),
                          backoff=Backoff(base_s=0.0, max_retries=1))
    r = res.report
    keep = [i for i in range(len(axes)) if i not in r.quarantined_indices()]
    print(f"quarantined={list(r.quarantined_indices())} "
          f"reason={r.quarantined[0].reason} retries={r.retries}")
    print(f"other rows bitwise_equal="
          f"{np.array_equal(res.final_deployed_mw[keep], ref.final_deployed_mw[keep])}; "
          f"quarantined row is NaN="
          f"{bool(np.isnan(res.final_deployed_mw[5]))}")


if __name__ == "__main__":
    main()
