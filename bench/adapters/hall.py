"""Single-hall Monte Carlo cells: whole grids through
`repro.core.mc_sweep.sharded_mc_sweep`.

A call is one grid: the traffic's designs × placement policies, each
with `n_trials` trials of `n_events` fill events and the refill, all
configurations on the call's seed (the policies of Fig. 7 face the same
arrivals), derived from the run's seed and the call's index.  The sweep
synthesises its own trials.  With rack-scale GPUs every grid of a mix
has the same shapes, so one warm-up grid of its own serves the window;
with GPU pods the sweep sizes its pod windows from each grid's pods, so
set-up warms every grid the window will submit (`warm_each_call`).

The check runs `bench.reference.hall` over a sample of the window's
trials, drawn from the run's seed, a few from every configuration.
"""
from __future__ import annotations

import numpy as np

from bench.adapters.common import aggregate, derived_seed
from bench.reference import arrivals as ref_arrivals
from bench.reference import engine as ref_engine
from bench.reference import hall as ref_hall

POLICIES = {"random": 0, "round_robin": 1, "min_waste": 2, "var_min": 3}


class Cell:
    unit = "trials"

    def __init__(self, cell, seed: int, devices, span):
        cfg, tr = cell.config, cell.traffic
        self.seed, self.devices, self.span = seed, devices, span
        grid = tr["grid"]
        self.design_names = list(grid["designs"])
        self.policy_ids = [POLICIES[p] for p in grid["policies"]]
        self.scenario = grid["scenario"]
        self.pod_racks = int(grid["pod_racks"])
        self.T = int(grid["n_trials"])
        self.E = int(grid["n_events"])
        self.Eb = int(grid["refill_events"])
        self.year = int(cfg["year"])
        self.share = float(cfg["gpu_power_share"])
        self.quantum = int(cfg["quantum_racks"])
        self.harvest = bool(cfg["harvest"])
        self.layout = [(d, p) for d in self.design_names
                       for p in self.policy_ids]
        self.ref_designs = {n: ref_engine.Design(name=n, **cfg["designs"][n])
                            for n in self.design_names}
        self.check_cfg = tr["check"]
        self.warm_each_call = self.pod_racks > 1
        self.window = []

        from repro.core import hierarchy
        from repro.core.mc_sweep import MCAxes
        designs = {n: hierarchy.DesignSpec(name=n, **cfg["designs"][n])
                   for n in self.design_names}
        self._designs, self._MCAxes = designs, MCAxes

    def _run(self, i: int):
        from repro.core.mc_sweep import sharded_mc_sweep
        s = derived_seed(self.seed, i)
        axes = self._MCAxes.zip(
            designs=[self._designs[d] for d, _ in self.layout],
            policies=[p for _, p in self.layout], seeds=[s])
        with self.span("bench.mc_sweep"):
            res = sharded_mc_sweep(
                axes, n_trials=self.T, n_events=self.E, year=self.year,
                scenario=self.scenario, gpu_power_share=self.share,
                pod_racks=self.pod_racks, quantum_racks=self.quantum,
                harvest=self.harvest, refill_events=self.Eb,
                devices=self.devices)
        return s, res

    def warm(self, i: int):
        self._run(i)

    def call(self, i: int) -> dict:
        s, res = self._run(i)
        self.window.append({
            "seed": s,
            "deployed_kw": np.asarray(res.deployed_kw),
            "hall_stranding": np.asarray(res.hall_stranding),
            "lineup_stranding": np.asarray(res.lineup_stranding),
            "saturated": np.asarray(res.saturated),
            "placed_a": np.asarray(res.placed_a),
            "placed_b": np.asarray(res.placed_b)})
        n = len(self.layout) * self.T
        return {"units": n, "events": n * (self.E + self.Eb)}

    # ---- correctness ----
    def sample(self):
        """(call, configuration, trial) triples: `per_config` trials of
        every configuration, from calls and trials drawn from the seed."""
        rng = np.random.default_rng([self.seed & (2 ** 63 - 1), 0xC4EC])
        k = int(self.check_cfg["per_config"])
        out = []
        for b in range(len(self.layout)):
            for _ in range(k):
                out.append((int(rng.integers(len(self.window))), b,
                            int(rng.integers(self.T))))
        return out

    def reference(self, c: int, b: int, t: int, dt) -> dict:
        d, p = self.layout[b]
        s = self.window[c]["seed"]
        mk = lambda phase, n: ref_arrivals.mixed_traces(
            self.T, n, self.year, self.scenario, s, phase, self.share,
            self.pod_racks, self.quantum)
        ta, tb = mk(0, self.E), mk(1, self.Eb)
        pick = lambda tr: {f: v[t] for f, v in tr.items()}
        R = max(x.n_rows for x in self.ref_designs.values())
        X = max(x.n_lineups for x in self.ref_designs.values())
        eng = ref_engine.Engine(
            ref_engine.Topology(self.ref_designs[d], 1, R, X), dt)
        keys = ref_hall.trial_keys(s, self.T)[t]
        return ref_hall.run_trial(eng, pick(ta), pick(tb), p, keys,
                                  self.harvest)

    def readings(self, prog: dict, ref: dict, b: int) -> dict:
        cap = self.ref_designs[self.layout[b][0]].ha_capacity_kw
        placed = np.concatenate([prog["placed_a"] != ref["placed_a"],
                                 prog["placed_b"] != ref["placed_b"]])
        return {
            "deployed_gap": abs(float(prog["deployed_kw"])
                                - ref["deployed_kw"]) / cap,
            "stranding_gap": max(
                abs(float(prog["hall_stranding"]) - ref["hall_stranding"]),
                float(np.max(np.abs(
                    np.asarray(prog["lineup_stranding"], np.float64)
                    - ref["lineup_stranding"])))),
            "placed_gap": float(placed.mean()),
            "saturated_gap": float(bool(prog["saturated"])
                                   != ref["saturated"]),
        }

    def program_outputs(self, c: int, b: int, t: int) -> dict:
        w = self.window[c]
        return {f: w[f][b, t] for f in ("deployed_kw", "hall_stranding",
                                        "lineup_stranding", "saturated",
                                        "placed_a", "placed_b")}

    def check(self, sample=None, dt=np.float32) -> dict:
        """Each number's widest and mean reading over the sample."""
        return aggregate([
            self.readings(self.program_outputs(c, b, t),
                          self.reference(c, b, t, dt), b)
            for c, b, t in (sample if sample is not None
                            else self.sample())])

    def control(self, sample) -> dict:
        import ml_dtypes
        return aggregate([
            self.readings(self.reference(c, b, t, ml_dtypes.bfloat16),
                          self.reference(c, b, t, np.float32), b)
            for c, b, t in sample])


def make(cell, seed, devices, span):
    return Cell(cell, seed, devices, span)
