"""Fleet lifecycle cells: whole grids through `repro.core.sweep.sharded_sweep`.

A call is one grid: every design of the traffic × every TDP scenario ×
`replicas` seeds, one lifecycle each.  The benchmark synthesises every
configuration's trace with `repro.core.arrivals.generate_fleet_trace`
(inside the `bench.synth` span) and hands the traces to the sweep.  The
configurations of one replica share its seed, as the designs of Fig. 13
face the same arrivals.

The device work of a grid follows the events of its busiest month, which
differ from seed to seed (20 to 24 in the Fig. 13 grid at 0.02).  So
every grid of a mix has the busiest month the traffic states
(`busiest_month_events`, a count of arrivals, over all the grid's
traces): a replica's seed is the first of (run seed, call, replica,
attempt) whose traces have it, tested with the frozen generator.  The
sweep pads every grid to shapes that follow its traces, so set-up warms
every grid the window will submit (`warm_each_call`), whatever the
program's padding.

The check runs `bench.reference.fleet` over a sample of the window's
lifecycles, drawn from the run's seed, and compares the monthly outputs.
"""
from __future__ import annotations

import numpy as np

from bench.adapters.common import aggregate, derived_seed, gap_abs, gap_rel
from bench.reference import arrivals as ref_arrivals
from bench.reference import engine as ref_engine
from bench.reference import fleet as ref_fleet

POLICIES = {"random": 0, "round_robin": 1, "min_waste": 2, "var_min": 3}
SUPPORTED_ENVELOPE = ("start_year", "end_year", "demand_scale", "gpu_gw",
                      "compute_gw", "storage_gw", "growth", "nongpu_scenario",
                      "pod_racks", "pod_scale_arch", "quantum_racks",
                      "la_fraction")


def _bucket(n, q):
    return int(np.ceil(max(n, 1) / q) * q)


def hall_cap(design: ref_engine.Design, env: ref_arrivals.Envelope) -> int:
    """Halls a configuration may open: cumulative demand over the HA
    rating, with 45 % slack for stranding and churn, plus 4."""
    total_mw = (env.gpu_gw + env.compute_gw + env.storage_gw) * 1e3 \
        * env.demand_scale
    return int(np.ceil(total_mw / (design.ha_capacity_kw / 1e3) * 1.45)) + 4


class Cell:
    unit = "lifecycles"

    def __init__(self, cell, seed: int, devices, span):
        cfg, tr = cell.config, cell.traffic
        unknown = set(cfg["envelope"]) - set(SUPPORTED_ENVELOPE)
        if unknown:
            raise ValueError(f"envelope keys the reference lacks: {unknown}")
        self.seed, self.devices, self.span = seed, devices, span
        self.months = (cfg["envelope"]["end_year"]
                       - cfg["envelope"]["start_year"] + 1) * 12
        self.policy = POLICIES[cfg["policy"]]
        self.harvest = bool(cfg["harvest"])
        self.mature = int(cfg["mature_months"])
        grid = tr["grid"]
        self.design_names = list(grid["designs"])
        self.scenarios = list(grid["gpu_scenarios"])
        self.replicas = int(grid.get("replicas", 1))
        self.ref_designs = {n: ref_engine.Design(name=n, **cfg["designs"][n])
                            for n in self.design_names}
        env = dict(cfg["envelope"])
        env["growth"] = {int(k): v for k, v in env["growth"].items()}
        self.ref_envs = {s: ref_arrivals.Envelope(gpu_scenario=s, **env)
                         for s in self.scenarios}
        # (replica, scenario, design) of every configuration, in grid order
        self.layout = [(r, s, d) for r in range(self.replicas)
                       for s in self.scenarios for d in self.design_names]
        self.check_cfg = tr["check"]
        self.month_events = tr.get("busiest_month_events")
        self._picked = {}         # (call, replica) -> seed
        self.warm_each_call = True
        self.window = []          # per timed call: seeds, traces, outputs

        from repro.core import hierarchy
        from repro.core.arrivals import EnvelopeSpec
        from repro.core.sweep import SweepAxes
        self._SweepAxes = SweepAxes
        self.designs = {n: hierarchy.DesignSpec(name=n, **cfg["designs"][n])
                        for n in self.design_names}
        self.envs = {s: EnvelopeSpec(gpu_scenario=s, **cfg["envelope"])
                     for s in self.scenarios}
        for e in self.envs.values():
            e.growth = {int(k): v for k, v in e.growth.items()}

    def busiest_month(self, seed: int) -> int:
        """Arrivals in the busiest month of any of a replica's traces."""
        return max(int(np.bincount(ref_arrivals.fleet_trace(e, seed)["month"],
                                   minlength=self.months).max())
                   for e in self.ref_envs.values())

    def _seed(self, i: int, r: int) -> int:
        if (i, r) not in self._picked:
            for a in range(10000):
                s = derived_seed(self.seed, i, r, a)
                if self.month_events in (None, self.busiest_month(s)):
                    break
            else:
                raise RuntimeError(f"no seed of call {i} has a busiest "
                                   f"month of {self.month_events} events")
            self._picked[(i, r)] = s
        return self._picked[(i, r)]

    def _seeds(self, i: int):
        return [self._seed(i, r) for r in range(self.replicas)]

    def _run(self, i: int):
        from repro.core.arrivals import generate_fleet_trace
        from repro.core.sweep import sharded_sweep
        seeds = self._seeds(i)
        with self.span("bench.synth"):
            traces = [generate_fleet_trace(self.envs[s], seeds[r])
                      for r, s, _ in self.layout]
        axes = self._SweepAxes.zip(
            designs=[self.designs[d] for _, _, d in self.layout],
            envs=[self.envs[s] for _, s, _ in self.layout],
            policies=[self.policy], seeds=[seeds[r] for r, _, _ in
                                           self.layout])
        with self.span("bench.sweep"):
            res = sharded_sweep(axes, traces=traces, devices=self.devices,
                                harvest=self.harvest,
                                mature_months=self.mature)
        return seeds, traces, res

    def warm(self, i: int):
        """Run call i's grid, and pick call i + 1's seeds, so that a window
        that runs one call past the warm-up picks none inside it."""
        self._run(i)
        self._seeds(i + 1)

    def call(self, i: int) -> dict:
        seeds, traces, res = self._run(i)
        self.window.append({
            "seeds": seeds,
            "traces": [{f: np.asarray(getattr(t, f))
                        for f in ref_arrivals.FIELDS} for t in traces],
            "halls_active": np.asarray(res.halls_active),
            "deployed_mw": np.asarray(res.deployed_mw),
            "p50": np.asarray(res.p50_stranding),
            "p90": np.asarray(res.p90_stranding),
            "n_halls_built": np.asarray(res.n_halls_built),
            "placed_fraction": np.asarray(res.placed_fraction)})
        return {"units": len(self.layout),
                "events": int(sum(len(t) for t in traces))}

    # ---- correctness ----
    def sample(self):
        """(call, configuration) pairs to check: the lifecycle with the
        most events, then others drawn from the run's seed."""
        rng = np.random.default_rng([self.seed & (2 ** 63 - 1), 0xC4EC])
        pairs = [(c, k) for c in range(len(self.window))
                 for k in range(len(self.layout))]
        longest = max(pairs, key=lambda p: len(
            self.window[p[0]]["traces"][p[1]]["month"]))
        rest = [p for p in pairs if p != longest]
        n = min(int(self.check_cfg["sample"]) - 1, len(rest))
        pick = rng.choice(len(rest), size=n, replace=False)
        return [longest] + [rest[j] for j in sorted(pick)]

    def _layout_shape(self):
        caps = {(s, d): hall_cap(self.ref_designs[d], self.ref_envs[s])
                for _, s, d in self.layout}
        H = _bucket(max(caps.values()), 4)
        R = max(d.n_rows for d in self.ref_designs.values())
        X = max(d.n_lineups for d in self.ref_designs.values())
        return caps, H, R, X

    def reference(self, c: int, k: int, dt):
        """The plain reference's lifecycle of call `c`, configuration `k`,
        and whether its own trace equals the one the program was given."""
        r, s, d = self.layout[k]
        seed = self.window[c]["seeds"][r]
        tr = ref_arrivals.fleet_trace(self.ref_envs[s], seed)
        given = self.window[c]["traces"][k]
        same = all(np.array_equal(tr[f], given[f])
                   for f in ref_arrivals.FIELDS)
        caps, H, R, X = self._layout_shape()
        eng = ref_engine.Engine(
            ref_engine.Topology(self.ref_designs[d], H, R, X), dt)
        out = ref_fleet.lifecycle(eng, tr, self.months, self.policy, seed,
                                  caps[(s, d)], self.harvest, self.mature)
        return out, same

    @staticmethod
    def readings(prog: dict, ref: dict) -> dict:
        """The compared numbers of one lifecycle (program vs reference):
        the widest monthly gap of each output, and for the stranding
        quantiles and open halls also the mean monthly gap."""
        final = max(abs(ref["deployed_kw"][-1]) / 1e3, 1.0)
        halls = np.abs(np.asarray(prog["halls_active"], np.float64)
                       - ref["halls_active"])
        out = {
            "deployed_gap": gap_rel(prog["deployed_mw"],
                                    ref["deployed_kw"] / 1e3, final),
            "halls_gap": float(halls.max()),
            "halls_dev": float(halls.mean()),
            "placed_gap": abs(float(prog["placed_fraction"])
                              - ref["placed_fraction"]),
        }
        for q in ("p50", "p90"):
            d = np.abs(np.asarray(prog[q], np.float64) - ref[q])
            out[q + "_gap"] = gap_abs(prog[q], ref[q])
            out[q + "_dev"] = float(np.nanmean(d)) if np.isfinite(
                out[q + "_gap"]) else float("inf")
        return out

    def program_outputs(self, c: int, k: int) -> dict:
        w = self.window[c]
        return {f: w[f][k] for f in ("halls_active", "deployed_mw", "p50",
                                     "p90", "placed_fraction")}

    def check(self, sample=None, dt=np.float32) -> dict:
        """Each number's widest and mean reading over the sample, plus the
        count of sampled traces that differ from the frozen generator."""
        rows, mismatched = [], 0
        for c, k in (sample if sample is not None else self.sample()):
            ref, same = self.reference(c, k, dt)
            mismatched += int(not same)
            rows.append(self.readings(self.program_outputs(c, k), ref))
        out = aggregate(rows)
        out["trace_mismatch"] = float(mismatched)
        return out

    def control(self, sample) -> dict:
        """The reference in bfloat16 put in the program's place, read
        against the float32 reference on the same lifecycles."""
        rows = []
        for c, k in sample:
            ref, _ = self.reference(c, k, np.float32)
            low, _ = self.reference(c, k, _bf16())
            prog = {"halls_active": low["halls_active"],
                    "deployed_mw": low["deployed_kw"] / 1e3,
                    "p50": low["p50"], "p90": low["p90"],
                    "placed_fraction": low["placed_fraction"]}
            rows.append(self.readings(prog, ref))
        out = aggregate(rows)
        out["trace_mismatch"] = 0.0
        return out


def _bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


def make(cell, seed, devices, span):
    return Cell(cell, seed, devices, span)
