"""Frozen copy of the arrival generators (paper §5.1–5.2, App. B).

The benchmark's yardstick for trace synthesis: `fleet_trace` and
`mixed_traces` draw the same numbers from the same `numpy` streams as
the simulator's generators did when the benchmark was written, so the
traces a run feeds the simulator, and the trials it synthesises itself,
can be compared with these bit for bit.  Nothing here imports the
simulator.  Powers are kW, months count from `start_year`.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

CLASS_GPU, CLASS_COMPUTE, CLASS_STORAGE = 0, 1, 2
TIER_HA, TIER_LA = 0, 1
LOW, MED, HIGH = "low", "med", "high"
TDP_GROWTH = {LOW: 0.05, MED: 0.125, HIGH: 0.20}

# Eq. 3 SKU clusters (alpha, probability) of non-GPU racks.
COMPUTE_SKUS = ((0.45, 0.25), (0.65, 0.35), (0.85, 0.25), (1.00, 0.15))
STORAGE_SKUS = ((0.60, 0.30), (0.80, 0.50), (1.00, 0.20))
# Lifetimes N(mu, sd) in years and harvest ceilings (paper §5.2).
LIFETIME = {CLASS_GPU: (5.0, 0.5), CLASS_COMPUTE: (7.0, 1.0),
            CLASS_STORAGE: (7.0, 1.0)}
HARVEST_FRAC = {CLASS_GPU: 0.10, CLASS_COMPUTE: 0.15, CLASS_STORAGE: 0.15}
_Q = np.array([0.8, 0.95, 1.05, 1.2])
SEASONALITY = np.repeat(_Q / _Q.sum(), 3) / 3.0

# Table 5 rack power (kW) by year: (low, med, high).
TABLE5_OBERON = {
    2025: (157, 180, 203), 2026: (160, 178, 196), 2027: (166, 197, 226),
    2028: (173, 218, 262), 2029: (180, 243, 341), 2030: (188, 271, 434),
    2031: (197, 303, 545), 2032: (205, 339, 677), 2033: (214, 379, 836),
    2034: (224, 425, 1025)}
TABLE5_KYBER = {
    2027: (515, 600, 685), 2028: (515, 600, 685), 2029: (539, 671, 815),
    2030: (564, 750, 971), 2031: (591, 839, 1158), 2032: (619, 940, 1382),
    2033: (648, 1053, 1652), 2034: (679, 1180, 1975)}
# Overhead kW of the rack architecture in service (Table 3).
_OVHD = {"kyber": 35.0, "oberon": 30.0}
_COMPUTE_2034 = {LOW: 26.0, MED: 38.0, HIGH: 52.0}
_STORAGE_2034 = {LOW: 18.0, MED: 22.0, HIGH: 26.0}


def gpu_rack_kw(year: int, scenario: str, pod_scale: bool) -> float:
    """Eq. 23 via Table 5; Kyber pods from 2027 when `pod_scale`.
    Years past 2034 grow the package share by Eq. 19."""
    if year < 2026:
        raise ValueError("the benchmark's horizons start in 2026")
    kyber = pod_scale and year >= 2027
    table = TABLE5_KYBER if kyber else TABLE5_OBERON
    idx = {LOW: 0, MED: 1, HIGH: 2}[scenario]
    y = min(year, max(table))
    base = float(table[y][idx])
    if year <= max(table):
        return base
    ovhd = _OVHD["kyber" if kyber else "oberon"]
    return (base - ovhd) * (1 + TDP_GROWTH[scenario]) ** (year - max(table)) \
        + ovhd


def compute_rack_kw(year: int, scenario: str) -> float:
    g = (_COMPUTE_2034[scenario] / 20.0) ** (1.0 / 9.0) - 1.0
    return 20.0 * (1 + g) ** (year - 2025)


def storage_rack_kw(year: int, scenario: str) -> float:
    g = (_STORAGE_2034[scenario] / 15.0) ** (1.0 / 9.0) - 1.0
    return 15.0 * (1 + g) ** (year - 2025)


FIELDS = ("month", "class_id", "rack_kw", "n_racks", "is_gpu", "is_pod",
          "tier", "lifetime_m", "harvest_frac")
DTYPES = {"month": np.int32, "class_id": np.int32, "rack_kw": np.float32,
          "n_racks": np.int32, "is_gpu": bool, "is_pod": bool,
          "tier": np.int32, "lifetime_m": np.int32,
          "harvest_frac": np.float32}


@dataclass
class Envelope:
    """The Table 1 demand envelope of a fleet configuration file."""
    start_year: int
    end_year: int
    demand_scale: float
    gpu_gw: float
    compute_gw: float
    storage_gw: float
    growth: dict              # class id -> annual growth factor
    gpu_scenario: str
    nongpu_scenario: str
    pod_racks: int
    pod_scale_arch: bool
    quantum_racks: int
    la_fraction: float

    @property
    def n_months(self) -> int:
        return (self.end_year - self.start_year + 1) * 12

    def annual_targets_kw(self, class_id: int) -> np.ndarray:
        total_gw = {CLASS_GPU: self.gpu_gw, CLASS_COMPUTE: self.compute_gw,
                    CLASS_STORAGE: self.storage_gw}[class_id]
        years = np.arange(self.start_year, self.end_year + 1)
        w = self.growth[class_id] ** np.arange(len(years))
        return total_gw * 1e6 * self.demand_scale * w / w.sum()


def _rack_kw(env: Envelope, class_id: int, year: int, rng) -> float:
    if class_id == CLASS_GPU:
        return gpu_rack_kw(year, env.gpu_scenario,
                           env.pod_scale_arch or env.pod_racks > 1)
    if class_id == CLASS_COMPUTE:
        pmax, skus = compute_rack_kw(year, env.nongpu_scenario), COMPUTE_SKUS
    else:
        pmax, skus = storage_rack_kw(year, env.nongpu_scenario), STORAGE_SKUS
    alphas = np.array([a for a, _ in skus])
    probs = np.array([p for _, p in skus])
    return float(pmax * rng.choice(alphas, p=probs))


def fleet_trace(env: Envelope, seed: int) -> dict:
    """One lifecycle's deployment events, sorted by month (stable): per
    class, monthly budgets (annual target × seasonality, with the
    over-spend carried into the next month) spent in whole events."""
    rng = np.random.default_rng(seed)
    years = np.arange(env.start_year, env.end_year + 1)
    recs = {f: [] for f in FIELDS}
    for class_id in (CLASS_GPU, CLASS_COMPUTE, CLASS_STORAGE):
        targets = env.annual_targets_kw(class_id)
        carry = 0.0
        for yi, year in enumerate(years):
            for mo in range(12):
                budget = targets[yi] * SEASONALITY[mo] * 1.0 + carry
                spent = 0.0
                while spent < budget:
                    kw = _rack_kw(env, class_id, int(year), rng)
                    if class_id == CLASS_GPU:
                        n = env.pod_racks if env.pod_racks > 1 else 1
                        is_pod = env.pod_racks > 1
                    else:
                        n, is_pod = env.quantum_racks, False
                    mu, sd = LIFETIME[class_id]
                    life = max(12, int(round(rng.normal(mu, sd) * 12)))
                    tier = TIER_LA if rng.random() < env.la_fraction \
                        else TIER_HA
                    for f, v in (("month", yi * 12 + mo),
                                 ("class_id", class_id), ("rack_kw", kw),
                                 ("n_racks", n),
                                 ("is_gpu", class_id == CLASS_GPU),
                                 ("is_pod", is_pod), ("tier", tier),
                                 ("lifetime_m", life),
                                 ("harvest_frac", HARVEST_FRAC[class_id])):
                        recs[f].append(v)
                    spent += kw * n
                carry = budget - spent
    t = {f: np.asarray(v).astype(DTYPES[f]) for f, v in recs.items()}
    order = np.argsort(t["month"], kind="stable")
    return {f: v[order] for f, v in t.items()}


def _class_probs(rng, year, scenario, gpu_power_share, pod_racks,
                 quantum_racks):
    gpu_n = pod_racks if pod_racks > 1 else 1
    gpu_kw = gpu_rack_kw(year, scenario, pod_racks > 1)
    shares = (gpu_power_share, (1 - gpu_power_share) * 0.7,
              (1 - gpu_power_share) * 0.3)
    mean_kw = [gpu_kw * gpu_n]
    for pmax, skus in ((compute_rack_kw(year, scenario), COMPUTE_SKUS),
                       (storage_rack_kw(year, scenario), STORAGE_SKUS)):
        alphas = np.array([a for a, _ in skus])
        probs = np.array([p for _, p in skus])
        mean_kw.append((pmax * rng.choice(alphas, size=64, p=probs)).mean()
                       * quantum_racks)
    p = np.array([s / k for s, k in zip(shares, mean_kw)])
    return p / p.sum(), gpu_kw, gpu_n


def _mixed_rng(seed: int, phase: int):
    salt = ([int(seed), 0x6D63] if phase == 0
            else [int(seed), int(phase), 0x6D63])
    return np.random.default_rng(salt)


def mixed_traces(n_trials, n_events, year, scenario, seed, phase,
                 gpu_power_share, pod_racks, quantum_racks,
                 la_fraction=0.0) -> dict:
    """[T, E] steady-state trial traces of the single-hall Monte Carlo
    (all events at month 0; pods first within a trial when pods exist)."""
    rng = _mixed_rng(seed, phase)
    T, E = int(n_trials), int(n_events)
    p, gpu_kw, gpu_n = _class_probs(rng, year, scenario, gpu_power_share,
                                    pod_racks, quantum_racks)
    cid = rng.choice(np.array([0, 1, 2], np.int32), size=(T, E),
                     p=p).astype(np.int32)
    is_gpu = cid == CLASS_GPU

    def sku_kw(pmax, skus):
        alphas = np.array([a for a, _ in skus])
        probs = np.array([q for _, q in skus])
        return pmax * rng.choice(alphas, size=(T, E), p=probs)

    rack_kw = np.where(
        is_gpu, gpu_kw,
        np.where(cid == CLASS_COMPUTE,
                 sku_kw(compute_rack_kw(year, scenario), COMPUTE_SKUS),
                 sku_kw(storage_rack_kw(year, scenario), STORAGE_SKUS)))
    tier = np.where(rng.random((T, E)) < la_fraction, TIER_LA, TIER_HA)
    mu = np.array([LIFETIME[c][0] for c in range(3)])[cid]
    sd = np.array([LIFETIME[c][1] for c in range(3)])[cid]
    lifetime_m = np.maximum(12, np.round(rng.normal(mu, sd) * 12.0))
    if pod_racks > 1:
        order = np.argsort(~is_gpu, axis=1, kind="stable")
        take = lambda a: np.take_along_axis(a, order, axis=1)
        cid, rack_kw, tier, lifetime_m = map(
            take, (cid, rack_kw, tier, lifetime_m))
        is_gpu = cid == CLASS_GPU
    return {
        "month": np.zeros((T, E), np.int32),
        "class_id": cid,
        "rack_kw": rack_kw.astype(np.float32),
        "n_racks": np.where(is_gpu, gpu_n, quantum_racks).astype(np.int32),
        "is_gpu": is_gpu,
        "is_pod": is_gpu & (pod_racks > 1),
        "tier": tier.astype(np.int32),
        "lifetime_m": lifetime_m.astype(np.int32),
        "harvest_frac": np.array([HARVEST_FRAC[c] for c in range(3)]
                                 )[cid].astype(np.float32),
    }
