"""Plain reference of one fleet lifecycle (paper §4.4, Fig. 8 pipeline).

Month by month over the horizon: decommission racks at end of life
(what harvest left of them), harvest racks one year after deployment
(their class ceiling), then place the month's arrivals in trace order.
A placement prefers the halls already open (a fixed score bias on the
rows of the next hall) and may open the next hall up to the hall cap;
an arrival that fits nowhere still opens it.  GPU pods try the open
halls whole, then retry whole with the next hall.  Monthly outputs are
the open halls, the deployed power and the p50/p90 stranding of the
halls older than `mature_months` (all open halls while none is).
"""
from __future__ import annotations

import numpy as np

from .engine import RANDOM, Engine, random_rows

NEW_HALL_BIAS = 1e6


def _month_draws(seed, m, n_events, max_racks, n_rows):
    """Random-policy draws [n_events, 1 + max_racks, R] of month `m`."""
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        mk = jax.random.fold_in(jax.random.PRNGKey(np.int32(seed) + 1), m)
        ev = jax.vmap(lambda i: jax.random.fold_in(mk, i))(
            np.arange(n_events))
        racks = jax.vmap(lambda k: jax.vmap(
            lambda r: jax.random.fold_in(k, r))(np.arange(max_racks)))(ev)
        keys = np.concatenate([np.asarray(ev)[:, None],
                               np.asarray(racks)], axis=1)
    return random_rows(keys, n_rows)


def _percentiles(x, mask, qs):
    v = np.sort(x[mask].astype(np.float64))
    if not len(v):
        return [np.nan for _ in qs]
    out = []
    for q in qs:
        pos = q / 100.0 * (len(v) - 1)
        lo, hi = int(np.floor(pos)), int(np.ceil(pos))
        out.append(v[lo] * (1 - (pos - lo)) + v[hi] * (pos - lo))
    return out


def lifecycle(eng: Engine, trace: dict, n_months: int, policy: int,
              seed: int, h_cap: int, harvest: bool = True,
              mature_months: int = 12) -> dict:
    """One configuration's lifecycle on the hall layout of `eng`."""
    eng.reset()
    t = eng.t
    M = n_months
    E = len(trace["month"])
    month, life = trace["month"], trace["lifetime_m"]
    hf, kw, gpu = trace["harvest_frac"], trace["rack_kw"], trace["is_gpu"]
    ha = trace["tier"] == 0
    reg_rows = [[-1]] * E
    reg_counts = [[0.0]] * E
    placed = np.zeros(E, bool)
    harvested = np.zeros(E, bool)
    removed = np.zeros(E, bool)
    n_act = 1
    act_month = np.full(t.n_halls, -1)
    act_month[0] = 0
    R1 = t.rows_per_hall
    starts = np.searchsorted(month, np.arange(M + 1))
    out = {k: [] for k in ("halls_active", "deployed_kw", "p50", "p90")}

    def release(sel, frac):
        idx = np.flatnonzero(sel)
        if len(idx):
            eng.release([reg_rows[i] for i in idx],
                        [reg_counts[i] for i in idx], kw[idx], gpu[idx],
                        ha[idx], frac[idx])

    for m in range(M):
        expire = placed & ~removed & (month + life <= m)
        release(expire, np.where(harvested, 1.0 - hf, 1.0)
                .astype(np.float32))
        removed |= expire
        if harvest:
            h = placed & ~removed & ~harvested & (month + 12 <= m)
            release(h, hf)
            harvested |= h
        ev = np.arange(starts[m], starts[m + 1])
        draws = None
        if policy == RANDOM and len(ev):
            pods = trace["is_pod"][ev]
            mr = int(trace["n_racks"][ev][pods].max()) if pods.any() else 1
            draws = _month_draws(seed, m, len(ev), mr, t.n_rows)
        for j, e in enumerate(ev):
            n_try = min(n_act + 1, h_cap)
            K = n_try * R1
            row_hall = t.row_hall[:K]
            rands = None if draws is None else draws[j]
            if trace["is_pod"][e]:
                got = eng.place_pod(int(trace["n_racks"][e]), kw[e], ha[e],
                                    policy, row_hall < n_act,
                                    None if rands is None else rands[1:])
                if got is None:
                    got = eng.place_pod(int(trace["n_racks"][e]), kw[e],
                                        ha[e], policy, row_hall < n_try,
                                        None if rands is None
                                        else rands[1:])
                    n_act = n_try
                ok = got is not None
                rows, counts = (got, [1.0] * len(got)) if ok else ([-1],
                                                                  [0.0])
            else:
                bias = np.where(row_hall >= n_act, eng.dt(NEW_HALL_BIAS),
                                eng.dt(0))
                row = eng.place_in_row(int(trace["n_racks"][e]), kw[e],
                                       bool(gpu[e]), ha[e], policy,
                                       np.ones(K, bool), bias=bias,
                                       rand=None if rands is None
                                       else rands[0])
                ok = row >= 0
                if not (ok and t.row_hall[row] < n_act):
                    n_act = n_try
                rows, counts = ([row], [float(trace["n_racks"][e])]) \
                    if ok else ([-1], [0.0])
            placed[e] = ok
            if ok:
                reg_rows[e], reg_counts[e] = rows, counts
        act_month[(act_month < 0) & (np.arange(t.n_halls) < n_act)] = m
        hs = eng.hall_stranding()
        mature = (act_month >= 0) & (act_month <= m - mature_months)
        if not mature.any():
            mature = act_month >= 0
        p50, p90 = _percentiles(hs, mature, (50.0, 90.0))
        out["halls_active"].append(n_act)
        out["deployed_kw"].append(eng.deployed_kw())
        out["p50"].append(p50)
        out["p90"].append(p90)
    res = {k: np.asarray(v, np.float64) for k, v in out.items()}
    res["final_hall_stranding"] = np.asarray(
        eng.hall_stranding(), np.float64)[:n_act]
    res["n_halls_built"] = n_act
    res["placed_fraction"] = float(placed.sum()) / max(E, 1)
    return res
