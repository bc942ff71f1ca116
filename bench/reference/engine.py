"""Plain numpy placement engine: halls, feasibility, policies, release.

The benchmark's reference for the placement layer (paper §2.3, §4.1–4.3,
App. C), written from the paper's equations and the semantics the
simulator documents, and importing nothing of it.  One event at a time,
in the arithmetic type `dt` of the caller: `numpy.float32` for the
reference (the simulator's stated precision) and `ml_dtypes.bfloat16`
for the control, with every intermediate rounded to `dt`.

A hall is line-ups → rows; H halls are laid out hall-major with
`rows_per_hall` rows and `lineups_per_hall` line-ups each (a grid pads
every configuration to the largest design: padding rows have no
capacity, padding line-ups are inactive).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import numpy as np

POWER, AIR, LIQ, TILES = 0, 1, 2, 3
AIR_CFM_PER_KW = 165.0
LIQ_LPM_PER_RACK = 2.0
GPU_AIR_FRACTION = 0.10
MAX_FEEDS = 4
RANDOM, ROUND_ROBIN, MIN_WASTE, VAR_MIN = 0, 1, 2, 3
LD_PREFERENCE = 100.0      # non-GPU racks prefer low-density rows
BIG = 1e30
TOL = 1e-4                 # kW / CFM / LPM slack of every capacity test


@dataclass(frozen=True)
class Design:
    """A power-delivery reference design (Table 1, App. C.2)."""
    name: str
    kind: str                 # "distributed" | "block"
    n_lineups: int
    n_active: int
    lineup_kw: float
    n_domains: int
    ld_rows: int
    hd_rows: int
    ld_row_kw: float
    hd_row_kw: float
    ld_feeds: int
    hd_feeds: int
    tiles_per_row: int
    air_provision_ratio: float
    liq_gpu_share: float
    liq_ref_rack_kw: float

    @property
    def n_rows(self):
        return self.ld_rows + self.hd_rows

    @property
    def ha_capacity_kw(self):
        return self.n_active * self.lineup_kw

    @property
    def ha_frac(self):
        return (self.n_active / self.n_lineups
                if self.kind == "distributed" else 1.0)


class Topology:
    """Numpy arrays of H identical halls, hall-major."""

    def __init__(self, d: Design, n_halls: int, rows_per_hall: int,
                 lineups_per_hall: int):
        if d.kind == "distributed":
            n_act, per_dom = d.n_lineups, d.n_lineups // d.n_domains
        else:
            n_act, per_dom = d.n_active, d.n_active // d.n_domains
        feeds, hd, dom = [], [], []
        for g in range(d.n_domains):
            off = g * per_dom
            for n_rows, k, is_hd in ((d.ld_rows // d.n_domains, d.ld_feeds,
                                      False),
                                     (d.hd_rows // d.n_domains, d.hd_feeds,
                                      True)):
                if d.kind == "distributed":
                    combos = list(itertools.combinations(
                        range(per_dom), min(k, per_dom)))
                    rows = [tuple(off + c for c in combos[i % len(combos)])
                            for i in range(n_rows)]
                else:
                    rows = [(off + i % per_dom,) for i in range(n_rows)]
                feeds += rows
                hd += [is_hd] * n_rows
                dom += [g] * n_rows
        R1, X1, H = rows_per_hall, lineups_per_hall, n_halls
        f1 = np.full((R1, MAX_FEEDS), -1, np.int64)
        nf1 = np.zeros(R1, np.int64)
        for i, c in enumerate(feeds):
            f1[i, :len(c)] = c
            nf1[i] = len(c)
        hd1 = np.zeros(R1, bool)
        hd1[:len(hd)] = hd
        dom1 = np.zeros(R1, np.int64)
        dom1[:len(dom)] = dom
        cap1 = np.zeros((R1, 4), np.float32)
        kw = np.where(hd1, d.hd_row_kw, d.ld_row_kw).astype(np.float32)
        real = np.arange(R1) < len(feeds)
        cap1[real, POWER] = kw[real]
        cap1[real, AIR] = d.air_provision_ratio * AIR_CFM_PER_KW * kw[real]
        cap1[real, LIQ] = np.where(hd1[real], 1e9, 0.0)
        cap1[real, TILES] = d.tiles_per_row
        lcap1 = np.zeros(X1, np.float32)
        lcap1[:d.n_lineups] = d.lineup_kw
        lact1 = np.zeros(X1, bool)
        lact1[:n_act] = True

        self.design, self.n_halls = d, H
        self.rows_per_hall, self.lineups_per_hall = R1, X1
        self.row_cap = np.tile(cap1, (H, 1))
        self.row_feeds = np.concatenate(
            [np.where(f1 >= 0, f1 + h * X1, -1) for h in range(H)])
        self.row_nfeeds = np.tile(nf1, H)
        self.row_is_hd = np.tile(hd1, H)
        self.row_domain = np.concatenate([dom1 + h * d.n_domains
                                          for h in range(H)])
        self.row_hall = np.repeat(np.arange(H), R1)
        self.lineup_cap = np.tile(lcap1, H)
        self.lineup_is_active = np.tile(lact1, H)
        self.lineup_hall = np.repeat(np.arange(H), X1)
        liq = d.liq_gpu_share * d.ha_capacity_kw / d.liq_ref_rack_kw \
            * LIQ_LPM_PER_RACK
        self.hall_liq_cap = np.full(H, liq, np.float32)
        self.ha_frac = np.float32(d.ha_frac)
        self.is_block = d.kind == "block"

    @property
    def n_rows(self):
        return self.row_cap.shape[0]


class Engine:
    """Hall state and the placement steps, in arithmetic type `dt`."""

    def __init__(self, topo: Topology, dt):
        self.t, self.dt = topo, dt
        c = lambda a: np.asarray(a).astype(dt)
        self.row_cap = c(topo.row_cap)
        self.lineup_cap = c(topo.lineup_cap)
        self.hall_liq_cap = c(topo.hall_liq_cap)
        self.ha_frac = dt(topo.ha_frac)
        self.tol = dt(TOL)
        self.valid = topo.row_feeds >= 0
        self.safe = np.where(self.valid, topo.row_feeds, 0)
        self.nf = c(np.maximum(topo.row_nfeeds, 1))
        self.nf1 = c(np.maximum(c(np.maximum(topo.row_nfeeds, 1)) - dt(1),
                                dt(1)))
        self.cap_g = self.lineup_cap[self.safe]             # [R, F]
        self.cap_g1 = np.maximum(self.cap_g, dt(1))
        self.ha_cap = c(self.ha_frac * self.cap_g + self.tol)
        self.cap_tol = c(self.cap_g + self.tol)
        self.row_cap_tol = c(self.row_cap + self.tol)
        self.liq_cap_tol = c(self.hall_liq_cap + self.tol)
        self.cap_p1 = np.maximum(self.row_cap[:, POWER], dt(1))
        self.ld_pref = np.where(topo.row_is_hd, dt(LD_PREFERENCE), dt(0))
        self.reset()

    def reset(self):
        t, dt = self.t, self.dt
        self.row_load = np.zeros((t.n_rows, 4), dt)
        self.lineup_ha = np.zeros(t.lineup_cap.shape[0], dt)
        self.lineup_tot = np.zeros(t.lineup_cap.shape[0], dt)
        self.hall_liq = np.zeros(t.n_halls, dt)
        self.rr_cursor = 0

    def snapshot(self):
        return (self.row_load.copy(), self.lineup_ha.copy(),
                self.lineup_tot.copy(), self.hall_liq.copy(),
                self.rr_cursor)

    def restore(self, s):
        (self.row_load, self.lineup_ha, self.lineup_tot, self.hall_liq,
         self.rr_cursor) = (s[0].copy(), s[1].copy(), s[2].copy(),
                            s[3].copy(), s[4])

    def demand(self, rack_kw, is_gpu):
        """Per-rack (kW, CFM, LPM, tiles) — paper §4.1 conversions."""
        dt = self.dt
        kw = dt(rack_kw)
        air = dt(dt(dt(AIR_CFM_PER_KW) * kw)
                 * (dt(GPU_AIR_FRACTION) if is_gpu else dt(1)))
        return np.array([kw, air, LIQ_LPM_PER_RACK if is_gpu else 0.0, 1.0],
                        dt)

    def place_in_row(self, n, rack_kw, is_gpu, is_ha, policy, active,
                     bias=None, rand=None):
        """Best feasible active row among the first `len(active)` rows for
        `n` racks: (Eq. 26 feasibility, policy score, lowest-index argmin).
        Applies the placement and returns the row, or -1."""
        dt, K = self.dt, len(active)
        nn = dt(n)
        d = self.demand(rack_kw, is_gpu)
        D = (nn * d).astype(dt)
        P = dt(nn * dt(rack_kw))
        load = self.row_load[:K]
        fits = np.all((load + D) <= self.row_cap_tol[:K], axis=1)
        if is_gpu:
            fits &= self.t.row_is_hd[:K]
        hall = self.t.row_hall[:K]
        fits &= (self.hall_liq + D[LIQ])[hall] <= self.liq_cap_tol[hall]
        valid, safe = self.valid[:K], self.safe[:K]
        ha_l, tot_l = self.lineup_ha[safe], self.lineup_tot[safe]
        share = (P / self.nf[:K]).astype(dt)
        tot_ok = (tot_l + share[:, None]) <= self.cap_tol[:K]
        if self.t.is_block:
            per_feed = (tot_l + P) <= self.cap_tol[:K]
        elif is_ha:
            delta = (P / self.nf1[:K]).astype(dt)
            per_feed = ((ha_l + delta[:, None]) <= self.ha_cap[:K]) & tot_ok
        else:
            per_feed = tot_ok
        feas = fits & np.all(per_feed | ~valid, axis=1) & active
        if not feas.any():
            return -1
        if policy == VAR_MIN:
            cap1 = self.cap_g1[:K]
            s = (share[:, None] / cap1).astype(dt)
            lhat = ((ha_l if is_ha else tot_l) / cap1).astype(dt)
            term = np.where(valid, (dt(2) * lhat * s + s * s).astype(dt),
                            dt(0))
            score = term[:, 0]
            for f in range(1, MAX_FEEDS):
                score = (score + term[:, f]).astype(dt)
        elif policy == MIN_WASTE:
            score = ((self.row_cap[:K, POWER] - load[:, POWER] - P)
                     / self.cap_p1[:K]).astype(dt)
        elif policy == ROUND_ROBIN:
            R = self.t.n_rows
            score = (np.mod(np.arange(K) - self.rr_cursor, R).astype(dt)
                     / dt(R)).astype(dt)
        else:
            score = np.asarray(rand[:K], dt)
        score = ((self.ld_pref[:K] if not is_gpu else dt(0)) + score
                 ).astype(dt)
        if bias is not None:
            score = (score + bias[:K]).astype(dt)
        row = int(np.argmin(np.where(feas, score, dt(BIG))))
        self._apply(row, nn, d, D, P, is_ha)
        return row

    def _apply(self, row, nn, d, D, P, is_ha):
        dt = self.dt
        self.row_load[row] = (self.row_load[row] + D).astype(dt)
        f = self.t.row_feeds[row]
        f = f[f >= 0]
        share = dt(P / self.nf[row])
        if is_ha:
            self.lineup_ha[f] = (self.lineup_ha[f] + share).astype(dt)
        self.lineup_tot[f] = (self.lineup_tot[f] + share).astype(dt)
        h = self.t.row_hall[row]
        self.hall_liq[h] = dt(self.hall_liq[h] + D[LIQ])
        self.rr_cursor = row + 1

    def place_pod(self, n_racks, rack_kw, is_ha, policy, active, rands):
        """A GPU pod rack by rack, every rack in the power domain of the
        first; all or nothing.  Returns the rows, or None."""
        snap = self.snapshot()
        dom, rows = -1, []
        for i in range(n_racks):
            act = active if dom < 0 else \
                active & (self.t.row_domain[:len(active)] == dom)
            row = self.place_in_row(1, rack_kw, True, is_ha, policy, act,
                                    rand=None if rands is None else rands[i])
            if row < 0:
                self.restore(snap)
                return None
            if dom < 0:
                dom = int(self.t.row_domain[row])
            rows.append(row)
        return rows

    def release(self, rows, counts, rack_kw, is_gpu, is_ha, fraction):
        """Take back `fraction` of each listed placement's demand
        (harvest: the class ceiling; decommission: what is left)."""
        dt = self.dt
        R, X = self.t.n_rows, self.t.lineup_cap.shape[0]
        rel = np.zeros((R, 4), dt)
        rel_ha = np.zeros(R, dt)
        for rr, cc, kw, g, ha, fr in zip(rows, counts, rack_kw, is_gpu,
                                         is_ha, fraction):
            d = self.demand(kw, g)
            for r, c in zip(rr, cc):
                if r < 0:
                    continue
                v = (dt(dt(c) * dt(fr)) * d).astype(dt)
                rel[r] = (rel[r] + v).astype(dt)
                if ha:
                    rel_ha[r] = dt(rel_ha[r] + v[POWER])
        self.row_load = (self.row_load - rel).astype(dt)
        per = (rel[:, POWER] / self.nf).astype(dt)
        per_ha = (rel_ha / self.nf).astype(dt)
        dtot = np.zeros(X, dt)
        dha = np.zeros(X, dt)
        for f in range(MAX_FEEDS):
            ok = self.valid[:, f]
            np.add.at(dtot, self.safe[ok, f], per[ok])
            np.add.at(dha, self.safe[ok, f], per_ha[ok])
        self.lineup_tot = (self.lineup_tot - dtot).astype(dt)
        self.lineup_ha = (self.lineup_ha - dha).astype(dt)
        dliq = np.zeros(self.t.n_halls, dt)
        np.add.at(dliq, self.t.row_hall, rel[:, LIQ])
        self.hall_liq = (self.hall_liq - dliq).astype(dt)

    # ---- stranding (paper §4.3) ----
    def lineup_stranding(self):
        dt = self.dt
        eff = (self.ha_frac * self.lineup_cap).astype(dt)
        frac = ((eff - self.lineup_ha) / np.maximum(eff, dt(1))).astype(dt)
        return np.where(self.t.lineup_is_active,
                        np.clip(frac, dt(0), dt(1)).astype(dt), dt(0))

    def hall_stranding(self):
        dt, H = self.dt, self.t.n_halls
        act = self.t.lineup_is_active
        eff = np.where(act, (self.ha_frac * self.lineup_cap).astype(dt),
                       dt(0))
        load = np.where(act, self.lineup_ha, dt(0))
        eff_h = np.zeros(H, dt)
        load_h = np.zeros(H, dt)
        np.add.at(eff_h, self.t.lineup_hall, eff)
        np.add.at(load_h, self.t.lineup_hall, load)
        frac = ((eff_h - load_h) / np.maximum(eff_h, dt(1))).astype(dt)
        return np.clip(frac, dt(0), dt(1)).astype(dt)

    def deployed_kw(self):
        x = self.row_load[:, POWER]
        n = 1 << (len(x) - 1).bit_length()
        x = np.concatenate([x, np.zeros(n - len(x), self.dt)])
        while len(x) > 1:
            x = (x[:len(x) // 2] + x[len(x) // 2:]).astype(self.dt)
        return float(x[0])


def random_rows(keys, n_rows: int) -> np.ndarray:
    """[..., n_rows] uniform draws of the random policy, one row of draws
    per raw PRNG key `[..., 2]` (JAX's threefry, run on the host CPU)."""
    import jax
    keys = np.asarray(keys, np.uint32)
    with jax.default_device(jax.devices("cpu")[0]):
        draw = jax.jit(jax.vmap(lambda k: jax.random.uniform(k, (n_rows,))))
        out = np.asarray(draw(keys.reshape(-1, 2)))
    return out.reshape(keys.shape[:-1] + (n_rows,))
