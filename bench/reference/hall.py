"""Plain reference of one single-hall Monte Carlo trial (paper §4.4).

A trial fills an empty hall with its fill trace until 100 placements in
a row have failed, harvests every placed rack by its class ceiling
(§5.2), then fills again from its refill trace until saturation.
Events run in trace order; the random policy draws from
`fold_in(k, event)` (and `fold_in(·, rack)` inside a pod) of the
trial's key halves, as the simulator's grid call documents.
"""
from __future__ import annotations

import numpy as np

from .engine import RANDOM, Engine, random_rows

SATURATION_FAILS = 100


def trial_keys(seed: int, n_trials: int) -> np.ndarray:
    """[T, 2, 2] raw (fill, refill) keys of a configuration's trials."""
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        ks = jax.random.split(jax.random.PRNGKey(seed), n_trials)
        return np.asarray(jax.vmap(jax.random.split)(ks))


def _event_draws(key, trace, n_rows, max_racks):
    """Random-policy draws [E, 1 + max_racks, R]: slot 0 for a cluster
    event, slots 1.. for the racks of a pod."""
    import jax
    E = len(trace["rack_kw"])
    with jax.default_device(jax.devices("cpu")[0]):
        ev = jax.vmap(lambda i: jax.random.fold_in(key, i))(np.arange(E))
        racks = jax.vmap(lambda k: jax.vmap(
            lambda r: jax.random.fold_in(k, r))(np.arange(max_racks)))(ev)
        keys = np.concatenate([np.asarray(ev)[:, None],
                               np.asarray(racks)], axis=1)
    return random_rows(keys, n_rows)


def _fill(eng: Engine, trace: dict, policy: int, key) -> tuple:
    E = len(trace["rack_kw"])
    K = eng.t.n_rows
    active = np.ones(K, bool)
    max_racks = int(trace["n_racks"][trace["is_pod"]].max()) \
        if trace["is_pod"].any() else 1
    draws = (_event_draws(key, trace, K, max_racks)
             if policy == RANDOM else None)
    placed = np.zeros(E, bool)
    rows, counts = [], []
    streak = 0
    for i in range(E):
        rr, cc = [-1], [0.0]
        ok = False
        if streak < SATURATION_FAILS:
            kw, n = trace["rack_kw"][i], int(trace["n_racks"][i])
            is_ha = trace["tier"][i] == 0
            if trace["is_pod"][i]:
                got = eng.place_pod(n, kw, is_ha, policy, active,
                                    None if draws is None else draws[i, 1:])
                if got is not None:
                    ok, rr, cc = True, got, [1.0] * len(got)
            else:
                row = eng.place_in_row(
                    n, kw, bool(trace["is_gpu"][i]), is_ha, policy, active,
                    rand=None if draws is None else draws[i, 0])
                if row >= 0:
                    ok, rr, cc = True, [row], [float(n)]
        placed[i] = ok
        streak = 0 if ok else streak + 1
        rows.append(rr if ok else [-1])
        counts.append(cc if ok else [0.0])
    return placed, rows, counts, streak >= SATURATION_FAILS


def run_trial(eng: Engine, trace_a: dict, trace_b: dict, policy: int,
              keys, harvest: bool) -> dict:
    """One trial from an empty hall.  `keys` is the trial's raw
    [(fill key), (refill key)] pair."""
    eng.reset()
    placed_a, rows, counts, _ = _fill(eng, trace_a, policy, keys[0])
    if harvest:
        frac = np.where(placed_a, trace_a["harvest_frac"], 0.0)
        sel = np.flatnonzero(frac > 0)
        eng.release([rows[i] for i in sel], [counts[i] for i in sel],
                    trace_a["rack_kw"][sel], trace_a["is_gpu"][sel],
                    trace_a["tier"][sel] == 0, frac[sel])
    placed_b, _, _, saturated = _fill(eng, trace_b, policy, keys[1])
    return {"lineup_stranding": np.asarray(eng.lineup_stranding(),
                                           np.float64),
            "hall_stranding": float(eng.hall_stranding()[0]),
            "deployed_kw": eng.deployed_kw(),
            "saturated": bool(saturated),
            "placed_a": placed_a, "placed_b": placed_b}
