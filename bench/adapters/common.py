"""Pieces the simulator adapters share: seeds and the sample's numbers."""
from __future__ import annotations

import numpy as np

SEED_MOD = 2 ** 31 - 2       # program seeds must fit int32 after the +1
                             # the fleet adds for its PRNG key


def derived_seed(seed: int, *path: int) -> int:
    """A program seed for (run seed, call index, replica, ...)."""
    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1)]
                                + [int(p) & (2 ** 32 - 1) for p in path])
    return int(ss.generate_state(1, np.uint64)[0] % SEED_MOD)


def gap_rel(a, b, scale) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(float(scale), 1e-9))


def gap_abs(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    both = ~(np.isnan(a) & np.isnan(b))
    if (np.isnan(a) != np.isnan(b)).any():
        return float("inf")
    return float(np.max(np.abs(a[both] - b[both]))) if both.any() else 0.0


def aggregate(rows) -> dict:
    """Per number over a sample: `<name>` the widest reading, and
    `<name>_mean` the mean one."""
    out = {}
    for k in rows[0]:
        v = np.asarray([r[k] for r in rows], np.float64)
        out[k] = float(v.max())
        out[k + "_mean"] = float(v.mean())
    return out
