"""Placement power-feasibility Pallas TPU kernel.

The Monte Carlo studies (paper §4.4) evaluate, for every candidate row,
the redundancy admission condition (Eq. 1/2/26/27) inside every scan
step of every vmapped trial.  This kernel fuses the per-row feed
headroom checks and the reduction over a row's feeds into one VMEM pass
over row blocks.

Inputs are pre-gathered per row (HA/total loads and caps per feed,
padded with `valid=0`): the gather itself is XLA's job; the kernel owns
the comparisons and the feed reduction.  Scalars (deployment power P,
ha_frac, tier/topology flags) arrive as a small ``[1, 4]`` params block
given to every grid step; its last two dimensions are the whole array,
which keeps the block legal for Mosaic after `vmap` adds batch
dimensions in front.

Semantics mirror `core.placement.row_feasible`'s power condition term
for term (the jnp path is the bitwise oracle — see
`tests/test_placement_kernel.py`):

* distributed HA:   every feed holds failover headroom
  ``load_ha + P/(k−1) ≤ ha_frac·C`` AND balanced-share room
  ``load_tot + P/k ≤ C``  (Eq. 1/27);
* distributed LA:   ``load_tot + P/k ≤ C`` (may consume reserve);
* block N+k:        ``load_tot + P ≤ C`` on the single primary (Eq. 2);
* row power fit:    ``row_load + P ≤ row_cap``.

Rows lie on the 128-wide lane axis (``[F, bR]`` feed blocks, ``[1, bR]``
row vectors), so every block is lane-aligned for every row count the
engines pass.  The row grid pads to lane-aligned `block_r` tiles; padded
rows are masked infeasible (zero-valid feeds, negative row cap) and
sliced off before returning.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128          # TPU vector lane width: the row tile unit


def _feasible_kernel(loads_ha_ref, loads_tot_ref, caps_ref, valid_ref,
                     share_ref, delta_ref, row_load_ref, row_cap_ref,
                     params_ref, feas_ref):
    loads_ha = loads_ha_ref[...]                        # [F, bR]
    loads_tot = loads_tot_ref[...]
    caps = caps_ref[...]
    valid = valid_ref[...]
    share = share_ref[...]                              # [1, bR]
    delta = delta_ref[...]
    row_load = row_load_ref[...]
    row_cap = row_cap_ref[...]
    p_dep = params_ref[0, 0]
    ha_frac = params_ref[0, 1]
    is_ha = params_ref[0, 2]
    is_block = params_ref[0, 3]

    tot_ok = loads_tot + share <= caps + 1e-4
    ha_ok = (loads_ha + delta <= ha_frac * caps + 1e-4) & tot_ok
    block_ok = loads_tot + p_dep <= caps + 1e-4        # quantization (Eq. 2)
    # Mosaic cannot select between boolean vectors, so the 0/1 flags
    # pick the condition arithmetically (exact: every term is 0 or 1)
    f32 = lambda b: b.astype(jnp.float32)
    dist_ok = is_ha * f32(ha_ok) + (1.0 - is_ha) * f32(tot_ok)
    per_feed = is_block * f32(block_ok) + (1.0 - is_block) * dist_ok
    power_ok = jnp.min(jnp.where(valid > 0, per_feed, 1.0), axis=0,
                       keepdims=True)
    fits = f32(row_load + p_dep <= row_cap + 1e-4)
    feas_ref[...] = power_ok * fits


def placement_score(loads_ha, loads_tot, caps, valid, nf, row_load, row_cap,
                    params, block_r: int = LANES, interpret: bool = False):
    """loads_ha/loads_tot/caps/valid: [R, F]; nf/row_load/row_cap: [R];
    params: [4] (P_dep, ha_frac, is_ha, is_block — the flags as 0/1
    floats).  Returns feas [R] f32 0/1: the line-up power condition AND
    the row power fit.

    The arithmetic that rounds — the balanced share ``P/k`` and the
    failover ``Δ`` — is computed here by XLA, with the same expressions
    as `core.placement`'s jnp path, and the kernel keeps only the
    comparisons and the feed reduction, so it compares the same shares
    as the jnp path, bit for bit.

    Rows ride the 128-wide lane axis: the kernel sees ``[F, bR]`` feed
    blocks and ``[1, bR]`` row vectors, so `block_r` must be a multiple
    of 128 (`ValueError` otherwise).  The row axis is padded up to a
    multiple of the tile, ``min(block_r, R rounded up to 128)``; padded
    rows carry zero-valid feeds and a negative row cap, so they come
    back infeasible and are sliced off before returning — callers never
    see them win a selection.
    """
    if block_r <= 0 or block_r % LANES:
        raise ValueError(f"block_r={block_r} must be a positive multiple "
                         f"of {LANES} (rows lie on the TPU lane axis)")
    R, F = loads_ha.shape
    bR = min(block_r, -(-R // LANES) * LANES)
    R_pad = -(-R // bR) * bR
    f32 = lambda x: x.astype(jnp.float32)
    loads_ha, loads_tot, caps, valid, row_load, row_cap, params = map(
        f32, (loads_ha, loads_tot, caps, valid, row_load, row_cap, params))
    p_dep = params[0]
    nf = f32(nf)
    share = p_dep / jnp.maximum(nf, 1.0)               # balanced share P/k
    delta = p_dep / jnp.maximum(nf - 1.0, 1.0)         # failover Δ (Eq. 1)

    def lanes(x, fill):                 # [R, …] → […, R_pad], rows on lanes
        x = jnp.concatenate([x, jnp.full((R_pad - R,) + x.shape[1:], fill,
                                         x.dtype)])
        return x.reshape(R_pad, -1).T

    feed_spec = pl.BlockSpec((F, bR), lambda i: (0, i))
    row_spec = pl.BlockSpec((1, bR), lambda i: (0, i))
    feas = pl.pallas_call(
        _feasible_kernel,
        grid=(R_pad // bR,),
        in_specs=[feed_spec] * 4 + [row_spec] * 4
        + [pl.BlockSpec((1, 4), lambda i: (0, 0))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, R_pad), jnp.float32),
        interpret=interpret,
    )(lanes(loads_ha, 0.0), lanes(loads_tot, 0.0), lanes(caps, 1.0),
      lanes(valid, 0.0),                # no feeds → power trivially ok…
      lanes(share, 0.0), lanes(delta, 0.0), lanes(row_load, 0.0),
      lanes(row_cap, -1.0),             # …but the row itself never fits
      params.reshape(1, 4))
    return feas[0, :R]
