"""Oracle for the placement-score kernel (mirrors core.placement math)."""
from __future__ import annotations

import jax.numpy as jnp


def reference_feasible(loads_ha, loads_tot, caps, valid, nf, row_load,
                       row_cap, params):
    """Pure-jnp mirror of `kernel.placement_score` on one [R, F] block.

    Same argument convention as the kernel (all f32; params =
    [p_dep, ha_frac, is_ha, is_block]); no padding/tiling — this is the
    bitwise ground truth the Pallas path is tested against.  Returns
    feas [R] f32 0/1.
    """
    loads_ha = loads_ha.astype(jnp.float32)
    loads_tot = loads_tot.astype(jnp.float32)
    caps = caps.astype(jnp.float32)
    valid = valid.astype(jnp.float32)
    nf = nf.astype(jnp.float32)
    p_dep, ha_frac, is_ha, is_block = (params[0], params[1], params[2],
                                       params[3])

    share = p_dep / jnp.maximum(nf, 1.0)
    delta = p_dep / jnp.maximum(nf - 1.0, 1.0)
    tot_ok = loads_tot + share[:, None] <= caps + 1e-4
    ha_ok = (loads_ha + delta[:, None] <= ha_frac * caps + 1e-4) & tot_ok
    block_ok = loads_tot + p_dep <= caps + 1e-4
    dist_ok = jnp.where(is_ha > 0, ha_ok, tot_ok)
    per_feed = jnp.where(is_block > 0, block_ok, dist_ok)
    power_ok = jnp.all(per_feed | (valid <= 0), axis=-1)
    fits = row_load + p_dep <= row_cap + 1e-4
    return (power_ok & fits).astype(jnp.float32)
