"""Jitted wrappers: build kernel inputs from a placement state.

Float32 contract: the kernel computes in float32 (TPU VMEM tiles), and
the placement engine's jnp oracle also runs in float32 — so the wrapper
*requires* float32 (or weaker) float inputs.  Callers running under
`jax.config.update("jax_enable_x64", True)` must down-cast explicitly;
a silent cast here would let the kernel drift bitwise from an x64
oracle, which is exactly what the equivalence harness exists to rule
out.  Integer inputs are converted to int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import placement_score


def _require_f32(name, x):
    x = jnp.asarray(x)
    if x.dtype == jnp.float64:
        raise TypeError(
            f"feasible_rows: `{name}` is float64; the placement-score kernel "
            "computes in float32 (see module docstring). Cast inputs to "
            "float32 explicitly before calling.")
    return x.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def feasible_rows(jt_row_feeds, jt_row_nfeeds, jt_row_cap_kw, lineup_ha,
               lineup_tot, lineup_cap, row_load_kw, p_dep, ha_frac,
               is_ha, is_block, block_r: int = 128,
               interpret: bool = False):
    """Gathers per-feed line-up state at `jt_row_feeds` and runs the kernel
    on it (`feasible_rows_from_views`).

    `jt_row_feeds` may be a compacted subset view ([K, F] gathered at
    `hd_index[:K]`, with the other row arrays gathered to match) — the
    kernel itself is agnostic to row identity.  `is_ha`/`is_block` are
    0/1 flags (traced; deployment tier and topology family).  Returns
    feas [R] bool: the line-up power condition AND the row power fit.
    """
    jt_row_feeds = jnp.asarray(jt_row_feeds, jnp.int32)
    lineup_ha = _require_f32("lineup_ha", lineup_ha)
    lineup_tot = _require_f32("lineup_tot", lineup_tot)
    lineup_cap = _require_f32("lineup_cap", lineup_cap)
    valid = jt_row_feeds >= 0
    safe = jnp.where(valid, jt_row_feeds, 0)
    return feasible_rows_from_views(
        valid, lineup_cap[safe], lineup_ha[safe], lineup_tot[safe],
        jt_row_nfeeds, jt_row_cap_kw, row_load_kw, p_dep, ha_frac, is_ha,
        is_block, block_r=block_r, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def feasible_rows_from_views(valid, caps, loads_ha, loads_tot, nfeeds,
                             row_cap_kw, row_load_kw, p_dep, ha_frac,
                             is_ha, is_block, block_r: int = 128,
                             interpret: bool = False):
    """Runs the kernel on the line-up state behind each row's feeds.

    `valid`, `caps`, `loads_ha`, `loads_tot`: [R, F] per-(row, feed)
    views, however the caller built them (gathered, or hall-local);
    entries where `valid` is false are ignored.  `nfeeds`, `row_cap_kw`,
    `row_load_kw`: [R].  Returns feas [R] bool as `feasible_rows`.  The
    kernel launch is named after this function in the compiled program.
    """
    caps = _require_f32("caps", caps)
    loads_ha = _require_f32("loads_ha", loads_ha)
    loads_tot = _require_f32("loads_tot", loads_tot)
    row_cap_kw = _require_f32("row_cap_kw", row_cap_kw)
    row_load_kw = _require_f32("row_load_kw", row_load_kw)
    p_dep = _require_f32("p_dep", p_dep)
    ha_frac = _require_f32("ha_frac", ha_frac)
    params = jnp.stack([p_dep, ha_frac,
                        jnp.asarray(is_ha, jnp.float32).reshape(()),
                        jnp.asarray(is_block, jnp.float32).reshape(())])
    feas = placement_score(
        loads_ha, loads_tot, caps, jnp.asarray(valid, jnp.float32),
        jnp.asarray(nfeeds, jnp.int32), row_load_kw, row_cap_kw, params,
        block_r=block_r, interpret=interpret)
    return feas > 0
