"""Hierarchical multi-resource placement engine (paper §4.2, App. C.1).

Pure-JAX implementation: hall state is a pytree of arrays, a placement is a
pure function step, Monte-Carlo trials are `vmap`-ed and arrival sequences
are `lax.scan`-ned.  The same engine serves the single-hall simulator
(H = 1) and the fleet simulator (rows/line-ups globally indexed over H
halls, with an activation mask).

Feasibility (Eq. 26): a placement is admitted iff every ancestor node —
row (power/air/liquid/tiles), line-ups (power under redundancy), hall
(liquid plant) — retains capacity.  Redundancy semantics:

* distributed xN/y (HA): every feeding parent p must simultaneously hold
  failover headroom   (y/x)·C_p − ha_load_p ≥ Δ(P, k) = P/(k−1)    (Eq. 1/27)
  and each takes the balanced share P/k on admission.
* distributed (LA): may consume reserve — total load ≤ full rating C_p.
* block N+k: rows draw from one primary at full rating; reserve line-ups
  admit no load (quantization, Eq. 2).

Placement policies (paper §4.2, Fig. 7): random, round-robin, min-waste
(best fit), variance-minimization (default; minimizes post-placement UPS
load imbalance — implemented via the exact sufficient-statistic reduction:
argmin Var(loads') ≡ argmin Σ_{p∈feeds} [2·l̂_p·s + s²], s = P/(k·C)).

The hot steps run under `jax.named_scope("repro.placement.<step>")`, so
their device ops carry the step's name in a profile (docs/architecture.md,
"Tracing a sweep").
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .hierarchy import HallTopology, MAX_FEEDS
from .resources import LIQ, N_RES, POWER, TIER_HA, rack_demand
from ..kernels.placement_score.ops import feasible_rows_from_views

# Policy ids (paper §4.2).
POLICY_RANDOM, POLICY_ROUND_ROBIN, POLICY_MIN_WASTE, POLICY_VAR_MIN = 0, 1, 2, 3
POLICY_NAMES = ("random", "round_robin", "min_waste", "var_min")
DEFAULT_POLICY = POLICY_VAR_MIN

MAX_POD_RACKS = 8      # static bound on pod size (paper studies 3–7)
_BIG = 1e30
_LD_PREFERENCE = 100.0  # non-GPU racks prefer LD rows (paper §2.2)

# Pallas kernel path (see docs/architecture.md "kernel path").  The row
# block size trades VMEM footprint against grid steps; rows lie on the
# 128-wide lane axis, so it must be a multiple of 128, and
# `kernels.placement_score.kernel.placement_score` pads the row axis to
# a multiple internally, so the value is a tile size, not a constraint
# on the row count.
DEFAULT_BLOCK_R = 128


def default_use_kernel() -> bool:
    """Kernel dispatch default: on for TPU backends, off elsewhere (the
    interpreted Pallas path is correct on CPU but slower than jnp; CI
    exercises it explicitly via `interpret=True`)."""
    return jax.default_backend() == "tpu"


def resolve_use_kernel(use_kernel) -> bool:
    """Host-level resolution of a `use_kernel` engine flag: `None` means
    backend default (`default_use_kernel`)."""
    return default_use_kernel() if use_kernel is None else bool(use_kernel)


class JaxTopology(NamedTuple):
    """Device-resident mirror of `HallTopology`.

    Tiled path: when the topology tiles one hall H times, as
    `build_topology` builds every topology the sweeps run (hall h holds
    rows ``[h·Rh, (h+1)·Rh)`` and line-ups ``[h·Xh, (h+1)·Xh)``, and its
    rows' feeds are hall 0's plus ``h·Xh``), `jax_topology` sets
    `row_feeds_local` and `row_feed_cap`.  The full-row placement step
    then reads each row's feed state and hall state as hall-local views
    (line-up arrays reshaped to ``[H, Xh]`` and selected at the local
    table, hall arrays broadcast over ``[H, Rh]``) instead of gathering
    at `row_feeds` / `row_hall`.  A view holds the one selected value, so
    every result is bitwise the gather path's, which hand-built
    topologies (both fields `None`) and row-subset (pod) scans keep."""
    row_cap: jax.Array      # [R, N_RES]
    row_feeds: jax.Array    # [R, MAX_FEEDS] int32
    row_nfeeds: jax.Array   # [R] int32
    row_is_hd: jax.Array    # [R] bool
    row_domain: jax.Array   # [R] int32
    row_hall: jax.Array     # [R] int32
    hd_index: jax.Array     # [R] int32 — HD row ids first (ascending), then
                            # the rest; `hd_index[:n_hd]` is the compacted
                            # HD-row view the pod scans gather over
    lineup_cap: jax.Array   # [X]
    lineup_is_active: jax.Array  # [X] bool
    lineup_hall: jax.Array  # [X] int32 — hall owning each line-up
    hall_liq_cap: jax.Array  # [H]
    ha_frac: jax.Array      # scalar
    is_block: jax.Array     # scalar bool
    row_feeds_local: jax.Array | None = None  # [Rh, MAX_FEEDS] int32 —
                            # each feed's line-up within its hall (-1:
                            # none); tiled topologies only
    row_feed_cap: jax.Array | None = None     # [R, MAX_FEEDS] — each
                            # feed's rating (0: none); tiled topologies only


def _hall_tiling(topo: HallTopology) -> np.ndarray | None:
    """Hall 0's row→feed table in hall-local line-up ids, `[Rh, F]`, if
    `topo` tiles it over its halls (see `JaxTopology`); else None."""
    H = topo.n_halls
    feeds = np.asarray(topo.row_feeds)
    R, X = feeds.shape[0], topo.lineup_cap.shape[0]
    if R % H or X % H:
        return None
    Rh, Xh = R // H, X // H
    halls = np.arange(H)
    local = feeds[:Rh]
    if np.any(local >= Xh) or np.any(
            np.asarray(topo.row_hall) != np.repeat(halls, Rh)):
        return None
    tiled = np.where(local >= 0, local + (halls * Xh)[:, None, None], local)
    if not np.array_equal(feeds.reshape(H, Rh, -1), tiled):
        return None
    return local.astype(np.int32)


def jax_topology(topo: HallTopology) -> JaxTopology:
    # stable: HD rows keep their ascending id order, so a compacted argmin
    # tie-breaks exactly like the full-row argmin restricted to HD rows
    hd_index = np.argsort(~np.asarray(topo.row_is_hd), kind="stable")
    local = _hall_tiling(topo)
    tiled = {}
    if local is not None:
        feeds = np.asarray(topo.row_feeds)
        cap = np.asarray(topo.lineup_cap)[np.maximum(feeds, 0)]
        tiled = dict(row_feeds_local=jnp.asarray(local),
                     row_feed_cap=jnp.asarray(
                         np.where(feeds >= 0, cap, 0), jnp.float32))
    return JaxTopology(
        row_cap=jnp.asarray(topo.row_cap),
        row_feeds=jnp.asarray(topo.row_feeds),
        row_nfeeds=jnp.asarray(topo.row_nfeeds),
        row_is_hd=jnp.asarray(topo.row_is_hd),
        row_domain=jnp.asarray(topo.row_domain),
        row_hall=jnp.asarray(topo.row_hall),
        hd_index=jnp.asarray(hd_index, jnp.int32),
        lineup_cap=jnp.asarray(topo.lineup_cap),
        lineup_is_active=jnp.asarray(topo.lineup_is_active),
        lineup_hall=jnp.asarray(topo.lineup_hall, jnp.int32),
        hall_liq_cap=jnp.asarray(topo.hall_liq_cap),
        ha_frac=jnp.asarray(topo.ha_frac, jnp.float32),
        is_block=jnp.asarray(topo.is_block),
        **tiled,
    )


class HallState(NamedTuple):
    row_load: jax.Array     # [R, N_RES]
    lineup_ha: jax.Array    # [X]  HA load (balanced shares)
    lineup_tot: jax.Array   # [X]  HA + LA load
    hall_liq: jax.Array     # [H]  liquid plant load (LPM)
    rr_cursor: jax.Array    # []   round-robin cursor


def init_state(topo: HallTopology) -> HallState:
    return _empty_state(topo.row_cap.shape[0], topo.lineup_cap.shape[0],
                        topo.n_halls)


def init_state_from(jt: JaxTopology) -> HallState:
    """Empty state shaped after a device topology (usable inside jit/vmap)."""
    return _empty_state(jt.row_cap.shape[0], jt.lineup_cap.shape[0],
                        jt.hall_liq_cap.shape[0])


def _empty_state(R: int, X: int, H: int) -> HallState:
    return HallState(
        row_load=jnp.zeros((R, N_RES), jnp.float32),
        lineup_ha=jnp.zeros((X,), jnp.float32),
        lineup_tot=jnp.zeros((X,), jnp.float32),
        hall_liq=jnp.zeros((H,), jnp.float32),
        rr_cursor=jnp.zeros((), jnp.int32),
    )


class Deployment(NamedTuple):
    """One arrival: a same-SKU cluster (one row) or a GPU pod (multi-row)."""
    rack_kw: jax.Array   # f32 per-rack power
    n_racks: jax.Array   # i32
    is_gpu: jax.Array    # bool
    tier: jax.Array      # i32 (0=HA, 1=LA)
    is_pod: jax.Array    # bool — racks may span rows within one domain

    @staticmethod
    def make(rack_kw, n_racks=1, is_gpu=False, tier=TIER_HA, is_pod=False):
        return Deployment(jnp.asarray(rack_kw, jnp.float32),
                          jnp.asarray(n_racks, jnp.int32),
                          jnp.asarray(is_gpu, bool),
                          jnp.asarray(tier, jnp.int32),
                          jnp.asarray(is_pod, bool))


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _tiled(jt: JaxTopology, rows) -> bool:
    """Whether the step reads hall-local views (`JaxTopology`'s tiled
    path): a tiled topology scanned over all of its rows."""
    return rows is None and jt.row_feeds_local is not None


def _hall_local(jt: JaxTopology, x) -> jax.Array:
    """`x[row_feeds]` of a per-line-up array on the tiled path, [R, F]
    with 0 where a row has no feed, gathering nothing: `x` reshaped to
    [H, Xh] and selected at the hall-local feed table."""
    local = jt.row_feeds_local                           # [Rh, F]
    xh = x.reshape(jt.hall_liq_cap.shape[0], -1)         # [H, Xh]
    view = jnp.zeros(xh.shape[:1] + local.shape, x.dtype)
    for k in range(xh.shape[1]):
        col = jax.lax.slice_in_dim(xh, k, k + 1, axis=1)    # [H, 1]
        view = jnp.where(local == k, col.reshape(-1, 1, 1), view)
    return view.reshape(-1, local.shape[1])


@jax.named_scope("repro.placement.gather_feeds")
def _feed_views(jt: JaxTopology, state: HallState, r_feeds, rows):
    """(valid, cap, ha_load, tot_load), each [R|K, F]: the line-up state
    behind every feed of the rows `_row_view` gives (`r_feeds`).  Hall-local
    views on the tiled path, gathers at the feeds otherwise; both give
    valid feeds bitwise the same values, and every consumer masks the
    others."""
    valid = r_feeds >= 0
    if _tiled(jt, rows):
        return (valid, jt.row_feed_cap, _hall_local(jt, state.lineup_ha),
                _hall_local(jt, state.lineup_tot))
    safe = jnp.where(valid, r_feeds, 0)
    return (valid, jt.lineup_cap[safe], state.lineup_ha[safe],
            state.lineup_tot[safe])


@jax.named_scope("repro.placement.gather_feeds")
def _row_view(jt: JaxTopology, state: HallState, rows):
    """Row-axis arrays, gathered at `rows` when given (compacted view).

    Every consumer computes per-row quantities elementwise, so a gathered
    view yields bitwise the values the full computation would produce at
    those rows — the compacted pod scan stays exactly equivalent to the
    full-row scan restricted to the subset."""
    if rows is None:
        return (jt.row_cap, state.row_load, jt.row_feeds, jt.row_nfeeds,
                jt.row_is_hd, jt.row_hall)
    return (jt.row_cap[rows], state.row_load[rows], jt.row_feeds[rows],
            jt.row_nfeeds[rows], jt.row_is_hd[rows], jt.row_hall[rows])


def _row_fits(jt: JaxTopology, state: HallState, dep: Deployment,
              n_in_row, rows=None) -> jax.Array:
    """Row/hall constraints outside the line-up power condition: the
    multi-resource row fit, the GPU→HD-row restriction, and the hall
    liquid plant.  Shared by both `row_feasible` paths — the kernel only
    owns the feed-gathered power math."""
    n = jnp.asarray(n_in_row, jnp.float32)
    d = rack_demand(dep.rack_kw, dep.is_gpu)          # [N_RES]
    D = n * d
    r_cap, r_load, _, _, r_is_hd, r_hall = _row_view(jt, state, rows)
    fits_row = jnp.all(r_load + D[None, :] <= r_cap + 1e-4, axis=-1)
    hd_ok = jnp.where(dep.is_gpu, r_is_hd, True)
    if _tiled(jt, rows):        # hall h's rows are [h·Rh, (h+1)·Rh)
        liq_h = state.hall_liq + D[LIQ] <= jt.hall_liq_cap + 1e-4   # [H]
        H = liq_h.shape[0]
        liq_ok = jnp.broadcast_to(liq_h[:, None],
                                  (H, r_cap.shape[0] // H)).reshape(-1)
    else:
        liq_ok = (state.hall_liq + D[LIQ])[r_hall] <= \
            jt.hall_liq_cap[r_hall] + 1e-4
    return fits_row & hd_ok & liq_ok


def _kernel_feasible(jt: JaxTopology, state: HallState, dep: Deployment,
                     n_in_row, rows=None, interpret: bool = False,
                     block_r: int = DEFAULT_BLOCK_R) -> jax.Array:
    """Line-up power feasibility via the Pallas kernel: [R|K] bool, the
    power condition AND the row *power* fit.  A superset of the full
    feasibility (`row_feasible` additionally checks the other resources,
    HD and liquid), so callers AND it with `_row_fits`."""
    n = jnp.asarray(n_in_row, jnp.float32)
    P = n * dep.rack_kw
    r_cap, r_load, r_feeds, r_nfeeds, _, _ = _row_view(jt, state, rows)
    valid, cap, ha_l, tot_l = _feed_views(jt, state, r_feeds, rows)
    return feasible_rows_from_views(
        valid, cap, ha_l, tot_l, r_nfeeds, r_cap[:, POWER],
        r_load[:, POWER], P, jt.ha_frac, dep.tier == TIER_HA, jt.is_block,
        block_r=block_r, interpret=interpret)


@jax.named_scope("repro.placement.row_feasible")
def row_feasible(jt: JaxTopology, state: HallState, dep: Deployment,
                 n_in_row, rows=None, use_kernel: bool = False,
                 interpret: bool = False) -> jax.Array:
    """Feasibility mask over rows for placing `n_in_row` racks of `dep`'s
    SKU into a single row (Eq. 26 over the ancestor path).  With `rows`
    (int32 row-id subset) the mask covers only those rows — the
    HD-compacted pod scan's view.

    `use_kernel=True` (static) computes the line-up power condition with
    the Pallas kernel instead of the jnp comparisons; the result is
    bitwise identical (`tests/test_placement_kernel.py`).  `interpret`
    runs the kernel in Pallas interpret mode (CPU CI)."""
    extra = _row_fits(jt, state, dep, n_in_row, rows)
    if use_kernel:
        return extra & _kernel_feasible(jt, state, dep, n_in_row, rows,
                                        interpret=interpret)

    n = jnp.asarray(n_in_row, jnp.float32)
    P = n * dep.rack_kw
    _, _, r_feeds, r_nfeeds, _, _ = _row_view(jt, state, rows)
    valid, cap, ha_l, tot_l = _feed_views(jt, state, r_feeds, rows)
    nf = jnp.maximum(r_nfeeds, 1).astype(jnp.float32)        # [R|K]
    share = P / nf
    # distributed HA: simultaneous failover headroom on every parent (Eq. 1)
    delta = P / jnp.maximum(nf - 1.0, 1.0)
    dist_ha = (ha_l + delta[:, None] <= jt.ha_frac * cap + 1e-4) & \
              (tot_l + share[:, None] <= cap + 1e-4)
    # distributed LA: may consume reserve up to full rating (Flex-style)
    dist_la = tot_l + share[:, None] <= cap + 1e-4
    # block: single primary feed at full rating
    block_ok = tot_l + P <= cap + 1e-4

    is_ha = dep.tier == TIER_HA
    dist_ok = jnp.where(is_ha, dist_ha, dist_la)
    per_feed = jnp.where(jt.is_block, block_ok, dist_ok)
    power_ok = jnp.all(per_feed | ~valid, axis=-1)

    return extra & power_ok


@jax.named_scope("repro.placement.row_scores")
def row_scores(jt: JaxTopology, state: HallState, dep: Deployment,
               n_in_row, policy, key, rows=None) -> jax.Array:
    """Per-row placement score (lower is better).  With `rows`, scores are
    the full-row scores gathered at the subset (the random draw is taken
    from the full-`R` grid and the round-robin distance keeps full-`R`
    row ids), so a compacted argmin matches the full argmin bitwise."""
    n = jnp.asarray(n_in_row, jnp.float32)
    P = n * dep.rack_kw
    R = jt.row_cap.shape[0]
    r_cap, r_load, r_feeds, r_nfeeds, r_is_hd, _ = _row_view(jt, state, rows)
    row_ids = jnp.arange(R) if rows is None else rows

    # Structural preference: non-GPU racks go to LD rows when possible.
    base = jnp.where(r_is_hd & ~dep.is_gpu, _LD_PREFERENCE, 0.0)

    rand = jax.random.uniform(key, (R,))
    rand = rand if rows is None else rand[rows]
    rr = jnp.mod(row_ids - state.rr_cursor, R).astype(jnp.float32) / R
    waste = (r_cap[:, POWER] - r_load[:, POWER] - P) / \
        jnp.maximum(r_cap[:, POWER], 1.0)

    valid, cap, ha_l, tot_l = _feed_views(jt, state, r_feeds, rows)
    nf = jnp.maximum(r_nfeeds, 1).astype(jnp.float32)
    s = (P / nf)[:, None] / jnp.maximum(cap, 1.0)
    lhat = jnp.where(dep.tier == TIER_HA, ha_l, tot_l) / jnp.maximum(cap, 1.0)
    var = jnp.sum(jnp.where(valid, 2.0 * lhat * s + s * s, 0.0), axis=-1)

    score = jnp.select(
        [policy == POLICY_RANDOM, policy == POLICY_ROUND_ROBIN,
         policy == POLICY_MIN_WASTE, policy == POLICY_VAR_MIN],
        [rand, rr, waste, var], var)
    return base + score


def _apply_to_row(jt: JaxTopology, state: HallState, dep: Deployment,
                  n_in_row, row) -> HallState:
    n = jnp.asarray(n_in_row, jnp.float32)
    d = rack_demand(dep.rack_kw, dep.is_gpu)
    P = n * dep.rack_kw
    row_load = state.row_load.at[row].add(n * d)
    feeds = jt.row_feeds[row]
    valid = feeds >= 0
    safe = jnp.where(valid, feeds, 0)
    nf = jnp.maximum(jt.row_nfeeds[row], 1).astype(jnp.float32)
    share = jnp.where(valid, P / nf, 0.0)
    is_ha = dep.tier == TIER_HA
    lineup_ha = state.lineup_ha.at[safe].add(jnp.where(is_ha, share, 0.0))
    lineup_tot = state.lineup_tot.at[safe].add(share)
    hall_liq = state.hall_liq.at[jt.row_hall[row]].add(n * d[LIQ])
    return HallState(row_load, lineup_ha, lineup_tot, hall_liq,
                     (row + 1).astype(jnp.int32))


def place_in_row(jt: JaxTopology, state: HallState, dep: Deployment,
                 n_in_row, policy, key, row_active, score_bias=None,
                 row_subset=None, use_kernel: bool = False,
                 interpret: bool = False):
    """Place `n_in_row` racks into the best feasible active row.
    Returns (state', ok, row).  `score_bias` (per-row, finite, and large
    relative to policy scores) expresses structural preferences among
    feasible rows — e.g. the fleet engine's keep-to-existing-halls rule.

    `row_subset` (int32 row ids) restricts the scan to those rows —
    feasibility, scores, `row_active` and `score_bias` are gathered at
    the subset and the winning slot maps back to its full row id.  When
    the subset provably contains every feasible row (the HD-compacted pod
    scan: GPU racks are HD-only), the result is bitwise identical to the
    full scan.

    `use_kernel=True` (static) computes the line-up power feasibility
    with the Pallas kernel (`row_feasible`); `interpret` runs it in
    Pallas interpret mode.  Scores are the jnp `row_scores` either way,
    so chosen rows, state updates and `ok` are bitwise identical to the
    jnp path: the kernel's feasibility is AND-ed with the identical
    row/hall constraints, and its comparisons see the same shares."""
    feas = row_feasible(jt, state, dep, n_in_row, rows=row_subset,
                        use_kernel=use_kernel, interpret=interpret)
    score = row_scores(jt, state, dep, n_in_row, policy, key,
                       rows=row_subset)
    if row_subset is None:
        feas = feas & row_active
        if score_bias is not None:
            score = score + score_bias
    else:
        feas = feas & row_active[row_subset]
        if score_bias is not None:
            score = score + score_bias[row_subset]
    score = jnp.where(feas, score, _BIG)
    slot = jnp.argmin(score)
    ok = feas[slot]
    row = slot if row_subset is None else row_subset[slot]
    new_state = _apply_to_row(jt, state, dep, n_in_row, row)
    return _tree_where(ok, new_state, state), ok, jnp.where(ok, row, -1)


def place_cluster_in_row(jt: JaxTopology, state: HallState,
                         dep: Deployment, policy, key, row_active,
                         score_bias=None, use_kernel: bool = False,
                         interpret: bool = False):
    """`place_in_row` for a whole single-row cluster, with its result
    expanded to the `[MAX_POD_RACKS]` rows/counts registry convention
    `place` uses.  Returns (state', ok, rows, counts, row) — the shared
    cluster path of `place`, the fleet scan, and the single-hall
    simulator."""
    st, ok, row = place_in_row(jt, state, dep, dep.n_racks, policy, key,
                               row_active, score_bias=score_bias,
                               use_kernel=use_kernel, interpret=interpret)
    rows = jnp.full((MAX_POD_RACKS,), -1, jnp.int32).at[0].set(row)
    counts = jnp.zeros((MAX_POD_RACKS,)).at[0].set(
        jnp.where(ok, dep.n_racks.astype(jnp.float32), 0.0))
    return st, ok, rows, counts, row


@jax.named_scope("repro.placement.place_pod")
def _place_pod(jt: JaxTopology, state: HallState, dep: Deployment,
               policy, key, row_active, max_racks: int = MAX_POD_RACKS,
               hd_scan: int | None = None, use_kernel: bool = False,
               interpret: bool = False):
    """Place a GPU pod rack-by-rack; all racks must land in the same power
    domain (cross-row cables, paper §4.1); atomic commit.

    `max_racks` is the static rack-scan length; callers that know the
    largest pod in their trace (the split-trace scans) pass it to skip
    dead scan steps — it must be ≥ every pod's `n_racks`.  The returned
    registry rows/counts are always `[MAX_POD_RACKS]`.

    `hd_scan` (static, ≥ the topology's HD-row count) restricts each
    rack's row search to the compacted HD view `jt.hd_index[:hd_scan]`:
    GPU pods are HD-only (`row_feasible`'s `hd_ok`), so skipping LD and
    padding rows is bitwise identical to the full scan while cutting the
    per-rack feasibility/score work to the HD share of the hall."""
    state0 = state
    subset = None if hd_scan is None else jt.hd_index[:hd_scan]

    def body(carry, i):
        st, all_ok, dom = carry
        k = jax.random.fold_in(key, i)
        active = row_active & ((dom < 0) | (jt.row_domain == dom))
        st2, ok, row = place_in_row(jt, st, dep, 1, policy, k, active,
                                    row_subset=subset,
                                    use_kernel=use_kernel,
                                    interpret=interpret)
        live = i < dep.n_racks
        st = _tree_where(live, st2, st)
        all_ok = all_ok & (ok | ~live)
        dom = jnp.where(live & ok & (dom < 0), jt.row_domain[jnp.maximum(row, 0)], dom)
        return (st, all_ok, dom), jnp.where(live, row, -1)

    (state_n, ok, _), rows = jax.lax.scan(
        body, (state, jnp.asarray(True), jnp.asarray(-1, jnp.int32)),
        jnp.arange(max_racks))
    if max_racks < MAX_POD_RACKS:
        rows = jnp.concatenate(
            [rows, jnp.full((MAX_POD_RACKS - max_racks,), -1, jnp.int32)])
    counts = jnp.where((rows >= 0) & ok, 1.0, 0.0)
    rows = jnp.where(ok, rows, -1)
    return _tree_where(ok, state_n, state0), ok, rows, counts


def place(jt: JaxTopology, state: HallState, dep: Deployment, policy, key,
          row_active=None, use_kernel: bool = False,
          interpret: bool = False):
    """Place one arrival (cluster or pod).

    Returns (state', ok, rows[MAX_POD_RACKS], counts[MAX_POD_RACKS]) where
    `rows`/`counts` record how many racks landed in each row (-1 padded) —
    the registry that harvesting / decommissioning consumes later.
    """
    if row_active is None:
        row_active = jnp.ones((jt.row_cap.shape[0],), bool)

    def cluster():
        return place_cluster_in_row(jt, state, dep, policy, key,
                                    row_active, use_kernel=use_kernel,
                                    interpret=interpret)[:4]

    return jax.lax.cond(
        dep.is_pod,
        lambda: _place_pod(jt, state, dep, policy, key, row_active,
                           use_kernel=use_kernel, interpret=interpret),
        cluster,
    )


@jax.named_scope("repro.placement.release_bulk")
def release_bulk(jt: JaxTopology, state: HallState, rows, counts, rack_kw,
                 is_gpu, tier, fraction) -> HallState:
    """Release `fraction` of the demand recorded by a batch of placement
    registries (harvest: fraction<1; decommission: fraction=1).

    rows/counts: [..., MAX_POD_RACKS] as returned by `place` (flattened ok),
    rack_kw/is_gpu/tier/fraction: per-event [...] arrays.
    """
    R = jt.row_cap.shape[0]
    rows = rows.reshape(-1)
    n = (counts * fraction[..., None]).reshape(-1)
    d = rack_demand(rack_kw, is_gpu)                       # [..., N_RES]
    d = jnp.broadcast_to(d[..., None, :],
                         counts.shape + (N_RES,)).reshape(-1, N_RES)
    ha = jnp.broadcast_to((tier == TIER_HA)[..., None],
                          counts.shape).reshape(-1)
    valid = rows >= 0
    safe_rows = jnp.where(valid, rows, 0)
    rel = jnp.where(valid[:, None], n[:, None] * d, 0.0)   # [Nflat, N_RES]

    row_rel = jax.ops.segment_sum(rel, safe_rows, R)       # [R, N_RES]
    row_rel_ha = jax.ops.segment_sum(rel[:, POWER] * ha, safe_rows, R)
    row_load = state.row_load - row_rel

    # distribute row power release back over feeds (balanced shares)
    nf = jnp.maximum(jt.row_nfeeds, 1).astype(jnp.float32)
    feeds_valid = jt.row_feeds >= 0
    safe_feeds = jnp.where(feeds_valid, jt.row_feeds, 0)
    X = jt.lineup_cap.shape[0]
    per_feed_tot = jnp.where(feeds_valid, (row_rel[:, POWER] / nf)[:, None], 0.0)
    per_feed_ha = jnp.where(feeds_valid, (row_rel_ha / nf)[:, None], 0.0)
    lineup_tot = state.lineup_tot - jax.ops.segment_sum(
        per_feed_tot.reshape(-1), safe_feeds.reshape(-1), X)
    lineup_ha = state.lineup_ha - jax.ops.segment_sum(
        per_feed_ha.reshape(-1), safe_feeds.reshape(-1), X)

    H = jt.hall_liq_cap.shape[0]
    hall_liq = state.hall_liq - jax.ops.segment_sum(
        row_rel[:, LIQ], jt.row_hall, H)
    return HallState(row_load, lineup_ha, lineup_tot, hall_liq,
                     state.rr_cursor)


def remove_from_row(jt: JaxTopology, state: HallState, rack_kw, is_gpu,
                    tier, row, n_racks=1, fraction=1.0) -> HallState:
    """Release `fraction` of `n_racks` racks' demand from `row` (harvest /
    decommission, paper §4.1)."""
    n = jnp.asarray(n_racks, jnp.float32) * jnp.asarray(fraction, jnp.float32)
    d = rack_demand(rack_kw, is_gpu)
    P = n * rack_kw
    row_load = state.row_load.at[row].add(-n * d)
    feeds = jt.row_feeds[row]
    valid = feeds >= 0
    safe = jnp.where(valid, feeds, 0)
    nf = jnp.maximum(jt.row_nfeeds[row], 1).astype(jnp.float32)
    share = jnp.where(valid, P / nf, 0.0)
    is_ha = jnp.asarray(tier, jnp.int32) == TIER_HA
    lineup_ha = state.lineup_ha.at[safe].add(-jnp.where(is_ha, share, 0.0))
    lineup_tot = state.lineup_tot.at[safe].add(-share)
    hall_liq = state.hall_liq.at[jt.row_hall[row]].add(-n * d[LIQ])
    return HallState(row_load, lineup_ha, lineup_tot, hall_liq, state.rr_cursor)


# ---------------------------------------------------------------------------
# Stranding metrics (paper §4.3).
# ---------------------------------------------------------------------------

@jax.named_scope("repro.placement.stranding")
def lineup_stranding(jt: JaxTopology, state: HallState) -> jax.Array:
    """Per-line-up unused fraction of *effective HA* capacity.  At
    saturation (placements failing) this is the stranded fraction."""
    eff = jt.ha_frac * jt.lineup_cap
    frac = (eff - state.lineup_ha) / jnp.maximum(eff, 1.0)
    return jnp.where(jt.lineup_is_active, jnp.clip(frac, 0.0, 1.0), 0.0)


@jax.named_scope("repro.placement.stranding")
def hall_stranding(jt: JaxTopology, state: HallState) -> jax.Array:
    """Per-hall unused fraction of effective HA capacity, shape [H].

    Hall membership comes from the topology's real line-up→hall map
    (`lineup_hall`), not an `arange // (X // H)` guess — the latter
    silently mis-bins line-ups whenever the line-up count is not an
    exact per-hall tiling.  In-repo `build_topology` grids always tile
    evenly, so this hardens hand-built / custom topologies (uneven hall
    sizes) rather than changing any pipeline result."""
    eff = jt.ha_frac * jt.lineup_cap * jt.lineup_is_active
    H = jt.hall_liq_cap.shape[0]
    eff_h = jax.ops.segment_sum(eff, jt.lineup_hall, H)
    load_h = jax.ops.segment_sum(state.lineup_ha * jt.lineup_is_active,
                                 jt.lineup_hall, H)
    return jnp.clip((eff_h - load_h) / jnp.maximum(eff_h, 1.0), 0.0, 1.0)


def deployed_kw(state: HallState) -> jax.Array:
    """Deployed power: the rows' power loads added in a fixed pairwise
    order.  `jnp.sum` leaves the order to the compiler, and on a TPU v5e
    programs that differ only in batch shape or placement path ordered
    it differently, moving the total by a few ulps; explicit adds keep
    it bitwise equal across them (padding adds exact zeros)."""
    x = state.row_load[:, POWER]
    n = 1 << (x.shape[0] - 1).bit_length()
    x = jnp.pad(x, (0, n - x.shape[0]))
    while x.shape[0] > 1:
        x = x[:x.shape[0] // 2] + x[x.shape[0] // 2:]
    return x[0]
