"""Fleet-scale lifecycle simulator (paper §4.4, Fig. 8 pipeline).

Places a multi-year arrival trace across a growing fleet of identical
halls: opens a new hall when no feasible placement exists (instant
commissioning, §4.2), harvests racks one year after deployment, and
decommissions racks at end-of-life.

The whole lifecycle is ONE `jax.lax.scan` over months: hall-activation
bookkeeping (`act_month`) lives in the scan carry, and the per-month
p50/p90 stranding stats are either post-hoc reductions over the scanned
`[M, H]` history (`exact_quantiles=True`, the default and regression
reference) or O(1)-memory streaming histogram estimates computed inside
the scan body (`exact_quantiles=False`, see `repro.core.quantiles`).
`simulate_lifecycle` takes only device-typed arguments, so
`sweep.py` can `vmap` it over a batch of (design, scenario, policy,
seed) configurations; `run_fleet` is the thin single-configuration
wrapper that preserves the original `FleetResult` interface.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import cost, placement as pl, quantiles as qt
from .arrivals import EnvelopeSpec, Trace, generate_fleet_trace
from .hierarchy import DesignSpec, build_topology
from .placement import (DEFAULT_POLICY, Deployment, JaxTopology,
                        MAX_POD_RACKS)


@dataclass
class FleetConfig:
    design: DesignSpec
    env: EnvelopeSpec = field(default_factory=EnvelopeSpec)
    policy: int = DEFAULT_POLICY
    harvest: bool = True
    seed: int = 0
    n_halls_max: int = 0          # 0 → auto-size from demand
    mature_months: int = 12       # halls older than this enter tail stats


@dataclass
class FleetResult:
    months: np.ndarray            # [M]
    halls_active: np.ndarray      # [M]
    deployed_mw: np.ndarray       # [M]
    p50_stranding: np.ndarray     # [M] over mature halls
    p90_stranding: np.ndarray     # [M]
    final_hall_stranding: np.ndarray   # [H_active]
    final_lineup_stranding: np.ndarray  # [X_active] (active halls)
    n_halls_built: int
    final_deployed_mw: float
    placed_fraction: float
    design: DesignSpec = None
    env: EnvelopeSpec = None

    @property
    def initial_dpm(self):
        return cost.initial_dollars_per_mw(self.design)

    @property
    def effective_dpm(self):
        return cost.effective_dollars_per_mw(
            self.design, self.n_halls_built, self.final_deployed_mw)

    @property
    def total_capex(self):
        return self.n_halls_built * cost.hall_capex(self.design)


def _auto_halls(design: DesignSpec, env: EnvelopeSpec) -> int:
    # demand_multiplier() rescales cumulative demand under shock scenarios
    # (surge envelopes need more hall headroom; 1.0 for the paper grid)
    total_mw = (env.gpu_gw + env.compute_gw + env.storage_gw) * 1e3 \
        * env.demand_scale * env.demand_multiplier()
    # decommissioning returns capacity; 45% slack covers stranding + churn
    return int(np.ceil(total_mw / (design.ha_capacity_kw / 1e3) * 1.45)) + 4


class FleetTrace(NamedTuple):
    """Device-side trace columns consumed by the lifecycle scan."""
    month: jax.Array         # i32 [E]
    rack_kw: jax.Array       # f32 [E]
    n_racks: jax.Array       # i32 [E]
    is_gpu: jax.Array        # bool [E]
    is_pod: jax.Array        # bool [E]
    tier: jax.Array          # i32 [E]
    harvest_frac: jax.Array  # f32 [E]
    lifetime_m: jax.Array    # i32 [E]

    @staticmethod
    def from_trace(trace: Trace, pad_to: int | None = None,
                   pad_month: int = 0) -> "FleetTrace":
        """Pad to `pad_to` events with never-arriving placeholders
        (month = `pad_month`, which must be ≥ the simulated horizon)."""
        E = len(trace)
        n_pad = max(0, (pad_to or E) - E)

        def col(name, fill):
            a = np.asarray(getattr(trace, name))
            if n_pad:
                a = np.concatenate([a, np.full((n_pad,), fill, a.dtype)])
            return jnp.asarray(a)

        return FleetTrace(
            month=col("month", pad_month),
            rack_kw=col("rack_kw", 0.0),
            n_racks=col("n_racks", 1),
            is_gpu=col("is_gpu", False),
            is_pod=col("is_pod", False),
            tier=col("tier", 0),
            harvest_frac=col("harvest_frac", 0.0),
            lifetime_m=col("lifetime_m", 10 ** 6),
        )


def _month_e_max(trace: Trace, months: int,
                 select: np.ndarray | None = None) -> int:
    """Largest per-month event count (the inner scan length), optionally
    over the `select`-ed subset of events (split-trace pod/cluster
    windows)."""
    month = np.asarray(trace.month)
    if select is not None:
        month = month[np.asarray(select)]
    starts = np.searchsorted(month, np.arange(months))
    ends = np.searchsorted(month, np.arange(months), side="right")
    return max(1, int((ends - starts).max())) if len(month) else 1


def _month_slices(trace: Trace, months: int, e_max: int | None = None,
                  modulo: int | None = None,
                  select: np.ndarray | None = None):
    """Per-month event-index windows [M, e_max] plus validity mask.
    `modulo` must equal the (padded) device trace length.  With `select`
    (boolean event mask) the windows cover only the selected events —
    indices still refer to the full trace — which is how the split-trace
    scan gets separate pod and cluster windows per month."""
    month = np.asarray(trace.month)
    eids = None
    if select is not None:
        eids = np.flatnonzero(np.asarray(select))
        month = month[eids]
    starts = np.searchsorted(month, np.arange(months))
    ends = np.searchsorted(month, np.arange(months), side="right")
    e_max = e_max or (max(1, int((ends - starts).max()))
                      if len(month) else 1)
    pos = starts[:, None] + np.arange(e_max)[None, :]       # [M, e_max]
    valid = pos < ends[:, None]
    E = modulo or max(1, len(trace))
    if eids is None:
        idx = pos % E
    elif len(eids):
        idx = np.where(valid, eids[pos % len(eids)], 0)
    else:
        idx = np.zeros_like(pos)
    return idx.astype(np.int32), valid, e_max


def _pod_scan_len(traces) -> int:
    """Static rack-scan length for the split-trace pod path: the largest
    pod size across `traces` (capped at the `MAX_POD_RACKS` bound)."""
    n = 1
    for t in traces:
        pods = np.asarray(t.is_pod)
        if pods.any():
            n = max(n, int(np.asarray(t.n_racks)[pods].max()))
    return min(n, MAX_POD_RACKS)


def _event_windows(trace: Trace, months: int, split_pods: bool,
                   e_max: int | None = None, ep_max: int | None = None,
                   modulo: int | None = None):
    """(idx, valid, idx_pod, valid_pod) for `simulate_lifecycle`.

    `split_pods=True` partitions each month's window into pod events
    (placed first — the order generated traces already have) and cluster
    events; otherwise the first window covers all events and the pod
    window is a 1-wide all-invalid dummy (ignored by the compiled
    non-split paths).

    The split preserves placement order and PRNG keys ONLY when pods
    precede clusters within every month — always true for
    `generate_fleet_trace` output (GPU class emitted first, stable month
    sort).  Custom traces violating that order are rejected rather than
    silently reordered: sort them pods-first per month, or run with
    `legacy_pod_cond=True`."""
    if split_pods:
        pod = np.asarray(trace.is_pod)
        month = np.asarray(trace.month)
        same_month = month[1:] == month[:-1]
        if bool(np.any(same_month & pod[1:] & ~pod[:-1])):
            raise ValueError(
                "split-trace scan needs pod events to precede cluster "
                "events within each month (the generated-trace order); "
                "sort the trace pods-first per month or use "
                "legacy_pod_cond=True")
        idx, valid, _ = _month_slices(trace, months, e_max=e_max,
                                      modulo=modulo, select=~pod)
        idx_p, valid_p, _ = _month_slices(trace, months, e_max=ep_max,
                                          modulo=modulo, select=pod)
    else:
        idx, valid, _ = _month_slices(trace, months, e_max=e_max,
                                      modulo=modulo)
        idx_p = np.zeros((months, ep_max or 1), np.int32)
        valid_p = np.zeros((months, ep_max or 1), bool)
    return idx, valid, idx_p, valid_p


class SimOutputs(NamedTuple):
    """Device outputs of one lifecycle (leading batch dim under vmap)."""
    halls_active: jax.Array         # [M] i32
    deployed_kw: jax.Array          # [M] f32
    p50_stranding: jax.Array        # [M] f32
    p90_stranding: jax.Array        # [M] f32
    final_hall_stranding: jax.Array    # [H] f32
    final_lineup_stranding: jax.Array  # [X] f32
    n_halls_built: jax.Array        # [] i32
    final_deployed_kw: jax.Array    # [] f32
    placed_fraction: jax.Array      # [] f32


def _masked_percentiles(x, mask, qs):
    """np.percentile('linear') over x[mask] for each static q in `qs`
    (one shared sort); an all-False mask yields NaN (the undefined
    quantile's explicit sentinel — it used to leak the +inf sort
    padding instead)."""
    s = jnp.sort(jnp.where(mask, x, jnp.inf))
    nonempty = jnp.any(mask)
    top = (jnp.maximum(jnp.sum(mask), 1) - 1).astype(jnp.float32)
    out = []
    for q in qs:
        pos = q / 100.0 * top
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.ceil(pos).astype(jnp.int32)
        frac = pos - lo.astype(jnp.float32)
        out.append(jnp.where(nonempty,
                             s[lo] * (1.0 - frac) + s[hi] * frac,
                             jnp.nan))
    return tuple(out)


def _mature_mask(am, m, mature_months):
    """Which halls enter month `m`'s tail stats: active halls older than
    `mature_months`, falling back to all active halls while none are."""
    mature = (am >= 0) & (am <= m - mature_months)
    return jnp.where(jnp.any(mature), mature, am >= 0)


_NEW_HALL_BIAS = 1e6   # keeps placements in existing halls when feasible


def simulate_lifecycle(jt: JaxTopology, ft: FleetTrace, idx, valid,
                       idx_pod, valid_pod, policy, seed, h_cap, n_real, *,
                       harvest: bool, mature_months: int,
                       with_pods: bool = True,
                       legacy_pod_cond: bool = False,
                       pod_scan_len: int = MAX_POD_RACKS,
                       hd_scan: int | None = None,
                       use_kernel: bool = False,
                       kernel_interpret: bool = False,
                       exact_quantiles: bool = True,
                       quantile_bins: int | None = None) -> SimOutputs:
    """Run the full monthly lifecycle as a single `lax.scan`.

    All positional arguments are device-typed (vmap-able); `harvest`,
    `mature_months`, `with_pods` and `legacy_pod_cond` are static.
    `h_cap` caps hall opening per configuration (padded fleets share a
    larger static hall count).

    Placement is cost-shaped by the trace's content, because `vmap`
    evaluates both sides of every `lax.cond`:

    * `with_pods=False` (no multi-row pods): `idx`/`valid` window ALL
      events and each is placed with one biased attempt over
      `halls < n+1` — exactly equivalent to the try-then-open-a-hall
      retry for single-row clusters (a failed first attempt means no
      existing-hall row is feasible, so the biased argmin picks the same
      row either way) and roughly an order of magnitude cheaper batched.
    * `with_pods=True` (split-trace scan): each month runs TWO scans —
      `idx_pod`/`valid_pod` window the month's pod events (placed by
      `placement._place_pod` with the attempt/retry pair, which pods
      genuinely need: a pod that fails in existing halls must retry
      whole against the new hall), then `idx`/`valid` window the
      cluster events (cheap biased attempt).  Cluster events no longer
      pay for the 8-step pod scan and pods no longer pay for the
      cluster branch.  Trace order is preserved because generated
      traces emit pods before clusters within every month (GPU class
      first, stable month sort); PRNG keys stay aligned with the
      interleaved order via the per-month pod-count offset.
      `pod_scan_len` (static, ≥ the largest pod's `n_racks`) trims the
      rack scan to the batch's real max pod size instead of the
      `MAX_POD_RACKS` bound, and `hd_scan` (static, ≥ the batch's
      HD-row count) restricts each pod rack's row search to the
      compacted HD view `jt.hd_index[:hd_scan]` — GPU pods are HD-only,
      so the trim is bitwise inert (see `placement._place_pod`).
    * `legacy_pod_cond=True` (benchmark/regression reference): the
      pre-split behavior — `idx`/`valid` window ALL events and each one
      runs `placement.place`'s `lax.cond(is_pod, …)` plus the retry
      `lax.cond`, evaluating both pod and cluster branches per event
      under `vmap`.  `benchmarks/run.py --only pod_sweep_speedup`
      measures the split-trace win against exactly this path.

    `use_kernel` / `kernel_interpret` (static) route every placement's
    line-up power feasibility through the Pallas kernel
    (bitwise-identical results; see `placement.place_in_row`).

    `exact_quantiles` (static) selects the p50/p90 stranding path:

    * `True` (default, the regression reference — the `legacy_pod_cond`
      pattern): the scan emits the full `[M, H]` stranding/activation
      history and the percentiles are post-hoc `_masked_percentiles`
      reductions — exact, but O(M·H) memory per configuration.
    * `False` (streaming): each month's `[H]` stranding cross-section is
      folded into a `quantile_bins`-bucket histogram estimate *inside
      the scan body* (`quantiles.hist_masked_quantiles`), so the scan
      emits two scalars per month and no `[M, H]` history is ever
      materialized — O(1) stats memory per configuration, absolute
      error ≤ `1 / quantile_bins` (default `quantiles.DEFAULT_BINS`,
      512 → ≤ 0.2%).  This is the path giant grids compile
      (`benchmarks/run.py --only giant_grid`).
    """
    H = jt.hall_liq_cap.shape[0]
    E = ft.month.shape[0]
    M = idx.shape[0]
    split_pods = with_pods and not legacy_pod_cond
    n_bins = quantile_bins or qt.DEFAULT_BINS

    state = pl.init_state_from(jt)
    reg_rows = jnp.full((E, MAX_POD_RACKS), -1, jnp.int32)
    reg_counts = jnp.zeros((E, MAX_POD_RACKS), jnp.float32)
    placed = jnp.zeros((E,), bool)
    harvested = jnp.zeros((E,), bool)
    removed = jnp.zeros((E,), bool)
    n_active = jnp.asarray(1, jnp.int32)
    act_month = jnp.full((H,), -1, jnp.int32).at[0].set(0)
    key = jax.random.PRNGKey(jnp.asarray(seed, jnp.int32) + 1)
    policy = jnp.asarray(policy, jnp.int32)
    h_cap = jnp.asarray(h_cap, jnp.int32)

    # ---- placement modes (see docstring) ----
    def place_cluster(st, n_act, dep, k, n_try):
        """One biased attempt over halls < n_try (single-row clusters)."""
        bias = jnp.where(jt.row_hall >= n_act, _NEW_HALL_BIAS, 0.0)
        st_f, ok_f, rows_f, counts_f, row = pl.place_cluster_in_row(
            jt, st, dep, policy, k, jt.row_hall < n_try, score_bias=bias,
            use_kernel=use_kernel, interpret=kernel_interpret)
        in_existing = ok_f & (jt.row_hall[jnp.maximum(row, 0)] < n_act)
        n_f = jnp.where(in_existing, n_act, n_try)
        return st_f, ok_f, rows_f, counts_f, n_f

    def place_pod(st, n_act, dep, k, n_try):
        """Pod attempt in existing halls, whole-pod retry incl. the new
        hall (pods need the atomic retry: a partial fit must not lock a
        domain the full pod cannot share)."""
        st1, ok1, rows1, counts1 = pl._place_pod(jt, st, dep, policy, k,
                                                 jt.row_hall < n_act,
                                                 max_racks=pod_scan_len,
                                                 hd_scan=hd_scan,
                                                 use_kernel=use_kernel,
                                                 interpret=kernel_interpret)

        def retry():
            st2, ok2, rows2, counts2 = pl._place_pod(
                jt, st, dep, policy, k, jt.row_hall < n_try,
                max_racks=pod_scan_len, hd_scan=hd_scan,
                use_kernel=use_kernel, interpret=kernel_interpret)
            return st2, ok2, rows2, counts2, n_try

        return jax.lax.cond(
            ok1, lambda: (st1, ok1, rows1, counts1, n_act), retry)

    def place_any(st, n_act, dep, k, n_try):
        """Pre-split reference: `place`'s is_pod cond + attempt/retry."""
        def attempt(n):
            return pl.place(jt, st, dep, policy, k, jt.row_hall < n,
                            use_kernel=use_kernel,
                            interpret=kernel_interpret)

        st1, ok1, rows1, counts1 = attempt(n_act)

        def retry():
            st2, ok2, rows2, counts2 = attempt(n_try)
            return st2, ok2, rows2, counts2, n_try

        return jax.lax.cond(
            ok1, lambda: (st1, ok1, rows1, counts1, n_act), retry)

    def scan_events(carry, idx_m, valid_m, mkey, key_off, place_fn):
        """Inner event scan shared by every mode.  `key_off` keeps the
        per-event fold_in keys aligned with the interleaved event order
        when a month is split into pod + cluster scans."""
        def body(carry, i):
            st, n_act, rr, rc, plcd = carry
            e = idx_m[i]
            dep = Deployment(ft.rack_kw[e], ft.n_racks[e], ft.is_gpu[e],
                             ft.tier[e], ft.is_pod[e])
            k = jax.random.fold_in(mkey, key_off + i)
            n_try = jnp.minimum(n_act + 1, h_cap)
            st_f, ok_f, rows_f, counts_f, n_f = place_fn(st, n_act, dep,
                                                         k, n_try)
            live = valid_m[i]
            ok_f = ok_f & live
            st = pl._tree_where(ok_f, st_f, st)
            n_act = jnp.where(live, n_f, n_act)
            rr = rr.at[e].set(jnp.where(ok_f, rows_f, rr[e]))
            rc = rc.at[e].set(jnp.where(ok_f, counts_f, rc[e]))
            plcd = plcd.at[e].set(jnp.where(live, ok_f, plcd[e]))
            return (st, n_act, rr, rc, plcd), None

        return jax.lax.scan(body, carry,
                            jnp.arange(idx_m.shape[0]))[0]

    def month_step(carry, xs):
        (state, reg_rows, reg_counts, placed, harvested, removed,
         n_active, act_month) = carry
        m, idx_m, valid_m, idx_pod_m, valid_pod_m = xs
        mkey = jax.random.fold_in(key, m)

        # ---- 1. decommission expired racks ----
        expire = placed & ~removed & (ft.month + ft.lifetime_m <= m)
        frac_dec = jnp.where(
            expire, 1.0 - jnp.where(harvested, ft.harvest_frac, 0.0), 0.0)
        state = pl.release_bulk(jt, state, reg_rows, reg_counts,
                                ft.rack_kw, ft.is_gpu, ft.tier, frac_dec)
        removed = removed | expire

        # ---- 2. harvest one-year-old racks ----
        if harvest:
            h = placed & ~removed & ~harvested & (ft.month + 12 <= m)
            state = pl.release_bulk(jt, state, reg_rows, reg_counts,
                                    ft.rack_kw, ft.is_gpu, ft.tier,
                                    jnp.where(h, ft.harvest_frac, 0.0))
            harvested = harvested | h

        # ---- 3. place this month's arrivals ----
        pcarry = (state, n_active, reg_rows, reg_counts, placed)
        if split_pods:
            # pods first (the generated order), then clusters with the
            # fold_in offset continuing where the pod window left off
            pcarry = scan_events(pcarry, idx_pod_m, valid_pod_m, mkey,
                                 jnp.zeros((), jnp.int32), place_pod)
            n_pods = jnp.sum(valid_pod_m.astype(jnp.int32))
            pcarry = scan_events(pcarry, idx_m, valid_m, mkey, n_pods,
                                 place_cluster)
        elif with_pods:
            pcarry = scan_events(pcarry, idx_m, valid_m, mkey,
                                 jnp.zeros((), jnp.int32), place_any)
        else:
            pcarry = scan_events(pcarry, idx_m, valid_m, mkey,
                                 jnp.zeros((), jnp.int32), place_cluster)
        state, n_active, reg_rows, reg_counts, placed = pcarry

        act_month = jnp.where(
            (act_month < 0) & (jnp.arange(H) < n_active), m, act_month)
        carry = (state, reg_rows, reg_counts, placed, harvested, removed,
                 n_active, act_month)
        hs_m = pl.hall_stranding(jt, state)
        if exact_quantiles:
            ys = (hs_m, act_month)
        else:
            with jax.named_scope("repro.fleet.month_stats"):
                ys = qt.hist_masked_quantiles(
                    hs_m, _mature_mask(act_month, m, mature_months),
                    (50.0, 90.0), n_bins=n_bins)
        return carry, (n_active, pl.deployed_kw(state)) + ys

    carry0 = (state, reg_rows, reg_counts, placed, harvested, removed,
              n_active, act_month)
    xs = (jnp.arange(M, dtype=jnp.int32), jnp.asarray(idx),
          jnp.asarray(valid), jnp.asarray(idx_pod),
          jnp.asarray(valid_pod))
    carry, (halls, deployed, y3, y4) = jax.lax.scan(
        month_step, carry0, xs)
    state, placed = carry[0], carry[3]

    if exact_quantiles:
        # ---- post-hoc percentile reductions over the scanned history ----
        def stats(hs, am, m):
            return _masked_percentiles(
                hs, _mature_mask(am, m, mature_months), (50.0, 90.0))

        with jax.named_scope("repro.fleet.month_stats"):
            p50, p90 = jax.vmap(stats)(y3, y4,
                                       jnp.arange(M, dtype=jnp.int32))
    else:
        p50, p90 = y3, y4

    # padding events are never placed, so the sum counts only real events
    pf = jnp.sum(placed.astype(jnp.float32)) / \
        jnp.maximum(jnp.asarray(n_real, jnp.float32), 1.0)
    return SimOutputs(
        halls_active=halls, deployed_kw=deployed,
        p50_stranding=p50, p90_stranding=p90,
        final_hall_stranding=pl.hall_stranding(jt, state),
        final_lineup_stranding=pl.lineup_stranding(jt, state),
        n_halls_built=carry[6], final_deployed_kw=pl.deployed_kw(state),
        placed_fraction=pf)


@functools.partial(jax.jit,
                   static_argnames=("harvest", "mature_months", "with_pods",
                                    "legacy_pod_cond", "pod_scan_len",
                                    "hd_scan", "use_kernel",
                                    "kernel_interpret", "exact_quantiles",
                                    "quantile_bins"))
def _simulate_jit(jt, ft, idx, valid, idx_pod, valid_pod, policy, seed,
                  h_cap, n_real, harvest, mature_months, with_pods,
                  legacy_pod_cond=False, pod_scan_len=MAX_POD_RACKS,
                  hd_scan=None, use_kernel=False, kernel_interpret=False,
                  exact_quantiles=True, quantile_bins=None):
    return simulate_lifecycle(jt, ft, idx, valid, idx_pod, valid_pod,
                              policy, seed, h_cap, n_real, harvest=harvest,
                              mature_months=mature_months,
                              with_pods=with_pods,
                              legacy_pod_cond=legacy_pod_cond,
                              pod_scan_len=pod_scan_len, hd_scan=hd_scan,
                              use_kernel=use_kernel,
                              kernel_interpret=kernel_interpret,
                              exact_quantiles=exact_quantiles,
                              quantile_bins=quantile_bins)


def make_fleet_result(out, months: int, lineups_per_hall: int,
                      lineup_is_active: np.ndarray, design: DesignSpec,
                      env: EnvelopeSpec) -> FleetResult:
    """Host-side unpack of (per-configuration) `SimOutputs` into the
    public `FleetResult` (shared by `run_fleet` and `sweep.result`)."""
    na = int(out.n_halls_built)
    hs = np.asarray(out.final_hall_stranding)
    lstr = np.asarray(out.final_lineup_stranding)
    active_lineups = np.arange(lstr.shape[0]) // lineups_per_hall < na
    active_mask = np.asarray(lineup_is_active) & active_lineups
    return FleetResult(
        months=np.arange(months),
        halls_active=np.asarray(out.halls_active),
        deployed_mw=np.asarray(out.deployed_kw) / 1e3,
        p50_stranding=np.asarray(out.p50_stranding),
        p90_stranding=np.asarray(out.p90_stranding),
        final_hall_stranding=hs[:na],
        final_lineup_stranding=lstr[active_mask],
        n_halls_built=na,
        final_deployed_mw=float(out.final_deployed_kw) / 1e3,
        placed_fraction=float(out.placed_fraction),
        design=design, env=env,
    )


def run_fleet(cfg: FleetConfig, trace: Trace | None = None,
              use_kernel: bool | None = None,
              kernel_interpret: bool = False,
              exact_quantiles: bool = True,
              quantile_bins: int | None = None) -> FleetResult:
    """Single-configuration lifecycle (thin wrapper over the scanned
    engine).

    Builds the hall topology at its *exact* shape (no sweep padding),
    generates (or takes) the arrival trace, and runs the jitted
    `simulate_lifecycle` scan once.  This is the reference semantics the
    sweep engine is tested against: for a grid of configurations use
    `repro.core.sweep.sweep` (one vmapped call) or
    `repro.core.sweep.sharded_sweep` (vmapped + sharded over devices),
    whose `result(i)` reproduces this function's `FleetResult` up to
    float-padding noise.

    Args:
        cfg: design/envelope/policy/seed bundle (see `FleetConfig`).
        trace: optional pre-generated arrival trace; defaults to
            `generate_fleet_trace(cfg.env, cfg.seed)`.
        use_kernel: route placement's line-up power feasibility through
            the Pallas kernel (bitwise-identical results); `None` = backend
            default
            (`placement.default_use_kernel`: TPU on, CPU off).
        kernel_interpret: run the kernel in Pallas interpret mode (CPU
            CI fallback; only meaningful with the kernel path on).
        exact_quantiles: `True` (default) computes p50/p90 stranding as
            the exact post-hoc reduction over the `[M, H]` history;
            `False` compiles the O(1)-memory streaming histogram path
            (error ≤ `1 / quantile_bins`; see `simulate_lifecycle`).
        quantile_bins: streaming-histogram resolution (default
            `quantiles.DEFAULT_BINS`); ignored when exact.

    Returns:
        `FleetResult` with monthly [M] trajectories (halls active,
        deployed MW, p50/p90 mature-hall stranding), final per-hall
        [n_halls_built] and per-active-line-up stranding, and the cost
        roll-ups (`initial_dpm`, `effective_dpm`, `total_capex`).
    """
    design, env = cfg.design, cfg.env
    if trace is None:
        trace = generate_fleet_trace(env, cfg.seed)
    months = env.n_months
    H = cfg.n_halls_max or _auto_halls(design, env)
    topo = build_topology(design, H)
    jt = pl.jax_topology(topo)
    ft = FleetTrace.from_trace(trace)
    with_pods = bool(np.asarray(trace.is_pod).any())
    idx, valid, idx_p, valid_p = _event_windows(trace, months, with_pods)

    out = _simulate_jit(jt, ft, jnp.asarray(idx), jnp.asarray(valid),
                        jnp.asarray(idx_p), jnp.asarray(valid_p),
                        jnp.asarray(cfg.policy, jnp.int32),
                        jnp.asarray(cfg.seed, jnp.int32),
                        jnp.asarray(H, jnp.int32),
                        jnp.asarray(len(trace), jnp.int32),
                        harvest=cfg.harvest,
                        mature_months=cfg.mature_months,
                        with_pods=with_pods,
                        pod_scan_len=_pod_scan_len([trace]),
                        hd_scan=topo.n_hd_rows,
                        use_kernel=pl.resolve_use_kernel(use_kernel),
                        kernel_interpret=kernel_interpret,
                        exact_quantiles=exact_quantiles,
                        quantile_bins=quantile_bins)
    return make_fleet_result(out, months, topo.lineups_per_hall,
                             topo.lineup_is_active, design, env)
