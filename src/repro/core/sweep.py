"""Batched fleet-sweep engine (paper §5–6 evaluation methodology).

The paper's deployable-capacity claims are joint over designs, arrival
scenarios, placement policies, and stochastic seeds — a grid of
lifecycle simulations, not one run.  This module evaluates such a grid
as ONE jitted + vmapped call: every configuration's topology is padded
to a common static shape (`hierarchy.build_topology` padding), traces
are padded to a common event count, and `fleet.simulate_lifecycle` is
`vmap`-ed over the whole `SweepAxes` batch.

    axes = SweepAxes.product(designs=[get_design("4N/3"), get_design("3+1")],
                             envs=[EnvelopeSpec(gpu_scenario=s)
                                   for s in ("med", "high")],
                             seeds=(0, 1))
    res = sweep(axes)                      # one compiled call, 8 configs
    res.p90_stranding[i, -1], res.effective_dpm[i], res.result(i) ...

On a multi-device host, `sharded_sweep` splits the same batch over the
named 2-D (config × trial) mesh (`repro.sharding.axes.sweep_mesh`) with
`shard_map`, so each device simulates only its own slab of
configurations; `chunk_size` streams giant grids through one compiled
executable with donated input buffers, and `exact_quantiles=False`
swaps the per-config `[M, H]` stranding history for the O(1)-memory
streaming histogram quantiles (`repro.core.quantiles`):

    res = sharded_sweep(axes)              # == sweep(axes), D-way parallel
    res = sharded_sweep(axes, mesh_shape=(2, 2), chunk_size=256,
                        exact_quantiles=False)   # planet-scale settings

The configuration axis is embarrassingly parallel (no cross-config
collectives), so sharded and single-device results agree to float
tolerance; on one device `sharded_sweep` is a passthrough to `sweep`.
Simulated multi-device CPU runs use
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import functools
import itertools
import warnings
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from . import cost, placement as pl, throughput as tp
from .arrivals import EnvelopeSpec, Trace, generate_fleet_trace
from .fleet import (FleetConfig, FleetResult, FleetTrace, _auto_halls,
                    _event_windows, _month_e_max, _pod_scan_len,
                    make_fleet_result, simulate_lifecycle)
from .hierarchy import DesignSpec, SweepValidationError, build_topology
from .placement import DEFAULT_POLICY, MAX_POD_RACKS, POLICY_NAMES
from repro.runtime import spans
from repro.sharding import axes as shax


def _broadcast(seq, B, name):
    seq = list(seq)
    if len(seq) == 1:
        seq = seq * B
    if len(seq) != B:
        raise SweepValidationError(
            name, f"has length {len(seq)}, expected {B} (the batch size) "
            f"or 1 (broadcast)")
    return seq


@dataclass
class SweepAxes:
    """The configuration batch the engine vmaps over.

    Four aligned per-configuration lists of equal length ``B`` (the batch
    size): configuration ``i`` is ``(designs[i], envs[i], policies[i],
    seeds[i])``.  Length-1 lists broadcast to ``B`` in ``__post_init__``,
    so ``SweepAxes.zip(designs=[d], envs=envs_list)`` reuses one design
    across every envelope.

    Construct with:

    * `SweepAxes.zip` — aligned sequences, one entry per configuration.
    * `SweepAxes.product` — the full cross product (designs-major
      ordering: the seed axis varies fastest, designs slowest).

    `config(i)` recovers the i-th configuration as a sequential
    `fleet.FleetConfig`, which is how the equivalence tests compare a
    sweep against `fleet.run_fleet`.

    `tags` is an optional aligned list of free-form per-configuration
    labels (scenario generators use `"family:label"` — see
    `repro.core.scenarios`); it broadcasts like the other axes and rides
    along purely for reporting (`SweepResult.tags`).
    """
    designs: List[DesignSpec]
    envs: List[EnvelopeSpec]
    policies: List[int]
    seeds: List[int]
    tags: List[str] = field(default_factory=lambda: [""])

    def __len__(self):
        return len(self.designs)

    def __post_init__(self):
        B = max(len(self.designs), len(self.envs), len(self.policies),
                len(self.seeds), len(self.tags))
        self.designs = _broadcast(self.designs, B, "designs")
        self.envs = _broadcast(self.envs, B, "envs")
        self.policies = [int(p) for p in _broadcast(self.policies, B,
                                                    "policies")]
        self.seeds = [int(s) for s in _broadcast(self.seeds, B, "seeds")]
        self.tags = [str(t) for t in _broadcast(self.tags, B, "tags")]

    @staticmethod
    def zip(designs, envs, policies=(DEFAULT_POLICY,), seeds=(0,),
            tags=("",)) -> "SweepAxes":
        """Aligned per-configuration sequences (length-1 broadcasts)."""
        return SweepAxes(list(designs), list(envs), list(policies),
                         list(seeds), list(tags))

    @staticmethod
    def product(designs: Sequence[DesignSpec], envs: Sequence[EnvelopeSpec],
                policies: Sequence[int] = (DEFAULT_POLICY,),
                seeds: Sequence[int] = (0,),
                env_tags: Sequence[str] | None = None) -> "SweepAxes":
        """Full grid, designs-major ordering.  `env_tags` (aligned with
        `envs`) labels each envelope; the tag follows its envelope
        through the cross product."""
        env_tags = list(env_tags) if env_tags is not None else [""] * len(envs)
        if len(env_tags) != len(envs):
            raise ValueError(f"env_tags has length {len(env_tags)}, "
                             f"expected {len(envs)}")
        combos = list(itertools.product(designs, zip(envs, env_tags),
                                        policies, seeds))
        return SweepAxes([c[0] for c in combos], [c[1][0] for c in combos],
                         [c[2] for c in combos], [c[3] for c in combos],
                         [c[1][1] for c in combos])

    def config(self, i: int, harvest: bool = True,
               mature_months: int = 12) -> FleetConfig:
        """The i-th configuration as a sequential `FleetConfig`."""
        return FleetConfig(self.designs[i], self.envs[i],
                           policy=self.policies[i], seed=self.seeds[i],
                           harvest=harvest, mature_months=mature_months)

    def validate(self) -> "SweepAxes":
        """Raise `SweepValidationError` before any compile time is spent.

        Checks every distinct design and envelope (`DesignSpec.validate`
        / `EnvelopeSpec.validate`), policy ids, and horizon homogeneity.
        Distinct = by object identity, so a 10⁴-config grid sharing a
        handful of spec objects validates in microseconds."""
        if len(self) == 0:
            raise SweepValidationError(
                "designs", "empty sweep: zero configurations")
        seen: set = set()
        for d in self.designs:
            if id(d) not in seen:
                seen.add(id(d))
                d.validate()
        for e in self.envs:
            if id(e) not in seen:
                seen.add(id(e))
                e.validate()
        for i, p in enumerate(self.policies):
            if not 0 <= p < len(POLICY_NAMES):
                raise SweepValidationError(
                    "policies", f"policies[{i}] = {p} outside "
                    f"[0, {len(POLICY_NAMES)}); have {POLICY_NAMES}")
        horizons = {(e.start_year, e.end_year) for e in self.envs}
        if len(horizons) > 1:
            raise SweepValidationError(
                "envs", f"envelopes span different horizons: "
                f"{sorted(horizons)}; the lifecycle scan needs one common "
                f"month count")
        return self


@dataclass
class SweepResult:
    """Per-configuration metrics, leading axis = configuration."""
    axes: SweepAxes
    months: np.ndarray             # [M]
    halls_active: np.ndarray       # [B, M]
    deployed_mw: np.ndarray        # [B, M]
    p50_stranding: np.ndarray      # [B, M]
    p90_stranding: np.ndarray      # [B, M]
    final_hall_stranding: np.ndarray    # [B, H_max] (use n_halls_built)
    final_lineup_stranding: np.ndarray  # [B, X_tot]
    lineup_is_active: np.ndarray   # [B, X_tot]
    lineups_per_hall: int          # common padded per-hall line-up count
    n_halls_built: np.ndarray      # [B] int
    final_deployed_mw: np.ndarray  # [B]
    placed_fraction: np.ndarray    # [B]
    initial_dpm: np.ndarray        # [B] $/MW at commissioning
    effective_dpm: np.ndarray      # [B] lifecycle-effective $/MW
    total_capex: np.ndarray        # [B] $
    # --- metric stage (paper §5.4/§6.6: $/performance, not installed MW) ---
    provisioned_mw: np.ndarray = None   # [B] halls built × HA nameplate
    model_names: List[str] = field(default_factory=list)   # [Mdl]
    delivered_tps: np.ndarray = None         # [B, Mdl] fleet tokens/s
    tps_per_provisioned_w: np.ndarray = None  # [B, Mdl] tokens/s per built W
    dollars_per_tps: np.ndarray = None       # [B, Mdl] capex / delivered TPS
    # --- resilient execution (repro.core.resilience) ---
    report: object = None          # RunReport when run via resilient_sweep

    def __len__(self):
        return len(self.axes)

    @property
    def tags(self) -> List[str]:
        """Per-configuration labels (see `SweepAxes.tags`)."""
        return self.axes.tags

    def result(self, i: int) -> FleetResult:
        """Unpack configuration `i` into a sequential-style FleetResult."""
        out = SimpleNamespace(  # per-configuration SimOutputs view
            halls_active=self.halls_active[i],
            deployed_kw=self.deployed_mw[i] * 1e3,
            p50_stranding=self.p50_stranding[i],
            p90_stranding=self.p90_stranding[i],
            final_hall_stranding=self.final_hall_stranding[i],
            final_lineup_stranding=self.final_lineup_stranding[i],
            n_halls_built=self.n_halls_built[i],
            final_deployed_kw=self.final_deployed_mw[i] * 1e3,
            placed_fraction=self.placed_fraction[i])
        return make_fleet_result(out, len(self.months),
                                 self.lineups_per_hall,
                                 self.lineup_is_active[i],
                                 self.axes.designs[i], self.axes.envs[i])

    def results(self) -> List[FleetResult]:
        return [self.result(i) for i in range(len(self))]


@functools.partial(jax.jit,
                   static_argnames=("harvest", "mature_months", "with_pods",
                                    "legacy_pod_cond", "pod_scan_len",
                                    "hd_scan", "use_kernel",
                                    "kernel_interpret", "exact_quantiles",
                                    "quantile_bins"))
def _sweep_jit(jt, ft, idx, valid, idx_pod, valid_pod, policy, seed, h_cap,
               n_real, harvest, mature_months, with_pods,
               legacy_pod_cond=False, pod_scan_len=MAX_POD_RACKS,
               hd_scan=None, use_kernel=False, kernel_interpret=False,
               exact_quantiles=True, quantile_bins=None):
    fn = functools.partial(simulate_lifecycle, harvest=harvest,
                           mature_months=mature_months, with_pods=with_pods,
                           legacy_pod_cond=legacy_pod_cond,
                           pod_scan_len=pod_scan_len, hd_scan=hd_scan,
                           use_kernel=use_kernel,
                           kernel_interpret=kernel_interpret,
                           exact_quantiles=exact_quantiles,
                           quantile_bins=quantile_bins)
    return jax.vmap(fn)(jt, ft, idx, valid, idx_pod, valid_pod, policy,
                        seed, h_cap, n_real)


@functools.partial(jax.jit,
                   static_argnames=("harvest", "mature_months", "with_pods",
                                    "pod_scan_len", "hd_scan", "use_kernel",
                                    "kernel_interpret", "exact_quantiles",
                                    "quantile_bins", "mesh"),
                   donate_argnums=tuple(range(10)))
def _sharded_sweep_jit(jt, ft, idx, valid, idx_pod, valid_pod, policy, seed,
                       h_cap, n_real, harvest, mature_months, with_pods,
                       pod_scan_len, hd_scan, use_kernel, kernel_interpret,
                       exact_quantiles, quantile_bins, mesh):
    """`_sweep_jit` with the flat configuration batch split over `mesh`
    (2-D config × trial; the batch product-shards over both axes via
    `batch_spec`, so a (D, 1) mesh reproduces the historical 1-D
    layout): each device vmaps only its own B/(dc·dt) slab.  No
    collectives — configurations are independent — so out_specs keep
    everything batch-sharded.  All ten operand buffers are donated: a
    chunk's inputs die the moment its dispatch is queued, which is what
    keeps per-device memory flat while `sharded_sweep` streams chunks."""
    fn = functools.partial(simulate_lifecycle, harvest=harvest,
                           mature_months=mature_months, with_pods=with_pods,
                           pod_scan_len=pod_scan_len, hd_scan=hd_scan,
                           use_kernel=use_kernel,
                           kernel_interpret=kernel_interpret,
                           exact_quantiles=exact_quantiles,
                           quantile_bins=quantile_bins)
    spec = shax.batch_spec()
    sharded = jax.shard_map(jax.vmap(fn), mesh=mesh,
                            in_specs=(spec,) * 10, out_specs=spec,
                            check_vma=False)
    return sharded(jt, ft, idx, valid, idx_pod, valid_pod, policy, seed,
                   h_cap, n_real)


@spans.spanned("repro.sweep.prepare")
def _prepare(axes: SweepAxes, n_halls_max: int,
             traces: Sequence[Trace] | None,
             legacy_pod_cond: bool = False):
    """Host-side batch assembly shared by `sweep` and `sharded_sweep`.

    Pads every configuration to common static shapes, **bucketed** so
    sweeps over new seeds/scenarios reuse the compiled executable
    (jit-cache hit):

    * hall cap `H_max` — max auto-sized hall count, bucketed to 4;
    * rows/line-ups per hall — max over designs (zero-capacity padding
      rows are never feasible, padded line-ups are inactive);
    * trace events `E_max` — max trace length, bucketed to 64
      (padding events arrive at month `M`, beyond the horizon);
    * per-month event windows — max monthly cluster count bucketed to 4
      and, for pod traces on the split-trace path, max monthly pod
      count bucketed to 2 (pod scan steps are ~8× a cluster step, so
      the pod window is padded more tightly).

    Returns `(args, months, topos, X_pad, with_pods, pod_scan_len,
    hd_scan)` where `args` is the 10-tuple of stacked device inputs for
    `simulate_lifecycle` (leading axis = configuration), `topos` the
    per-configuration padded host topologies, and the trailing statics
    trim the pod rack scan / compacted HD row view.
    `legacy_pod_cond=True` windows all events together for the
    pre-split reference path (see `simulate_lifecycle`).

    Counts on its span: real trace `events`, the monthly scan's
    `event_slots`, padded hall `rows`, the stacked inputs' `h2d_bytes`
    and `hall_local_configs`, the configurations whose topology takes
    the hall-local placement path (`placement.JaxTopology`).
    """
    axes.validate()          # precise SweepValidationErrors, pre-compile
    B = len(axes)
    months = axes.envs[0].n_months

    if traces is None:
        traces = [generate_fleet_trace(e, s)
                  for e, s in zip(axes.envs, axes.seeds)]
    if len(traces) != B:
        raise SweepValidationError(
            "traces", f"need one trace per configuration: got "
            f"{len(traces)} traces for {B} configurations")

    def bucket(n, q):
        return int(np.ceil(max(n, 1) / q) * q)

    h_caps = [n_halls_max or _auto_halls(d, e)
              for d, e in zip(axes.designs, axes.envs)]
    H_max = bucket(max(h_caps), 4)
    R_pad = max(d.n_rows for d in axes.designs)
    X_pad = max(d.n_lineups for d in axes.designs)
    with spans.span("repro.sweep.prepare.topology"):
        topos = [build_topology(d, H_max, rows_per_hall=R_pad,
                                lineups_per_hall=X_pad)
                 for d in axes.designs]
        jts = [pl.jax_topology(t) for t in topos]
        jt = jax.tree.map(lambda *xs: jnp.stack(xs), *jts)

    with spans.span("repro.sweep.prepare.traces"):
        E_max = bucket(max(len(t) for t in traces), 64)
        ft = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[FleetTrace.from_trace(t, pad_to=E_max,
                                                  pad_month=months)
                            for t in traces])
        with_pods = any(bool(np.asarray(t.is_pod).any()) for t in traces)
        split = with_pods and not legacy_pod_cond
        pod_sel = [np.asarray(t.is_pod) for t in traces]
        e_max = bucket(max(_month_e_max(t, months,
                                        select=~p if split else None)
                           for t, p in zip(traces, pod_sel)), 4)
        # pod windows stay exact (no bucket): a pod scan step costs ~16
        # cluster steps (two 8-rack `_place_pod` scans), so one padded pod
        # slot per month would erase most of the split-trace win; monthly
        # pod counts are small and stable within a study, so the jit cache
        # still carries across same-scale grids.
        ep_max = (max(_month_e_max(t, months, select=p)
                      for t, p in zip(traces, pod_sel)) if split else 1)
        windows = [_event_windows(t, months, split, e_max=e_max,
                                  ep_max=ep_max, modulo=E_max)
                   for t in traces]
        idx = jnp.asarray(np.stack([w[0] for w in windows]))
        valid = jnp.asarray(np.stack([w[1] for w in windows]))
        idx_pod = jnp.asarray(np.stack([w[2] for w in windows]))
        valid_pod = jnp.asarray(np.stack([w[3] for w in windows]))

    args = (jt, ft, idx, valid, idx_pod, valid_pod,
            jnp.asarray(axes.policies, jnp.int32),
            jnp.asarray(axes.seeds, jnp.int32),
            jnp.asarray(h_caps, jnp.int32),
            jnp.asarray([len(t) for t in traces], jnp.int32))
    spans.count("events", sum(len(t) for t in traces))
    spans.count("event_slots",
                B * months * (e_max + (ep_max if split else 0)))
    spans.count("rows", B * H_max * R_pad)
    spans.count("hall_local_configs",
                sum(j.row_feeds_local is not None for j in jts))
    spans.count("h2d_bytes", sum(x.nbytes for x in jax.tree.leaves(args)))
    hd_scan = max(t.n_hd_rows for t in topos)
    return args, months, topos, X_pad, with_pods, _pod_scan_len(traces), \
        hd_scan


def serving_tpw_rows(envs: Sequence[EnvelopeSpec],
                     models: Sequence[tp.MoEModel],
                     metric_year: int | None = None) -> np.ndarray:
    """[B, Mdl] serving tokens/s-per-watt rows for a batch of envelopes.

    Each envelope implies one serving deployment (`tp.serving_deployment`
    at `metric_year`, default its `end_year`, at its placement quantum);
    batches share few distinct deployments, so rows are gathered from ONE
    jitted `tps_per_watt_grid` over the unique set.  Shared with
    `mc_sweep` and `payoff`."""
    keys = [(int(metric_year or e.end_year), e.gpu_scenario,
             max(int(e.pod_racks), 1),
             bool(e.pod_scale_arch or e.pod_racks > 1)) for e in envs]
    uniq = sorted(set(keys))
    deps = [tp.serving_deployment(*k) for k in uniq]
    grid = np.asarray(tp.tps_per_watt_grid(models, deps))
    row = {k: grid[i] for i, k in enumerate(uniq)}
    return np.stack([row[k] for k in keys])


def gpu_power_share(env: EnvelopeSpec) -> float:
    """Fraction of deployed MW that is GPU serving capacity (the rest is
    general compute / storage and delivers no tokens)."""
    total = env.gpu_gw + env.compute_gw + env.storage_gw
    return env.gpu_gw / total if total > 0 else 0.0


def _metric_stage(axes: SweepAxes, models, metric_year,
                  deployed_mw: np.ndarray, provisioned_mw: np.ndarray,
                  capex: np.ndarray):
    """Batched throughput/cost columns over final deployed capacity.

    `deployed_mw`/`provisioned_mw`/`capex` are [B]; returns
    (model_names, delivered_tps, tps_per_provisioned_w, dollars_per_tps)
    each [B, Mdl].  NaN marks undefined ratios (nothing built or nothing
    delivered), never inf."""
    models = (tp.MODEL_SUITE if models is None
              else tuple(tp.resolve_model(m) for m in models))
    B = len(axes)
    if not models:
        empty = np.zeros((B, 0))
        return [], empty, empty.copy(), empty.copy()
    tpw = serving_tpw_rows(axes.envs, models, metric_year)
    share = np.array([gpu_power_share(e) for e in axes.envs])
    delivered = tpw * (deployed_mw * 1e6 * share)[:, None]
    with np.errstate(divide="ignore", invalid="ignore"):
        tps_per_pw = np.where(provisioned_mw[:, None] > 0,
                              delivered / (provisioned_mw[:, None] * 1e6),
                              np.nan)
        dpt = np.where(delivered > 0, capex[:, None] / delivered, np.nan)
    return [m.name for m in models], delivered, tps_per_pw, dpt


@spans.spanned("repro.sweep.finalize")
def _finalize(out, axes: SweepAxes, months: int, topos, X_pad: int,
              mature_months: int, models=None,
              metric_year: int | None = None) -> SweepResult:
    """Host-side unpack of batched `SimOutputs` + cost model into a
    `SweepResult` (shared by `sweep` and `sharded_sweep`).  Counts on
    its span: `halls_built` and their designs' `rows_built`."""
    n_built = np.asarray(out.n_halls_built).astype(int)
    spans.count("halls_built", int(n_built.sum()))
    spans.count("rows_built", sum(int(n) * d.n_rows
                                  for d, n in zip(axes.designs, n_built)))
    deployed_mw = np.asarray(out.final_deployed_kw) / 1e3
    initial = np.array([cost.initial_dollars_per_mw(d)
                        for d in axes.designs])
    effective = np.array([
        cost.effective_dollars_per_mw(d, int(n), float(mw))
        for d, n, mw in zip(axes.designs, n_built, deployed_mw)])
    capex = np.array([int(n) * cost.hall_capex(d)
                      for d, n in zip(axes.designs, n_built)])
    provisioned = np.array([int(n) * d.ha_capacity_kw / 1e3
                            for d, n in zip(axes.designs, n_built)])
    with spans.span("repro.sweep.finalize.metrics"):
        names, delivered, tps_per_pw, dpt = _metric_stage(
            axes, models, metric_year, deployed_mw, provisioned, capex)
    return SweepResult(
        axes=axes,
        months=np.arange(months),
        halls_active=np.asarray(out.halls_active),
        deployed_mw=np.asarray(out.deployed_kw) / 1e3,
        p50_stranding=np.asarray(out.p50_stranding),
        p90_stranding=np.asarray(out.p90_stranding),
        final_hall_stranding=np.asarray(out.final_hall_stranding),
        final_lineup_stranding=np.asarray(out.final_lineup_stranding),
        lineup_is_active=np.stack([np.asarray(t.lineup_is_active)
                                   for t in topos]),
        lineups_per_hall=X_pad,
        n_halls_built=n_built,
        final_deployed_mw=deployed_mw,
        placed_fraction=np.asarray(out.placed_fraction),
        initial_dpm=initial,
        effective_dpm=effective,
        total_capex=capex,
        provisioned_mw=provisioned,
        model_names=names,
        delivered_tps=delivered,
        tps_per_provisioned_w=tps_per_pw,
        dollars_per_tps=dpt,
    )


def sweep(axes: SweepAxes, harvest: bool = True, mature_months: int = 12,
          n_halls_max: int = 0,
          traces: Sequence[Trace] | None = None,
          legacy_pod_cond: bool = False, models=None,
          metric_year: int | None = None,
          use_kernel: bool | None = None,
          kernel_interpret: bool = False,
          exact_quantiles: bool = True,
          quantile_bins: int | None = None) -> SweepResult:
    """Evaluate every configuration in `axes` in one compiled call.

    All envelopes must share the same buildout horizon (the scan length).
    Returns a `SweepResult`; `result(i)` recovers the `FleetResult` a
    sequential `run_fleet(axes.config(i))` would produce (identical up to
    float-padding noise for score-based policies).

    Padding is provably inert for the exact single-configuration
    semantics: padded rows have zero capacity (never feasible), padded
    line-ups are inactive (excluded from stranding stats), and padded
    trace events arrive after the simulated horizon.  Pod-free traces
    compile the cheap biased-placement path: instead of the
    try-then-open-a-hall `lax.cond` retry (which vmap evaluates on both
    branches), a single `place_in_row` attempt with `score_bias` added to
    rows of the not-yet-open hall picks the same row either way — a
    failed first attempt means no existing-hall row was feasible, so the
    biased argmin lands in the new hall exactly when the retry would.
    Pod traces compile the split-trace scan: each month's pod events run
    through the genuine attempt/retry pod path and its cluster events
    through the biased attempt, so neither pays for the other's branch
    (see `fleet.simulate_lifecycle`).

    Args:
        axes: the configuration batch (see `SweepAxes`).
        harvest: harvest one-year-old racks (static across the batch).
        mature_months: hall age before it enters tail stranding stats.
        n_halls_max: static hall cap; 0 auto-sizes per configuration.
        traces: optional pre-generated per-configuration arrival traces
            (defaults to `generate_fleet_trace(envs[i], seeds[i])`).
        legacy_pod_cond: compile the pre-split per-event
            `lax.cond(is_pod, …)` + retry path instead (reference for
            `pod_sweep_speedup` and the split-equivalence tests; results
            are identical).
        models: Table 2 models (objects or names) for the $/performance
            metric stage (default `throughput.MODEL_SUITE`; `()` skips
            the stage).
        metric_year: serving-deployment year for the metric stage
            (default: each envelope's `end_year`).
        use_kernel: route placement's line-up power feasibility through
            the Pallas kernel (static; bitwise-identical results).  `None` = backend
            default (`placement.default_use_kernel`: TPU on, CPU off).
        kernel_interpret: run the kernel in Pallas interpret mode (CPU
            CI fallback; only meaningful with the kernel path on).
        exact_quantiles: `True` (default) keeps the exact post-hoc
            p50/p90 reduction over each configuration's `[M, H]`
            stranding history; `False` compiles the O(1)-memory
            streaming histogram path (error ≤ `1 / quantile_bins`; see
            `fleet.simulate_lifecycle`) — the right choice for giant
            grids where the per-config history dominates memory.
        quantile_bins: streaming-histogram resolution (default
            `quantiles.DEFAULT_BINS` = 512); ignored when exact.
    """
    with spans.span("repro.sweep", configs=len(axes)):
        args, months, topos, X_pad, with_pods, pod_len, hd_scan = _prepare(
            axes, n_halls_max, traces, legacy_pod_cond)
        with spans.span("repro.sweep.dispatch"):
            out = _sweep_jit(*args, harvest=harvest,
                             mature_months=mature_months,
                             with_pods=with_pods,
                             legacy_pod_cond=legacy_pod_cond,
                             pod_scan_len=pod_len, hd_scan=hd_scan,
                             use_kernel=pl.resolve_use_kernel(use_kernel),
                             kernel_interpret=kernel_interpret,
                             exact_quantiles=exact_quantiles,
                             quantile_bins=quantile_bins)
        with spans.span("repro.sweep.wait"):
            out = jax.block_until_ready(out)
        return _finalize(out, axes, months, topos, X_pad, mature_months,
                         models=models, metric_year=metric_year)


def sharded_sweep(axes: SweepAxes, harvest: bool = True,
                  mature_months: int = 12, n_halls_max: int = 0,
                  traces: Sequence[Trace] | None = None,
                  devices: Sequence[jax.Device] | None = None,
                  models=None, metric_year: int | None = None,
                  use_kernel: bool | None = None,
                  kernel_interpret: bool = False,
                  exact_quantiles: bool = True,
                  quantile_bins: int | None = None,
                  mesh_shape: tuple[int, int] | None = None,
                  chunk_size: int | None = None) -> SweepResult:
    """`sweep`, with the configuration batch sharded over a device mesh.

    The batch is split over the named 2-D (config × trial) mesh of
    `repro.sharding.axes.sweep_mesh` via `shard_map`: the flat
    configuration axis product-shards over BOTH mesh axes
    (`batch_spec`), so each device receives only its own slab of padded
    topologies and traces (`jax.device_put` with a batch-sharded
    `NamedSharding`, so slabs land on their device up front rather than
    being replicated) and vmaps `simulate_lifecycle` over the B/(dc·dt)
    configurations it owns.  The default `mesh_shape` is `(D, 1)` —
    bitwise the historical 1-D `CONFIG_AXIS` layout — and any `(dc, dt)`
    with `dc·dt = D` places the same slabs on the same device order.
    Configurations are independent, so results match single-device
    `sweep` to float tolerance.

    Grids whose size does not divide the device count are padded by
    replicating configuration 0 up to the next multiple of D; the
    replicas are dropped before `SweepResult` assembly, so remainder
    grids return exactly `B` configurations.

    `chunk_size` streams the batch through the compiled executable in
    fixed-size chunks instead of one dispatch: every chunk shares one
    executable (identical static shapes), dispatches asynchronously
    (JAX queues the next chunk while the previous computes), and donates
    its input buffers (`donate_argnums` on `_sharded_sweep_jit`), so
    per-device live memory is bounded by one chunk — flat in grid size.
    This is how `giant_grid` sweeps ≥10⁴ configurations.

    With one device (or a length-1 batch) this is a passthrough to
    `sweep` — unless `chunk_size` is set, which engages the chunked
    streaming dispatch on a trivial 1×1 mesh (bounded live memory is
    useful without parallelism).  To exercise the sharded path on a
    single-CPU host, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the
    first jax import.

    Args: as `sweep`, plus
        devices: devices to shard over (default `jax.devices()`).
        mesh_shape: (config, trial) mesh extents; must multiply out to
            the device count (default `(D, 1)`).
        chunk_size: configurations per dispatch (rounded up to a
            multiple of the device count; default: the whole batch).
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    # chunked dispatch is meaningful even on one device (live memory
    # bounded by one chunk), so only passthrough when it isn't requested
    if (len(devs) <= 1 and chunk_size is None) or len(axes) == 1:
        return sweep(axes, harvest=harvest, mature_months=mature_months,
                     n_halls_max=n_halls_max, traces=traces, models=models,
                     metric_year=metric_year, use_kernel=use_kernel,
                     kernel_interpret=kernel_interpret,
                     exact_quantiles=exact_quantiles,
                     quantile_bins=quantile_bins)

    with spans.span("repro.sweep", configs=len(axes)):
        args, months, topos, X_pad, with_pods, pod_len, hd_scan = _prepare(
            axes, n_halls_max, traces)
        B, D = len(axes), len(devs)
        with spans.span("repro.sweep.dispatch"):
            C = -(-B // D) * D if chunk_size is None \
                else max(-(-int(chunk_size) // D) * D, D)
            B_pad = -(-B // C) * C
            if B_pad != B:
                def pad(x):
                    fill = jnp.broadcast_to(x[:1],
                                            (B_pad - B,) + x.shape[1:])
                    return jnp.concatenate([x, fill])
                args = jax.tree.map(pad, args)

            mesh = shax.sweep_mesh(devs, mesh_shape)
            sharding = NamedSharding(mesh, shax.batch_spec())
            kw = dict(harvest=harvest, mature_months=mature_months,
                      with_pods=with_pods, pod_scan_len=pod_len,
                      hd_scan=hd_scan,
                      use_kernel=pl.resolve_use_kernel(use_kernel),
                      kernel_interpret=kernel_interpret,
                      exact_quantiles=exact_quantiles,
                      quantile_bins=quantile_bins, mesh=mesh)
            outs = []
            with warnings.catch_warnings():
                # int topology/trace buffers can never alias the f32
                # output curves; XLA's per-buffer "donated but not
                # usable" note is expected here, and the usable
                # donations still land
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                for s in range(0, B_pad, C):
                    chunk = jax.device_put(
                        jax.tree.map(lambda x: x[s:s + C], args), sharding)
                    outs.append(_sharded_sweep_jit(*chunk, **kw))
            out = outs[0] if len(outs) == 1 else \
                jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)
        with spans.span("repro.sweep.wait"):
            out = jax.block_until_ready(out)
            if B_pad != B:
                # drop the replicas on the host: slicing a mesh-sharded
                # array needs an explicit out_sharding, and `_finalize`
                # copies the outputs to the host anyway
                out = jax.tree.map(lambda x: np.asarray(x)[:B], out)
        return _finalize(out, axes, months, topos, X_pad, mature_months,
                         models=models, metric_year=metric_year)
