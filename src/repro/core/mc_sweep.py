"""Batched single-hall Monte Carlo engine (paper §4.4, Figs. 5–7).

The paper's single-hall results are grids — stranding CDFs per design
(Fig. 5), a 21-point single-SKU kW sweep per design (Fig. 6), a policy
comparison (Fig. 7) — yet `singlehall.monte_carlo` used to be called
once per grid point, each call synthesizing its trial traces in a
host-side Python loop.  This module is the sweep-style front end: trial
*generation* is one vectorized numpy pass (`arrivals.sample_mixed_traces`)
and trial *evaluation* is ONE jitted call that vmaps
`singlehall.run_trial` over the whole (configuration × trial) grid, with
topologies padded to common shapes exactly like `sweep.SweepAxes`:

    axes = MCAxes.product(designs=[get_design("4N/3"), get_design("3+1")],
                          sku_kw=np.arange(200, 2501, 115))
    res = mc_sweep(axes, n_trials=4, n_events=300,
                   harvest=False, single_sku_gpu=True)   # one compiled call
    res.deployed_kw[i].mean(), res.result(i) ...

On a multi-device host, `sharded_mc_sweep` splits the (config × trial)
grid over the same named 2-D (config × trial) mesh the fleet sweep uses
(`repro.sharding.axes.sweep_mesh`): flattened and product-sharded by
default (bitwise the historical 1-D `CONFIG_AXIS` layout on a (D, 1)
mesh), or block-sharded as a true [B, T] grid with
`mesh_shape=(dc, dt)` so topologies ship once per configuration;
trials are independent, so sharded and single-device results agree to
float tolerance and one device is a passthrough.
`singlehall.monte_carlo` remains the exact one-configuration wrapper.
"""
from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import arrivals, cost, placement as pl, projections as proj
from . import throughput as tp
from .hierarchy import (DesignSpec, HallTopology, SweepValidationError,
                        build_topology)
from .placement import DEFAULT_POLICY, POLICY_NAMES, JaxTopology
from .singlehall import TraceArrays, run_trial
from repro.runtime import spans
from repro.sharding import axes as shax
from .sweep import _broadcast


@dataclass
class MCAxes:
    """The single-hall configuration batch `mc_sweep` vmaps over.

    Four aligned per-configuration lists of equal length ``B``:
    configuration ``i`` is ``(designs[i], sku_kw[i], policies[i],
    seeds[i])``, where `sku_kw` is the optional Fig. 6 GPU SKU-kW
    override (None = empirical SKU mix).  Length-1 lists broadcast, and
    `tags` rides along for reporting exactly like `sweep.SweepAxes.tags`.

    Trial count, event count, year/scenario and the other trace-stream
    parameters are *call-level* arguments of `mc_sweep` (they set static
    array shapes / generator behavior shared by the whole grid).
    """
    designs: List[DesignSpec]
    sku_kw: List[Optional[float]] = field(default_factory=lambda: [None])
    policies: List[int] = field(default_factory=lambda: [DEFAULT_POLICY])
    seeds: List[int] = field(default_factory=lambda: [0])
    tags: List[str] = field(default_factory=lambda: [""])

    def __len__(self):
        return len(self.designs)

    def __post_init__(self):
        B = max(len(self.designs), len(self.sku_kw), len(self.policies),
                len(self.seeds), len(self.tags))
        self.designs = _broadcast(self.designs, B, "designs")
        self.sku_kw = [None if k is None else float(k)
                       for k in _broadcast(self.sku_kw, B, "sku_kw")]
        self.policies = [int(p) for p in _broadcast(self.policies, B,
                                                    "policies")]
        self.seeds = [int(s) for s in _broadcast(self.seeds, B, "seeds")]
        self.tags = [str(t) for t in _broadcast(self.tags, B, "tags")]

    @staticmethod
    def zip(designs, sku_kw=(None,), policies=(DEFAULT_POLICY,), seeds=(0,),
            tags=("",)) -> "MCAxes":
        """Aligned per-configuration sequences (length-1 broadcasts)."""
        return MCAxes(list(designs), list(sku_kw), list(policies),
                      list(seeds), list(tags))

    @staticmethod
    def product(designs: Sequence[DesignSpec],
                sku_kw: Sequence[Optional[float]] = (None,),
                policies: Sequence[int] = (DEFAULT_POLICY,),
                seeds: Sequence[int] = (0,),
                tags: Sequence[str] | None = None) -> "MCAxes":
        """Full grid, designs-major ordering (seeds vary fastest).

        `tags` (aligned with `designs`, length-1 broadcasts) labels each
        design and follows it through the cross product — the `MCAxes`
        analogue of `SweepAxes.product(env_tags=…)`."""
        tags = _broadcast(tags, len(designs), "tags") \
            if tags is not None else [""] * len(designs)
        combos = list(itertools.product(zip(designs, tags), sku_kw,
                                        policies, seeds))
        return MCAxes([c[0][0] for c in combos], [c[1] for c in combos],
                      [c[2] for c in combos], [c[3] for c in combos],
                      [c[0][1] for c in combos])

    def validate(self) -> "MCAxes":
        """Raise `SweepValidationError` before any compile time is spent
        (see `sweep.SweepAxes.validate`)."""
        if len(self) == 0:
            raise SweepValidationError(
                "designs", "empty MC sweep: zero configurations")
        seen: set = set()
        for d in self.designs:
            if id(d) not in seen:
                seen.add(id(d))
                d.validate()
        for i, kw in enumerate(self.sku_kw):
            if kw is not None and kw <= 0:
                raise SweepValidationError(
                    "sku_kw", f"sku_kw[{i}] = {kw}: non-positive rack "
                    f"power override")
        for i, p in enumerate(self.policies):
            if not 0 <= p < len(POLICY_NAMES):
                raise SweepValidationError(
                    "policies", f"policies[{i}] = {p} outside "
                    f"[0, {len(POLICY_NAMES)}); have {POLICY_NAMES}")
        return self


@dataclass
class MCResult:
    """Per-configuration MC metrics, leading axes = (config, trial)."""
    axes: MCAxes
    lineup_stranding: np.ndarray   # [B, T, X_pad] (use result(i) to strip)
    hall_stranding: np.ndarray     # [B, T]
    deployed_kw: np.ndarray        # [B, T]
    saturated: np.ndarray          # [B, T] refill phase ended saturated
    placed_a: np.ndarray           # [B, T, E]
    placed_b: np.ndarray           # [B, T, E_b]
    ha_capacity_kw: np.ndarray     # [B]
    # --- metric stage (per-trial $/performance; see `sweep.SweepResult`) ---
    provisioned_mw: np.ndarray = None   # [B] hall nameplate
    model_names: List[str] = field(default_factory=list)   # [Mdl]
    delivered_tps: np.ndarray = None         # [B, T, Mdl]
    tps_per_provisioned_w: np.ndarray = None  # [B, T, Mdl]
    dollars_per_tps: np.ndarray = None       # [B, T, Mdl]
    # --- resilient execution (repro.core.resilience) ---
    report: object = None          # RunReport when run via resilient_mc_sweep

    def __len__(self):
        return len(self.axes)

    @property
    def n_trials(self) -> int:
        return self.deployed_kw.shape[1]

    @property
    def tags(self) -> List[str]:
        return self.axes.tags

    def result(self, i: int) -> dict:
        """Configuration `i` as the `singlehall.monte_carlo` metrics dict
        (line-up padding stripped to the design's own line-up count)."""
        X = self.axes.designs[i].n_lineups
        return {
            "lineup_stranding": self.lineup_stranding[i, :, :X],  # [T, X]
            "hall_stranding": self.hall_stranding[i],             # [T]
            "deployed_kw": self.deployed_kw[i],                   # [T]
            "ha_capacity_kw": float(self.ha_capacity_kw[i]),
            "saturated": self.saturated[i],
            "placed_a": self.placed_a[i],
            "placed_b": self.placed_b[i],
        }


# Request-keyed staging cache: (design, padded shape) → (topo, jt).
# DesignSpec is a frozen dataclass, so it hashes by value; repeated
# `monte_carlo` calls (e.g. Fig. 6's per-kW loop before batching) build
# each topology exactly once, mirroring the benchmarks' `_FLEET_CACHE`.
# The empty initial state needs no staging — it is created inside the
# traced trial (`placement.init_state_from`), like the fleet scan does.
_TOPO_CACHE: Dict[tuple, Tuple[HallTopology, JaxTopology]] = {}


def _staged_topology(design: DesignSpec, rows_per_hall: int,
                     lineups_per_hall: int):
    key = (design, rows_per_hall, lineups_per_hall)
    if key not in _TOPO_CACHE:
        topo = build_topology(design, 1, rows_per_hall=rows_per_hall,
                              lineups_per_hall=lineups_per_hall)
        _TOPO_CACHE[key] = (topo, pl.jax_topology(topo))
    return _TOPO_CACHE[key]


def _mc_trial(jt_c, pol, t_a, t_b, k, *, harvest, with_pods, **trial_kw):
    """One trial's device outputs.  The empty initial state is built
    inside the trace (`init_state_from`), so every operand but the
    shared `windows` carries the batch axes.  `trial_kw` forwards the
    split-pods placement-mode keywords (`split_pods`, `windows`,
    `pod_scan_len`, `hd_scan`) to `run_trial`."""
    state, res_a, res_b = run_trial(jt_c, pl.init_state_from(jt_c),
                                    t_a, t_b, pol, k, harvest, with_pods,
                                    **trial_kw)
    return (pl.lineup_stranding(jt_c, state),
            pl.hall_stranding(jt_c, state)[0],
            pl.deployed_kw(state),
            res_b.saturated, res_a.placed, res_b.placed)


_MC_STATICS = ("harvest", "with_pods", "split_pods", "pod_scan_len",
               "hd_scan", "use_kernel", "kernel_interpret")


@functools.partial(jax.jit, static_argnames=_MC_STATICS)
def _mc_sweep_jit(jt, ta, tb, keys, policy, harvest, with_pods,
                  split_pods=False, windows=None,
                  pod_scan_len=pl.MAX_POD_RACKS, hd_scan=None,
                  use_kernel=False, kernel_interpret=False):
    """vmap `_mc_trial` over (configuration × trial): [B] topology /
    policy axes outer, [B, T] trace/key axes inner; the split-pods
    `windows` are shared by every trial."""
    trial = functools.partial(
        _mc_trial, harvest=harvest, with_pods=with_pods,
        split_pods=split_pods, windows=windows,
        pod_scan_len=pod_scan_len, hd_scan=hd_scan, use_kernel=use_kernel,
        kernel_interpret=kernel_interpret)
    per_cfg = jax.vmap(trial, in_axes=(None, None, 0, 0, 0))
    return jax.vmap(per_cfg)(jt, policy, ta, tb, keys)


@functools.partial(jax.jit, static_argnames=_MC_STATICS + ("mesh",),
                   donate_argnums=tuple(range(5)))
def _mc_sharded_jit(jt, ta, tb, keys, policy, mesh, harvest, with_pods,
                    split_pods=False, windows=None,
                    pod_scan_len=pl.MAX_POD_RACKS, hd_scan=None,
                    use_kernel=False, kernel_interpret=False):
    """Sharded trial batch: operands arrive FLATTENED to one [B·T]
    (config × trial) axis — `sharded_mc_sweep` repeats the per-config
    topology/policy per trial — which a single `vmap` consumes under
    `shard_map`, so trials load-balance across devices in B·T/(dc·dt)
    slabs (`batch_spec` product-shards the flat axis over both mesh
    axes; a (D, 1) mesh is bitwise the historical 1-D layout).
    (A nested config × trial vmap inside `shard_map` trips an XLA CPU
    compile crash; the flat axis sidesteps it and shards finer anyway.)
    Trials are independent, so out_specs stay sharded; no collectives.
    The split-pods `windows` are replicated.  Operand buffers are
    donated — the staged flat batch dies with the dispatch."""
    spec = shax.batch_spec()
    trial = functools.partial(
        _mc_trial, harvest=harvest, with_pods=with_pods,
        split_pods=split_pods, pod_scan_len=pod_scan_len,
        hd_scan=hd_scan, use_kernel=use_kernel,
        kernel_interpret=kernel_interpret)

    def shard_fn(jt_s, ta_s, tb_s, keys_s, pol_s, win):
        return jax.vmap(lambda jt_c, t_a, t_b, k, pol: trial(
            jt_c, pol, t_a, t_b, k, windows=win))(jt_s, ta_s, tb_s, keys_s,
                                                   pol_s)

    sharded = jax.shard_map(shard_fn, mesh=mesh,
                            in_specs=(spec,) * 5 + (P(),),
                            out_specs=spec, check_vma=False)
    return sharded(jt, ta, tb, keys, policy, windows)


@functools.partial(jax.jit, static_argnames=_MC_STATICS + ("mesh",),
                   donate_argnums=tuple(range(5)))
def _mc_sharded2d_jit(jt, ta, tb, keys, policy, mesh, harvest, with_pods,
                      split_pods=False, windows=None,
                      pod_scan_len=pl.MAX_POD_RACKS, hd_scan=None,
                      use_kernel=False, kernel_interpret=False):
    """2-D grid sharding: the [B, T] trial grid block-shards over the
    (config × trial) mesh — configurations over `CONFIG_AXIS`, trial
    replicas over `TRIAL_AXIS` — while the [B] topology/policy leaves
    shard over `CONFIG_AXIS` only (replicated across the trial axis).
    The global per-trial `jnp.repeat` of topologies the flat path stages
    on the host never happens: each shard repeats its own [b] slab
    across its [t] local trials *inside* the compiled program, flattens
    to one [b·t] axis for a single vmap (the nested-vmap XLA CPU crash
    again), and reshapes back, so out_specs are grid-sharded [B, T].
    The split-pods `windows` are replicated."""
    cspec = shax.config_spec()
    gspec = shax.grid_spec()
    trial = functools.partial(
        _mc_trial, harvest=harvest, with_pods=with_pods,
        split_pods=split_pods, pod_scan_len=pod_scan_len,
        hd_scan=hd_scan, use_kernel=use_kernel,
        kernel_interpret=kernel_interpret)

    def shard_fn(jt_s, ta_s, tb_s, keys_s, pol_s, win):
        b, t = keys_s.shape[:2]
        # tile [b, …] → [b·t, …] with a GATHER, not broadcast/repeat:
        # broadcasting a config-sharded, trial-replicated operand inside
        # the shard SIGFPEs the XLA CPU partitioner (same family as the
        # nested-vmap crash); the row gather compiles clean everywhere
        rep = jnp.arange(b * t) // t
        jt_f = jax.tree.map(lambda x: x[rep], jt_s)
        pol_f = pol_s[rep]
        ta_f, tb_f, keys_f = jax.tree.map(
            lambda x: x.reshape((b * t,) + x.shape[2:]),
            (ta_s, tb_s, keys_s))
        out = jax.vmap(lambda jt_c, t_a, t_b, k, pol: trial(
            jt_c, pol, t_a, t_b, k, windows=win))(jt_f, ta_f, tb_f, keys_f,
                                                   pol_f)
        return jax.tree.map(
            lambda x: x.reshape((b, t) + x.shape[1:]), out)

    sharded = jax.shard_map(shard_fn, mesh=mesh,
                            in_specs=(cspec, gspec, gspec, gspec, cspec,
                                      P()),
                            out_specs=gspec, check_vma=False)
    return sharded(jt, ta, tb, keys, policy, windows)


def _pod_geometry(batches) -> Tuple[int, int]:
    """(max, min) per-trial pod count over a list of `TraceBatch`es — the
    pod-window end and cluster-window start the split-pods scan runs
    (passed as data, so they never reach a compile).  Also validates the
    pods-first contract (mirroring `fleet._event_windows`)."""
    counts = np.concatenate([b.n_pods.ravel() for b in batches])
    for b in batches:
        ip = np.asarray(b.is_pod)
        if np.any(ip[:, 1:] & ~ip[:, :-1]):
            raise ValueError(
                "split-pods scan needs pod events to precede cluster "
                "events within each trial (the generated-trace order); "
                "use legacy_pod_cond=True for unordered traces")
    return int(counts.max()), int(counts.min())


@spans.spanned("repro.mc_sweep.prepare")
def _mc_prepare(axes: MCAxes, n_trials: int, n_events: int, year: int,
                scenario: str, gpu_power_share: float, pod_racks: int,
                quantum_racks: int, la_fraction: float,
                single_sku_gpu: bool, refill_events: int | None,
                legacy_pod_cond: bool = False):
    """Host-side staging shared by `mc_sweep` and `sharded_mc_sweep`:
    padded/stacked topologies ([B] leading axis), batched fill + refill
    trial traces ([B, T, E]), per-trial PRNG keys, per-config policies,
    plus the placement-mode keywords for the jitted trial: the static
    `with_pods` / `split_pods` / `pod_scan_len` / `hd_scan`, and with
    split pods the traced `windows`, int32 ``(W_a, W_b, S_a, S_b)``: the
    grid's largest pod count of the fill and the refill phase, then its
    smallest, exactly (they change from seed to seed; as data they
    leave every grid of one mix on one executable).

    Refill traces draw from the phase-1 stream of the *same* seed
    (`sample_mixed_traces(phase=1)`); the historical `seed + 1` refill
    made a configuration seeded `s` share its refill trace bitwise with
    configuration `s+1`'s fill trace — correlated trials across
    adjacent-seed grid points.

    Counts on its span: trace `events` (fill and refill), padded `rows`,
    the stacked inputs' `h2d_bytes` and `hall_local_configs`, the
    configurations whose topology takes the hall-local placement path
    (`placement.JaxTopology`); on pod grids also `pod_events` (real pod
    arrivals) and `pod_racks` (their racks), and with split pods
    `pod_steps`, the lane-steps of the pod window: configurations ×
    trials × (W_a + W_b)."""
    axes.validate()          # precise SweepValidationErrors, pre-compile
    B = len(axes)
    R_pad = max(d.n_rows for d in axes.designs)
    X_pad = max(d.n_lineups for d in axes.designs)
    staged = [_staged_topology(d, R_pad, X_pad) for d in axes.designs]

    E_b = refill_events or max(200, n_events // 3)
    share = 1.0 if single_sku_gpu else gpu_power_share
    gen = functools.partial(
        arrivals.sample_mixed_traces, year=year, scenario=scenario,
        gpu_power_share=share, pod_racks=pod_racks,
        quantum_racks=quantum_racks, la_fraction=la_fraction,
        single_sku_gpu=single_sku_gpu)
    stack = lambda ts: jax.tree.map(       # [B, T, E] device columns
        lambda *xs: jnp.stack(xs), *[TraceArrays.from_trace(t) for t in ts])
    tas = [gen(n_trials, n_events, seed=s, sku_kw_override=kw)
           for s, kw in zip(axes.seeds, axes.sku_kw)]
    tbs = [gen(n_trials, E_b, seed=s, phase=1, sku_kw_override=kw)
           for s, kw in zip(axes.seeds, axes.sku_kw)]
    with_pods = any(bool(t.is_pod.any()) for t in tas + tbs)
    trial_kw = dict(with_pods=with_pods)
    if with_pods:
        spans.count("pod_events", sum(int(t.n_pods.sum())
                                      for t in tas + tbs))
        spans.count("pod_racks", sum(int(t.n_racks[t.is_pod].sum())
                                     for t in tas + tbs))
    if with_pods and not legacy_pod_cond:
        wa, sa = _pod_geometry(tas)
        wb, sb = _pod_geometry(tbs)
        trial_kw.update(
            split_pods=True,
            windows=np.array([wa, wb, sa, sb], np.int32),
            pod_scan_len=min(max(t.max_pod_racks for t in tas + tbs),
                             pl.MAX_POD_RACKS),
            hd_scan=max(s[0].n_hd_rows for s in staged))
        spans.count("pod_steps", B * n_trials * (wa + wb))
    with spans.span("repro.mc_sweep.prepare.stage"):
        jt = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[s[1] for s in staged])
        ta, tb = stack(tas), stack(tbs)
        keys = jnp.stack([jax.random.split(jax.random.PRNGKey(s), n_trials)
                          for s in axes.seeds])
        policy = jnp.asarray(axes.policies, jnp.int32)
    args = (jt, ta, tb, keys, policy)
    spans.count("events", B * n_trials * (n_events + E_b))
    spans.count("rows", B * R_pad)
    spans.count("hall_local_configs",
                sum(j.row_feeds_local is not None for _, j in staged))
    spans.count("h2d_bytes", sum(x.nbytes for x in jax.tree.leaves(args)))
    return args, trial_kw


@spans.spanned("repro.mc_sweep.finalize")
def _mc_finalize(out, axes: MCAxes, models=None, year: int = 2028,
                 scenario: str = proj.MED, gpu_share: float = 1.0,
                 pod_racks: int = 1) -> MCResult:
    lineup_str, hall_str, deployed, saturated, placed_a, placed_b = out
    deployed = np.asarray(deployed)                              # [B, T] kW
    provisioned = np.array([d.ha_capacity_kw / 1e3 for d in axes.designs])
    models = (tp.MODEL_SUITE if models is None
              else tuple(tp.resolve_model(m) for m in models))
    if models:
        # one serving deployment for the whole call (year/scenario/pod size
        # are call-level), so the metric stage is a single [1, Mdl] grid
        with spans.span("repro.mc_sweep.finalize.metrics"):
            dep = tp.serving_deployment(year, scenario, pod_racks)
            tpw = np.asarray(tp.tps_per_watt_grid(models, [dep]))[0]  # [Mdl]
            capex = np.array([cost.hall_capex(d) for d in axes.designs])
            delivered = (deployed * 1e3 * gpu_share)[..., None] * tpw
            with np.errstate(divide="ignore", invalid="ignore"):
                tps_per_pw = delivered / (provisioned[:, None, None] * 1e6)
                dpt = np.where(delivered > 0,
                               capex[:, None, None] / delivered, np.nan)
    else:
        B, T = deployed.shape
        delivered = np.zeros((B, T, 0))
        tps_per_pw, dpt = delivered.copy(), delivered.copy()
    return MCResult(
        axes=axes,
        lineup_stranding=np.asarray(lineup_str),
        hall_stranding=np.asarray(hall_str),
        deployed_kw=deployed,
        saturated=np.asarray(saturated),
        placed_a=np.asarray(placed_a),
        placed_b=np.asarray(placed_b),
        ha_capacity_kw=np.array([d.ha_capacity_kw for d in axes.designs]),
        provisioned_mw=provisioned,
        model_names=[m.name for m in models],
        delivered_tps=delivered,
        tps_per_provisioned_w=tps_per_pw,
        dollars_per_tps=dpt,
    )


def mc_sweep(axes: MCAxes, n_trials: int = 32, n_events: int = 600,
             year: int = 2028, scenario: str = proj.MED,
             gpu_power_share: float = 0.6, pod_racks: int = 1,
             quantum_racks: int = 10, la_fraction: float = 0.0,
             harvest: bool = True, single_sku_gpu: bool = False,
             refill_events: int | None = None,
             legacy_pod_cond: bool = False, models=None,
             use_kernel: bool | None = None,
             kernel_interpret: bool = False) -> MCResult:
    """Evaluate every single-hall MC configuration in `axes` in one
    compiled call (`n_trials` trials each).

    Trial traces come from `arrivals.sample_mixed_traces` — one
    vectorized numpy pass per configuration phase, seeded by the
    configuration's `seed` at phase 0 (fill) and phase 1 (refill) — and
    `singlehall.run_trial` is vmapped over the (config × trial) grid.
    Topologies are padded to the batch's common (rows, line-ups) shape;
    padding rows have zero capacity and padded line-ups are inactive, so
    real-row results are unchanged and `result(i)` strips the padding.

    Pod traces (`pod_racks > 1`) compile the split-pods fast path: the
    generator emits pods first within every trial, so each phase runs a
    pod window (`placement._place_pod` over the HD-compacted row view,
    rack scan trimmed to the batch's true max pod size) then a cluster
    window (`place_cluster_in_row`), instead of paying `place`'s
    `lax.cond(is_pod, …)` both-branches cost on every event under vmap.
    Results are bit-identical to `legacy_pod_cond=True`, which keeps the
    per-event cond path compilable as the regression/benchmark
    reference (`benchmarks/run.py --only mc_pod_speedup`).

    Args:
        axes: the configuration batch (see `MCAxes`).
        n_trials / n_events: trials per configuration, fill-phase events.
        year / scenario: SKU-projection operating point (all configs).
        gpu_power_share / pod_racks / quantum_racks / la_fraction: trace
            mix parameters (`arrivals.sample_mixed_traces`).
        harvest: apply the §5.2 harvest between fill and refill (static).
        single_sku_gpu: Fig. 6 mode — GPU-only events at each
            configuration's `sku_kw` override.
        refill_events: refill-phase event count (default
            ``max(200, n_events // 3)``, matching `monte_carlo`).
        legacy_pod_cond: compile the pre-split per-event
            `lax.cond(is_pod, …)` path instead (results identical).
        models: Table 2 models (objects or names) for the per-trial
            $/performance columns (default `throughput.MODEL_SUITE`;
            `()` skips the stage).
        use_kernel: route placement's line-up power feasibility through
            the Pallas kernel (static; bitwise-identical results).  `None` = backend
            default: on for TPU, off elsewhere
            (`placement.default_use_kernel`).
        kernel_interpret: run the kernel in Pallas interpret mode (the
            CPU CI fallback; only meaningful with `use_kernel=True`).
    """
    with spans.span("repro.mc_sweep", configs=len(axes),
                    trials=len(axes) * n_trials):
        args, trial_kw = _mc_prepare(axes, n_trials, n_events, year,
                                     scenario, gpu_power_share, pod_racks,
                                     quantum_racks, la_fraction,
                                     single_sku_gpu, refill_events,
                                     legacy_pod_cond)
        with spans.span("repro.mc_sweep.dispatch"):
            out = _mc_sweep_jit(*args, harvest=harvest,
                                use_kernel=pl.resolve_use_kernel(use_kernel),
                                kernel_interpret=kernel_interpret,
                                **trial_kw)
        with spans.span("repro.mc_sweep.wait"):
            out = jax.block_until_ready(out)
        return _mc_finalize(
            out, axes, models=models, year=year, scenario=scenario,
            gpu_share=1.0 if single_sku_gpu else gpu_power_share,
            pod_racks=pod_racks)


def sharded_mc_sweep(axes: MCAxes, n_trials: int = 32, n_events: int = 600,
                     year: int = 2028, scenario: str = proj.MED,
                     gpu_power_share: float = 0.6, pod_racks: int = 1,
                     quantum_racks: int = 10, la_fraction: float = 0.0,
                     harvest: bool = True, single_sku_gpu: bool = False,
                     refill_events: int | None = None,
                     legacy_pod_cond: bool = False,
                     devices: Sequence[jax.Device] | None = None,
                     models=None, use_kernel: bool | None = None,
                     kernel_interpret: bool = False,
                     mesh_shape: Tuple[int, int] | None = None) -> MCResult:
    """`mc_sweep`, with the (config × trial) grid sharded over devices.

    Two placements on the named 2-D (config × trial) mesh
    (`repro.sharding.axes.sweep_mesh`):

    * Default (`mesh_shape=None` or a trial extent of 1): the FLATTENED
      `B·T` trial grid product-shards over both mesh axes — each trial
      is an independent simulation, so sharding trials, not just
      configurations, load-balances even when `B < D·T`.  Per-config
      topologies and policies are repeated per trial on the host, the
      flat batch splits over `devices` (default: all local devices) via
      `shard_map`, and outputs reshape back to `[B, T, …]`.  A (D, 1)
      mesh is bitwise the historical 1-D `CONFIG_AXIS` layout.
    * `mesh_shape=(dc, dt)` with `dt > 1`: the `[B, T]` grid
      block-shards — configurations over `CONFIG_AXIS`, trial replicas
      over `TRIAL_AXIS` — and topologies ship once per configuration
      ([B] leaves shard over `CONFIG_AXIS` only), never host-repeated
      per trial; each shard flattens its own (b × t) block inside the
      compiled program (`_mc_sharded2d_jit`).

    Non-divisible grids pad by replicating the first flat entry (or the
    first configuration/trial row on the 2-D path) and drop the
    replicas on exit; one device (or a single trial) is a passthrough
    to `mc_sweep`.  Simulated multi-device CPU runs use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    kw = dict(n_trials=n_trials, n_events=n_events, year=year,
              scenario=scenario, gpu_power_share=gpu_power_share,
              pod_racks=pod_racks, quantum_racks=quantum_racks,
              la_fraction=la_fraction, harvest=harvest,
              single_sku_gpu=single_sku_gpu, refill_events=refill_events,
              legacy_pod_cond=legacy_pod_cond, models=models,
              use_kernel=use_kernel, kernel_interpret=kernel_interpret)
    devs = list(devices) if devices is not None else list(jax.devices())
    B, T = len(axes), int(n_trials)
    if len(devs) <= 1 or B * T == 1:
        return mc_sweep(axes, **kw)

    with spans.span("repro.mc_sweep", configs=B, trials=B * T):
        (jt, ta, tb, keys, policy), trial_kw = _mc_prepare(
            axes, n_trials, n_events, year, scenario, gpu_power_share,
            pod_racks, quantum_racks, la_fraction, single_sku_gpu,
            refill_events, legacy_pod_cond)
        mesh = shax.sweep_mesh(devs, mesh_shape)
        dc, dt = mesh.devices.shape

        if dt > 1:
            # ---- 2-D grid path: pad B → ·dc and T → ·dt, ship [B] leaves
            # config-sharded and [B, T] leaves grid-sharded ----
            with spans.span("repro.mc_sweep.dispatch"):
                B_pad, T_pad = -(-B // dc) * dc, -(-T // dt) * dt

                def pad_axis(x, n, axis):
                    if x.shape[axis] == n:
                        return x
                    take = jnp.take(
                        x, jnp.zeros((n - x.shape[axis],), jnp.int32),
                        axis=axis)
                    return jnp.concatenate([x, take], axis=axis)

                cfg_leaves = jax.tree.map(lambda x: pad_axis(x, B_pad, 0),
                                          (jt, policy))
                grid_leaves = jax.tree.map(
                    lambda x: pad_axis(pad_axis(x, B_pad, 0), T_pad, 1),
                    (ta, tb, keys))
                cfg_leaves = jax.device_put(
                    cfg_leaves, NamedSharding(mesh, shax.config_spec()))
                ta, tb, keys = jax.device_put(
                    grid_leaves, NamedSharding(mesh, shax.grid_spec()))
                out = _mc_sharded2d_jit(
                    cfg_leaves[0], ta, tb, keys, cfg_leaves[1],
                    harvest=harvest, mesh=mesh,
                    use_kernel=pl.resolve_use_kernel(use_kernel),
                    kernel_interpret=kernel_interpret, **trial_kw)
            with spans.span("repro.mc_sweep.wait"):
                out = jax.block_until_ready(out)
                # drop padding on the host (see `sweep.sharded_sweep`)
                out = jax.tree.map(lambda x: np.asarray(x)[:B, :T], out)
        else:
            # ---- flat path: repeat per-config leaves per trial and shard
            # the [B·T] axis over the whole mesh ----
            with spans.span("repro.mc_sweep.dispatch"):
                jt = jax.tree.map(lambda x: jnp.repeat(x, T, axis=0), jt)
                policy = jnp.repeat(policy, T)
                flat = jax.tree.map(
                    lambda x: x.reshape((B * T,) + x.shape[2:]),
                    (ta, tb, keys))
                args = (jt,) + flat + (policy,)

                D = len(devs)
                N_pad = -(-B * T // D) * D
                if N_pad != B * T:
                    def pad(x):
                        fill = jnp.broadcast_to(
                            x[:1], (N_pad - B * T,) + x.shape[1:])
                        return jnp.concatenate([x, fill])
                    args = jax.tree.map(pad, args)

                args = jax.device_put(args,
                                      NamedSharding(mesh, shax.batch_spec()))
                out = _mc_sharded_jit(
                    *args, harvest=harvest, mesh=mesh,
                    use_kernel=pl.resolve_use_kernel(use_kernel),
                    kernel_interpret=kernel_interpret, **trial_kw)
            with spans.span("repro.mc_sweep.wait"):
                out = jax.block_until_ready(out)
                out = jax.tree.map(lambda x: np.asarray(x)[:B * T].reshape(
                    (B, T) + x.shape[1:]), out)
        return _mc_finalize(
            out, axes, models=models, year=year, scenario=scenario,
            gpu_share=1.0 if single_sku_gpu else gpu_power_share,
            pod_racks=pod_racks)
