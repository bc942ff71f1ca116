"""Single-hall Monte Carlo simulator (paper §4.4).

Each trial: instantiate one hall, place arrivals until SATURATION_FAILS
consecutive placements fail, apply harvesting, resume placement until
another SATURATION_FAILS consecutive failures.  Trials are vmapped; the
event loop is a `lax.scan` over a pre-generated arrival trace.

This module owns the per-trial machinery (`run_trial` and friends); the
batched front end that evaluates whole (design × SKU-kW × policy × seed)
grids in one jitted/vmapped — optionally device-sharded — call lives in
`repro.core.mc_sweep`.  `monte_carlo` here is the exact one-configuration
wrapper over it.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import arrivals, placement as pl
from .hierarchy import DesignSpec
from .placement import DEFAULT_POLICY, Deployment, HallState, JaxTopology

SATURATION_FAILS = 100


class TraceArrays(NamedTuple):
    """Device-side trace columns (one entry per event)."""
    rack_kw: jax.Array
    n_racks: jax.Array
    is_gpu: jax.Array
    is_pod: jax.Array
    tier: jax.Array
    harvest_frac: jax.Array

    @staticmethod
    def from_trace(t: arrivals.Trace) -> "TraceArrays":
        return TraceArrays(
            jnp.asarray(t.rack_kw), jnp.asarray(t.n_racks),
            jnp.asarray(t.is_gpu), jnp.asarray(t.is_pod),
            jnp.asarray(t.tier), jnp.asarray(t.harvest_frac))

    def event(self, i) -> Deployment:
        return Deployment(self.rack_kw[i], self.n_racks[i], self.is_gpu[i],
                          self.tier[i], self.is_pod[i])


class TrialResult(NamedTuple):
    state: HallState
    placed: jax.Array          # [E] bool
    rows: jax.Array            # [E, MAX_POD_RACKS]
    counts: jax.Array          # [E, MAX_POD_RACKS]
    saturated: jax.Array       # [] bool — phase ended in saturation


def _fill_phase(jt: JaxTopology, state: HallState, trace: TraceArrays,
                policy, key, with_pods: bool = True,
                split_pods: bool = False, windows=None,
                pod_scan_len: int = pl.MAX_POD_RACKS,
                hd_scan: int | None = None, use_kernel: bool = False,
                kernel_interpret: bool = False) -> TrialResult:
    """Place the trace until saturation.  Three static placement modes
    (all bit-identical on the same trace — the split modes just avoid
    tracing work `vmap` would otherwise evaluate for every event):

    * ``with_pods=False`` — pod-free traces (rack-scale GPUs,
      `pod_racks=1`) skip `place`'s `lax.cond(is_pod, …)` — whose pod
      branch vmap would evaluate for every event — and call the
      single-row `place_in_row` directly (exactly the cluster branch
      `place` would take).
    * ``with_pods=True, split_pods=False`` — the legacy per-event
      `lax.cond(is_pod, …)` path (`place`), kept compilable as the
      regression/benchmark reference (`legacy_pod_cond=True` upstream).
    * ``with_pods=True, split_pods=True`` — the split-trace fast path:
      the trace must be pods-first (`arrivals.sample_mixed_traces`
      emits it that way), so a **pod window** over events ``[0, W)``
      (live while ``i < n_pods``, `_place_pod` with the static
      `pod_scan_len` rack scan and the HD-compacted `hd_scan` row view)
      runs first, then a **cluster window** over ``[S, E)`` (live while
      ``i >= n_pods``, `place_cluster_in_row`).  ``windows = (W, S)``
      are traced int32 scalars, not compile-time constants, so every
      grid of one trace mix runs one executable: `W` must be ≥ every
      trial's pod count and `S` ≤ every trial's pod count (upstream
      passes the grid's max/min).  Event order, the saturation streak
      and the per-event `fold_in(key, i)` keys are exactly the legacy
      path's, so results are bit-identical.

    `use_kernel` (static) routes every placement's line-up power
    feasibility through the Pallas kernel (`placement.place_in_row`), with
    `kernel_interpret` selecting Pallas interpret mode (CPU CI); results
    are bitwise identical to the jnp path in every mode.
    """
    E = trace.rack_kw.shape[0]
    R = jt.row_cap.shape[0]
    all_rows = jnp.ones((R,), bool)

    if not (with_pods and split_pods):
        def body(carry, i):
            st, streak = carry
            frozen = streak >= SATURATION_FAILS
            dep = trace.event(i)
            k = jax.random.fold_in(key, i)
            if with_pods:
                st2, ok, rows, counts = pl.place(
                    jt, st, dep, policy, k, use_kernel=use_kernel,
                    interpret=kernel_interpret)
            else:
                st2, ok, rows, counts, _ = pl.place_cluster_in_row(
                    jt, st, dep, policy, k, all_rows,
                    use_kernel=use_kernel, interpret=kernel_interpret)
            ok = ok & ~frozen
            st = pl._tree_where(ok, st2, st)
            rows = jnp.where(ok, rows, -1)
            counts = jnp.where(ok, counts, 0.0)
            streak = jnp.where(ok, 0, streak + 1)
            return (st, streak), (ok, rows, counts)

        (state, streak), (placed, rows, counts) = jax.lax.scan(
            body, (state, jnp.zeros((), jnp.int32)), jnp.arange(E))
        return TrialResult(state, placed, rows, counts,
                           streak >= SATURATION_FAILS)

    n_pods = jnp.sum(trace.is_pod.astype(jnp.int32))
    pod_end, cluster_start = windows

    def window_step(place_fn, live_of):
        # outputs are written at the loop index with dynamic slices; the
        # two windows are live-disjoint, so an event keeps what the
        # window that owns it wrote (the -1/0 defaults otherwise)
        def body(i, carry):
            st, streak, placed, rows, counts = carry
            frozen = streak >= SATURATION_FAILS
            dep = trace.event(i)
            k = jax.random.fold_in(key, i)
            st2, ok, rows_i, counts_i = place_fn(st, dep, k)
            live = live_of(i)
            ok = ok & ~frozen & live
            st = pl._tree_where(ok, st2, st)
            at = lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, False)
            put = lambda x, v: jax.lax.dynamic_update_index_in_dim(x, v,
                                                                   i, 0)
            placed = put(placed, at(placed) | ok)
            rows = put(rows, jnp.where(ok, rows_i, at(rows)))
            counts = put(counts, jnp.where(ok, counts_i, at(counts)))
            streak = jnp.where(live, jnp.where(ok, 0, streak + 1), streak)
            return st, streak, placed, rows, counts
        return body

    def pod_place(st, dep, k):
        return pl._place_pod(jt, st, dep, policy, k, all_rows,
                             max_racks=pod_scan_len, hd_scan=hd_scan,
                             use_kernel=use_kernel,
                             interpret=kernel_interpret)

    def cluster_place(st, dep, k):
        return pl.place_cluster_in_row(jt, st, dep, policy, k, all_rows,
                                       use_kernel=use_kernel,
                                       interpret=kernel_interpret)[:4]

    carry = (state, jnp.zeros((), jnp.int32), jnp.zeros((E,), bool),
             jnp.full((E, pl.MAX_POD_RACKS), -1, jnp.int32),
             jnp.zeros((E, pl.MAX_POD_RACKS), jnp.float32))
    carry = jax.lax.fori_loop(0, pod_end,
                              window_step(pod_place, lambda i: i < n_pods),
                              carry)
    carry = jax.lax.fori_loop(cluster_start, E,
                              window_step(cluster_place,
                                          lambda i: i >= n_pods), carry)
    state, streak, placed, rows, counts = carry
    return TrialResult(state, placed, rows, counts,
                       streak >= SATURATION_FAILS)


def _apply_harvest(jt: JaxTopology, res: TrialResult,
                   trace: TraceArrays) -> HallState:
    """Harvest every placed rack by its class ceiling (paper §5.2)."""
    frac = jnp.where(res.placed, trace.harvest_frac, 0.0)
    return pl.release_bulk(jt, res.state, res.rows, res.counts,
                           trace.rack_kw, trace.is_gpu, trace.tier, frac)


def run_trial(jt: JaxTopology, topo_init: HallState,
              trace_a: TraceArrays, trace_b: TraceArrays,
              policy, key, harvest: bool = True, with_pods: bool = True,
              split_pods: bool = False, windows=None,
              pod_scan_len: int = pl.MAX_POD_RACKS,
              hd_scan: int | None = None, use_kernel: bool = False,
              kernel_interpret: bool = False):
    """One MC trial: fill → harvest → refill.  Returns final state and the
    two phase results.  Every keyword but `windows` is static (jit
    static argnames upstream): the non-harvest variant never traces the
    harvest branch, pod-free traces compile the cheap single-row
    placement, and `split_pods=True` compiles the split-trace pod fast
    path, with `pod_scan_len` / `hd_scan` the pod rack-scan trims.  Its
    window bounds are data: `windows` is int32 ``(W_a, W_b, S_a, S_b)``,
    the pod-window ends and cluster-window starts of the fill and
    refill phases (see `_fill_phase`).  `use_kernel` / `kernel_interpret`
    route placement's line-up power feasibility through the Pallas kernel
    (bitwise-identical results; see `placement.place_in_row`)."""
    ka, kb = jax.random.split(key)
    phase = lambda p: None if windows is None else (windows[p],
                                                     windows[p + 2])
    res_a = _fill_phase(jt, topo_init, trace_a, policy, ka, with_pods,
                        split_pods, phase(0), pod_scan_len, hd_scan,
                        use_kernel, kernel_interpret)
    state = _apply_harvest(jt, res_a, trace_a) if harvest else res_a.state
    res_b = _fill_phase(jt, state, trace_b, policy, kb, with_pods,
                        split_pods, phase(1), pod_scan_len, hd_scan,
                        use_kernel, kernel_interpret)
    return res_b.state, res_a, res_b


def monte_carlo(design: DesignSpec, n_trials: int = 32, n_events: int = 600,
                policy: int = DEFAULT_POLICY, seed: int = 0,
                year: int = 2028, scenario: str = "med",
                gpu_power_share: float = 0.6, pod_racks: int = 1,
                quantum_racks: int = 10, harvest: bool = True,
                sku_kw_override: float | None = None,
                single_sku_gpu: bool = False,
                legacy_pod_cond: bool = False,
                use_kernel: bool | None = None,
                kernel_interpret: bool = False):
    """Run `n_trials` single-hall MC trials.  Returns dict of metrics.

    Exact thin wrapper over the batched engine: one-configuration
    `repro.core.mc_sweep.mc_sweep` call (which also serves whole
    parameter grids — Fig. 6's 21-point kW sweep × 2 designs is ONE
    call there).  Trial traces come from the vectorized
    `arrivals.sample_mixed_traces` (one numpy RNG pass for the whole
    trial batch); `single_sku_gpu` + `sku_kw_override` reproduce the
    paper's Fig. 6 single-SKU sweep (repeated identical GPU deployments
    until saturation) as generator arguments.  Pod traces
    (`pod_racks > 1`) compile the split-trace fast path;
    `legacy_pod_cond=True` keeps the per-event `lax.cond(is_pod, …)`
    reference compilable (results are bit-identical).
    """
    from .mc_sweep import MCAxes, mc_sweep   # deferred: avoids import cycle
    axes = MCAxes.zip(designs=[design], sku_kw=[sku_kw_override],
                      policies=[policy], seeds=[seed])
    res = mc_sweep(axes, n_trials=n_trials, n_events=n_events, year=year,
                   scenario=scenario, gpu_power_share=gpu_power_share,
                   pod_racks=pod_racks, quantum_racks=quantum_racks,
                   harvest=harvest, single_sku_gpu=single_sku_gpu,
                   legacy_pod_cond=legacy_pod_cond, use_kernel=use_kernel,
                   kernel_interpret=kernel_interpret)
    return res.result(0)
