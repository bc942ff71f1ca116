"""Chip smoke run: both simulators once on a TPU, through their entry points.

    python chip_smoke.py               # one chip: single hall, fleet, ...
    python chip_smoke.py --four-chips  # only the sharded paths, four chips

One chip, in order:

* device — platform, kind and count; anything but a TPU exits non-zero
  (there is no CPU fallback);
* single hall — the Fig. 7 grid (10N/8 and 8+2 halls × 4 placement
  policies, 32 trials of 900 events) and a pod grid (10N/8, pods of 7
  racks under the high TDP scenario, so `_place_pod` and the HD-compacted
  scan run) through `mc_sweep`, with `use_kernel` at its default, which
  must resolve to the compiled `placement_score` kernel; each is run
  again with the jnp placement path and every output column compared
  (placement counts and stranding bitwise, deployed kW to f32 summation
  order);
* fleet — the Fig. 13 grid (4 reference designs × 3 TDP scenarios) at
  `demand_scale=0.1` (1 GW of cumulative demand) through
  `sharded_sweep`, with configuration 0 checked against the plain
  reference `run_fleet`;
* resilient — a small `resilient_sweep` in two checkpoint-free chunks,
  which must retry and quarantine nothing and agree with `sweep` (the
  columns that differ in any bit are printed).

Each phase runs its program once and prints the wall time of that
compile-inclusive call, the part of it JAX spent compiling (or loading
from the compilation cache), a digest of its outputs and the device's
`peak_bytes_in_use`.  These are information, not measurements of
record.  A failed check is printed when
it fails and the run raises after its last phase, so the process exits
non-zero without a result line; an exception inside a phase ends the run
at once.  The last line of a passing run is the JSON object
``{"ok": true, "device": {...}}``.

`--four-chips` runs only `sharded_sweep` (8 fleet configurations at
`FLEET_SCALE`: the Fig. 7 designs 10N/8 and 8+2 × the medium and high
TDP scenarios × 2 seeds) and `sharded_mc_sweep` on a (2, 2) mesh, each
compared bitwise with the unsharded engine on one device of the same
process, and checks that the sharded results hold shards on 4 distinct
devices.

The persistent compilation cache lives where `JAX_COMPILATION_CACHE_DIR`
says, or else at ``<checkout>/.jax_cache`` (`repro.runtime.compile_cache`);
the last lines before the result count its hits and misses.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# Relative tolerance of a sweep row against `run_fleet` on
# final_deployed_mw: the one tests/test_sweep.py holds the engines to
# (the padded sweep hall and the exact-size reference hall sum the same
# placements in a different order).
FLEET_RTOL = 1e-5
# Sizes: the Fig. 13 fleet at 1 GW, the Fig. 7 event count, and a small
# fleet grid for the resilient executor.
FLEET_SCALE = 0.1
MC_EVENTS = 900
RESILIENT_SCALE = 0.01
# Largest deviation allowed between the kernel and jnp placement paths
# in the stranding columns.  Both paths compare the same shares and
# score with the same column, so they must choose the same rows and
# build the same per-row state: 0.
KERNEL_TOL = 0.0
# deployed_kw sums the hall's rows.  `placement.deployed_kw` fixes the
# order of the adds, but the check bounds the deviation by what any two
# orders can give: two f32 recursive sums of n non-negative terms differ
# by at most 2·(n−1)·2⁻²⁴ of the total.
F32_SUM_ULP = 2.0 ** -24
MC_COUNT_COLUMNS = ("placed_a", "placed_b", "saturated")
MC_STRANDING_COLUMNS = ("lineup_stranding", "hall_stranding")
MC_COLUMNS = MC_STRANDING_COLUMNS + ("deployed_kw",) + MC_COUNT_COLUMNS + (
    "delivered_tps", "tps_per_provisioned_w", "dollars_per_tps")
FLEET_COLUMNS = ("halls_active", "deployed_mw", "p50_stranding",
                 "p90_stranding", "final_hall_stranding",
                 "final_lineup_stranding", "n_halls_built",
                 "final_deployed_mw", "placed_fraction", "effective_dpm",
                 "total_capex", "delivered_tps", "dollars_per_tps")


class Checks:
    """The run's checks.  A failed check is printed at once and the run
    goes on to its remaining phases, so one chip call shows every
    failure; `main` then raises, so the process exits non-zero and
    prints no result line.  An exception inside a phase is never
    caught: it ends the run where it happens."""

    def __init__(self):
        self.failed = []

    def require(self, ok: bool, what: str) -> None:
        if not ok:
            print(f"check failed: {what}", flush=True)
            self.failed.append(what)


def digest(res, columns) -> str:
    h = hashlib.sha256()
    for c in columns:
        a = np.ascontiguousarray(getattr(res, c))
        h.update(c.encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def max_dev(a, b, columns) -> float:
    dev = 0.0
    for c in columns:
        x = np.asarray(getattr(a, c), np.float64)
        y = np.asarray(getattr(b, c), np.float64)
        if x.shape != y.shape:
            raise RuntimeError(f"{c}: shapes {x.shape} != {y.shape}")
        ok = ~(np.isnan(x) | np.isnan(y))
        if not (np.isnan(x) == np.isnan(y)).all():
            return float("inf")
        if ok.any():
            dev = max(dev, float(np.abs(x[ok] - y[ok]).max()))
    return dev


def bitwise_equal(a, b, columns) -> list:
    """Names of the columns whose arrays differ in any bit."""
    return [c for c in columns
            if not np.array_equal(np.asarray(getattr(a, c)),
                                  np.asarray(getattr(b, c)), equal_nan=True)]


class Dispatch:
    """Records what an engine entry point hands its jitted program.

    Inside the `with` block, `module.name` is replaced by a wrapper that
    keeps the arguments' shapes and shardings, the static keywords and
    the device outputs of every call, then calls the program unchanged.
    `kernel_compiled(i)` lowers call `i` again from those shapes and says
    whether the program holds a compiled TPU kernel (`tpu_custom_call`;
    a Pallas call in interpret mode lowers to plain HLO instead)."""

    def __init__(self, module, name: str):
        self.module, self.name = module, name
        self.real = getattr(module, name)
        self.calls = []

    def __enter__(self):
        def spy(*args, **kwargs):
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), args)
            out = self.real(*args, **kwargs)
            self.calls.append((shapes, kwargs, out))
            return out

        setattr(self.module, self.name, spy)
        return self

    def __exit__(self, *exc):
        setattr(self.module, self.name, self.real)

    def kernel_compiled(self, i: int = 0) -> bool:
        shapes, kwargs, _ = self.calls[i]
        if not kwargs.get("use_kernel") or kwargs.get("kernel_interpret"):
            return False
        return "tpu_custom_call" in self.real.lower(*shapes,
                                                    **kwargs).as_text()


def peak_bytes(devices) -> str:
    return ",".join(str((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                      "n/a"))
                    for d in devices)


# Seconds JAX has spent in backend compilation, cache loads included:
# JAX reports every one as a duration event.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
compile_seconds = [0.0]


def on_duration(event, duration, **_):
    if event == COMPILE_EVENT:
        compile_seconds[0] += duration


def timed(fn):
    """(fn(), wall seconds, the part of them spent compiling)."""
    c0, t0 = compile_seconds[0], time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, compile_seconds[0] - c0


def phase_line(name, wall_s, compile_s, res, columns, devices, **extra):
    fields = dict(phase=name, wall_s=f"{wall_s:.3f}",
                  compile_s=f"{compile_s:.3f}",
                  run_s=f"{wall_s - compile_s:.3f}",
                  digest=digest(res, columns),
                  peak_bytes_in_use=peak_bytes(devices), **extra)
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def single_hall(dev0, checks):
    from repro.core import mc_sweep as mcs, placement
    from repro.core import projections as proj

    checks.require(placement.resolve_use_kernel(None),
                   "use_kernel=None does not resolve to the kernel here")
    grids = {
        "single_hall.fig7": (fig7_axes(("10N/8", "8+2")),
                             dict(n_events=MC_EVENTS)),
        "single_hall.pod7": (fig7_axes(("10N/8",)),
                             dict(n_events=MC_EVENTS, pod_racks=7,
                                  scenario=proj.HIGH)),
    }
    for name, (axes, kw) in grids.items():
        with Dispatch(mcs, "_mc_sweep_jit") as spy:
            res, wall, comp = timed(lambda: mcs.mc_sweep(axes, **kw))
        statics = spy.calls[0][1]
        checks.require(
            statics["use_kernel"] and not statics["kernel_interpret"],
            f"{name}: engine dispatched use_kernel={statics['use_kernel']} "
            f"kernel_interpret={statics['kernel_interpret']}")
        checks.require(spy.kernel_compiled(0),
                       f"{name}: no tpu_custom_call in the lowered program")
        if name.endswith("pod7"):
            checks.require(
                statics.get("split_pods") and statics.get("hd_scan"),
                f"{name}: the HD-compacted pod scan did not compile")
        ref, ref_s, _ = timed(lambda: mcs.mc_sweep(axes, use_kernel=False,
                                                   **kw))
        strand_dev = max_dev(res, ref, MC_STRANDING_COLUMNS)
        kw_dev = max_dev(res, ref, ("deployed_kw",))
        rows = max(d.n_rows for d in axes.designs)
        kw_tol = (2 * (rows - 1) * F32_SUM_ULP
                  * float(np.abs(ref.deployed_kw).max()))
        counts_differ = bitwise_equal(res, ref, MC_COUNT_COLUMNS)
        differ = bitwise_equal(res, ref, MC_COLUMNS)
        phase_line(name, wall, comp, res, MC_COLUMNS, [dev0],
                   configs=len(axes), trials=res.n_trials,
                   events=kw["n_events"], kernel="compiled",
                   jnp_wall_s=f"{ref_s:.3f}",
                   kernel_vs_jnp_stranding_max_dev=repr(strand_dev),
                   kernel_vs_jnp_deployed_kw_max_dev=repr(kw_dev),
                   deployed_kw_tol=repr(kw_tol),
                   columns_not_bitwise=",".join(differ) or "none",
                   statics={k: v for k, v in statics.items()
                            if k not in ("harvest",)})
        checks.require(not counts_differ,
                       f"{name}: kernel and jnp placement counts differ in "
                       f"{counts_differ}")
        checks.require(strand_dev <= KERNEL_TOL,
                       f"{name}: kernel vs jnp stranding deviation "
                       f"{strand_dev!r} > {KERNEL_TOL}")
        checks.require(kw_dev <= kw_tol,
                       f"{name}: kernel vs jnp deployed_kw deviation "
                       f"{kw_dev!r} > {kw_tol!r}")


def fig7_axes(design_names):
    """The Fig. 7 layout: every design × the 4 placement policies."""
    from repro.core import hierarchy
    from repro.core.mc_sweep import MCAxes
    return MCAxes.product(
        designs=[hierarchy.get_design(d) for d in design_names],
        policies=range(4), seeds=(7,))


def fig13_axes(scale, scenarios):
    from repro.core import hierarchy
    from repro.core.arrivals import EnvelopeSpec
    from repro.core.sweep import SweepAxes
    combos = [(s, n) for s in scenarios
              for n in ("4N/3", "3+1", "10N/8", "8+2")]
    return SweepAxes.zip(
        designs=[hierarchy.get_design(n) for _, n in combos],
        envs=[EnvelopeSpec(demand_scale=scale, gpu_scenario=s)
              for s, _ in combos])


def fleet8_axes(scale):
    """Eight fleet configurations for the four-chip check: the Fig. 7
    designs (10N/8, 8+2) × the medium and high TDP scenarios × 2 seeds."""
    from repro.core import hierarchy, projections as proj
    from repro.core.arrivals import EnvelopeSpec
    from repro.core.sweep import SweepAxes
    combos = [(n, s, seed) for n in ("10N/8", "8+2")
              for s in (proj.MED, proj.HIGH) for seed in (0, 1)]
    return SweepAxes.zip(
        designs=[hierarchy.get_design(n) for n, _, _ in combos],
        envs=[EnvelopeSpec(demand_scale=scale, gpu_scenario=s)
              for _, s, _ in combos],
        seeds=[seed for _, _, seed in combos])


def fleet(dev0, checks):
    from repro.core import projections as proj, sweep as sw
    from repro.core.fleet import run_fleet

    axes = fig13_axes(FLEET_SCALE, (proj.LOW, proj.MED, proj.HIGH))
    with Dispatch(sw, "_sweep_jit") as spy:
        res, wall, comp = timed(lambda: sw.sharded_sweep(axes))
    statics = spy.calls[0][1]
    checks.require(
        statics["use_kernel"] and not statics["kernel_interpret"],
        f"fleet: engine dispatched use_kernel={statics['use_kernel']} "
        f"kernel_interpret={statics['kernel_interpret']}")
    checks.require(spy.kernel_compiled(0),
                   "fleet: no tpu_custom_call in the lowered program")
    ref, ref_s, _ = timed(lambda: run_fleet(axes.config(0)))
    rel = (abs(float(res.final_deployed_mw[0]) - ref.final_deployed_mw)
           / max(ref.final_deployed_mw, 1e-9))
    phase_line("fleet.fig13", wall, comp, res, FLEET_COLUMNS, [dev0],
               configs=len(axes), demand_scale=FLEET_SCALE,
               events_padded=spy.calls[0][0][1].month.shape[-1],
               rows_per_config=spy.calls[0][0][0].row_cap.shape[1],
               kernel="compiled", run_fleet_s=f"{ref_s:.3f}",
               halls=",".join(str(int(n)) for n in res.n_halls_built),
               cfg0_halls=f"{int(res.n_halls_built[0])}/"
                          f"{ref.n_halls_built}",
               cfg0_final_deployed_mw=f"{float(res.final_deployed_mw[0])!r}/"
                                      f"{ref.final_deployed_mw!r}",
               cfg0_rel_dev=repr(rel))
    checks.require(int(res.n_halls_built[0]) == ref.n_halls_built,
                   f"fleet: n_halls_built {int(res.n_halls_built[0])} != "
                   f"run_fleet {ref.n_halls_built}")
    checks.require(rel <= FLEET_RTOL,
                   f"fleet: final_deployed_mw rel. deviation {rel!r} > "
                   f"{FLEET_RTOL}")
    checks.require(bool(np.isfinite(res.final_deployed_mw).all())
                   and bool((res.final_deployed_mw > 0).all()),
                   "fleet: non-finite or empty final_deployed_mw")


def resilient(dev0, checks):
    from repro.core import projections as proj
    from repro.core.resilience import resilient_sweep
    from repro.core.sweep import sweep

    axes = fig13_axes(RESILIENT_SCALE, (proj.HIGH,))
    res, wall, comp = timed(lambda: resilient_sweep(axes, chunk_size=2))
    ref = sweep(axes)
    r = res.report
    differ = bitwise_equal(res, ref, FLEET_COLUMNS)
    phase_line("resilient", wall, comp, res, FLEET_COLUMNS, [dev0],
               configs=len(axes), chunks=r.n_chunks,
               retries=r.retries, oom_halvings=r.oom_halvings,
               quarantined=len(r.quarantined),
               columns_not_bitwise=",".join(differ) or "none")
    checks.require(not r.quarantined,
                   f"resilient: quarantined {r.quarantined}")
    checks.require(r.retries == 0 and r.oom_halvings == 0,
                   f"resilient: {r.retries} retries, {r.oom_halvings} "
                   f"halvings")
    checks.require(
        bool((res.n_halls_built == ref.n_halls_built).all())
        and bool(np.allclose(res.final_deployed_mw, ref.final_deployed_mw,
                             rtol=FLEET_RTOL, atol=0.0)),
        "resilient: halls or final_deployed_mw differ from sweep")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def shard_devices(out) -> set:
    devs = set()
    for leaf in jax.tree.leaves(out):
        devs |= {s.device for s in leaf.addressable_shards}
    return devs


def leaves_sharded_over(out, devices) -> bool:
    return all({s.device for s in leaf.addressable_shards} == set(devices)
               for leaf in jax.tree.leaves(out))


def four_chips(devs, checks):
    from repro.core import mc_sweep as mcs, projections as proj
    from repro.core import sweep as sw

    axes = fleet8_axes(FLEET_SCALE)
    with Dispatch(sw, "_sharded_sweep_jit") as spy:
        res, wall, comp = timed(lambda: sw.sharded_sweep(axes, devices=devs))
    ref, ref_s, _ = timed(lambda: sw.sweep(axes))
    out = spy.calls[0][2]
    differ = bitwise_equal(res, ref, FLEET_COLUMNS)
    phase_line("four_chips.fleet", wall, comp, res, FLEET_COLUMNS, devs,
               configs=len(axes), demand_scale=FLEET_SCALE,
               unsharded_digest=digest(ref, FLEET_COLUMNS),
               unsharded_wall_s=f"{ref_s:.3f}",
               shard_devices=len(shard_devices(out)),
               kernel=spy.calls[0][1]["use_kernel"],
               columns_not_bitwise=",".join(differ) or "none")
    checks.require(spy.kernel_compiled(0), "four_chips.fleet: no "
                   "tpu_custom_call in the lowered program")
    checks.require(leaves_sharded_over(out, devs),
                   f"four_chips.fleet: outputs on {len(shard_devices(out))} "
                   f"devices, not all of {len(devs)}")
    checks.require(not differ,
                   f"four_chips.fleet: sharded != unsharded in {differ}")

    maxes = fig7_axes(("10N/8", "8+2"))
    kw = dict(n_events=MC_EVENTS)
    with Dispatch(mcs, "_mc_sharded2d_jit") as spy:
        res, wall, comp = timed(lambda: mcs.sharded_mc_sweep(
            maxes, devices=devs, mesh_shape=(2, 2), **kw))
    ref, ref_s, _ = timed(lambda: mcs.mc_sweep(maxes, **kw))
    out = spy.calls[0][2]
    differ = bitwise_equal(res, ref, MC_COLUMNS)
    phase_line("four_chips.single_hall", wall, comp, res, MC_COLUMNS, devs,
               configs=len(maxes), trials=res.n_trials, mesh="2x2",
               unsharded_digest=digest(ref, MC_COLUMNS),
               unsharded_wall_s=f"{ref_s:.3f}",
               shard_devices=len(shard_devices(out)),
               kernel=spy.calls[0][1]["use_kernel"],
               columns_not_bitwise=",".join(differ) or "none")
    checks.require(spy.kernel_compiled(0), "four_chips.single_hall: no "
                   "tpu_custom_call in the lowered program")
    checks.require(leaves_sharded_over(out, devs),
                   f"four_chips.single_hall: outputs on "
                   f"{len(shard_devices(out))} devices, not all of "
                   f"{len(devs)}")
    checks.require(not differ, f"four_chips.single_hall: sharded != "
                               f"unsharded in {differ}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweeps over four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    print(f"device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        print(f"no TPU: JAX found {d0.platform} devices", file=sys.stderr)
        return 1

    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        for k in cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    checks = Checks()
    t0 = time.perf_counter()
    if args.four_chips:
        if len(devices) < 4:
            raise RuntimeError(
                f"--four-chips needs 4 devices, JAX found {len(devices)}")
        four_chips(devices[:4], checks)
    else:
        single_hall(d0, checks)
        fleet(d0, checks)
        resilient(d0, checks)
    print(f"compile_cache dir={cache_dir} hits={cache['hits']} "
          f"misses={cache['misses']}", flush=True)
    print(f"total_s={time.perf_counter() - t0:.3f}", flush=True)
    if checks.failed:
        raise RuntimeError(f"{len(checks.failed)} chip smoke check(s) "
                           f"failed: {checks.failed}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
