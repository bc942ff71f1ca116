"""Host spans, counters and device scopes of the sweeps
(`repro.runtime.spans`, `jax.named_scope`).

With no profiler session nothing is recorded.  Under `jax.profiler.trace`
each grid call leaves one tree of spans that share a call id, with counts
equal to the shapes the engines pad to, and the same spans appear on the
profile's host plane.  The lowered programs carry the named scopes.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import hierarchy as h, placement as pl, projections as proj
from repro.core.arrivals import (EnvelopeSpec, generate_fleet_trace,
                                 sample_mixed_traces)
from repro.core.fleet import _auto_halls
from repro.core.mc_sweep import MCAxes, _mc_prepare, _mc_sweep_jit, mc_sweep
from repro.core.sweep import SweepAxes, _prepare, _sweep_jit, sweep
from repro.runtime import spans

DESIGNS = ("4N/3", "10N/8")
T, E, E_B = 2, 30, 10
MC_KW = dict(n_trials=T, n_events=E, refill_events=E_B)
ENV = EnvelopeSpec(demand_scale=0.002)
MONTHS = ENV.n_months
SEED = 3

MC_TREE = {    # span: parent
    "repro.mc_sweep": None,
    "repro.mc_sweep.prepare": "repro.mc_sweep",
    "repro.arrivals.mixed_traces": "repro.mc_sweep.prepare",
    "repro.mc_sweep.prepare.stage": "repro.mc_sweep.prepare",
    "repro.mc_sweep.dispatch": "repro.mc_sweep",
    "repro.mc_sweep.wait": "repro.mc_sweep",
    "repro.mc_sweep.finalize": "repro.mc_sweep",
    "repro.mc_sweep.finalize.metrics": "repro.mc_sweep.finalize",
}
SWEEP_TREE = {
    "repro.sweep": None,
    "repro.sweep.prepare": "repro.sweep",
    "repro.arrivals.fleet_trace": "repro.sweep.prepare",
    "repro.sweep.prepare.topology": "repro.sweep.prepare",
    "repro.sweep.prepare.traces": "repro.sweep.prepare",
    "repro.sweep.dispatch": "repro.sweep",
    "repro.sweep.wait": "repro.sweep",
    "repro.sweep.finalize": "repro.sweep",
    "repro.sweep.finalize.metrics": "repro.sweep.finalize",
}


def _mc_axes():
    return MCAxes.zip(designs=[h.get_design(n) for n in DESIGNS],
                      policies=[pl.POLICY_VAR_MIN], seeds=[5])


def _sweep_axes():
    return SweepAxes.zip(designs=[h.get_design(n) for n in DESIGNS],
                         envs=[ENV], seeds=[SEED])


def _grids():
    return mc_sweep(_mc_axes(), **MC_KW), sweep(_sweep_axes())


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Both tiny grids once without a profiler (compiling them), then
    once under one: (records, .xplane.pb path, results)."""
    spans.clear()
    _grids()
    assert spans.records() == ()
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(out, profiler_options=opts):
        results = _grids()
    recs = spans.records()
    spans.clear()
    xplane, = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                        recursive=True)
    return recs, xplane, results


def test_nothing_is_recorded_without_a_profiler():
    spans.clear()
    with spans.span("repro.outside", configs=1):
        spans.count("rows", 4)
    _grids()
    assert spans.records() == ()


def _tree(recs, root):
    """The records of the one call under `root`, by name."""
    roots = [r for r in recs if r.name == root]
    assert len(roots) == 1 and roots[0].parent is None
    call = [r for r in recs if r.call == roots[0].call]
    by_id = {r.id: r for r in call}
    by_name = {}
    for r in call:
        by_name.setdefault(r.name, []).append(r)
    return roots[0], by_id, by_name


@pytest.mark.parametrize("root,tree", [("repro.mc_sweep", MC_TREE),
                                       ("repro.sweep", SWEEP_TREE)])
def test_one_tree_per_grid_call(profiled, root, tree):
    recs, _, _ = profiled
    top, by_id, by_name = _tree(recs, root)
    assert set(by_name) == set(tree)
    for name, parent in tree.items():
        for r in by_name[name]:
            assert (by_id[r.parent].name if r.parent is not None
                    else None) == parent
            assert top.start_ns <= r.start_ns <= r.end_ns <= top.end_ns
    children = [r for r in by_id.values() if r.parent == top.id]
    covered = sum(r.end_ns - r.start_ns for r in children)
    assert covered >= 0.98 * (top.end_ns - top.start_ns)


def test_mc_counts_are_the_padded_shapes(profiled):
    recs, _, (res, _) = profiled
    top, _, by_name = _tree(recs, "repro.mc_sweep")
    B = len(DESIGNS)
    assert dict(top.counts) == {"configs": B, "trials": B * T}
    prep, = by_name["repro.mc_sweep.prepare"]
    assert prep.counts["events"] == B * T * (E + E_B)
    assert prep.counts["rows"] == B * max(h.get_design(n).n_rows
                                          for n in DESIGNS)
    assert prep.counts["h2d_bytes"] > 0
    synth = by_name["repro.arrivals.mixed_traces"]
    assert sorted(r.counts["events"] for r in synth) == \
        [T * E_B] * B + [T * E] * B
    assert res.deployed_kw.shape == (B, T)


def test_sweep_counts_are_the_padded_shapes(profiled):
    recs, _, (_, res) = profiled
    top, _, by_name = _tree(recs, "repro.sweep")
    B = len(DESIGNS)
    designs = [h.get_design(n) for n in DESIGNS]
    assert dict(top.counts) == {"configs": B}
    trace = generate_fleet_trace(ENV, SEED)
    busiest = np.bincount(trace.month, minlength=MONTHS).max()
    H_max = -(-max(_auto_halls(d, ENV) for d in designs) // 4) * 4
    prep, = by_name["repro.sweep.prepare"]
    assert prep.counts["events"] == B * len(trace)
    assert prep.counts["event_slots"] == B * MONTHS * (-(-busiest // 4) * 4)
    assert prep.counts["rows"] == B * H_max * max(d.n_rows for d in designs)
    assert [r.counts["events"] for r in
            by_name["repro.arrivals.fleet_trace"]] == [len(trace)] * B
    fin, = by_name["repro.sweep.finalize"]
    assert fin.counts["halls_built"] == int(res.n_halls_built.sum())
    assert fin.counts["rows_built"] == sum(
        int(n) * d.n_rows for n, d in zip(res.n_halls_built, designs))
    assert 0 < fin.counts["rows_built"] <= prep.counts["rows"]


def test_mc_pod_counts_are_their_definitions(tmp_path):
    """On a split-pods grid the prepare span counts `pod_events`, the real
    pod arrivals, `pod_racks`, their racks, and `pod_steps`, the pod
    window's lane-steps: configurations × trials × (W_a + W_b), with the
    windows the grid's largest pod counts of the fill and the refill."""
    pod = 7
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    spans.clear()
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        _, trial_kw = _mc_prepare(_mc_axes(), T, E, 2028, proj.HIGH, 0.6,
                                  pod, 10, 0.0, False, E_B)
    prep, = [r for r in spans.records()
             if r.name == "repro.mc_sweep.prepare"]
    spans.clear()
    gen = lambda n, phase: sample_mixed_traces(
        T, n, year=2028, scenario=proj.HIGH, gpu_power_share=0.6,
        pod_racks=pod, quantum_racks=10, la_fraction=0.0, seed=5,
        phase=phase)
    fill, refill = gen(E, 0), gen(E_B, 1)
    B = len(DESIGNS)
    pods = int(fill.n_pods.sum() + refill.n_pods.sum())
    assert pods > 0
    assert prep.counts["pod_events"] == B * pods
    assert prep.counts["pod_racks"] == B * pod * pods
    W_a, W_b = int(fill.n_pods.max()), int(refill.n_pods.max())
    assert prep.counts["pod_steps"] == B * T * (W_a + W_b)
    assert trial_kw["windows"].tolist() == [
        W_a, W_b, int(fill.n_pods.min()), int(refill.n_pods.min())]


@pytest.mark.parametrize("prepare", ["repro.mc_sweep.prepare",
                                     "repro.sweep.prepare"])
def test_hall_local_configs_counts_every_tiled_config(profiled, prepare):
    """Every configuration of a `build_topology` batch takes the
    hall-local placement path, and the prepare span says so."""
    recs, _, _ = profiled
    _, _, by_name = _tree(recs, prepare.rsplit(".", 1)[0])
    prep, = by_name[prepare]
    assert prep.counts["hall_local_configs"] == len(DESIGNS)


def test_profile_host_plane_holds_the_same_spans(profiled):
    recs, xplane, _ = profiled
    from jax.profiler import ProfileData
    host = [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in ProfileData.from_file(xplane).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.")]
    host.sort(key=lambda e: e[1])
    mine = sorted(recs, key=lambda r: r.start_ns)
    assert [e[0] for e in host] == [r.name for r in mine]
    for (_, _, dur), r in zip(host, mine):
        assert abs(dur - (r.end_ns - r.start_ns)) <= 1e6


def _lowered_sweep(pod_racks):
    env = EnvelopeSpec(demand_scale=0.002, pod_racks=pod_racks,
                       gpu_scenario=proj.HIGH)
    axes = SweepAxes.zip(designs=[h.get_design(n) for n in DESIGNS],
                         envs=[env], seeds=[SEED])
    args, _, _, _, with_pods, pod_len, hd_scan = _prepare(axes, 0, None)
    return _sweep_jit.lower(*args, harvest=True, mature_months=12,
                            with_pods=with_pods, pod_scan_len=pod_len,
                            hd_scan=hd_scan)


def _lowered_mc(pod_racks):
    args, statics = _mc_prepare(_mc_axes(), T, E, 2028, proj.HIGH, 0.6,
                                pod_racks, 10, 0.0, False, E_B)
    return _mc_sweep_jit.lower(*args, harvest=True, **statics)


PLACEMENT = ["repro.placement." + s for s in (
    "gather_feeds", "row_feasible", "row_scores", "place_pod",
    "release_bulk", "stranding")]


@pytest.mark.parametrize("lower,scopes", [
    (_lowered_sweep, PLACEMENT + ["repro.fleet.month_stats"]),
    (_lowered_mc, PLACEMENT)], ids=["sweep", "mc_sweep"])
def test_lowered_programs_carry_the_named_scopes(lower, scopes):
    text = lower(pod_racks=7).as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
