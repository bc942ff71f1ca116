"""Kernel-path placement ≡ jnp oracle (the bitwise harness for the
`use_kernel` dispatch in `core.placement`).

The jnp path is the ground truth; the Pallas kernel (run here in
interpret mode — CPU CI) must reproduce feasibility masks bitwise, and
therefore chosen rows, state updates and stranding outputs bitwise,
across policies, deployment kinds, row subsets and saturation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hierarchy as h
from repro.core import placement as pl
from repro.core.resources import TIER_HA, TIER_LA

KEY = jax.random.PRNGKey(11)


def _assert_states_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _busy_state(jt, topo, seed, n_events=12):
    """A part-filled hall state (jnp path) so feasibility is non-trivial."""
    st = pl.init_state(topo)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    for i in range(n_events):
        dep = pl.Deployment.make(float(rng.uniform(100, 500)),
                                 int(rng.integers(1, 4)),
                                 is_gpu=bool(rng.random() < 0.5),
                                 tier=int(rng.random() < 0.3))
        st, _, _, _ = pl.place(jt, st, dep, int(rng.integers(0, 4)),
                               jax.random.fold_in(key, i))
    return st


DESIGNS = [h.design_4n3(), h.design_3p1()]   # distributed + block family


@pytest.mark.parametrize("design", DESIGNS, ids=["4N/3", "3+1"])
@pytest.mark.parametrize("tier", [TIER_HA, TIER_LA], ids=["HA", "LA"])
def test_row_feasible_and_scores_bitwise(design, tier):
    topo = h.build_topology(design)
    jt = pl.jax_topology(topo)
    st = _busy_state(jt, topo, seed=3)
    dep = pl.Deployment.make(350.0, 2, is_gpu=False, tier=tier)
    key = jax.random.fold_in(KEY, tier)
    f_j = pl.row_feasible(jt, st, dep, 2)
    f_k = pl.row_feasible(jt, st, dep, 2, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(f_j), np.asarray(f_k))
    assert np.asarray(f_j).any() and not np.asarray(f_j).all()
    # both paths score with the same jnp column, so var-min picks the
    # same row from the same mask
    active = jnp.ones((topo.row_cap.shape[0],), bool)
    _, ok_j, row_j = pl.place_in_row(jt, st, dep, 2, pl.POLICY_VAR_MIN, key,
                                     active)
    _, ok_k, row_k = pl.place_in_row(jt, st, dep, 2, pl.POLICY_VAR_MIN, key,
                                     active, use_kernel=True, interpret=True)
    assert bool(ok_j) == bool(ok_k) and int(row_j) == int(row_k)


@pytest.mark.parametrize("design", DESIGNS, ids=["4N/3", "3+1"])
@pytest.mark.parametrize("policy", range(4), ids=pl.POLICY_NAMES)
def test_place_in_row_bitwise_across_policies(design, policy):
    """Chosen row, ok flag and every state leaf identical for cluster
    placements under all four policies, both families."""
    topo = h.build_topology(design)
    jt = pl.jax_topology(topo)
    active = jnp.ones((topo.row_cap.shape[0],), bool)
    st = _busy_state(jt, topo, seed=policy)
    for i in range(4):
        k = jax.random.fold_in(KEY, 10 * policy + i)
        dep = pl.Deployment.make(200.0 + 90.0 * i, 1 + i % 3,
                                 is_gpu=(i % 2 == 0), tier=i % 2)
        st_j, ok_j, row_j = pl.place_in_row(jt, st, dep, dep.n_racks,
                                            policy, k, active)
        st_k, ok_k, row_k = pl.place_in_row(jt, st, dep, dep.n_racks,
                                            policy, k, active,
                                            use_kernel=True, interpret=True)
        assert bool(ok_j) == bool(ok_k)
        assert int(row_j) == int(row_k)
        _assert_states_equal(st_j, st_k)
        st = st_k


@pytest.mark.parametrize("policy", range(4), ids=pl.POLICY_NAMES)
def test_pod_scan_kernel_bitwise(policy):
    """`_place_pod` (multi-row pod, domain locking) with the kernel path:
    full-R scan and the HD-compacted subset both bitwise vs jnp."""
    topo = h.build_topology(h.design_10n8())
    jt = pl.jax_topology(topo)
    dep = pl.Deployment.make(600.0, 5, is_gpu=True, is_pod=True)
    active = jnp.ones((topo.row_cap.shape[0],), bool)
    st = pl.init_state(topo)
    for i in range(4):
        k = jax.random.fold_in(jax.random.PRNGKey(7 + policy), i)
        ref = pl._place_pod(jt, st, dep, policy, k, active)
        for hd_scan in (None, topo.n_hd_rows):
            got = pl._place_pod(jt, st, dep, policy, k, active,
                                hd_scan=hd_scan, use_kernel=True,
                                interpret=True)
            assert bool(ref[1]) == bool(got[1])
            np.testing.assert_array_equal(np.asarray(ref[2]),
                                          np.asarray(got[2]))
            np.testing.assert_array_equal(np.asarray(ref[3]),
                                          np.asarray(got[3]))
            _assert_states_equal(ref[0], got[0])
        st = ref[0]


def test_uneven_block_r_remainder():
    """Engine-level padding: a topology whose row count is not a multiple
    of the lane-aligned `block_r` exercises the kernel's remainder tile;
    padded rows are masked infeasible and sliced off.  Three 10N/8 halls
    (R = 300) give three different grids: 3 × 128, 2 × 256, 1 × 384."""
    topo = h.build_topology(h.design_10n8(), 3)
    jt = pl.jax_topology(topo)
    R = topo.row_cap.shape[0]
    assert R == 300                    # every tiling below pads
    st = _busy_state(jt, topo, seed=5)
    dep = pl.Deployment.make(420.0, 1, is_gpu=True)
    feas = np.asarray(pl.row_feasible(jt, st, dep, 1))
    extra = np.asarray(pl._row_fits(jt, st, dep, 1))
    outs = {}
    for block_r in (128, 256, 384):
        f_k = pl._kernel_feasible(jt, st, dep, 1, interpret=True,
                                  block_r=block_r)
        assert f_k.shape == (R,)
        np.testing.assert_array_equal(feas, np.asarray(f_k) & extra)
        outs[block_r] = np.asarray(f_k)
    # padding must be invisible: every tiling bitwise-identical
    for block_r in (256, 384):
        np.testing.assert_array_equal(outs[block_r], outs[128])


def test_all_infeasible_rows():
    """A deployment nothing can host: both paths refuse identically and
    leave the state untouched (the BIG-masked argmin never 'places')."""
    topo = h.build_topology(h.design_4n3())
    jt = pl.jax_topology(topo)
    st = pl.init_state(topo)
    dep = pl.Deployment.make(10_000.0, 8, is_gpu=True)   # overflows any row
    active = jnp.ones((topo.row_cap.shape[0],), bool)
    st_j, ok_j, row_j = pl.place_in_row(jt, st, dep, dep.n_racks,
                                        pl.POLICY_VAR_MIN, KEY, active)
    st_k, ok_k, row_k = pl.place_in_row(jt, st, dep, dep.n_racks,
                                        pl.POLICY_VAR_MIN, KEY, active,
                                        use_kernel=True, interpret=True)
    assert not bool(ok_j) and not bool(ok_k)
    assert int(row_j) == int(row_k) == -1
    _assert_states_equal(st_j, st)
    _assert_states_equal(st_k, st)
    assert not bool(np.asarray(
        pl.row_feasible(jt, st, dep, dep.n_racks, use_kernel=True,
                        interpret=True)).any())


def test_run_trial_kernel_end_to_end():
    """Whole-trial equivalence: `run_trial(use_kernel=True,
    interpret=True)` bitwise vs the jnp path — states, placements and
    stranding outputs — on fill → harvest → refill."""
    from repro.core import arrivals
    from repro.core.singlehall import TraceArrays, run_trial
    topo = h.build_topology(h.design_4n3())
    jt = pl.jax_topology(topo)
    tr_a = arrivals.sample_mixed_traces(2, 50, year=2028, seed=0)
    tr_b = arrivals.sample_mixed_traces(2, 30, year=2028, seed=0, phase=1)
    for t in range(2):
        t_a = TraceArrays.from_trace(tr_a.trial(t))
        t_b = TraceArrays.from_trace(tr_b.trial(t))
        key = jax.random.fold_in(KEY, t)
        out_j = run_trial(jt, pl.init_state(topo), t_a, t_b,
                          pl.POLICY_VAR_MIN, key)
        out_k = run_trial(jt, pl.init_state(topo), t_a, t_b,
                          pl.POLICY_VAR_MIN, key, use_kernel=True,
                          kernel_interpret=True)
        _assert_states_equal(out_j[0], out_k[0])
        for res_j, res_k in zip(out_j[1:], out_k[1:]):
            np.testing.assert_array_equal(np.asarray(res_j.placed),
                                          np.asarray(res_k.placed))
            np.testing.assert_array_equal(np.asarray(res_j.rows),
                                          np.asarray(res_k.rows))
        np.testing.assert_array_equal(
            np.asarray(pl.lineup_stranding(jt, out_j[0])),
            np.asarray(pl.lineup_stranding(jt, out_k[0])))
        np.testing.assert_array_equal(
            np.asarray(pl.hall_stranding(jt, out_j[0])),
            np.asarray(pl.hall_stranding(jt, out_k[0])))


def test_mc_sweep_kernel_end_to_end():
    """Small MC grid (pods → split-trace + HD-compacted scan) through
    `mc_sweep(use_kernel=True, kernel_interpret=True)`: every output
    column bitwise vs the jnp path."""
    from repro.core.mc_sweep import MCAxes, mc_sweep
    axes = MCAxes.zip(designs=[h.design_4n3()], policies=[0, 3], seeds=[0])
    kw = dict(n_trials=2, n_events=40, pod_racks=3, models=())
    a = mc_sweep(axes, **kw)
    b = mc_sweep(axes, use_kernel=True, kernel_interpret=True, **kw)
    for name in ("lineup_stranding", "hall_stranding", "deployed_kw",
                 "saturated", "placed_a", "placed_b"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)


def test_fleet_sweep_kernel_end_to_end():
    """Fleet lifecycle through `sweep(use_kernel=True,
    kernel_interpret=True)`: stranding trajectories and hall counts
    bitwise vs the jnp path on a small 2-config grid."""
    from repro.core.arrivals import EnvelopeSpec
    from repro.core.sweep import SweepAxes, sweep
    env = EnvelopeSpec(start_year=2026, end_year=2027, gpu_gw=0.004,
                       compute_gw=0.002, storage_gw=0.0)
    axes = SweepAxes.zip(designs=[h.design_4n3(), h.design_3p1()],
                         envs=[env])
    a = sweep(axes, models=())
    b = sweep(axes, models=(), use_kernel=True, kernel_interpret=True)
    for name in ("halls_active", "deployed_mw", "p50_stranding",
                 "p90_stranding", "final_hall_stranding",
                 "final_lineup_stranding", "n_halls_built",
                 "placed_fraction"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)
