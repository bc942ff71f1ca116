"""Hall-local views of a tiled topology ≡ the gathers they replace.

`placement.jax_topology` marks a topology that tiles one hall over its
halls (every `build_topology` topology), and the full-row placement step
then reads line-up and hall state through reshapes, broadcasts and
selects instead of gathers at `row_feeds` / `row_hall`.  The same
topology with the two tiled-path fields set to None takes the gathers,
so every comparison here is between the two paths on one topology, and
must be bitwise.
"""
import jax
import jax.numpy as jnp
from jax.extend import core as jex
import numpy as np
import pytest

from repro.core import hierarchy as h
from repro.core import mc_sweep as mc
from repro.core import placement as pl
from repro.core.arrivals import EnvelopeSpec
from repro.core.fleet import FleetConfig, run_fleet
from repro.core.mc_sweep import MCAxes, mc_sweep
from repro.core.resources import N_RES, TIER_HA, TIER_LA
from repro.core.sweep import SweepAxes, sweep

DESIGNS = ("4N/3", "3+1", "10N/8", "8+2")
R_PAD, X_PAD = 100, 10          # the sweeps' padding over the four designs
KEY = jax.random.PRNGKey(5)


def _gather_path(jt):
    return jt._replace(row_feeds_local=None, row_feed_cap=None)


def _topology(name, H):
    return h.build_topology(h.get_design(name), H, rows_per_hall=R_PAD,
                            lineups_per_hall=X_PAD)


def _random_state(topo, seed):
    """A part-loaded state: line-ups from empty to full, rows up to 90 %
    of every resource, one hall's liquid plant over its cap."""
    rng = np.random.default_rng(seed)
    cap = np.asarray(topo.lineup_cap)
    ha = rng.uniform(0.0, 0.8, cap.shape) * cap
    tot = ha + rng.uniform(0.0, 0.2, cap.shape) * cap
    R = topo.row_cap.shape[0]
    row_load = rng.uniform(0.0, 0.9, (R, N_RES)) * np.asarray(topo.row_cap)
    liq = rng.uniform(0.3, 0.99, topo.n_halls) * np.asarray(
        topo.hall_liq_cap)
    liq[-1] = topo.hall_liq_cap[-1]
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return pl.HallState(f32(row_load), f32(ha), f32(tot), f32(liq),
                        jnp.asarray(7, jnp.int32))


DEPLOYMENTS = [(130.0, 2, True), (20.0, 10, False), (600.0, 1, False)]


def _assert_same(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("tier", [TIER_HA, TIER_LA], ids=["HA", "LA"])
@pytest.mark.parametrize("H", [1, 3])
@pytest.mark.parametrize("name", DESIGNS)
def test_views_match_the_gather_path(name, H, tier):
    """`row_feasible` (jnp and kernel) and `row_scores` (every policy)
    are bitwise equal on the two paths, with padded rows (no feeds) and
    padded line-ups in the topology."""
    topo = _topology(name, H)
    jt = pl.jax_topology(topo)
    assert jt.row_feeds_local is not None
    jt_g = _gather_path(jt)
    st = _random_state(topo, seed=H * 10 + tier)
    mixed = []
    for kw, n, gpu in DEPLOYMENTS:
        dep = pl.Deployment.make(kw, n, is_gpu=gpu, tier=tier)
        feas = pl.row_feasible(jt, st, dep, n)
        _assert_same(feas, pl.row_feasible(jt_g, st, dep, n))
        for path in (jt, jt_g):
            _assert_same(feas, pl.row_feasible(path, st, dep, n,
                                               use_kernel=True,
                                               interpret=True))
        for policy in range(4):
            _assert_same(pl.row_scores(jt, st, dep, n, policy, KEY),
                         pl.row_scores(jt_g, st, dep, n, policy, KEY))
        mixed.append(np.asarray(feas))
    mixed = np.concatenate(mixed)
    assert 0 < mixed.sum() < mixed.size      # the masks are not trivial

    # the views themselves: valid feeds read the gathered values
    valid, cap, ha, tot = pl._feed_views(jt, st, jt.row_feeds, None)
    valid_g, cap_g, ha_g, tot_g = pl._feed_views(jt_g, st, jt.row_feeds,
                                                 None)
    _assert_same(valid, valid_g)
    assert not np.asarray(valid).all()       # padded and LD rows
    for x, y in ((cap, cap_g), (ha, ha_g), (tot, tot_g)):
        _assert_same(np.where(valid, x, 0), np.where(valid, y, 0))


def test_mixed_design_batch_under_vmap():
    """The four designs stacked as `sweep._prepare` stacks them, vmapped:
    both paths agree row for row."""
    topos = [_topology(n, 3) for n in DESIGNS]
    stack = lambda jts: jax.tree.map(lambda *xs: jnp.stack(xs), *jts)
    jt = stack([pl.jax_topology(t) for t in topos])
    jt_g = _gather_path(jt)
    assert jt.row_feeds_local.shape == (len(DESIGNS), R_PAD, h.MAX_FEEDS)
    st = stack([_random_state(t, seed=i) for i, t in enumerate(topos)])
    dep = pl.Deployment.make(130.0, 2, is_gpu=True)

    @jax.jit
    def step(jt, st):
        return jax.vmap(lambda j, s: (
            pl.row_feasible(j, s, dep, 2),
            pl.row_feasible(j, s, dep, 2, use_kernel=True, interpret=True),
            pl.row_scores(j, s, dep, 2, pl.POLICY_VAR_MIN, KEY)))(jt, st)

    for a, b in zip(step(jt, st), step(jt_g, st)):
        _assert_same(a, b)


def test_untiled_topology_keeps_the_gathers():
    """A topology whose rows are not blocked by hall takes the gather
    path: `jax_topology` leaves both fields None."""
    topo = h.build_topology(h.design_4n3(), 2)
    perm = np.r_[topo.rows_per_hall:2 * topo.rows_per_hall,
                 0:topo.rows_per_hall]
    shuffled = type(topo)(**{**vars(topo),
                             "row_hall": np.asarray(topo.row_hall)[perm]})
    jt = pl.jax_topology(shuffled)
    assert jt.row_feeds_local is None and jt.row_feed_cap is None
    assert pl.jax_topology(topo).row_feeds_local is not None


def _subjaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jex.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex.Jaxpr):
                yield x


def _gather_operands(jaxpr):
    """Shapes of every gather operand in `jaxpr` and its sub-jaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.append(tuple(eqn.invars[0].aval.shape))
        for sub in _subjaxprs(eqn.params):
            out.extend(_gather_operands(sub))
    return out


@pytest.mark.parametrize("step", ["jnp", "kernel", "scores"])
def test_no_lineup_or_hall_gather_on_the_tiled_path(step):
    """The full-row step of a tiled topology gathers nothing indexed by
    line-up (leading axis X) or by hall (leading axis H); the gather
    path of the same topology does, so the check can fail."""
    topo = _topology("4N/3", 3)             # R = 300, X = 30, H = 3
    X, H = topo.lineup_cap.shape[0], topo.n_halls
    jt = pl.jax_topology(topo)
    st = pl.init_state(topo)
    dep = pl.Deployment.make(130.0, 2, is_gpu=True)
    fn = {"jnp": lambda j, s: pl.row_feasible(j, s, dep, 2),
          "kernel": lambda j, s: pl.row_feasible(j, s, dep, 2,
                                                 use_kernel=True,
                                                 interpret=True),
          "scores": lambda j, s: pl.row_scores(j, s, dep, 2,
                                               pl.POLICY_VAR_MIN, KEY)}[step]

    def indexed(j):
        shapes = _gather_operands(jax.make_jaxpr(fn)(j, st).jaxpr)
        return [s for s in shapes if s and s[0] in (X, H)]

    assert indexed(jt) == []
    assert indexed(_gather_path(jt))


# ---------------------------------------------------------------------------
# Whole engines: every output bitwise equal with the tiled path switched off.
# ---------------------------------------------------------------------------

ENV = EnvelopeSpec(demand_scale=0.002)


def _outputs(res):
    return {k: v for k, v in vars(res).items()
            if isinstance(v, (np.ndarray, np.generic, int, float))}


def _assert_outputs_equal(a, b):
    oa, ob = _outputs(a), _outputs(b)
    assert oa.keys() == ob.keys() and oa
    for k in oa:
        np.testing.assert_array_equal(oa[k], ob[k], err_msg=k)


def _both_paths(monkeypatch, run):
    tiled = run()
    monkeypatch.setattr(pl, "_hall_tiling", lambda topo: None)
    return tiled, run()


def test_sweep_outputs_bitwise(monkeypatch):
    axes = SweepAxes.zip(designs=[h.get_design(n) for n in ("4N/3", "8+2")],
                         envs=[ENV], seeds=[3])
    a, b = _both_paths(monkeypatch, lambda: sweep(axes))
    _assert_outputs_equal(a, b)


def test_mc_sweep_outputs_bitwise(monkeypatch):
    axes = MCAxes.product(designs=[h.get_design(n) for n in ("3+1", "10N/8")],
                          policies=range(4), seeds=(5,))

    def run():
        monkeypatch.setattr(mc, "_TOPO_CACHE", {})
        return mc_sweep(axes, n_trials=2, n_events=60, refill_events=20)

    a, b = _both_paths(monkeypatch, run)
    _assert_outputs_equal(a, b)


def test_run_fleet_outputs_bitwise(monkeypatch):
    cfg = FleetConfig(h.get_design("10N/8"), ENV, seed=4)
    a, b = _both_paths(monkeypatch, lambda: run_fleet(cfg))
    _assert_outputs_equal(a, b)
