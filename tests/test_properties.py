"""Hypothesis property tests on system invariants."""
import pytest

pytest.importorskip("hypothesis")  # optional dev dep, requirements-dev.txt

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import hierarchy as h, placement as pl
from repro.core import throughput as tp, projections as proj
from repro.launch.hlo_analysis import _shape_bytes, parse_hlo

TOPO = h.build_topology(h.design_4n3())
JT = pl.jax_topology(TOPO)
TOPO_B = h.build_topology(h.design_3p1())
JT_B = pl.jax_topology(TOPO_B)


@settings(max_examples=12, deadline=None)
@given(st.lists(st.tuples(st.floats(5, 800), st.integers(1, 6),
                          st.booleans(), st.integers(0, 3)),
                min_size=1, max_size=15),
       st.integers(0, 2 ** 16))
def test_capacity_never_exceeded(seq, seed):
    """Invariant (Eq. 26): no placement sequence can overfill any node."""
    for jt, topo in ((JT, TOPO), (JT_B, TOPO_B)):
        state = pl.init_state(topo)
        key = jax.random.PRNGKey(seed)
        for i, (kw, n, gpu, policy) in enumerate(seq):
            dep = pl.Deployment.make(kw, n, is_gpu=gpu)
            state, ok, _, _ = pl.place(jt, state, dep, policy,
                                       jax.random.fold_in(key, i))
        assert (np.asarray(state.row_load)
                <= np.asarray(topo.row_cap) + 1e-2).all()
        eff = topo.ha_frac * np.asarray(topo.lineup_cap)
        assert (np.asarray(state.lineup_ha) <= eff + 1e-2).all()
        assert (np.asarray(state.hall_liq)
                <= np.asarray(topo.hall_liq_cap) + 1e-2).all()


@settings(max_examples=20, deadline=None)
@given(st.floats(10, 1200), st.integers(1, 7), st.booleans(),
       st.integers(0, 2 ** 16))
def test_place_release_is_identity(kw, n, gpu, seed):
    state0 = pl.init_state(TOPO)
    dep = pl.Deployment.make(kw, n, is_gpu=gpu, is_pod=gpu and n > 1)
    state1, ok, rows, counts = pl.place(JT, state0, dep, pl.POLICY_VAR_MIN,
                                        jax.random.PRNGKey(seed))
    if not bool(ok):
        return
    state2 = pl.release_bulk(JT, state1, rows[None], counts[None],
                             jnp.asarray([kw], jnp.float32),
                             jnp.asarray([gpu]), jnp.asarray([0]),
                             jnp.asarray([1.0]))
    for a, b in zip(jax.tree.leaves(state0._replace(rr_cursor=state2.rr_cursor)),
                    jax.tree.leaves(state2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.05)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 7), st.sampled_from(list(tp.MODELS)))
def test_tps_positive_and_pod_monotone(pod, mname):
    m = tp.MODELS[mname]
    d = tp.Deployment(proj.KYBER, 2028, pod, proj.HIGH)
    t = float(tp.tps_request(m, d))
    assert t > 0
    assert 0.0 <= tp.f_ib(m, d) < 1.0
    d1 = tp.Deployment(proj.KYBER, 2028, 1, proj.HIGH)
    assert tp.tps_per_watt(m, d) >= tp.tps_per_watt(m, d1) * 0.999


@settings(max_examples=25, deadline=None)
@given(st.integers(2025, 2040), st.sampled_from(list(proj.SCENARIOS)))
def test_projections_monotone_in_scenario(year, scenario):
    lo = proj.gpu_rack_kw(year, proj.LOW)
    hi = proj.gpu_rack_kw(year, proj.HIGH)
    mid = proj.gpu_rack_kw(year, proj.MED)
    assert lo <= mid <= hi
    assert proj.gpu_rack_kw(year, scenario) > 0


@settings(max_examples=30, deadline=None)
@given(st.sampled_from(["pred", "bf16", "f32", "s32"]),
       st.lists(st.integers(1, 64), min_size=0, max_size=4))
def test_shape_bytes(dtype, dims):
    n = int(np.prod(dims)) if dims else 1
    per = {"pred": 1, "bf16": 2, "f32": 4, "s32": 4}[dtype]
    assert _shape_bytes(dtype, ",".join(map(str, dims))) == n * per


@settings(max_examples=15, deadline=None)
@given(st.sampled_from([5, 12, 17]), st.integers(2, 5),
       st.floats(10, 1500), st.floats(0.25, 1.0), st.booleans(),
       st.booleans(), st.integers(0, 2 ** 16))
def test_score_rows_matches_reference(R, F, p_dep, ha_frac, is_ha,
                                      is_block, seed):
    """Property: the Pallas `feasible_rows` path (interpret mode, padded
    to block_r tiles) agrees bitwise with the pure-jnp
    `reference_feasible` oracle on random feed maps / loads."""
    from repro.kernels.placement_score.ops import feasible_rows
    from repro.kernels.placement_score.ref import reference_feasible
    rng = np.random.default_rng(seed)
    X = 6
    feeds = np.where(rng.random((R, F)) < 0.25, -1,
                     rng.integers(0, X, (R, F))).astype(np.int32)
    nfeeds = (feeds >= 0).sum(-1).astype(np.int32)
    ha = rng.uniform(0, 2000, X).astype(np.float32)
    tot = (ha + rng.uniform(0, 400, X)).astype(np.float32)
    caps = np.full((X,), 2500.0, np.float32)
    row_cap = rng.uniform(400, 900, R).astype(np.float32)
    row_load = rng.uniform(0, 500, R).astype(np.float32)
    feas_k = feasible_rows(feeds, nfeeds, row_cap, ha, tot, caps,
                           row_load, p_dep, ha_frac, is_ha, is_block,
                           block_r=128, interpret=True)
    safe = np.where(feeds >= 0, feeds, 0)
    valid = (feeds >= 0).astype(np.float32)
    params = jnp.array([p_dep, ha_frac, float(is_ha), float(is_block)],
                       jnp.float32)
    feas_r = reference_feasible(
        jnp.asarray(ha[safe]), jnp.asarray(tot[safe]),
        jnp.asarray(caps[safe]), jnp.asarray(valid), jnp.asarray(nfeeds),
        jnp.asarray(row_load), jnp.asarray(row_cap), params)
    np.testing.assert_array_equal(np.asarray(feas_k),
                                  np.asarray(feas_r) > 0)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 6), st.floats(50, 900), st.booleans(),
       st.integers(0, 3), st.integers(0, 2 ** 16))
def test_kernel_subset_padding_never_wins(n_hd, kw, gpu, policy, seed):
    """Property: restricting the kernel path to an HD-compacted subset
    (padded internally to block_r tiles) never selects a padded/masked
    row — the chosen row and resulting state are bitwise the jnp path's."""
    dep = pl.Deployment.make(kw, 1, is_gpu=gpu)
    key = jax.random.PRNGKey(seed)
    active = jnp.ones((TOPO.row_cap.shape[0],), bool)
    rows = JT.hd_index[:max(n_hd, 1)]
    st_j, ok_j, row_j = pl.place_in_row(JT, pl.init_state(TOPO), dep, 1,
                                        policy, key, active,
                                        row_subset=rows)
    st_k, ok_k, row_k = pl.place_in_row(JT, pl.init_state(TOPO), dep, 1,
                                        policy, key, active,
                                        row_subset=rows, use_kernel=True,
                                        interpret=True)
    assert bool(ok_j) == bool(ok_k)
    assert int(row_j) == int(row_k)
    if bool(ok_k):   # selection stayed inside the real subset
        assert int(row_k) in np.asarray(rows).tolist()
    for a, b in zip(jax.tree.leaves(st_j), jax.tree.leaves(st_k)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hlo_parser_on_synthetic_module():
    txt = """HloModule test

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16] get-tuple-element(%p), index=1
  %w = f32[16,16] constant({...})
  %y = f32[8,16] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16]) tuple(%i2, %y)
}

%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,16]) tuple(%z, %a)
  %w = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,16] get-tuple-element(%w), index=1
}
"""
    from repro.launch.hlo_analysis import analyze
    cost = analyze(txt, 1)
    # 12 loop trips × (2·8·16·16) flops per dot
    assert cost.flops == 12 * 2 * 8 * 16 * 16
    assert cost.n_while == 1
