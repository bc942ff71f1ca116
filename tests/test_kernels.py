"""Pallas kernel sweeps: shapes × dtypes, assert_allclose vs pure-jnp
oracles (interpret mode on CPU; same kernels target TPU VMEM tiling)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(7)


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,Hk,hd,causal,dt", [
        (2, 128, 4, 2, 32, True, jnp.float32),
        (1, 96, 2, 2, 16, False, jnp.float32),
        (2, 64, 4, 1, 64, True, jnp.bfloat16),
        (1, 80, 8, 4, 32, True, jnp.float32),   # non-divisible seq (pad)
    ])
    def test_vs_oracle(self, B, S, H, Hk, hd, causal, dt):
        from repro.kernels.flash_attention.ops import flash_attention
        from repro.kernels.flash_attention.ref import reference_attention
        q = jax.random.normal(KEY, (B, S, H, hd), dt)
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hk, hd), dt)
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hk, hd), dt)
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True)
        ref = jnp.swapaxes(reference_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=causal), 1, 2)
        tol = 0.05 if dt == jnp.bfloat16 else 3e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=tol)


class TestSSDScan:
    @pytest.mark.parametrize("B,S,nh,hd,st,chunk", [
        (2, 64, 4, 16, 8, 16),
        (1, 100, 8, 8, 16, 32),    # pad path
        (2, 128, 16, 32, 16, 64),
    ])
    def test_vs_naive_recurrence(self, B, S, nh, hd, st, chunk):
        from repro.kernels.ssd_scan.ops import ssd_scan
        from repro.kernels.ssd_scan.ref import reference_ssd
        ks = jax.random.split(KEY, 4)
        xdt = 0.5 * jax.random.normal(ks[0], (B, S, nh, hd), jnp.float32)
        log_a = -0.5 * jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
        b = 0.5 * jax.random.normal(ks[2], (B, S, st))
        c = 0.5 * jax.random.normal(ks[3], (B, S, st))
        out = ssd_scan(xdt, log_a, b, c, chunk=chunk, head_block=4,
                       interpret=True)
        ref = reference_ssd(xdt, log_a, b, c)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-3)

    def test_model_chunked_matches_oracle(self):
        from repro.kernels.ssd_scan.ref import reference_ssd
        from repro.models.ssm import _ssd_chunked
        ks = jax.random.split(KEY, 4)
        xdt = 0.3 * jax.random.normal(ks[0], (2, 96, 4, 8), jnp.float32)
        log_a = -0.4 * jax.nn.softplus(jax.random.normal(ks[1], (2, 96, 4)))
        b = 0.5 * jax.random.normal(ks[2], (2, 96, 8))
        c = 0.5 * jax.random.normal(ks[3], (2, 96, 8))
        np.testing.assert_allclose(
            np.asarray(_ssd_chunked(xdt, log_a, b, c, 32)),
            np.asarray(reference_ssd(xdt, log_a, b, c)), atol=1e-3)


class TestMoEGating:
    @pytest.mark.parametrize("N,E,k", [(128, 16, 2), (100, 64, 6),
                                       (256, 32, 8), (64, 8, 1)])
    def test_vs_oracle(self, N, E, k):
        from repro.kernels.moe_gating.ops import fused_gating
        from repro.kernels.moe_gating.ref import reference_gating
        logits = jax.random.normal(jax.random.fold_in(KEY, N + E), (N, E))
        g1, i1 = fused_gating(logits, k, block_n=64, interpret=True)
        g2, i2 = reference_gating(logits, k)
        assert np.array_equal(np.sort(np.asarray(i1), -1),
                              np.sort(np.asarray(i2), -1))
        np.testing.assert_allclose(np.sort(np.asarray(g1), -1),
                                   np.sort(np.asarray(g2), -1), atol=1e-5)
        np.testing.assert_allclose(np.asarray(g1).sum(-1),
                                   np.ones(N), atol=1e-5)


def _placement_score_inputs(R, F, seed=0, p_dep=150.0, ha_frac=0.75,
                            is_ha=1.0, is_block=0.0):
    """Random [R, F] feed-gathered kernel inputs (params v2 layout)."""
    ks = jax.random.split(jax.random.fold_in(KEY, 1000 * seed + R), 4)
    loads_ha = jax.random.uniform(ks[0], (R, F)) * 1800
    loads_tot = loads_ha + jax.random.uniform(ks[3], (R, F)) * 400
    caps = jnp.full((R, F), 2500.0)
    valid = (jax.random.uniform(ks[1], (R, F)) > 0.3).astype(jnp.float32)
    nf = jnp.maximum(valid.sum(-1), 1)
    row_load = jax.random.uniform(ks[2], (R,)) * 500
    row_cap = jnp.full((R,), 625.0)
    params = jnp.array([p_dep, ha_frac, is_ha, is_block], jnp.float32)
    return loads_ha, loads_tot, caps, valid, nf, row_load, row_cap, params


class TestPlacementScore:
    @pytest.mark.parametrize("R,F", [(64, 4), (30, 4), (128, 2)])
    @pytest.mark.parametrize("is_ha,is_block",
                             [(1.0, 0.0), (0.0, 0.0), (1.0, 1.0)])
    def test_vs_oracle(self, R, F, is_ha, is_block):
        from repro.kernels.placement_score.kernel import placement_score
        from repro.kernels.placement_score.ref import reference_feasible
        args = _placement_score_inputs(R, F, is_ha=is_ha, is_block=is_block)
        f1 = placement_score(*args, block_r=128, interpret=True)
        f2 = reference_feasible(*args)
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))

    @pytest.mark.parametrize("block_r", [128, 256, 512])
    @pytest.mark.parametrize("R", [7, 129, 301])
    def test_block_r_padding_sweep(self, block_r, R):
        """Odd row counts against every lane-aligned tile size: the
        internal padding (rows masked infeasible, outputs sliced back to
        R) must be exact for every remainder pattern, from one tile to a
        multi-step grid."""
        from repro.kernels.placement_score.kernel import placement_score
        from repro.kernels.placement_score.ref import reference_feasible
        args = _placement_score_inputs(R, 4, seed=block_r)
        f1 = placement_score(*args, block_r=block_r, interpret=True)
        assert f1.shape == (R,)
        f2 = reference_feasible(*args)
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))

    @pytest.mark.parametrize("block_r", [0, 8, 200])
    def test_rejects_unaligned_block_r(self, block_r):
        """Rows ride the 128-wide lane axis, so a tile that is not a
        positive multiple of 128 is refused before any lowering."""
        from repro.kernels.placement_score.kernel import placement_score
        args = _placement_score_inputs(30, 4)
        with pytest.raises(ValueError, match="multiple of 128"):
            placement_score(*args, block_r=block_r, interpret=True)

    def test_matches_placement_engine(self):
        """`feasible_rows` + the row/hall constraints reproduce
        `row_feasible` exactly on a distributed hall, and the public
        `use_kernel=True` dispatch is bitwise the jnp path."""
        from repro.core import hierarchy as h, placement as pl
        from repro.kernels.placement_score.ops import feasible_rows
        topo = h.build_topology(h.design_10n8())
        jt = pl.jax_topology(topo)
        st = pl.init_state(topo)._replace(
            lineup_ha=jnp.linspace(0, 1900, 10))
        st = st._replace(lineup_tot=st.lineup_ha)
        p_dep = 300.0
        feas_k = feasible_rows(jt.row_feeds, jt.row_nfeeds,
                               jt.row_cap[:, 0], st.lineup_ha,
                               st.lineup_tot, jt.lineup_cap,
                               st.row_load[:, 0], p_dep, topo.ha_frac,
                               True, jt.is_block, interpret=True)
        dep = pl.Deployment.make(p_dep, 1, is_gpu=False)
        feas_full = pl.row_feasible(jt, st, dep, 1)
        # engine adds HD/LD + cooling rules; kernel covers the power
        # condition — engine-feasible ⇒ kernel-feasible
        assert bool((~np.asarray(feas_full) | np.asarray(feas_k)).all())
        feas_disp = pl.row_feasible(jt, st, dep, 1, use_kernel=True,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(feas_full),
                                      np.asarray(feas_disp))

    def test_all_feeds_invalid(self):
        """Rows whose every `jt_row_feeds` entry is −1 (zero-capacity
        sweep-padding rows): the power condition is vacuous and the row
        fit decides — no NaN/garbage."""
        from repro.kernels.placement_score.ops import feasible_rows
        R, F, X = 16, 4, 6
        feeds = jnp.full((R, F), -1, jnp.int32)
        nfeeds = jnp.zeros((R,), jnp.int32)
        zeros_x = jnp.zeros((X,), jnp.float32)
        caps_x = jnp.full((X,), 2500.0)
        row_cap = jnp.full((R,), 625.0)
        row_load = jnp.zeros((R,), jnp.float32)
        feas = feasible_rows(feeds, nfeeds, row_cap, zeros_x, zeros_x,
                             caps_x, row_load, 150.0, 0.75, True, False,
                             block_r=128, interpret=True)
        assert bool(np.asarray(feas).all())
        # and with the deployment overflowing the row: cleanly infeasible
        feas2 = feasible_rows(feeds, nfeeds, row_cap, zeros_x, zeros_x,
                              caps_x, row_load, 1000.0, 0.75, True, False,
                              block_r=128, interpret=True)
        assert not bool(np.asarray(feas2).any())

    def test_rejects_float64_inputs(self):
        """x64 callers get a clear error, not silent downcast drift (the
        float32 contract in `placement_score/ops.py`)."""
        from repro.kernels.placement_score.ops import feasible_rows
        R, F, X = 8, 2, 4
        feeds = np.zeros((R, F), np.int32)
        nfeeds = np.full((R,), F, np.int32)
        with jax.enable_x64(True):
            args = [feeds, nfeeds, np.full((R,), 625.0),
                    np.zeros((X,)), np.zeros((X,)), np.full((X,), 2500.0),
                    np.zeros((R,)), 150.0, 0.75, True, False]
            with pytest.raises(TypeError, match="float64"):
                feasible_rows(*args, interpret=True)
