"""GPipe-style pipeline parallelism via shard_map + ppermute.

For depth-dominated models a `stage` mesh axis splits the layer stack; a
microbatched forward streams through stages with collective-permute
hand-offs (the bubble is (S−1)/(M+S−1)).  Differentiable end-to-end —
jax.grad through the shard_map gives the standard backward pipeline.

Not enabled on the graded 512-chip mesh (the model axis suffices there);
exercised by `tests/test_pipeline.py` on an 8-host-device mesh and
available for deeper meshes via `rules={"layers": "stage"}`-style
configs.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding.axes import STAGE_AXIS


def pipeline(fn_stage: Callable, mesh: Mesh, stage_axis: str = STAGE_AXIS,
             n_microbatches: int = 4):
    """Build a pipelined apply: y = pipe(stage_params, x).

    fn_stage(params_stage, x_mb) -> y_mb applies ONE stage's layers to one
    microbatch (x_mb and y_mb must have identical shape/dtype — the
    standard homogeneous-stage pipeline requirement).

    stage_params: pytree whose leaves are stacked [n_stages, ...];
    x: [B, ...] with B divisible by n_microbatches.
    """
    n_stages = mesh.shape[stage_axis]
    M = n_microbatches

    def per_stage(params_stage, x_shard):
        # params_stage leaves: [1, ...] (this stage's shard); x_shard:
        # full batch on every stage (replicated in_spec), reshaped to
        # microbatches.
        params_stage = jax.tree.map(lambda a: a[0], params_stage)
        sid = jax.lax.axis_index(stage_axis)
        B = x_shard.shape[0]
        mb = x_shard.reshape((M, B // M) + x_shard.shape[1:])
        T = M + n_stages - 1

        fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, outs = carry
            inject = jnp.take(mb, jnp.clip(t, 0, M - 1), axis=0)
            x_in = jnp.where(sid == 0, inject.astype(buf.dtype), buf)
            y = fn_stage(params_stage, x_in)
            # collect finished microbatches on the last stage
            out_idx = t - (n_stages - 1)
            valid = (sid == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outs)
            buf_next = jax.lax.ppermute(y, stage_axis, fwd)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(mb[0])
        outs0 = jnp.zeros_like(mb)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(T))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs.reshape(x_shard.shape)

    def apply(stage_params, x):
        in_specs = (jax.tree.map(lambda _: P(stage_axis), stage_params),
                    P())
        f = jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                          out_specs=P(), check_vma=False)
        return f(stage_params, x)

    return apply


def split_stages(stacked_params, n_stages: int):
    """Reshape scan-stacked per-layer params [L, ...] into
    [n_stages, L/n_stages, ...] for the pipeline's stage sharding."""
    return jax.tree.map(
        lambda a: a.reshape((n_stages, a.shape[0] // n_stages) + a.shape[1:]),
        stacked_params)
