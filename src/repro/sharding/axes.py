"""Logical-axis → mesh-axis sharding rules (MaxText-style).

Model code annotates parameters and activations with *logical* axis names;
a rule set maps them onto physical mesh axes.  Rules are swappable per
launch configuration (single-pod, multi-pod, long-context), which is how
the §Perf hillclimb iterates sharding without touching model code.

Besides the model meshes ("pod", "data", "model"), this module owns the
sweep meshes: `repro.core.sweep.sharded_sweep` and
`repro.core.mc_sweep.sharded_mc_sweep` shard their embarrassingly-parallel
grids over a named 2-D (`CONFIG_AXIS` × `TRIAL_AXIS`) mesh (`sweep_mesh`)
whose PartitionSpecs come from the `SWEEP_RULES` logical-axis table via
`spec_for` (`batch_spec` for flat batches, `grid_spec` for [B, T] trial
grids; the default (D, 1) shape reproduces the historical 1-D
`config_mesh` layout bitwise).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]
Rules = Dict[str, AxisVal]

# ---------------------------------------------------------------------------
# Fleet-sweep configuration mesh (repro.core.sweep.sharded_sweep).
# ---------------------------------------------------------------------------

# Mesh-axis names for the sweep's (configuration × trial) grid.  The grid
# is embarrassingly parallel (one lifecycle per configuration/trial, no
# cross-config collectives), so mesh geometry is purely a placement
# choice: a 1-D `CONFIG_AXIS` mesh for flat configuration batches, or a
# 2-D (config × trial) mesh that spreads Monte-Carlo trial replicas over
# their own axis (multi-host fleets put `TRIAL_AXIS` on the fast
# intra-host interconnect; here it keeps per-device memory flat in both
# grid dimensions).
CONFIG_AXIS = "config"
TRIAL_AXIS = "trial"

# Pipeline-parallel stage axis (train/pipeline.py's GPipe mesh).  Every
# mesh-axis name used anywhere in the repo is declared in this module —
# `tools/repro_lint` rule RL601 rejects axis-name literals it cannot
# find here, so a typo'd axis can't silently replicate.
STAGE_AXIS = "stage"

# Logical-axis rules for the sweep engines (the levanter named-axis
# idiom: engine code names *logical* axes, this table maps them onto
# mesh axes, `spec_for` builds the PartitionSpecs).  "batch" is a flat
# (config·trial) axis product-sharded over BOTH mesh axes — on a (D, 1)
# mesh that is exactly the old 1-D `P(CONFIG_AXIS)` layout, so the 2-D
# generalization is bitwise-inert for flat batches.
SWEEP_RULES: Rules = {
    "config": CONFIG_AXIS,
    "trial": TRIAL_AXIS,
    "batch": (CONFIG_AXIS, TRIAL_AXIS),
}


def config_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D device mesh over `devices` (default: all local devices) with the
    single axis `CONFIG_AXIS`, for sharding a sweep's configuration batch."""
    devs = list(devices) if devices is not None else list(jax.devices())
    return jax.make_mesh((len(devs),), (CONFIG_AXIS,), devices=devs)


def config_spec() -> P:
    """PartitionSpec sharding the leading (configuration) axis over
    `CONFIG_AXIS`; trailing dims replicated."""
    return spec_for(("config",), SWEEP_RULES)


def sweep_mesh(devices: Optional[Sequence[jax.Device]] = None,
               shape: Optional[Tuple[int, int]] = None) -> Mesh:
    """2-D (`CONFIG_AXIS` × `TRIAL_AXIS`) device mesh over `devices`.

    `shape=(dc, dt)` must multiply out to the device count; the default
    `(D, 1)` puts every device on the configuration axis, which makes
    flat-batch sharding under `batch_spec()` bitwise-identical to the
    historical 1-D `config_mesh` layout (same device order, same slabs).
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    D = len(devs)
    if shape is None:
        shape = (D, 1)
    dc, dt = int(shape[0]), int(shape[1])
    if dc < 1 or dt < 1 or dc * dt != D:
        raise ValueError(
            f"mesh shape {shape} needs {max(dc, 1) * max(dt, 1)} devices, "
            f"got {D}")
    return jax.make_mesh((dc, dt), (CONFIG_AXIS, TRIAL_AXIS), devices=devs)


def batch_spec() -> P:
    """PartitionSpec for a FLAT (config·trial) batch axis: product-sharded
    over both mesh axes (dc·dt-way)."""
    return spec_for(("batch",), SWEEP_RULES)


def grid_spec() -> P:
    """PartitionSpec for a [B, T] (config, trial) grid: configurations
    block-shard over `CONFIG_AXIS`, trials over `TRIAL_AXIS`."""
    return spec_for(("config", "trial"), SWEEP_RULES)

# Baseline rule set for the production mesh ("pod", "data", "model").
# DP over (pod×data); TP/EP/vocab over model; optimizer state additionally
# sharded over data (ZeRO-1) via OPT_OVERRIDES.
def base_rules(multi_pod: bool) -> Rules:
    data = ("pod", "data") if multi_pod else ("data",)
    return {
        "batch": data,
        "seq": None,
        "seq_kv": None,
        "embed": None,
        # residual-stream activations are sharded over `model` (Megatron-SP
        # style): XLA inserts all-gather before each projection and
        # reduce-scatter after, so scan-saved residuals cost 1/TP memory.
        "act_embed": "model",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "expert": "model",
        "expert_cap": None,
        "vocab": "model",
        "layers": None,
        "ssm_heads": "model",
        "ssm_state": None,
        "ssm_inner": "model",
        "conv": None,
        "frontend": None,
    }


# ZeRO-1: optimizer moments additionally sharded over the data axes on the
# first data-shardable logical dim.
def _data_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


def opt_overrides(multi_pod: bool) -> Rules:
    return {"embed": _data_axes(multi_pod), "layers": None}


def fsdp_rules(rules: Rules, multi_pod: bool) -> Rules:
    """ZeRO-3/FSDP: parameters themselves sharded over the data axes on
    their `embed` dim (per-layer all-gather at use, inserted by GSPMD)."""
    r = dict(rules)
    r["embed"] = _data_axes(multi_pod)
    return r


def pure_dp_rules(multi_pod: bool) -> Rules:
    """Full data parallelism: batch sharded across the mesh, weights
    replicated (optimizer still ZeRO-sharded).  The right regime for
    models whose parameters fit one chip (≲ 4B at bf16 on v5e): removes
    all per-layer TP collectives, leaving only the gradient reduction
    (§Perf qwen3/mamba2 iterations).

    Multi-pod: global batch 256 < 512 chips, so the batch shards 256-way
    over (data×model) and the sequence splits 2-way over the `pod` axis
    (context parallelism across the DCN — measured near-ideal 2× compute
    scaling for qwen3, §Perf)."""
    r: Rules = {k: None for k in base_rules(multi_pod)}
    if multi_pod:
        r["batch"] = ("data", "model")
        r["seq"] = "pod"
    else:
        r["batch"] = ("data", "model")
    return r


def sequence_parallel_rules(multi_pod: bool) -> Rules:
    """Long-context decode variant (long_500k, batch=1): the KV sequence is
    sharded over `model` (flash-decode style partial-softmax), while heads
    and SSM state occupy the otherwise-idle `data` axis.  Weights keep
    their TP sharding."""
    r = dict(base_rules(multi_pod))
    r["batch"] = None
    r["seq_kv"] = "model"
    r["heads"] = "data"
    r["kv_heads"] = "data"
    r["ssm_heads"] = "data"
    r["ssm_inner"] = "data"
    return r


_state = threading.local()


def set_rules(rules: Optional[Rules], mesh: Optional[Mesh] = None):
    _state.rules = rules
    _state.mesh = mesh


def get_rules() -> Optional[Rules]:
    return getattr(_state, "rules", None)


def get_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_rules(rules: Rules, mesh: Optional[Mesh] = None):
    prev_r, prev_m = get_rules(), get_mesh()
    set_rules(rules, mesh)
    try:
        yield
    finally:
        set_rules(prev_r, prev_m)


def spec_for(axes: Sequence[Optional[str]], rules: Optional[Rules] = None) -> P:
    """Logical axes tuple → PartitionSpec under `rules`."""
    rules = rules if rules is not None else get_rules()
    if rules is None:
        return P()
    out, used = [], set()
    for a in axes:
        v = rules.get(a) if a is not None else None
        if v is None:
            out.append(None)
            continue
        vs = (v,) if isinstance(v, str) else tuple(v)
        vs = tuple(x for x in vs if x not in used)
        used.update(vs)
        out.append(vs if len(vs) > 1 else (vs[0] if vs else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def divisible_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop (or shrink to a divisible prefix) any axis mapping whose mesh
    extent does not divide the dimension — GSPMD requires exact
    divisibility for argument shardings.  Non-divisible cases (e.g. 40
    heads over a 16-way model axis) fall back to replication; §Perf
    iterations introduce arch-specific overrides instead."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(None)
            continue
        axes_t = (entry,) if isinstance(entry, str) else tuple(entry)
        kept, k = [], 1
        for a in axes_t:
            if shape[i] % (k * sizes[a]) == 0:
                kept.append(a)
                k *= sizes[a]
        out.append(tuple(kept) if len(kept) > 1 else
                   (kept[0] if kept else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard(x, *axes):
    """Apply a sharding constraint if rules+mesh are active (no-op in plain
    CPU tests)."""
    rules, mesh = get_rules(), get_mesh()
    if rules is None or mesh is None:
        return x
    spec = divisible_spec(spec_for(axes, rules), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_specs(axes_tree, rules: Optional[Rules] = None):
    """Axes pytree → PartitionSpec pytree."""
    return jax.tree.map(lambda a: spec_for(a, rules), axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def tree_shardings(axes_tree, mesh: Mesh, rules: Optional[Rules] = None):
    return jax.tree.map(lambda a: NamedSharding(mesh, spec_for(a, rules)),
                        axes_tree, is_leaf=lambda x: isinstance(x, tuple))


def _is_axes_leaf(x):
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)


def tree_shardings_matched(axes_tree, abstract_tree, mesh: Mesh,
                           rules: Optional[Rules] = None):
    """Shape-aware shardings: like `tree_shardings` but drops mappings that
    don't divide the concrete dimension."""
    flat_axes, treedef = jax.tree.flatten(axes_tree, is_leaf=_is_axes_leaf)
    flat_abs = treedef.flatten_up_to(abstract_tree)
    shardings = [
        NamedSharding(mesh, divisible_spec(spec_for(a, rules), s.shape, mesh))
        for a, s in zip(flat_axes, flat_abs)]
    return jax.tree.unflatten(treedef, shardings)


def opt_rules(rules: Rules, multi_pod: bool = False) -> Rules:
    r = dict(rules)
    r.update(opt_overrides(multi_pod))
    return r
