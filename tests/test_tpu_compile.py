"""Compile the kernel path for a TPU v5e that is described, not attached.

Interpret mode, which every other kernel test uses, accepts programs the
TPU's compiler refuses (boolean selects, unaligned or rank-1 blocks).  So
this file compiles, with the TPU compiler that ships with `libtpu`, the
`placement_score` kernel at real hall sizes and the two jitted engines
that call it under `vmap` inside their event scans, each with
`use_kernel=True`, and asserts that the compiled program holds the kernel
(`tpu_custom_call`) and fits the chip's 16 GB of HBM.  Nothing runs, so
nothing here is a time or a result.

The topology is described inside a module-scoped fixture only: one
process at a time may load the TPU library, and describing it while
modules are imported would make test collection differ between workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hierarchy, projections as proj
from repro.core.arrivals import EnvelopeSpec
from repro.core.mc_sweep import MCAxes, _mc_prepare, _mc_sweep_jit
from repro.core.sweep import SweepAxes, _prepare, _sweep_jit
from repro.kernels.placement_score.kernel import placement_score

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described `v5e:2x2` topology.  The persistent
    compilation cache is off meanwhile: an executable compiled for a
    described chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        jnp.shape(x), jnp.result_type(x), sharding=sharding), tree)


def _assert_kernel_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"{total} bytes exceed one v5e's HBM"


# 30 / 100 rows: the 4N/3 and 10N/8 halls; 37: an odd HD-compacted subset
@pytest.mark.parametrize("R", [30, 100, 37])
def test_placement_score_compiles(one_chip, R):
    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    F = hierarchy.MAX_FEEDS
    args = [shape(R, F)] * 4 + [shape(R, dtype=jnp.int32), shape(R),
                                shape(R), shape(4)]
    compiled = jax.jit(placement_score).lower(*args).compile()
    _assert_kernel_fits(compiled)


@pytest.mark.parametrize("pod_racks", [1, 7], ids=["fig7", "pod7"])
def test_mc_sweep_jit_compiles_with_kernel(one_chip, pod_racks):
    """The Fig. 7 grid (10N/8 and 8+2 × 4 policies, 32 trials of 900
    events), and its pod variant whose split scan runs `_place_pod`
    over the HD-compacted rows."""
    designs = ("10N/8", "8+2") if pod_racks == 1 else ("10N/8",)
    axes = MCAxes.product(designs=[hierarchy.get_design(d)
                                   for d in designs],
                          policies=range(4), seeds=(7,))
    scenario = proj.MED if pod_racks == 1 else proj.HIGH
    args, statics = _mc_prepare(axes, 32, 900, 2028, scenario, 0.6,
                                pod_racks, 10, 0.0, False, None)
    assert statics.get("split_pods", False) == (pod_racks > 1)
    compiled = _mc_sweep_jit.lower(
        *_on(one_chip, args), harvest=True, use_kernel=True,
        kernel_interpret=False, **statics).compile()
    _assert_kernel_fits(compiled)


def test_sweep_jit_compiles_with_kernel(one_chip):
    """The Fig. 13 fleet grid (4 designs × 3 TDP scenarios) at
    `demand_scale=0.1`, 20k padded rows per configuration."""
    combos = [(s, n) for s in (proj.LOW, proj.MED, proj.HIGH)
              for n in ("4N/3", "3+1", "10N/8", "8+2")]
    axes = SweepAxes.zip(
        designs=[hierarchy.get_design(n) for _, n in combos],
        envs=[EnvelopeSpec(demand_scale=0.1, gpu_scenario=s)
              for s, _ in combos])
    args, _, _, _, with_pods, pod_len, hd_scan = _prepare(axes, 0, None)
    compiled = _sweep_jit.lower(
        *_on(one_chip, args), harvest=True, mature_months=12,
        with_pods=with_pods, pod_scan_len=pod_len, hd_scan=hd_scan,
        use_kernel=True, kernel_interpret=False).compile()
    _assert_kernel_fits(compiled)
