"""The control of `correct`, at a size a test run can hold.

The control is the plain reference computed in bfloat16, one step below
the float32 the configurations state, put in the program's place.  The
program's own readings must pass every limit of its cell; the control's
must fail at least one.
"""
from __future__ import annotations

import pytest

import bench_tiny
from bench import control
from bench.spec import Spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.tiny_root(str(tmp_path_factory.mktemp("control")))


@pytest.mark.parametrize("workload", ["fleet.fig13", "hall.fig7",
                                      "hall.fig7-pod7-high"])
def test_control_fails_and_program_passes(root, workload):
    import jax
    limits = Spec(root).cell(workload).traffic["check"]["limits"]
    rows = control.readings(root, workload, [2 ** 31 + 3], {2 ** 31 + 3},
                            find_devices=lambda n: jax.devices()[:n],
                            out=lambda s: None)
    program, ctl = rows[0]["program"], rows[0]["control"]
    assert all(program[k] <= lim for k, lim in limits.items()), program
    assert any(ctl[k] > lim for k, lim in limits.items()), ctl
