"""The trace-to-metric reduction (`bench/trace.py`, `bench/readers.py`,
`bench/work.py`) on a small trace recorded on a TPU v5e and committed as
a fixture: one `mc_sweep` call of 2 trials of 8 + 4 events on a 4N/3
hall, traced with the Python tracer off."""
from __future__ import annotations

import os

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench import readers, trace, work

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tpu_mc_sweep_small.xplane.pb")
KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def recorded():
    return trace.read(FIXTURE)


def test_reads_the_device_plane_and_the_benchmark_spans(recorded):
    assert [d.index for d in recorded.devices] == [0]
    dev = recorded.devices[0]
    assert len(dev.start) == len(dev.end) == len(dev.names) > 0
    assert np.all(dev.end >= dev.start)
    assert {s[0] for s in recorded.spans} == {"bench.call", "bench.mc_sweep"}
    call = [s for s in recorded.spans if s[0] == "bench.call"][0]
    iv = dev.busy_intervals()
    # host spans and device ops share one clock, to within a millisecond:
    # the call encloses its ops
    assert call[1] - 1e6 <= iv[0, 0] and iv[-1, 1] <= call[2] + 1e6


def test_busy_time_is_the_union_of_op_intervals(recorded):
    dev = recorded.devices[0]
    iv = dev.busy_intervals()
    assert np.all(iv[1:, 0] > iv[:-1, 1])          # disjoint, ordered
    grid = np.zeros(int(dev.end.max() - dev.start.min()) + 1, bool)
    for s, e in zip(dev.start - dev.start.min(), dev.end - dev.start.min()):
        grid[s:e] = True
    assert dev.busy_ns() == int(grid.sum())


def test_kernel_launches_are_found_and_sized(recorded):
    names = [n for n in recorded.devices[0].names
             if n.startswith(readers.KERNEL_PREFIX)]
    assert names
    # 1 config x 2 trials, 4 feeds, 30 rows padded to one 128-lane tile
    assert {work.launch_shape(n) for n in names} == {(2, 4, 128)}


def test_readers_on_the_recorded_call(recorded):
    call = [s for s in recorded.spans if s[0] == "bench.call"][0]
    seconds = (call[2] - call[1]) / 1e9
    busy = recorded.devices[0].busy_ns() / 1e9
    traced = {"seconds": seconds, "units": 2, "events": 2 * (8 + 4),
              "busy_s": busy, "idle_share_per_device": [1 - busy / seconds],
              "trace": recorded}

    class Ctx:
        device_kind = KIND
        window = {"seconds": 1.0, "spans": [("bench.synth", 0.0, 0.25)]}

    ctx = Ctx()
    ctx.traced = traced
    idle = readers.idle_share(ctx)
    assert 0 < idle < 100
    assert readers.scan_us_per_event(ctx) == pytest.approx(busy * 1e6 / 24)
    share = readers.kernel_busy_share(ctx)
    roof = readers.kernel_roofline(ctx)
    assert 0 < share < 100 and 0 < roof < 100
    launches = [(e - s) for s, e, n in zip(
        recorded.devices[0].start, recorded.devices[0].end,
        recorded.devices[0].names) if n.startswith(readers.KERNEL_PREFIX)]
    least = len(launches) * work.least_seconds(2, 4, 128, KIND)
    assert roof == pytest.approx(100 * least / (sum(launches) / 1e9))
    assert readers.span_share(ctx, "bench.synth") == pytest.approx(25.0)
    gaps = trace.idle_gaps(recorded.devices[0], recorded.spans,
                           int(call[1]), int(call[2]))
    assert 0 < len(gaps) <= 10
    assert all(g[0].startswith("bench.") for g in gaps)
    ops = trace.top_ops(recorded.devices[0])
    assert ops and not any(o[0].startswith(("while", "conditional"))
                           for o in ops)


def test_nothing_to_read_gives_nothing():
    class Ctx:
        device_kind = KIND
        traced = None
        window = {"seconds": 1.0, "spans": []}

    for fn in (readers.idle_share, readers.scan_us_per_event,
               readers.kernel_busy_share, readers.kernel_roofline):
        assert fn(Ctx()) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9000")


def test_roofline_bound_is_bytes_on_v5e():
    b, ops = work.feasibility_work(12, 4, 4480)
    assert b == 12 * 4480 * 21 * 4
    p = work.peaks(KIND)
    assert work.least_seconds(12, 4, 4480, KIND) == b / p["hbm_bytes_per_s"]
