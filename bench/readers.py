"""Reductions the per-layer readers in `bench/metrics/` share.

A reader gets the run's `Context` (`bench/run.py`) and returns a number,
or None where its cell gives it nothing to read (no traced call, no
kernel launch): the harness then leaves the metric out.
"""
from __future__ import annotations

from bench import work

KERNEL_PREFIX = "%feasible_rows"     # the placement_score custom call


def idle_share(ctx):
    """Percent of the traced call in which the device ran no op, mean
    over the chips used."""
    t = ctx.traced
    if not t or not t["idle_share_per_device"]:
        return None
    per = t["idle_share_per_device"]
    return 100.0 * sum(per) / len(per)


def scan_us_per_event(ctx):
    """Device busy time of the traced call over its real events."""
    t = ctx.traced
    if not t or not t["events"] or not t["busy_s"]:
        return None
    return t["busy_s"] * 1e6 / t["events"]


def _kernel(ctx):
    t = ctx.traced
    if not t:
        return None
    launches = []
    for dev in t["trace"].devices:
        for s, e, name in zip(dev.start, dev.end, dev.names):
            if name.startswith(KERNEL_PREFIX):
                launches.append((int(e - s), work.launch_shape(name)))
    return launches or None


def kernel_busy_share(ctx):
    """Percent of device busy time spent in placement_score launches."""
    launches = _kernel(ctx)
    if not launches:
        return None
    busy = ctx.traced["busy_s"] * len(ctx.traced["trace"].devices)
    return 100.0 * sum(d for d, _ in launches) / 1e9 / busy


def kernel_roofline(ctx):
    """Percent: the least time of the launches' feasibility work at the
    device's peaks (`bench/work.py`) over their measured time.  None when
    a launch's shapes cannot be read from the trace."""
    launches = _kernel(ctx)
    if not launches or any(s is None for _, s in launches):
        return None
    least = sum(work.least_seconds(*s, ctx.device_kind) for _, s in launches)
    return 100.0 * least / (sum(d for d, _ in launches) / 1e9)


def span_share(ctx, name: str):
    """Percent of the measured window inside the benchmark's `name` spans."""
    w = ctx.window
    inside = sum(t1 - t0 for n, t0, t1 in w["spans"] if n == name)
    return 100.0 * inside / w["seconds"] if w["seconds"] > 0 else None
