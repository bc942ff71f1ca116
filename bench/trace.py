"""Reduction of a JAX profiler trace (`.xplane.pb`) to device numbers.

Reads the file with `jax.profiler.ProfileData` and nothing else.  A TPU
plane is named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event
per executed operation (a `while` op's event encloses those of its
body), so a device's busy time is the union of those intervals.  The
host plane holds, on the line of the thread that opened them, the
`TraceAnnotation` spans of the benchmark's own steps (``bench.*``).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
# Ops whose event spans the ops of a nested computation: left out of the
# per-op breakdown, which would otherwise count their body twice.
CONTROL_OPS = ("while", "conditional", "call")


@dataclass
class Device:
    index: int
    start: np.ndarray          # ns, one entry per op event
    end: np.ndarray
    names: list

    def busy_intervals(self):
        """Merged [start, end) intervals in which an op ran."""
        if not len(self.start):
            return np.zeros((0, 2), np.int64)
        o = np.argsort(self.start, kind="stable")
        s, e = self.start[o], self.end[o]
        run_end = np.maximum.accumulate(e)
        new = np.ones(len(s), bool)
        new[1:] = s[1:] > run_end[:-1]
        starts = s[new]
        ends = run_end[np.r_[np.flatnonzero(new)[1:] - 1, len(s) - 1]]
        return np.stack([starts, ends], axis=1)

    def busy_ns(self) -> int:
        iv = self.busy_intervals()
        return int((iv[:, 1] - iv[:, 0]).sum())


@dataclass
class Trace:
    devices: list
    spans: list = field(default_factory=list)   # (name, start_ns, end_ns)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(files)}")
    return files[0]


def read(path: str) -> Trace:
    """Device op events of every TPU plane and the benchmark's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            start, end, names = [], [], []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    start.append(ev.start_ns)
                    end.append(ev.start_ns + ev.duration_ns)
                    names.append(ev.name)
            devices.append(Device(int(m.group(1)),
                                  np.asarray(start, np.int64),
                                  np.asarray(end, np.int64), names))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    devices.sort(key=lambda d: d.index)
    return Trace(devices, spans)


def op_label(name: str) -> str:
    """The op's name and output shape: '%fusion.394 = f32[211200]{…}
    fusion(…)' gives 'fusion.394 f32[211200]'."""
    head, _, rest = name.partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (" " + shape.group(1) if shape else "")


def is_control(name: str) -> bool:
    _, _, rest = name.partition(" = ")
    call = re.search(r"\}\s+([a-z\-]+)\(", rest) or re.search(
        r"\s([a-z\-]+)\(", rest)
    return bool(call) and call.group(1) in CONTROL_OPS


def top_ops(dev: Device, n: int = 10):
    """[(label, seconds)] of the ops that took most device time, control
    flow left out."""
    tot: dict = {}
    for s, e, name in zip(dev.start, dev.end, dev.names):
        if is_control(name):
            continue
        k = op_label(name)
        tot[k] = tot.get(k, 0) + int(e - s)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(dev: Device, spans, t0: int, t1: int, n: int = 10):
    """The `n` longest stretches of [t0, t1) with no op on `dev`, each
    named by the innermost benchmark span open at its middle."""
    iv = dev.busy_intervals()
    edges = np.concatenate([[t0], iv.ravel(), [t1]]).reshape(-1, 2)
    gaps = [(int(a), int(b)) for a, b in edges if b > a]
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) // 2
        open_ = [s for s in spans if s[1] <= mid < s[2]]
        label = (min(open_, key=lambda s: s[2] - s[1])[0] if open_
                 else "outside the benchmark's spans")
        out.append([label, (b - a) / 1e9])
    return out
