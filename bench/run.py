#!/usr/bin/env python3
"""The chip benchmark: one cell of `BENCHMARK.json`, closed loop.

    python bench/run.py --workload fleet.fig13 --seed 7 --seconds 40 --trace 0

Set-up imports the simulator, turns on JAX's persistent compilation
cache at ``<checkout>/.bench_cache/jax`` and warms up, which compiles
(or loads from the cache) every program the window's calls run: one
warm-up grid of its own, or, where the program's shapes follow each
grid's contents (`warm_each_call`), every grid the window will submit,
as many as `--seconds` over the fastest warm-up call, with a margin.
Then the window: whole grid calls back to back, each a fresh grid from
`--seed` and its index, until `--seconds` have passed; a call that
starts inside the window runs to its end, and all its work and time
count.  With `--trace 1` one more call runs under the JAX profiler and
the per-layer readers (`bench/metrics/<name>.py`) reduce its trace.  Last, the window's
answers are compared with the plain reference (`bench/reference/`) on a
sample drawn from the seed, each number beside its limit.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (with `--trace 1` also
`breakdown`) and `checks`.  A machine without a TPU, or with fewer chips
than the cell asks for, gets exit code 3 and no result line.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.spec import Spec  # noqa: E402

COMPILE_EVENTS = "/jax/core/compile/"    # tracing, lowering, compiling
COMPILE_EVENT = COMPILE_EVENTS + "backend_compile_duration"  # or loading
EXIT_NO_CHIP = 3
WARM_MARGIN = 0.9     # window calls may run this much faster than warm-ups


def process_age() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T0


class Spans:
    """Host-clock spans of the benchmark's own steps, each also written
    into the profiler's trace as a `TraceAnnotation`."""

    def __init__(self):
        self.spans = []
        import jax
        self._annotate = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name: str):
        t = time.perf_counter()
        with self._annotate(name):
            try:
                yield
            finally:
                self.spans.append((name, t, time.perf_counter()))

    def within(self, t0: float, t1: float):
        return [s for s in self.spans if s[1] >= t0 and s[2] <= t1]


class Context:
    """What a per-layer reader may read: the window's host-clock numbers,
    the traced call and its trace, the device kind, the cell."""

    def __init__(self, cell, device_kind, window, traced):
        self.cell, self.device_kind = cell, device_kind
        self.window, self.traced = window, traced


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def tpu_devices(n: int):
    """The first `n` TPU chips, or None where JAX finds fewer."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        print(f"bench: {n} TPU chip(s) needed; JAX found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return None
    return devices[:n]


def main(argv=None, root: str = ROOT, find_devices=tpu_devices) -> int:
    args = parse_args(argv)
    spec = Spec(root)
    cell = spec.cell(args.workload)

    import jax
    devices = find_devices(cell.chips)
    if devices is None:
        return EXIT_NO_CHIP
    if devices[0].platform == "tpu":
        # a fixed path inside the checkout: the path is part of the key
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".bench_cache", "jax"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles, compile_s = [], [0.0]   # end time of each; seconds of all

    def on_event(event, duration, **_):
        if event.startswith(COMPILE_EVENTS):
            compile_s[0] += duration
        if event == COMPILE_EVENT:
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)

    spans = Spans()
    sim = spec.adapter(cell).make(cell, args.seed, devices, spans.span)
    warmed = warm_up(sim, args.seconds, lambda: compile_s[0], args.trace)
    setup_s = process_age()
    print(f"set-up: {warmed} warm-up grid call(s), "
          f"{len(compiles)} compilation(s)", flush=True)

    # ---- the measured window ----
    units = events = calls = 0
    durations = []
    load0 = os.getloadavg()[0]
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        t = time.perf_counter()
        with spans.span("bench.call"):
            r = sim.call(calls)
        durations.append(time.perf_counter() - t)
        units += r["units"]
        events += r["events"]
        calls += 1
    t1 = time.perf_counter()
    window = {"seconds": t1 - t0, "units": units, "events": events,
              "calls": calls, "spans": spans.within(t0, t1)}
    in_window = sum(t0 <= t <= t1 for t in compiles)
    print(f"window: {calls} calls, {units} {sim.unit}, {events} events, "
          f"{t1 - t0!r} s; compilations inside the window: {in_window}",
          flush=True)
    print("call seconds: min {!r}, median {!r}, max {!r} (call {}); "
          "host load average {!r} before, {!r} after".format(
              min(durations), sorted(durations)[len(durations) // 2],
              max(durations), durations.index(max(durations)), load0,
              os.getloadavg()[0]), flush=True)

    traced = None
    if args.trace:
        traced = traced_call(sim, spans, calls,
                             os.path.join(root, ".bench_cache", "trace"),
                             {d.id for d in devices})
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    print(f"memory_peak_bytes: {peak}", flush=True)

    # ---- metrics ----
    kind = devices[0].device_kind
    metrics = {}
    if args.trace:
        ctx = Context(cell, kind, window, traced)
        for m in cell.per_layer:
            v = spec.metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        rate = units / window["seconds"]
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else rate
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": None, "attempted": units + (traced or {}).get(
        "units", 0), "failed": 0, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["seconds"]
        for i, idle in enumerate(traced["idle_share_per_device"]):
            print(f"device {i} idle share: {idle!r}", flush=True)
        result["breakdown"] = traced["breakdown"]

    # ---- correctness, once the window has closed ----
    limits = cell.traffic["check"]["limits"]
    t_check = time.perf_counter()
    readings = sim.check()
    # a gap that cannot be measured (NaN against a number) has no bound
    checks = {k: {"value": min(readings[k], sys.float_info.max),
                  "limit": lim} for k, lim in limits.items()}
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    result["checks"] = checks
    print(f"reference check: {time.perf_counter() - t_check!r} s",
          flush=True)
    print(json.dumps(result), flush=True)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return 0


def warm_up(sim, seconds: float, compile_seconds, trace: int) -> int:
    """Run the grid calls that load every program the window needs, and
    return how many ran.  An adapter whose shapes follow each grid's
    contents (`warm_each_call`) runs the grids of calls 0, 1, ... until
    they cover `seconds` at the fastest warm-up call's pace, less
    `WARM_MARGIN`, and one more for the traced call; a call's time leaves
    out what it spent tracing, lowering and compiling.  Any other runs
    one grid of its own."""
    if not sim.warm_each_call:
        sim.warm(-1)
        return 1
    fastest, n = math.inf, 0
    while n == 0 or n < math.ceil(
            seconds / (WARM_MARGIN * fastest)) + trace:
        c, t = compile_seconds(), time.perf_counter()
        sim.warm(n)
        spent = time.perf_counter() - t - (compile_seconds() - c)
        fastest = min(fastest, max(spent, 1e-3))
        n += 1
    return n


def traced_call(sim, spans, index: int, trace_dir: str, used) -> dict:
    """One more call under the profiler, reduced to device numbers: busy
    time (the union of op intervals) per chip used, the ops that took most
    time and the longest idle gaps, named by the benchmark span open then."""
    import jax
    from bench import trace as tr
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # spans stay; per-function events go
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        t = time.perf_counter()
        with spans.span("bench.call"):
            r = sim.call(index)
        seconds = time.perf_counter() - t
    try:
        trace = tr.read(tr.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    trace.devices = [d for d in trace.devices if d.index in used]
    busy = [d.busy_ns() / 1e9 for d in trace.devices]
    out = {"seconds": seconds, "units": r["units"], "events": r["events"],
           "busy_s": sum(busy) / len(busy) if busy else 0.0,
           "idle_share_per_device": [max(0.0, 1.0 - b / seconds)
                                     for b in busy],
           "trace": trace,
           "breakdown": {"device_ops": [], "idle_gaps": []}}
    if trace.devices and len(trace.devices[0].start):
        d0 = trace.devices[0]
        calls = [s for s in trace.spans if s[0] == "bench.call"]
        iv = d0.busy_intervals()
        c0, c1 = (calls[-1][1], calls[-1][2]) if calls else (iv[0, 0],
                                                             iv[-1, 1])
        inside = (iv[0, 0] >= c0 - 1e6) and (iv[-1, 1] <= c1 + 1e6)
        print(f"traced call: device ops inside the host span: {inside}",
              flush=True)
        out["breakdown"] = {"device_ops": tr.top_ops(d0),
                            "idle_gaps": tr.idle_gaps(d0, trace.spans,
                                                      int(c0), int(c1))}
    return out


if __name__ == "__main__":
    sys.exit(main())
