"""Reductions of the program's own spans for the per-layer readers.

The program records its host spans and counters in memory while a
profiler session collects (`repro.runtime.spans`): one tree per grid
call, under a root span named for its engine (`repro.sweep`,
`repro.mc_sweep`), on the `time.perf_counter` clock that `bench/run.py`
times the traced call with.  A reader returns None where the program
records no spans, or where the run recorded other than exactly one root
span of the engine.
"""
from __future__ import annotations


def call_spans(root: str):
    """The records of the one grid call under a root span named `root`,
    or None."""
    try:
        from repro.runtime import spans
    except ImportError:          # a program without spans
        return None
    recs = spans.records()
    roots = [r for r in recs if r.name == root and r.parent is None]
    if len(roots) != 1:
        return None
    return [r for r in recs if r.call == roots[0].call]


def share(ctx, root: str, name: str):
    """Percent of the traced call's host-clock length inside the spans
    called `name` of the grid call under `root`."""
    t, recs = ctx.traced, call_spans(root)
    if not t or recs is None or t["seconds"] <= 0:
        return None
    return 100.0 * sum(r.seconds for r in recs if r.name == name) \
        / t["seconds"]


def count_ratio(ctx, root: str, num, den):
    """Percent: the count `num` = (span, count) over the count `den` in
    the grid call under `root`."""
    recs = call_spans(root) if ctx.traced else None
    if recs is None:
        return None

    def total(span, count):
        return sum(r.counts.get(count, 0) for r in recs if r.name == span)

    d = total(*den)
    return 100.0 * total(*num) / d if d else None
