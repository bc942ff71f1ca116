"""Percent of the monthly scan's event slots that hold a real event in the
traced fleet call: `events` over `event_slots` on `repro.sweep.prepare`."""
from bench import program_spans

PREPARE = "repro.sweep.prepare"


def read(ctx):
    return program_spans.count_ratio(ctx, "repro.sweep",
                                     (PREPARE, "events"),
                                     (PREPARE, "event_slots"))
