"""Percent of the pod window's lane-steps that place a real pod in the
traced hall call: `pod_events` over `pod_steps` on
`repro.mc_sweep.prepare` (the rest are steps of trials whose pods have
run out before the grid's largest pod count)."""
from bench import program_spans

PREPARE = "repro.mc_sweep.prepare"


def read(ctx):
    return program_spans.count_ratio(ctx, "repro.mc_sweep",
                                     (PREPARE, "pod_events"),
                                     (PREPARE, "pod_steps"))
