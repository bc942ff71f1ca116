"""Percent of the traced hall call synthesising trials
(`repro.arrivals.mixed_traces`, every call under the grid call)."""
from bench import program_spans


def read(ctx):
    return program_spans.share(ctx, "repro.mc_sweep",
                               "repro.arrivals.mixed_traces")
