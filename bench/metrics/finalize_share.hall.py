"""Percent of the traced hall call in finalize and the metric stage
(`repro.mc_sweep.finalize`)."""
from bench import program_spans


def read(ctx):
    return program_spans.share(ctx, "repro.mc_sweep",
                               "repro.mc_sweep.finalize")
