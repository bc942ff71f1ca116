"""Percent of the traced hall call in batch preparation
(`repro.mc_sweep.prepare`: trial synthesis and staging)."""
from bench import program_spans


def read(ctx):
    return program_spans.share(ctx, "repro.mc_sweep",
                               "repro.mc_sweep.prepare")
