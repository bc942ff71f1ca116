"""Percent of the traced fleet call in finalize and the metric stage
(`repro.sweep.finalize`)."""
from bench import program_spans


def read(ctx):
    return program_spans.share(ctx, "repro.sweep", "repro.sweep.finalize")
