"""Percent of the traced fleet call in batch preparation
(`repro.sweep.prepare`: padding, stacking and staging)."""
from bench import program_spans


def read(ctx):
    return program_spans.share(ctx, "repro.sweep", "repro.sweep.prepare")
