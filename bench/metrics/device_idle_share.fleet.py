"""Percent of the traced fleet call with no op on the device, mean over
chips."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
