"""Device busy microseconds of the traced hall call per (configuration x
trial x fill and refill event)."""
from bench import readers


def read(ctx):
    return readers.scan_us_per_event(ctx)
