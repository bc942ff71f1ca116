"""Device busy microseconds of the traced fleet call per real trace event
(padding not counted)."""
from bench import readers


def read(ctx):
    return readers.scan_us_per_event(ctx)
