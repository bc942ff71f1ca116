"""Share of the fleet window spent synthesising traces (`bench.synth` spans
around `generate_fleet_trace`), percent."""
from bench import readers


def read(ctx):
    return readers.span_share(ctx, "bench.synth")
