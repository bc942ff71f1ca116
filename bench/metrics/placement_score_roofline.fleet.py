"""placement_score's share of its roofline in the traced fleet call,
percent."""
from bench import readers


def read(ctx):
    return readers.kernel_roofline(ctx)
