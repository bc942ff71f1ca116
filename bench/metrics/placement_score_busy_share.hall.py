"""placement_score's device time over device busy time in the traced hall
call, percent."""
from bench import readers


def read(ctx):
    return readers.kernel_busy_share(ctx)
