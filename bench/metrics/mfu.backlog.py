"""Model operations per second of the traced window over the peak (backlog)."""
from benchlib import readers


def read(ctx):
    return readers.mfu_rate(ctx)
