"""Model operations per tick over (mean tick wall time x peak)."""
from benchlib import readers


def read(ctx):
    return readers.mfu_tick(ctx)
