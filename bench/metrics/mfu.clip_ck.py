"""Model operations, C_k included, per second of the traced window over
the peak (clip_ck)."""
from benchlib import readers


def read(ctx):
    return readers.mfu_rate(ctx)
