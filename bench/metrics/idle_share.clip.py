"""Share of the traced window with no op on the device (clip)."""
from benchlib import readers


def read(ctx):
    return readers.idle_share(ctx)
