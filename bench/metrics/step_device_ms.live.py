"""Device-busy time of one slab-step program execution, from the trace."""
from benchlib import readers


def read(ctx):
    return readers.step_device_ms(ctx)
"""Device-busy time of one (live) program execution, from the trace."""
from benchlib import readers


def read(ctx):
    return readers.step_device_ms(ctx)
