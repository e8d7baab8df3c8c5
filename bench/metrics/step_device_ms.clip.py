"""Device-busy time of one clip-step program execution, from the trace."""
from benchlib import readers


def read(ctx):
    return readers.step_device_ms(ctx)
