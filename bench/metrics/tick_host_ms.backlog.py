"""Host time inside GcnService.tick() per tick (backlog traffic)."""
from benchlib import readers


def read(ctx):
    return readers.tick_host_ms(ctx)
