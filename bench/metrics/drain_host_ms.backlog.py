"""Host time per tick in drain bookkeeping (svc.drain), from the program's
spans (backlog traffic)."""
from benchlib import phases


def read(ctx):
    return phases.phase_ms(ctx, "drain")
