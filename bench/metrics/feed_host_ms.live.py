"""Host time per tick in the scheduler feed (svc.feed), from the program's
spans (live traffic)."""
from benchlib import phases


def read(ctx):
    return phases.phase_ms(ctx, "feed")
