"""Host time per tick in the jitted calls (svc.dispatch), from the
program's spans (live traffic)."""
from benchlib import phases


def read(ctx):
    return phases.phase_ms(ctx, "dispatch")
