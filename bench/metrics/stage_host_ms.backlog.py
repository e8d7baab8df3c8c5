"""Host time per tick in staging the step's inputs to the device
(svc.stage), from the program's spans (backlog traffic)."""
from benchlib import phases


def read(ctx):
    return phases.phase_ms(ctx, "stage")
