"""Device time per clip step of the C_k kernels (``ck_proj``, ``ck_sim``),
from the trace: their seconds in the traced window over the steps the
window's busy time holds."""


def read(ctx):
    red, ck_s = ctx["red"], ctx["counters"].get("ck_s")
    steps = red.get("step_busy_s") or []
    if not steps or not red.get("busy_s") or not ck_s:
        return None
    return 1e3 * ck_s * (sum(steps) / len(steps)) / red["busy_s"]
