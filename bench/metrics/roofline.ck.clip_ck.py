"""Roofline share of the C_k similarity kernel ``ck_sim`` (clip_ck)."""
from benchlib import readers


def read(ctx):
    return readers.roofline(ctx, "ck")
