"""Roofline share of the tconv kernel family (clip traffic)."""
from benchlib import readers


def read(ctx):
    return readers.roofline(ctx, "tconv")
