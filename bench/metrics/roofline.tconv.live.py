"""Roofline share of the tconv kernel family (live traffic)."""
from benchlib import readers


def read(ctx):
    return readers.roofline(ctx, "tconv")
