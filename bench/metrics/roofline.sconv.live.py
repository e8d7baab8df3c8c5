"""Roofline share of the sconv kernel family (live traffic)."""
from benchlib import readers


def read(ctx):
    return readers.roofline(ctx, "sconv")
