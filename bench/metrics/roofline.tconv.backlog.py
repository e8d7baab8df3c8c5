"""Roofline share of the tconv kernel family (backlog traffic)."""
from benchlib import readers


def read(ctx):
    return readers.roofline(ctx, "tconv")
