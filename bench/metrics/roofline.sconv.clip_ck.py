"""Roofline share of the per-row spatial kernel ``graph_sconv_rows``
(clip_ck)."""
from benchlib import readers


def read(ctx):
    return readers.roofline(ctx, "sconv_rows")
