"""Rows of the path matrix recomputed by one call over the median call time (harness/readers.median_call_rate)."""

from harness import readers


def read(ctx):
    return readers.median_call_rate(ctx)
