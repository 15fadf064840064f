"""Share of the traced window in which no operation ran on the device (harness/readers.idle_pct)."""

from harness import readers


def read(ctx):
    return readers.idle_pct(ctx)
