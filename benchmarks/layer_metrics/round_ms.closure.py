"""Device busy time of the traced window over its closure rounds (harness/readers.busy_ms_per_step)."""

from harness import readers


def read(ctx):
    return readers.busy_ms_per_step(ctx)
