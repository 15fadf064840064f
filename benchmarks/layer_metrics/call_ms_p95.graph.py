"""95th percentile of the window's readings on the host clock (harness/readers.call_p95_ms)."""

from harness import readers


def read(ctx):
    return readers.call_p95_ms(ctx)
