"""Peak device memory of the chip at the window's end (harness/readers.hbm_peak_gb)."""

from harness import readers


def read(ctx):
    return readers.hbm_peak_gb(ctx)
