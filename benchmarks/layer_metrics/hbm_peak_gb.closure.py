"""Peak device memory of the chip at the window's end: the path matrix a round reads and the one it writes (harness/readers.hbm_peak_gb)."""

from harness import readers


def read(ctx):
    return readers.hbm_peak_gb(ctx)
