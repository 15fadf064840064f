"""Device time of the all-reduce operations over the steps, mean over
chips. A cell on one data shard has none and reports nothing."""

from harness import readers

PATTERN = r"all-reduce"


def read(ctx):
    return readers.kernel_ms_per_step(ctx, PATTERN)
