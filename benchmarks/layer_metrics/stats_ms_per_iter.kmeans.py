"""Device self time an iteration under the program's
``tda.kmeans.stats`` scope (one-hot sums and counts; on the lanes
layout what is left outside the kernel: the fold of the partial sums),
mean over chips; nothing where the trace names no scope."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.kmeans.stats")
