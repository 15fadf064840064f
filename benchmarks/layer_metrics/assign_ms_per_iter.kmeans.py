"""Device self time an iteration under the program's
``tda.kmeans.assign`` scope (distances and argmin; on the lanes layout
the one kernel that also accumulates the partial sums), mean over
chips; nothing where the trace names no scope (harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.kmeans.assign")
