"""Device self time a round under the program's ``tda.closure.compose``
scope (the boolean product of the path matrix with itself or-ed into
it, with the per-tile pair counts), mean over chips; nothing where the
trace names no scope (harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.closure.compose")
