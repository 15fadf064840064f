"""Device self time a round under the program's ``tda.closure.join``
scope (the pairs the round before found new joined with the arcs: the
segmented expand and its gathers), mean over chips; nothing where the
trace names no such scope (harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.closure.join")
