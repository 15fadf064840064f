"""Device self time a sweep under the program's ``tda.pagerank.sync``
scope (the scalar psum of the dangling mass and the all-gather of the
shards' new rank ranges), mean over chips, hidden behind other work or
not; nothing where the trace names no scope (harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.pagerank.sync")
