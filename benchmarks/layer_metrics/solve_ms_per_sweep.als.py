"""Device self time an ALS iteration under the program's
``tda.als.solve`` scope (the ridge, the Cholesky factorisation along the lanes and the two substitutions, both halves), mean
over chips; nothing where the trace names no such scope
(harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.als.solve")
