"""Device self time an ALS iteration under the program's
``tda.als.gram`` scope (the rating's and validity's lanes, the per-owner products on the MXU, the pieces of a large owner added up, both halves), mean
over chips; nothing where the trace names no such scope
(harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.als.gram")
