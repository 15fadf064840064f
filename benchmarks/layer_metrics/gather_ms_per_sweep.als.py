"""Device self time an ALS iteration under the program's
``tda.als.gather`` scope (the other side's factor rows fetched by index, both halves), mean
over chips; nothing where the trace names no such scope
(harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.als.gather")
