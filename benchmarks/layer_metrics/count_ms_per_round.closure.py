"""Device self time a round under the program's ``tda.closure.count``
scope (the partial counts added up in two words, the fixpoint test),
mean over chips; nothing where the trace names no scope
(harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.closure.count")
