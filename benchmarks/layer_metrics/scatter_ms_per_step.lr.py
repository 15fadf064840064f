"""Device self time a step under the program's ``tda.ssgd.scatter``
scope (a hashed step's backward pass: the residuals added up slot by
slot), mean over chips; nothing where the trace names no such scope
(harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.ssgd.scatter")
