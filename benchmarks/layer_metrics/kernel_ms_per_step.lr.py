"""Device self time a step under the program's ``tda.ssgd.kernel``
scope (the Mosaic call and the tile/reshape ops XLA puts round it),
mean over chips; nothing where the trace names no scope
(harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.ssgd.kernel")
