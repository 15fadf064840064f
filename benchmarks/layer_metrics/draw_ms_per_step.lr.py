"""Device self time a step under the program's ``tda.ssgd.draw`` scope
(the block draw: threefry bits, the argsort, the slice), mean over
chips; nothing where the trace names no scope (harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.ssgd.draw")
