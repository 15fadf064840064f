"""Device self time a step under the program's ``tda.ssgd.gather`` scope
(a hashed step's forward pass: the weights at each row's slots, the
margins, the residuals), mean over chips; nothing where the trace names
no such scope (harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.ssgd.gather")
