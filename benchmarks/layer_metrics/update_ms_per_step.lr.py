"""Device self time a step under the program's ``tda.ssgd.update``
scope: the count's guard, the regulariser's gradient and ``w - eta (g /
n + lam reg)`` over the whole model vector, 219 MB read twice and
written once a step where the model is 54.7M weights (the scope has
been there since PR 24; where a model is 40 or 2^20 weights it is not
worth a reader), mean over chips; nothing where the trace names no
scope (harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.ssgd.update")
