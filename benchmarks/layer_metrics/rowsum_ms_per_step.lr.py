"""Device self time a step under the program's ``tda.ssgd.rowsum``
scope: the sum over a row's pairs in the gather pass and the row's
residual handed back to its pairs in the scatter pass, together. The
scope lies inside ``tda.ssgd.gather`` / ``tda.ssgd.scatter``, whose own
readers go on counting it (harness/scopes_inner.py); nothing where no
op is under it (a program from before the scope, an untraced run)."""

from harness import scopes_inner


def read(ctx):
    return scopes_inner.inner_scope_ms_per_step(ctx, "tda.ssgd.rowsum")
