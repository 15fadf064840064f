"""Device self time a sweep under the program's ``tda.pagerank.spmv``
scope (the ranks' table made and the fused kernel's calls), mean over
chips; nothing where the trace names no scope (harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.pagerank.spmv")
