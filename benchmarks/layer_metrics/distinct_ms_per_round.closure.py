"""Device self time a round under the program's ``tda.closure.distinct``
scope (whatever keeps the set distinct and merges the new pairs in: the
sort of set and candidates, the duplicates marked, both brought to the
front), mean over chips; nothing where the trace names no such scope
(harness/scopes.py)."""

from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "tda.closure.distinct")
