"""Share of the device's busy time spent under any ``tda.`` scope,
mean over chips: whether the five ``tda.als.`` scopes account for
``sweep_ms.als``. A refactor that drops a scope, or work that grows
outside all of them, shows here before the per-scope metrics are
trusted."""

from harness import scopes, trace


def _share(events):
    by = trace.self_seconds(events)
    busy = sum(by.values())
    return (busy - by.get("", 0.0)) / busy * 100 if busy > 0 else None


def read(ctx):
    shares = [x for x in scopes.per_chip(ctx, _share) or [] if x is not None]
    return sum(shares) / len(shares) if shares else None
