"""Share of the device's busy time spent under any ``tda.`` scope, mean
over chips: whether ``tda.closure.compose`` and ``tda.closure.count``
account for ``round_ms.closure``. Work that grows outside both (a copy
of the donated matrix, a job's start scatter) shows here before
``compose_ms_per_round.closure`` is trusted."""

from harness import scopes, trace


def _share(events):
    by = trace.self_seconds(events)
    busy = sum(by.values())
    return (busy - by.get("", 0.0)) / busy * 100 if busy > 0 else None


def read(ctx):
    shares = [x for x in scopes.per_chip(ctx, _share) or [] if x is not None]
    return sum(shares) / len(shares) if shares else None
