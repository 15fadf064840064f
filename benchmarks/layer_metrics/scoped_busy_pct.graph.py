"""Share of the device's busy time spent under any ``tda.`` scope, mean
over chips: whether ``tda.pagerank.spmv`` and ``tda.pagerank.update``
account for ``sweep_ms.graph``. Work that grows outside both shows here
before ``spmv_ms_per_sweep.graph`` is trusted."""

from harness import scopes, trace


def _share(events):
    by = trace.self_seconds(events)
    busy = sum(by.values())
    return (busy - by.get("", 0.0)) / busy * 100 if busy > 0 else None


def read(ctx):
    shares = [x for x in scopes.per_chip(ctx, _share) or [] if x is not None]
    return sum(shares) / len(shares) if shares else None
