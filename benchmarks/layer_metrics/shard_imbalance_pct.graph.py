"""The fullest shard's distinct edges over the mean shard's, less one,
in per cent (the program's ``pagerank_shard_edges_max`` and ``_mean``
counters): every shard sweeps the same number of slots, so the share
of them that hold no edge on the lighter shards is work the mesh does
not do. Nothing where the program counts no shards."""


def read(ctx):
    most = ctx.counters.get("shard_edges_max")
    mean = ctx.counters.get("shard_edges_mean")
    if not most or not mean:
        return None
    return (most / mean - 1) * 100
