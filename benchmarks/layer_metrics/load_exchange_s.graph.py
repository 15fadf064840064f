"""Seconds of the program's own ``pagerank:exchange`` span (the sort
of every shard's draws by the shard that owns the destination, the
buckets and their ``all_to_all``, compiles or cache loads included),
from the ring of finished spans the program keeps
(``telemetry/events.finished``). Nothing where the program has no such
span or keeps no ring."""


def read(ctx):
    try:
        from tpu_distalg.telemetry import events

        done = events.finished()
    except (ImportError, AttributeError):
        return None
    got = [s.seconds for s in done if s.name == "pagerank:exchange"]
    return sum(got) if got else None
