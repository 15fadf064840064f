"""Host seconds from the raw edge list to the compiled sweep's inputs
on the device: ``ops/graph.prepare_edges`` (dedupe), the planner's
sorts (``pagerank:plan_spmv:rgN`` spans, printed one by one on an
earlier line), the uploads. Host clock round those calls."""


def read(ctx):
    return ctx.span_seconds("host_prep")
