"""Seconds the program's loader and planner took to make the graph and
the fused sweep's plan on the device: the draw, the dedup sort and the
degrees (``pagerank.build_rmat_graph``), the plan's sort and layout
(``pagerank.prepare_device_spmv``, which ends in a fetch of the widest
span). Host clock round both calls."""


def read(ctx):
    return ctx.span_seconds("data_build")
