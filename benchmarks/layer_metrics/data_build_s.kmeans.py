"""Seconds the benchmark's generator took to draw the resident points
on the device, in the program's layout (host clock round
``families/kmeans.make_table``, ended by ``block_until_ready``)."""


def read(ctx):
    return ctx.span_seconds("data_build")
