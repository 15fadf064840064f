"""Seconds the program's loader took to make the packed rows on the
device (host clock round ``ssgd.prepare_fused_synthetic``, ended by
``block_until_ready``)."""


def read(ctx):
    return ctx.span_seconds("data_build")
