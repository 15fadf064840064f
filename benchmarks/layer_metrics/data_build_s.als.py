"""Seconds the program's loader took to make the packed ratings on the
device: the degrees and the pack on the host, both sides' draw and the
held-out pairs (host clock round ``als.build_ratings_table``, which ends
in ``block_until_ready``)."""


def read(ctx):
    return ctx.span_seconds("data_build")
