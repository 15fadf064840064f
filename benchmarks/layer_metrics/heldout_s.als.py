"""Self time of the program's ``als:heldout`` span(s) inside
``data_build`` (harness/spans.self_seconds: its seconds less the spans
directly beneath it, ``jit:*`` included)."""

from harness import spans


def read(ctx):
    return spans.self_seconds(ctx, "als:heldout")
