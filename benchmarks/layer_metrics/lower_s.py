"""Seconds of jaxpr-to-MLIR lowering in set-up, which is where a
Pallas kernel's body is traced and serialised for Mosaic: the
program's ``jit:lower`` spans that ended by the end of ``warm_up`` and
lie inside no other trace or lowering (``trace_s.py`` has the rule)."""

import os

from harness import manifest as mf

_shared = mf.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace_s.py"),
    "bench_reader_trace_s")


def read(ctx):
    return _shared.seconds_of(ctx, "jit:lower")
