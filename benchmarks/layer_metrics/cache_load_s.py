"""Seconds set-up spent reading the persistent compilation cache and
loading the executables it held: the program's ``jit:cache_load``
spans that ended by the end of ``warm_up`` (``trace_s.py`` has the
rule). Each lies inside the ``jit:compile`` that hit, whose seconds
``compile_s`` already holds, so the two are never added; on a miss the
seconds are ``compile_s``'s alone and this reads 0."""

import os

from harness import manifest as mf

_shared = mf.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace_s.py"),
    "bench_reader_trace_s")


def read(ctx):
    return _shared.seconds_of(ctx, "jit:cache_load")
