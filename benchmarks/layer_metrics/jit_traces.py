"""How many functions JAX traced in set-up: the count of the program's
``jit:trace`` spans that ended by the end of ``warm_up`` (``trace_s.py``
has the rule). One span is one function the program dispatched and JAX
had no trace of, an eager ``jnp`` operation included; the functions
jitted inside it are its time and its ``inner`` field, not spans. The
work count beside ``trace_s``, ``lower_s`` and ``cache_load_s``: a
function traced a second time shows here as one more."""

import os

from harness import manifest as mf

_shared = mf.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace_s.py"),
    "bench_reader_trace_s")


def read(ctx):
    got = _shared.setup_spans(ctx)
    if got is None:
        return None
    return sum(s.name == "jit:trace" and not nested for s, nested in got)
