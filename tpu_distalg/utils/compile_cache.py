"""The runtime layer: where the persistent XLA/Mosaic compilation cache
lives, and the record of what JAX traces, lowers, compiles and loads.

One function, called by every entry point (``cli.main``,
``chip_smoke.py``, ``tests_tpu``, the benchmark) before the first
compile. It places the cache by one rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set — jax reads it itself; this
    module touches nothing, so whoever runs the program places the
    cache (a chip machine that keeps one between calls, a CI volume);
  * unset — ``<checkout>/.jax_cache``, resolved from this file's
    location. The directory is part of the cache key's world (a cache
    that moves never hits), so it is never derived from ``tempfile``,
    a pid or the clock;
  * unset AND the CPU was asked for (``JAX_PLATFORMS=cpu``,
    ``--emulate N``) — no cache: the cache exists for chip compile
    times, the CPU runs are the tests, and they stay hermetic (XLA:CPU
    also logs an error line on every cached load on this jaxlib).

And on every one of the three it registers, once a process, the
program's only ``jax.monitoring`` listeners. Each thing JAX does to a
function becomes a span of ``telemetry/events.py`` (its ring, and its
sink where one is on), a child of the span open on the calling thread:

  ``jit:trace``       jaxpr tracing: the Python that describes a step
  ``jit:lower``       jaxpr to MLIR, where a Pallas body is lowered
  ``jit:compile``     the backend compile, or its stand-in from the
                      persistent cache; ``hit`` says which
  ``jit:cache_load``  inside a ``jit:compile`` that hit: reading the
                      cache and loading the executable (never add the
                      two); ``saved_s`` is what JAX says the hit saved

``fun`` is one name for all four: JAX sends the function's
``__name__`` when it traces (``seg``) and the module's when it lowers
and compiles (``jit(seg)``). A function jitted inside another is traced
inside it: that is the outer ``jit:trace``'s time and one more of its
``inner`` count, not a span, so the ``jit:trace`` spans are the
functions the program dispatched. An eager operation on a constant can
still compile inside a trace: a sum of seconds leaves out a span that
lies inside another's ``jit:trace`` or ``jit:lower``. Readers: ``tda
report``'s per-function table, ``chip_smoke.py``'s stage lines, the
benchmark's ``trace_s``, ``lower_s``, ``cache_load_s``, ``jit_traces``.
"""

from __future__ import annotations

import os
import re
import threading

from tpu_distalg.telemetry import events

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default (git-ignored): this file is
#: ``<checkout>/tpu_distalg/utils/compile_cache.py``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

TRACE, LOWER, COMPILE, CACHE_LOAD = (
    "jit:trace", "jit:lower", "jit:compile", "jit:cache_load")
# the three phases JAX times itself (dispatch.log_elapsed_time: a
# scalar at the start, a duration at the end, both with fun_name)
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE,
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_WRAPPED = re.compile(r"\w+\((.*)\)")    # jit(seg), pmap(step)

_listening = False
_INNER = threading.local()   # .depth: inner traces open on this thread


def fun_of(fun_name: str) -> str:
    """The function's own name from either of JAX's two."""
    m = _WRAPPED.fullmatch(fun_name)
    return m.group(1) if m else fun_name


def _open(name: str):
    sp = events.current()
    return sp if sp is not None and sp.name == name else None


def _on_start(event, _value, fun_name="?", **_):
    name = _PHASES.get(event)
    if name is None:
        return
    outer = events.current()
    if name == TRACE and outer is not None and outer.name in (TRACE, LOWER):
        # a function jitted inside another (every jnp operation is
        # one) is traced inside it: the outer span's seconds, one more
        # of its ``inner``, and no span of its own
        outer.fields["inner"] = outer.fields.get("inner", 0) + 1
        _INNER.depth = getattr(_INNER, "depth", 0) + 1
        return
    events.begin(name, fun=fun_of(fun_name))


def _on_duration(event, seconds, **_):
    name = _PHASES.get(event)
    if name is not None:
        if name == TRACE and getattr(_INNER, "depth", 0):
            _INNER.depth -= 1
            return
        sp = _open(name)
        if sp is not None:
            events.end(sp)
    elif event == _CACHE_READ:
        # sent when the read is over, from inside the compile's timer
        outer = _open(COMPILE)
        sp = events.begin(CACHE_LOAD, fun=outer.fields["fun"] if outer
                          else "?")
        sp.t0 -= seconds
        events.end(sp)
    elif event == _CACHE_SAVED:
        outer = _open(COMPILE)
        if outer is not None:
            outer.fields["saved_s"] = round(seconds, 6)


def _on_event(event, **_):
    if event == _CACHE_HIT or event == _CACHE_MISS:
        outer = _open(COMPILE)
        if outer is not None:
            outer.fields["hit"] = event == _CACHE_HIT


def _listen(jax) -> None:
    global _listening
    if _listening:
        return
    _listening = True
    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def configure() -> str | None:
    """Place the compilation cache and start the record of compiles;
    returns the directory in effect (None: no persistent cache for
    this run)."""
    import jax

    _listen(jax)
    placed = os.environ.get(ENV_DIR)
    if placed:
        return placed

    from tpu_distalg.parallel.mesh import cpu_requested

    if cpu_requested():
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
