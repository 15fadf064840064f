"""Where the persistent XLA/Mosaic compilation cache lives.

One rule, one function, called by every entry point (``cli.main``,
``chip_smoke.py``, ``tests_tpu``) before the first compile:

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
"""

from __future__ import annotations

import os

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default (git-ignored): this file is
#: ``<checkout>/tpu_distalg/utils/compile_cache.py``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str | None:
    """Place the compilation cache; returns the directory in effect
    (None: no persistent cache for this run)."""
    placed = os.environ.get(ENV_DIR)
    if placed:
        return placed
    import jax

    from tpu_distalg.parallel.mesh import cpu_requested

    if cpu_requested():
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
