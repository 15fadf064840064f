"""Persistent-compilation-cache misses of the run
(``/jax/compilation_cache/cache_misses``); 0 in a warm run."""


def read(ctx):
    return ctx.counters.get("cache_misses")
