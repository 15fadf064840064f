"""Backend-compile seconds of the whole run, as ``jax.monitoring``
reports them (``/jax/core/compile/backend_compile_duration``). A warm
run loads from the persistent cache and reads near 0."""


def read(ctx):
    return ctx.counters.get("compile_s")
