"""Profiling and tracing helpers.

The reference's only observability is ``print`` per iteration plus Spark's
(unused) web UI (SURVEY.md §5). Here: a TensorBoard/Perfetto trace context
(``jax.profiler``) and an honest steps/sec measurement that blocks on
device completion.
"""

from __future__ import annotations

import contextlib
import time

import jax


def maybe_trace(logdir):
    """``trace(logdir)`` when a directory is given, else a no-op context —
    the one-liner behind every ``--profile DIR`` flag."""
    return trace(logdir) if logdir else contextlib.nullcontext()


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a device trace viewable in TensorBoard / Perfetto:

        with profiling.trace("/tmp/trace"):
            out = train_fn(...)
            jax.block_until_ready(out)
    """
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def steps_per_sec(fn, *args, steps: int, repeats: int = 3,
                  warmup: bool = True, with_output: bool = False,
                  with_stats: bool = False, chain: int = 1):
    """Best-of-``repeats`` throughput of ``fn(*args)``, where one call runs
    ``steps`` device-side steps (e.g. a scan segment) as ONE compiled
    program. Completion is observed by fetching the program's first
    output leaf to the host — a fetch cannot complete before the
    program has, whatever the backend's ``block_until_ready`` does —
    and exactly one small fetch is made (one jit execution produces all
    outputs, so one leaf proves completion of all of them). Huge leaves
    fetch a single element instead (stays addressable on multi-host
    meshes).

    ``chain`` enqueues that many back-to-back calls per timed repeat and
    fetches once at the end. Dispatch is async, so the device runs call
    k while call k+1 is in flight and the single host round-trip
    amortizes over ``chain × steps`` steps instead of ``steps`` (on a
    rig with a ~100 ms round-trip a TRIVIAL 1500-step scan "measured"
    63 µs/step at chain=1 and 4.5 µs/step at chain=16 — the difference
    is pure host round-trip, not device time). The result still charges
    1/chain of the round-trip, so it remains a conservative
    underestimate of device throughput. Calls are independent repeats of
    ``fn(*args)``; the device executes them in order on one stream.

    ``with_output=True`` appends the last output (e.g. trained weights
    for a convergence check — no re-run needed). ``with_stats=True``
    appends a ``{"repeats", "chain", "best", "median", "min"}`` dict of
    the per-repeat rates: on shared chips run-to-run throughput varies
    (±40% observed), so a single best-of number is not comparable
    across sessions without the spread next to it."""
    import numpy as np

    def fetch(n_calls=chain):
        for _ in range(n_calls):
            out = fn(*args)
        leaf = jax.numpy.asarray(jax.tree.leaves(out)[0])
        if leaf.size <= (1 << 20):
            np.asarray(leaf)     # small: one plain D2H, no dispatch
        else:
            # large/sharded: fetch one element — the extra tiny dispatch
            # beats shipping the whole buffer to the host
            np.asarray(leaf[(0,) * leaf.ndim])
        return out

    # ONE call compiles and primes the path; warming the whole chain
    # would burn chain-1 redundant full executions
    out = fetch(1) if warmup else None
    rates = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fetch()
        rates.append(chain * steps / (time.perf_counter() - t0))
    stats = {
        "repeats": repeats,
        "chain": chain,
        "best": round(max(rates), 2),
        "median": round(float(np.median(rates)), 2),
        "min": round(min(rates), 2),
    }
    result = (max(rates),)
    if with_stats:
        result += (stats,)
    if with_output:
        result += (out,)
    return result[0] if len(result) == 1 else result
