"""Arithmetic that readers of several families share. A reader is a
file of its own under ``layer_metrics/``, named as its metric; where
two families read the same quantity (``device_idle_pct.lr``,
``device_idle_pct.graph``: one moves ``rows_per_s``, the other
``edges_per_s``) both files call the function here."""

from __future__ import annotations

import math
import statistics

from harness import trace


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all the values, no interpolation."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def idle_pct(ctx):
    """1 - the union of the device's operation intervals over the
    traced window, mean over chips."""
    if not ctx.reduced:
        return None
    return (1 - ctx.reduced["busy_s"] / ctx.reduced["window_s"]) * 100


def gap_ms(ctx):
    """Mean device-idle gap between consecutive programs inside the
    traced window (the gaps at its two edges left out)."""
    return trace.inner_gap_ms(ctx.reduced) if ctx.reduced else None


def call_p95_ms(ctx):
    """95th percentile (nearest rank) of the window's readings, host
    clock, dispatch to ``block_until_ready``."""
    if len(ctx.readings_s) < 2:
        return None
    return percentile(ctx.readings_s, 0.95) * 1e3


def median_call_rate(ctx):
    """Work of one call over the median call time: the steadier
    statistic beside the end-to-end rate, which is all the window's
    work over all its time. One host hiccup moves that and not this."""
    if not ctx.readings_s:
        return None
    return ctx.counters["work_per_call"] / statistics.median(ctx.readings_s)


def busy_ms_per_step(ctx):
    """Device busy time of the traced window over the steps (SGD steps,
    sweeps) run in it."""
    if not ctx.reduced:
        return None
    steps = ctx.counters["window_calls"] * ctx.counters["steps_per_call"]
    return ctx.reduced["busy_s"] / steps * 1e3


def kernel_ms_per_step(ctx, pattern: str):
    """Device ms a step of the ops whose name matches, mean over
    chips; None where nothing matched."""
    if not ctx.reduced:
        return None
    seconds, n = trace.kernel_seconds(ctx.reduced, pattern)
    if n == 0:
        return None
    steps = ctx.counters["window_calls"] * ctx.counters["steps_per_call"]
    return seconds / steps * 1e3


def hbm_peak_gb(ctx):
    """``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read
    when the window closes and before the reference runs: what the
    rate was bought with."""
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
