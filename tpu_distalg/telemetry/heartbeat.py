"""Liveness heartbeat + stall detection.

A daemon thread emits a ``heartbeat`` event every ``interval`` seconds
carrying the last progress mark's phase/age and the current counter
snapshot. When ``stall_after`` is set and no :func:`events.mark` lands
within that deadline, ONE ``stall`` event fires per frozen mark (naming
the stuck phase — "hung in backend_init for 1560s" instead of round 5's
silent 26-minute blackout) and the optional ``on_stall`` callback runs
— a harness can use it to print what it has and exit instead of
hanging until its driver's rc=124.

The thread never blocks the main loop (it only reads the in-memory mark
tuple and writes through the sink's own lock), runs fine with telemetry
disabled (events become no-ops; ``on_stall`` still fires — the
watchdog mode), and ``beat()`` is callable directly with an
injected clock so tests exercise the stall logic without sleeping.
"""

from __future__ import annotations

import threading
import time
from typing import Callable

from tpu_distalg.telemetry import events

DEFAULT_INTERVAL_SECONDS = 10.0
DEFAULT_STALL_SECONDS = 120.0


class Heartbeat(threading.Thread):
    """``start()`` it once; ``stop()`` is prompt (event-based wait)."""

    def __init__(self, interval: float = DEFAULT_INTERVAL_SECONDS,
                 stall_after: float | None = DEFAULT_STALL_SECONDS, *,
                 on_stall: Callable[[str, float], None] | None = None,
                 emit_fn=None, now=time.monotonic):
        super().__init__(name="tda-heartbeat", daemon=True)
        if interval <= 0:
            raise ValueError(f"interval must be > 0, got {interval}")
        self.interval = interval
        self.stall_after = stall_after
        self.on_stall = on_stall
        self._emit = emit_fn or events.emit
        self._now = now
        self._halt = threading.Event()
        self.n_beats = 0
        self.n_stalls = 0
        self.n_errors = 0
        self._flagged_mark: float | None = None

    def run(self) -> None:
        while not self._halt.wait(self.interval):
            self.safe_beat()

    def safe_beat(self) -> None:
        """beat(), but a failing sink (disk full, unlinked dir) must
        not KILL the thread: stall detection — and a watchdog riding
        ``on_stall`` — stays armed, and the next beat retries.
        (A dead heartbeat would silently reopen the r5 blind-hang mode
        this subsystem exists to close.)"""
        try:
            self.beat()
        except Exception:  # noqa: BLE001 — liveness must outlive I/O
            self.n_errors += 1

    def beat(self) -> None:
        """One heartbeat + stall check (the thread body; tests call it
        directly with an injected ``now``)."""
        t_mark, phase = events.last_mark()
        age = self._now() - t_mark
        self._emit("heartbeat", phase=phase,
                   seconds_since_mark=round(age, 3),
                   counters=events.counters())
        self.n_beats += 1
        if (self.stall_after is not None and age > self.stall_after
                and self._flagged_mark != t_mark):
            # one stall per frozen mark: a new mark re-arms detection,
            # a still-frozen one does not re-fire every beat
            self._flagged_mark = t_mark
            self.n_stalls += 1
            self._emit("stall", phase=phase,
                       seconds_since_mark=round(age, 3),
                       stall_after=self.stall_after)
            if self.on_stall is not None:
                self.on_stall(phase, age)

    def stop(self) -> None:
        self._halt.set()


def start_heartbeat(interval: float = DEFAULT_INTERVAL_SECONDS,
                    stall_after: float | None = DEFAULT_STALL_SECONDS,
                    on_stall=None) -> Heartbeat | None:
    """Start a heartbeat if it would do anything: telemetry enabled, or
    an ``on_stall`` action given (a watchdog runs even with
    telemetry off). Returns the thread, or ``None`` if skipped."""
    if not events.enabled() and on_stall is None:
        return None
    hb = Heartbeat(interval, stall_after, on_stall=on_stall)
    hb.safe_beat()  # immediate first beat: even a sub-interval run
    #                 records one heartbeat for `tda report`
    hb.start()
    return hb
