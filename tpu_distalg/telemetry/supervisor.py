"""Supervised execution — deadline, retry/backoff/jitter.

Round 5's failure mode: ``jax.devices()`` on a remote TPU backend
hung for ~26 minutes with no deadline, no retry, and no record — the
bench window expired and the artifact was empty (rc=124).
:func:`supervised` is the generalized core that grew out of that fix:
run any callable under a per-attempt watchdog deadline (in a worker
thread), record every attempt as telemetry events, retry retryable
failures with exponential backoff + jitter, and resolve exhaustion
loudly — a machine-readable event + raise, never a quiet substitute
(a backend that never came up is an error, not a reason to run on the
host CPU instead).
:func:`init_backend` is its original backend-init instantiation
(unchanged event names and semantics); ``utils/checkpoint.save`` and
``data/cache.build_cache`` ride the same core for transient disk
faults.

A hung attempt's worker thread cannot be killed (that is the nature of
a wedged C extension call); it is a daemon thread that dies with the
process. Retries after a timeout are SINGLE-FLIGHT: the next attempt
waits another deadline window on the SAME in-flight call rather than
racing a second concurrent call against it (jax's global backend init
is not guarded against concurrent first-time callers); a fresh call
only starts once the previous one finished. Everything is
injection-friendly (``fn``/``init_fn``, ``sleep``, ``rng``) so tests
fake a hanging ``jax.devices`` without a real backend.
"""

from __future__ import annotations

import random
import sys
import threading
import time
from typing import Callable

from tpu_distalg.telemetry import events


class BackendUnavailableError(RuntimeError):
    """Backend init failed/hung through every retry (and no fallback)."""


def _default_init():
    import jax

    return jax.devices()


def _call_with_deadline(fn: Callable, timeout: float | None,
                        pending=None):
    """Run ``fn()`` with a deadline. Returns ``(ok, value_or_exc,
    timed_out, pending)``.

    On timeout the worker thread cannot be killed; instead of
    abandoning it AND launching a second concurrent call next attempt
    (two threads racing e.g. jax's unguarded global init), the
    still-running call is returned as ``pending`` — pass it back in and
    the SAME in-flight call is awaited for another ``timeout`` window
    (single-flight). A fresh thread only ever starts once the previous
    one has finished."""
    if timeout is None:
        try:
            return True, fn(), False, None
        except Exception as e:  # noqa: BLE001 — judged by the caller
            return False, e, False, None
    if pending is not None:
        th, box, done = pending
    else:
        box = {}
        done = threading.Event()

        def work():
            try:
                # tda: ignore[TDA020] -- single-writer box: the reader
                # only looks after done.wait(), and done.set() in the
                # finally below is the release that orders this write
                box["value"] = fn()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                # tda: ignore[TDA020] -- same Event-ordered handoff
                box["error"] = e
            finally:
                done.set()

        th = threading.Thread(target=work, daemon=True,
                              name="tda-supervised")
        th.start()
    if not done.wait(timeout):
        return False, None, True, (th, box, done)
    if "error" in box:
        return False, box["error"], False, None
    return True, box["value"], False, None


def supervised(fn: Callable, *, phase: str,
               timeout: float | None = None, retries: int = 0,
               backoff: float = 1.0, backoff_cap: float = 60.0,
               jitter: float = 0.1, retry_on=(Exception,),
               sleep: Callable[[float], None] = time.sleep,
               rng: Callable[[], float] = random.random,
               log: Callable[[str], None] | None = None,
               event: str = "supervised",
               retry_event: str | None = None,
               exhausted_event: str | None = None,
               stall_on_timeout: bool = False,
               failure_counter: str | None = None,
               error_cls: type | None = None):
    """Run ``fn()`` under supervision; returns its value.

    ``timeout``: per-attempt deadline seconds (``None`` = unguarded;
    with a deadline each attempt runs in a single-flight daemon worker
    — see module docstring). ``retries``: extra attempts after the
    first (total = retries + 1). ``backoff``: first retry delay;
    doubles per retry up to ``backoff_cap``, times ``1 + jitter·U[0,1)``
    (pass ``backoff_cap=backoff`` for a fixed-delay schedule).
    ``retry_on``: exception classes worth retrying — anything else
    raises IMMEDIATELY after recording the failed attempt (a
    deterministic config error fails identically every time; only
    transient faults earn the backoff loop). Exhaustion emits
    ``exhausted_event`` and raises — ``error_cls`` when given
    (wrapping the last error), else the LAST underlying error itself,
    so callers and retry layers above still see the real exception
    type (timeouts become ``TimeoutError``).

    Telemetry: one ``event`` record per attempt (outcome ok/error/
    timeout + seconds), ``retry_event`` (default ``<event>_retry``)
    before each backoff sleep, ``stall`` records on timeouts when
    ``stall_on_timeout`` (a timed-out attempt IS a detected hang), and
    ``failure_counter`` bumped per failed attempt. Progress marks are
    NOT advanced during failing attempts, so an outer heartbeat
    watchdog still sees the whole retry storm as one stalled phase and
    can enforce a total-time budget on top of the per-attempt deadline
    enforced here.
    """
    if retries < 0:
        raise ValueError(f"retries must be >= 0, got {retries}")
    retry_event = retry_event or f"{event}_retry"
    exhausted_event = exhausted_event or f"{event}_exhausted"
    # log lines read as prose ("backend init failed ..."), events carry
    # the exact phase token ("backend_init")
    label = phase.replace("_", " ")
    emit_err = log or (lambda m: print(f"[supervisor] {m}",
                                       file=sys.stderr))
    n_attempts = retries + 1
    last_err: Exception | None = None
    pending = None
    for attempt in range(1, n_attempts + 1):
        t0 = time.monotonic()
        ok, value, timed_out, pending = _call_with_deadline(
            fn, timeout, pending)
        dt = round(time.monotonic() - t0, 3)
        if ok:
            events.emit(event, phase=phase, attempt=attempt,
                        of=n_attempts, outcome="ok", seconds=dt)
            return value
        if timed_out:
            err_txt = f"hung past the {timeout}s deadline"
            last_err = (error_cls or TimeoutError)(
                f"{phase} attempt {attempt}/{n_attempts} {err_txt}")
        else:
            err_txt = f"{type(value).__name__}: {value}"
            last_err = value
        events.emit(event, phase=phase, attempt=attempt, of=n_attempts,
                    outcome="timeout" if timed_out else "error",
                    seconds=dt, error=err_txt)
        if timed_out and stall_on_timeout:
            # age since the last REAL progress mark, not this attempt's
            # duration: attempt 10 of a retry storm must report the
            # full outage, matching the heartbeat lines in the same log
            events.emit("stall", phase=phase,
                        seconds_since_mark=round(
                            time.monotonic() - events.last_mark()[0], 3),
                        attempt_seconds=dt, stall_after=timeout)
        if failure_counter:
            events.counter(failure_counter)
        emit_err(f"{label} failed (attempt {attempt}/{n_attempts}): "
                 f"{err_txt}")
        if not timed_out and not isinstance(value, retry_on):
            raise value  # not a transient — retrying cannot help
        if attempt < n_attempts:
            delay = min(backoff * (2 ** (attempt - 1)), backoff_cap)
            delay *= 1.0 + jitter * rng()
            events.emit(retry_event, phase=phase, attempt=attempt,
                        sleep_seconds=round(delay, 3))
            sleep(delay)
    events.emit(exhausted_event, phase=phase, attempts=n_attempts,
                error=str(last_err))
    if error_cls is None:
        raise last_err
    raise error_cls(
        f"{phase} failed after {n_attempts} attempts: {last_err}"
    ) from (last_err if isinstance(last_err, Exception) else None)


def init_backend(timeout: float | None = None, retries: int = 0,
                 backoff: float = 1.0, *, backoff_cap: float = 60.0,
                 jitter: float = 0.1, init_fn: Callable | None = None,
                 sleep: Callable[[float], None] = time.sleep,
                 rng: Callable[[], float] = random.random,
                 log: Callable[[str], None] | None = None):
    """Initialize the backend under supervision; returns ``init_fn()``'s
    value (default ``jax.devices()``). The original :func:`supervised`
    instantiation — event names (``backend_init``/``backend_retry``/
    ``backend_unavailable``) and retry semantics are unchanged from
    when this was a standalone loop. Exhaustion emits
    ``backend_unavailable`` and raises
    :class:`BackendUnavailableError`.

    The ``backend:init`` fault-injection point fires inside each
    attempt (inside the deadline-guarded worker), so injected hangs are
    caught by the SAME watchdog that caught the real r5 one.
    """
    from tpu_distalg import faults

    init_fn = init_fn or _default_init

    def guarded_init():
        faults.inject("backend:init")
        return init_fn()

    value = supervised(
        guarded_init, phase="backend_init", timeout=timeout,
        retries=retries, backoff=backoff, backoff_cap=backoff_cap,
        jitter=jitter, retry_on=(Exception,), sleep=sleep, rng=rng, log=log, event="backend_init",
        retry_event="backend_retry",
        exhausted_event="backend_unavailable", stall_on_timeout=True,
        failure_counter="backend_init_failures",
        error_cls=BackendUnavailableError)
    events.mark("backend_ready")
    return value
