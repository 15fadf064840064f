"""Structured runtime telemetry — thread-safe JSONL events.

Round 5's defining failure was *invisible*: the TPU backend hung ~26
minutes during init, the bench window expired, and the artifact recorded
nothing about where the time went. This module is the
record-keeping half of the fix: every run can append structured events
to one JSONL file, cheaply enough to leave on everywhere, and a no-op
when nobody asked for it.

Event schema — one JSON object per line, every line carries:

  ``ev``      event type (``run_start``, ``mark``, ``span_start``,
              ``span_end``, ``heartbeat``, ``stall``, ``backend_init``,
              ``backend_retry``, ``backend_unavailable``,
              ``restart``, ``quarantine``, ``checkpoint_saved``,
              ``metric``, ``gauge``, ``counters``, ``run_end``)
  ``t_wall``  wall-clock seconds (``time.time()`` — cross-host ordering)
  ``t_mono``  monotonic seconds (``time.monotonic()`` — durations)
  ``run``     short hex run id, one per :func:`configure`
  ``pid``, ``host``
  plus event-specific fields (``phase``, ``name``, ``seconds``, ...).

Conventions:

  * ``mark(phase)`` is the liveness primitive: cheap (one tuple
    assignment when telemetry is off), called at every phase boundary a
    run reaches — training segments, bench phases, checkpoint saves.
    ``heartbeat.Heartbeat`` compares the last mark's age against a
    stall deadline; a run that stops marking IS the hang signal.
  * ``span(name)`` wraps a timed phase: ``span_start``/``span_end``
    events with the duration and error status, and a mark at both
    edges. Every span has an ``id`` and the ``parent`` id of the span
    open on its thread when it began, so ``tda report`` prints spans as
    a tree with self times. Once ``jax`` is imported the span is also a
    ``jax.profiler.TraceAnnotation`` named ``tda:<name>``: under a
    profiler session (``--profile``) the host phase lands on the
    profiler's clock beside the device ops.
  * every finished span is also kept in memory, sink or no sink: one
    :class:`Finished` tuple ``(name, id, parent, t0, seconds, ok,
    fields)`` in a bounded ring (``RING_SIZE`` entries, the oldest
    dropped), ``t0`` on ``time.perf_counter()``. :func:`finished`
    returns a copy, so a reader in the same process (a benchmark's
    per-layer metric, ``chip_smoke.py``'s stage lines) reads the
    program's spans without a sink or a profiler session.
  * ``jit:trace``, ``jit:lower``, ``jit:compile`` and
    ``jit:cache_load`` are what JAX did to one function: jaxpr
    tracing, jaxpr to MLIR (where a Pallas kernel's body is lowered),
    the backend compile, and inside it the persistent cache's read.
    ``utils/compile_cache.configure`` opens and closes them from
    ``jax.monitoring``'s events through :func:`begin` / :func:`end`,
    with ``fun`` (the function's name), ``hit`` on ``jit:compile``
    where a persistent cache answered, and the span open on the
    calling thread as ``parent``. They land in the ring and, with a
    sink, as ``span_end`` lines, so ``tda report`` nests them in its
    tree and prints its per-function table from them.
  * a span that is handed ``devices`` (a mesh's, the ones its caller
    already holds) records what it left on the chip: the fields
    ``hbm_in_use`` and ``hbm_peak`` (:func:`memory` at its end) and
    ``hbm_in_use_start`` (at its start), bytes, one entry a device.
    Loader phases and ``train:*`` spans take it; a ``jit:*`` pair and
    anything a step never do. Absent where the backend keeps no stats
    (the CPU). :func:`memory` is the one place in ``tpu_distalg`` that
    asks a device for its ``memory_stats()``.
  * counters are in memory as the spans are, sink or no sink: one
    process-wide store (thread-safe) that :func:`counters` copies, that
    starts empty at every :func:`configure` (a run's counts) and that a
    sink flushes as one ``counters`` event at close; gauges/metrics are
    emitted inline.

The process-global default sink is selected by :func:`configure` (CLI
``--telemetry-dir``, env ``TDA_TELEMETRY_DIR``); when disabled, every
emitting function returns before touching any file — guarded by a test
(tests/test_telemetry.py) asserting zero file I/O on the disabled path.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import itertools
import json
import os
import socket
import sys
import threading
import time
import uuid
from typing import NamedTuple

ENV_DIR = "TDA_TELEMETRY_DIR"
# a span's name in a profiler trace is this prefix + its name
ANNOTATION_PREFIX = "tda:"

_LOCK = threading.Lock()  # guards the _SINK swap only
_SINK: EventSink | None = None
# (monotonic seconds, phase) of the last progress mark — a plain tuple
# so assignment is atomic under the GIL and mark() costs nothing but
# the tuple when telemetry is disabled (heartbeat stall math still
# works against it either way)
_LAST_MARK: tuple[float, str] = (time.monotonic(), "start")
_SPAN_IDS = itertools.count(1)   # next() is atomic under the GIL
_OPEN_SPANS = threading.local()  # .stack: this thread's open spans
# finished spans, newest last: a span is a phase boundary, so a run
# appends tens to hundreds; append and list() are atomic under the GIL
RING_SIZE = 4096


class Finished(NamedTuple):
    """One finished span as the ring keeps it."""

    name: str
    id: int
    parent: int | None     # the span open on its thread when it began
    t0: float              # time.perf_counter() at its start
    seconds: float
    ok: bool
    fields: dict


_FINISHED: collections.deque[Finished] = collections.deque(maxlen=RING_SIZE)
# the run's counters, sink or no sink; _COUNT_LOCK guards every access
_COUNT_LOCK = threading.Lock()
_COUNTERS: dict[str, int] = {}


class EventSink:
    """Thread-safe JSONL writer: ``events-<run>.jsonl`` under ``directory``.

    One lock serializes every line (each event is a single ``write``
    call of one ``\\n``-terminated line, so concurrent emitters can
    never splice lines — the bench stdout-splicing failure mode, fixed
    at the sink instead of at every call site). Line-buffered so a
    ``kill -9`` loses at most the torn tail line, which
    :mod:`tpu_distalg.telemetry.report` tolerates.
    """

    def __init__(self, directory: str, run_id: str | None = None):
        os.makedirs(directory, exist_ok=True)
        self.run_id = run_id or uuid.uuid4().hex[:12]
        self.directory = directory
        self.path = os.path.join(directory, f"events-{self.run_id}.jsonl")
        self._f = open(self.path, "a", buffering=1)
        self._lock = threading.Lock()
        self._host = socket.gethostname()
        self.closed = False
        self.write("run_start", argv=list(sys.argv))

    def _record(self, ev: str, fields: dict) -> str:
        return json.dumps(
            {"ev": ev, "t_wall": round(time.time(), 6),
             "t_mono": round(time.monotonic(), 6), "run": self.run_id,
             "pid": os.getpid(), "host": self._host, **fields},
            default=str)

    def write(self, ev: str, **fields) -> None:
        line = self._record(ev, fields) + "\n"
        with self._lock:
            if not self.closed:
                self._f.write(line)

    def counters(self) -> dict[str, int]:
        """The run's counters (:func:`counters`: the store is the
        process's, not the sink's)."""
        return counters()

    def close(self) -> None:
        end = self._record("counters", {"counters": counters()}) + "\n" \
            + self._record("run_end", {}) + "\n"
        with self._lock:
            if self.closed:
                return
            self.closed = True
            self._f.write(end)
            self._f.close()


def configure(directory: str | None | bool = None, *,
              run_id: str | None = None) -> EventSink | None:
    """Select the process-global sink. ``directory=None`` falls back to
    ``$TDA_TELEMETRY_DIR``; unset/empty disables telemetry (the
    default). ``directory=False`` force-disables, IGNORING the env var
    — the teardown/no-really-off spelling (with the env var exported,
    ``configure(None)`` would re-enable). Replacing an active sink
    closes it (its ``counters`` line takes the run's counts, and the
    store starts empty again). Returns the new sink (or ``None`` when
    disabled)."""
    global _SINK
    if directory is False:
        directory = None
    else:
        directory = directory or os.environ.get(ENV_DIR) or None
    with _LOCK:
        old, _SINK = _SINK, None
    if old is not None:
        old.close()
    with _COUNT_LOCK:
        _COUNTERS.clear()
    if directory:
        sink = EventSink(directory, run_id=run_id)
        with _LOCK:
            _SINK = sink
    return _SINK


def enabled() -> bool:
    return _SINK is not None


def get_sink() -> EventSink | None:
    return _SINK


def emit(ev: str, **fields) -> None:
    """Append one event — a silent no-op when telemetry is disabled."""
    sink = _SINK
    if sink is None:
        return
    sink.write(ev, **fields)


def mark(phase: str, emit_event: bool = True) -> None:
    """Record main-loop progress: the heartbeat flags a stall when no
    mark lands within its deadline, naming the LAST marked phase as the
    stuck one. Always updates the in-memory mark (one tuple assignment
    — safe in per-step loops); ``emit_event=False`` skips the JSONL
    line for high-frequency call sites."""
    global _LAST_MARK
    _LAST_MARK = (time.monotonic(), str(phase))
    if emit_event:
        sink = _SINK
        if sink is not None:
            sink.write("mark", phase=phase)


def last_mark() -> tuple[float, str]:
    """(monotonic seconds, phase) of the newest mark."""
    return _LAST_MARK


def counter(name: str, n: int = 1) -> None:
    """Increment an in-memory counter, sink or no sink (flushed as one
    ``counters`` event where a sink closes; also snapshotted into every
    heartbeat)."""
    with _COUNT_LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def counters() -> dict[str, int]:
    """A copy of the run's counters: every :func:`counter` since the
    last :func:`configure` (or the process's start)."""
    with _COUNT_LOCK:
        return dict(_COUNTERS)


def memory(devices) -> list[tuple[int, int]] | None:
    """``(bytes_in_use, peak_bytes_in_use)`` of each of ``devices``, in
    their order, as the backend's allocator has them now: a host-side
    read that never waits for the device. ``devices`` are the ones the
    caller already holds (``mesh.local_devices``): this package looks
    none up and starts no backend. ``None`` where a device keeps no
    stats (the CPU), and for no devices."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_in_use" not in stats:
            return None
        out.append((int(stats["bytes_in_use"]),
                    int(stats.get("peak_bytes_in_use", 0))))
    return out or None


def memory_limit(devices) -> int | None:
    """The bytes the backend's allocator may hand out on ``devices``
    together (``bytes_limit``), or ``None`` where a device keeps no
    stats (the CPU), and for no devices."""
    limits = [int((d.memory_stats() or {}).get("bytes_limit", 0))
              for d in devices]
    return sum(limits) if limits and all(limits) else None


def gauge(name: str, value, **fields) -> None:
    emit("gauge", name=name, value=value, **fields)


def _annotation(name: str, **args):
    """The span as a ``TraceAnnotation`` on the profiler's clock, or
    ``None`` while ``jax`` is not imported (this package never imports
    it: the CLI configures telemetry before a backend exists)."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    return profiler.TraceAnnotation(ANNOTATION_PREFIX + name, **args)


class OpenSpan:
    """A span that has begun; :func:`end` finishes it."""

    __slots__ = ("name", "id", "parent", "t0", "fields", "devices",
                 "hbm_start")

    def __init__(self, name: str, parent: int | None, fields: dict,
                 devices=None):
        self.name = name
        self.id = next(_SPAN_IDS)
        self.parent = parent
        self.fields = fields
        self.devices = tuple(devices) if devices is not None else ()
        self.hbm_start = memory(self.devices) if self.devices else None
        self.t0 = time.perf_counter()


def _stack() -> list[OpenSpan]:
    return _OPEN_SPANS.__dict__.setdefault("stack", [])


def current() -> OpenSpan | None:
    """The innermost span open on the calling thread."""
    stack = _stack()
    return stack[-1] if stack else None


def finished() -> list[Finished]:
    """A copy of the ring of finished spans, oldest first."""
    return list(_FINISHED)


def begin(name: str, devices=None, **fields) -> OpenSpan:
    """Open a span on the calling thread: the half of :func:`span`
    for a caller that learns of a phase's two edges in two calls (the
    ``jax.monitoring`` listeners of ``utils/compile_cache``). ``fields``
    may be added to until :func:`end`. With ``devices`` the span
    samples :func:`memory` here and at its end."""
    stack = _stack()
    sp = OpenSpan(name, stack[-1].id if stack else None, fields, devices)
    stack.append(sp)
    sink = _SINK
    if sink is not None:
        sink.write("span_start", name=name,
                   **{**fields, "id": sp.id, "parent": sp.parent})
    return sp


def end(sp: OpenSpan, error: str | None = None) -> Finished:
    """Finish a span opened by :func:`begin` on this thread: into the
    ring, and as a ``span_end`` line where a sink is on."""
    seconds = time.perf_counter() - sp.t0
    held = memory(sp.devices) if sp.hbm_start else None
    if held:
        sp.fields.update(
            hbm_in_use=[b for b, _ in held], hbm_peak=[p for _, p in held],
            hbm_in_use_start=[b for b, _ in sp.hbm_start])
    stack = _stack()
    if sp in stack:
        stack.remove(sp)          # the top, unless an inner one leaked
    done = Finished(sp.name, sp.id, sp.parent, sp.t0, seconds,
                    error is None, sp.fields)
    _FINISHED.append(done)
    sink = _SINK
    if sink is not None:
        # ONE merged dict, span keys overwriting caller fields: twin
        # splats would TypeError out of a finally on a caller-
        # supplied 'error'/'seconds'/'ok' and mask the real exception
        line = dict(sp.fields)
        line.update(seconds=round(seconds, 6), ok=error is None,
                    id=sp.id, parent=sp.parent)
        if error is not None:
            line["error"] = error
        sink.write("span_end", name=sp.name, **line)
    return done


@contextlib.contextmanager
def span(name: str, devices=None, **fields):
    """Timed phase: ``span_start``/``span_end`` (+duration, +error on
    failure) around the body, with a progress mark at both edges, the
    same interval as ``tda:<name>`` in a profiler trace, and a
    :class:`Finished` entry in the ring whether or not anything else
    listens. ``id`` and ``parent`` (the span open on this thread when
    this one began, or ``None``) ride in all three. With ``devices`` it
    ends with ``hbm_in_use``, ``hbm_peak`` and ``hbm_in_use_start``
    (:func:`memory`). A phase boundary, never a per-step call."""
    mark(name, emit_event=False)
    sp = begin(name, devices, **fields)
    note = _annotation(name, id=sp.id, parent=sp.parent or 0)
    err = None
    try:
        with note or contextlib.nullcontext():
            yield
    except BaseException as e:
        err = f"{type(e).__name__}: {e}"
        raise
    finally:
        end(sp, err)
        mark(name, emit_event=False)


@atexit.register
def _close_default_sink() -> None:
    sink = _SINK
    if sink is not None:
        sink.close()
