"""Runtime telemetry & supervision.

The observability layer the reference never had (its only instrument is
``print`` per iteration, SURVEY.md §5) and round 5 proved this repo
needed (a 26-minute invisible backend hang): structured JSONL events
(:mod:`events`), a liveness heartbeat with stall detection
(:mod:`heartbeat`), deadline-guarded backend init with retry/backoff
(:mod:`supervisor`), and log summarization for humans and CI
(:mod:`report`, ``tda report <dir>``). Every finished span is also kept
in memory (:func:`finished`) and every counter (:func:`counters`), sink
or no sink.

Import cost is stdlib-only (no jax) so the CLI can configure telemetry
before the backend exists — which is exactly when it matters most.
"""

from tpu_distalg.telemetry import events, heartbeat, report, supervisor
from tpu_distalg.telemetry.events import (
    configure,
    counter,
    counters,
    emit,
    enabled,
    finished,
    gauge,
    get_sink,
    last_mark,
    mark,
    span,
)
from tpu_distalg.telemetry.heartbeat import Heartbeat, start_heartbeat
from tpu_distalg.telemetry.supervisor import (
    BackendUnavailableError,
    init_backend,
    supervised,
)

__all__ = [
    "BackendUnavailableError",
    "Heartbeat",
    "configure",
    "counter",
    "counters",
    "emit",
    "enabled",
    "events",
    "finished",
    "gauge",
    "get_sink",
    "heartbeat",
    "init_backend",
    "last_mark",
    "mark",
    "report",
    "span",
    "start_heartbeat",
    "supervised",
    "supervisor",
]
