"""Deterministic fault injection — seeded chaos that replays bitwise.

Every recovery path this framework grew (``run_with_restarts`` retry,
corrupt-checkpoint quarantine, deadline-guarded backend init, prefetch
error forwarding) only ran when real infrastructure broke — the r5
outage was diagnosed *after* the fact precisely because failure code is
the least-executed code in the repo. This module turns failure into a
routine, reproducible input: named injection points sit at every I/O
and supervision seam, and a seeded :class:`FaultPlan` decides which
invocation of which point misbehaves and how. The same plan + seed
replays the identical failure sequence, so a chaos run is as
deterministic as a clean one — and the chaos suite can assert the
recovered state is BITWISE-equal to an undisturbed run.

Injection points (wired at the call sites named):

  ``shard:straggle``  SSP schedule compilation
                    (``parallel/ssp.compile_straggle_schedule``) — one
                    probe per (tick, shard) in fixed row-major order,
                    so rule ``@N`` addresses invocation
                    ``tick·n_shards + shard``
  ``shard:leave``   elastic-membership epoch compilation
                    (``parallel/membership.compile_epochs``) — one
                    probe per (window boundary, shard), same ordering
  ``cluster:worker``  multi-process worker schedule compilation
                    (``cluster/worker.compile_worker_schedule``) — one
                    probe per (window, slot) in row-major order; kinds
                    ``kill`` (the worker SIGKILLs itself mid-window)
                    and ``straggle`` (interference compute at the
                    window boundary, delivery skipped while busy)
  ``cluster:rpc``   the cluster transport's framed send/recv seams
                    (``cluster/transport.py``) — ``oserror`` models a
                    torn connection, ``hang`` a network partition the
                    recv deadline / heartbeat timeout must observe
  ``cluster:coordinator``  coordinator crash schedule compilation
                    (``cluster/coordinator.compile_coordinator_
                    schedule``) — one probe per window; ``kill`` = the
                    coordinator SIGKILLs itself at that window's
                    commit point (mid-window: pushes in RAM, commit
                    not yet WAL'd), ``hang`` = it freezes ``arg``
                    seconds there
  ``cluster:wal``   the coordinator's write-ahead-ledger append
                    (``cluster/wal.py``) — ``corrupt`` REALLY flips
                    record bytes (replay's CRC truncates the tail
                    with a quarantine), ``oserror``/``hang`` model
                    transient disk faults
  ``cluster:ps``    PS-shard crash schedule compilation
                    (``cluster/rowstore.compile_point_schedule``) —
                    one probe per window; ``kill`` = the shard dies at
                    the merge seam AFTER the commit record is durable
                    but BEFORE the merge applies (the WAL's REDO path:
                    recovery re-applies the logged row deltas),
                    ``hang`` = a slow shard merge
  ``cluster:replica``  the serving replica's per-score-frame seam
                    (``cluster/serve.py``) — ``kill`` = the replica
                    SIGKILLs itself mid-burst (thread mode slams its
                    sockets for the same router-side EOF observable),
                    ``hang`` = a frozen replica the router's
                    heartbeat timeout must detect and route around

  ``ckpt:write``    ``utils/checkpoint.save`` — the bytes about to land
                    on disk (``corrupt`` really flips file bytes; the
                    CRC footer catches it on restore)
  ``ckpt:read``     ``utils/checkpoint.restore`` — the bytes just read
  ``cache:write``   ``data/cache.build_cache`` — the packed-cache
                    generation + publish sequence
  ``data:gather``   ``ShardedDataset.gather`` — the host block gather
                    (runs on the prefetch producer thread when
                    streaming, so ``kill`` here dies silently and
                    exercises the consumer's liveness guard)
  ``data:h2d``      ``ShardedDataset.put`` — the host→device staging
  ``backend:init``  ``telemetry.supervisor.init_backend`` — each init
                    attempt (inside the deadline-guarded worker)
  ``segment:run``   ``utils/checkpoint.run_segmented`` — before each
                    compiled training segment

Fault kinds:

  ``oserror``   raise :class:`InjectedOSError` (a transient disk/net
                fault — the supervised-retry and restart paths recover)
  ``hang``      sleep ``arg`` seconds (default 0.05) then proceed — a
                stall that deadline guards (supervisor timeout,
                heartbeat, ``Prefetcher.get`` bounded wait) must
                observe, not a permanent wedge
  ``corrupt``   with a ``payload``: flip ``arg`` (default 8) bytes at
                seed-deterministic positions and return the corrupted
                copy (the torn-write model — checksums downstream must
                detect it); without a payload: raise
                :class:`InjectedCorruptionError` (checksum-detected
                corruption in flight, recovered like a transient fault)
  ``kill``      raise :class:`InjectedKill` — "the thread doing this
                work died". ``Prefetcher``'s producer catches it and
                dies WITHOUT posting (the silent-death failure mode its
                consumer guard exists for); everywhere else it
                propagates as a restartable ``RuntimeError``.
  ``straggle``  a SCHEDULING kind (``shard:straggle`` only): the
                matched (tick, shard) cell spends the tick on ``arg``
                units of injected interference compute instead of a
                logical training step. Consumed via :func:`probe` by
                the SSP schedule compiler — it never raises; the
                straggle cost is paid inside the compiled program.
  ``leave``     a SCHEDULING kind (``shard:leave`` only): the matched
                (boundary, shard) cell leaves the active membership for
                ``arg`` windows (default 2) and rejoins after. Consumed
                via :func:`probe` by the membership epoch compiler.

Plan spec (CLI ``--fault-plan`` / env ``$TDA_FAULT_PLAN``) — either a
path to a JSON file (``{"seed": 42, "rules": [{"point": ..., "hit":
2|"*", "prob": 0.1, "kind": ..., "arg": ...}]}``) or an inline string::

    seed=42;ckpt:write@1=oserror;segment:run@*=hang:0.1;data:gather@p0.2=kill

``point@N=kind`` fires on the N-th invocation (0-based) of the point;
``@*`` fires on every invocation; ``@pP`` fires with probability P from
a per-point RNG seeded by (seed, point) — deterministic given the
plan and the invocation sequence. First matching rule wins.

Like telemetry, the registry is process-global and free when disabled:
:func:`inject` is one global read on the clean path. Everything is
stdlib-only so cache builds and checkpoint writes in plain host
processes can run under chaos too.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import threading
import time
import zlib

from tpu_distalg.telemetry import events as tevents

ENV_PLAN = "TDA_FAULT_PLAN"

POINTS = (
    "ckpt:write",
    "ckpt:read",
    "cache:write",
    "data:gather",
    "data:h2d",
    "backend:init",
    "segment:run",
    "shard:straggle",
    "shard:leave",
    "cluster:worker",
    "cluster:rpc",
    "cluster:coordinator",
    "cluster:wal",
    "cluster:replica",
    "cluster:ps",
)

KINDS = ("oserror", "hang", "corrupt", "kill", "straggle", "leave")

#: the SCHEDULING kinds: they fire at schedule-compilation seams via
#: :func:`probe` (which returns the rule instead of raising) — the
#: fault itself plays out inside the compiled SSP/cluster program,
#: bitwise-replayable because the schedule is a pure function of the
#: plan. A kind may be consumable at several points (``straggle`` is
#: both the in-process SSP schedule's and the cluster worker
#: schedule's interference kind).
_SCHEDULING_KINDS = {"straggle": ("shard:straggle", "cluster:worker"),
                     "leave": ("shard:leave",)}

#: points that take ONLY a restricted kind set (schedule-compilation
#: points take scheduling kinds; the cluster worker point also takes
#: ``kill`` — probed, then acted out by the worker itself as a real
#: SIGKILL; the rpc seam takes the transient transport kinds)
_POINT_KINDS = {
    "shard:straggle": ("straggle",),
    "shard:leave": ("leave",),
    "cluster:worker": ("straggle", "kill"),
    "cluster:rpc": ("oserror", "hang"),
    # the coordinator's own schedule: probed once per window by
    # cluster/coordinator.compile_coordinator_schedule — kill = a real
    # SIGKILL (thread mode slams every socket) at the window's commit
    # point, hang = a frozen coordinator the workers' reconnect/
    # deadline machinery must ride out
    "cluster:coordinator": ("kill", "hang"),
    # the WAL append seam (cluster/wal.py): corrupt flips record bytes
    # (the replay CRC quarantines the tail), oserror a transient disk
    # fault, hang a slow fsync
    "cluster:wal": ("oserror", "hang", "corrupt"),
    # the serving replica's score seam (cluster/serve.py): kill = a
    # real SIGKILL mid-burst (thread mode slams the replica's sockets
    # so the router sees the same EOF), hang = a frozen replica
    "cluster:replica": ("kill", "hang"),
    # the PS shard's merge seam (schedule-compiled, one probe per
    # window): kill = the shard dies AFTER the commit record is
    # durable but BEFORE the merge applies — the redo half of the WAL
    # contract (the coordinator point covers the rollback half);
    # hang = a slow shard the commit path rides out
    "cluster:ps": ("kill", "hang"),
}

DEFAULT_HANG_SECONDS = 0.05
DEFAULT_CORRUPT_BYTES = 8
DEFAULT_STRAGGLE_UNITS = 200
DEFAULT_LEAVE_WINDOWS = 2


class InjectedOSError(OSError):
    """A scheduled transient I/O fault (disk hiccup, flaky NFS, torn
    connection) — retryable by construction."""


class InjectedCorruptionError(InjectedOSError):
    """Scheduled in-flight corruption DETECTED at the seam (the checksum
    caught it) — recovered like any transient I/O fault. Undetected
    corruption is modeled separately: ``corrupt`` with a payload returns
    silently-flipped bytes and relies on a downstream CRC."""


class InjectedKill(RuntimeError):
    """The thread executing this work was killed. ``Prefetcher``'s
    producer dies silently on it (no error posted — the consumer's
    liveness guard must notice); in synchronous code it propagates as a
    restartable error."""


@dataclasses.dataclass(frozen=True)
class FaultRule:
    """One schedule entry: fire ``kind`` at ``point`` when the
    invocation index matches ``hit`` (``None`` = every invocation) or,
    when ``prob`` is set, with that per-invocation probability from the
    point's seeded RNG."""

    point: str
    kind: str
    hit: int | None = None
    prob: float | None = None
    arg: float | None = None

    def __post_init__(self):
        if self.point not in POINTS:
            raise ValueError(
                f"unknown injection point {self.point!r}; valid points: "
                f"{', '.join(POINTS)}")
        if self.kind not in KINDS:
            raise ValueError(
                f"unknown fault kind {self.kind!r}; valid kinds: "
                f"{', '.join(KINDS)}")
        if self.prob is not None and not 0.0 < self.prob <= 1.0:
            raise ValueError(
                f"fault probability must be in (0, 1], got {self.prob}")
        if self.hit is not None and self.hit < 0:
            raise ValueError(f"fault hit index must be >= 0, got {self.hit}")
        want_points = _SCHEDULING_KINDS.get(self.kind)
        if want_points is not None and self.point not in want_points:
            raise ValueError(
                f"scheduling kind {self.kind!r} fires at "
                f"{' / '.join(map(repr, want_points))} only "
                f"(got {self.point!r})")
        allowed = _POINT_KINDS.get(self.point)
        if allowed is not None and self.kind not in allowed:
            sched = all(k in _SCHEDULING_KINDS for k in allowed)
            raise ValueError(
                f"point {self.point!r} takes "
                f"{'scheduling ' if sched else ''}kinds only "
                f"({', '.join(allowed)}), got {self.kind!r}")

    def spec(self) -> str:
        where = (f"p{self.prob}" if self.prob is not None
                 else "*" if self.hit is None else str(self.hit))
        arg = f":{self.arg}" if self.arg is not None else ""
        return f"{self.point}@{where}={self.kind}{arg}"


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A seed plus an ordered rule schedule — the whole chaos input."""

    seed: int = 0
    rules: tuple[FaultRule, ...] = ()

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        """Parse an inline ``seed=..;point@hit=kind[:arg];..`` spec or a
        JSON plan file path (detected by existence / ``.json`` suffix)."""
        spec = spec.strip()
        if spec.endswith(".json") or os.path.isfile(spec):
            with open(spec) as f:
                doc = json.load(f)
            rules = []
            for r in doc.get("rules", []):
                hit = r.get("hit")
                rules.append(FaultRule(
                    point=r["point"], kind=r["kind"],
                    hit=None if hit in (None, "*") else int(hit),
                    prob=(None if r.get("prob") is None
                          else float(r["prob"])),
                    arg=(None if r.get("arg") is None
                         else float(r["arg"]))))
            return cls(seed=int(doc.get("seed", 0)), rules=tuple(rules))
        seed = 0
        rules = []
        for term in (t.strip() for t in spec.split(";") if t.strip()):
            if term.startswith("seed="):
                seed = int(term[len("seed="):])
                continue
            try:
                where_part, kind_part = term.split("=", 1)
                point, where = where_part.rsplit("@", 1)
            except ValueError:
                raise ValueError(
                    f"bad fault-plan term {term!r}: want "
                    f"'point@hit=kind[:arg]' (hit = N, '*', or 'pP') "
                    f"or 'seed=N'") from None
            kind, _, arg = kind_part.partition(":")
            rules.append(FaultRule(
                point=point, kind=kind,
                hit=(None if where in ("*",) or where.startswith("p")
                     else int(where)),
                prob=(float(where[1:]) if where.startswith("p")
                      else None),
                arg=float(arg) if arg else None))
        return cls(seed=seed, rules=tuple(rules))

    def spec(self) -> str:
        """The canonical inline spelling (parse/spec round-trips)."""
        return ";".join([f"seed={self.seed}"]
                        + [r.spec() for r in self.rules])


def _point_seed(seed: int, point: str, hit: int | None = None) -> int:
    tag = point if hit is None else f"{point}#{hit}"
    return (seed << 20) ^ zlib.crc32(tag.encode())


class FaultRegistry:
    """The live injector for one :class:`FaultPlan`: per-point
    invocation counters, per-point seeded RNGs (probability rules), and
    the record of every fault fired (``fired`` — what the chaos suite
    and the replay-determinism check compare)."""

    def __init__(self, plan: FaultPlan, *, sleep=time.sleep,
                 quiet: bool = False):
        self.plan = plan
        self._sleep = sleep
        self._quiet = quiet  # no telemetry: the plan-pure scratch
        #                      registries the SSP schedule compilers
        #                      probe (fires reach telemetry exactly
        #                      once, via the live ledger's record())
        self._lock = threading.Lock()
        self._hits: dict[str, int] = {}
        self._rngs: dict[str, random.Random] = {}
        self.fired: list[tuple[str, int, str]] = []

    def _match(self, point: str, hit: int) -> FaultRule | None:
        """First matching rule for this invocation. Probability rules
        consume one RNG draw per invocation of their point whether or
        not they fire — the property that keeps a prob-rule schedule
        deterministic in the invocation sequence."""
        chosen = None
        for rule in self.plan.rules:
            if rule.point != point:
                continue
            if rule.prob is not None:
                rng = self._rngs.setdefault(point, random.Random(
                    _point_seed(self.plan.seed, point)))
                fires = rng.random() < rule.prob
            else:
                fires = rule.hit is None or rule.hit == hit
            if fires and chosen is None:
                chosen = rule
        return chosen

    def _consume(self, point: str):
        """One invocation of ``point``: bump the counter, match, record
        and emit. Returns ``(rule | None, hit)`` — shared by
        :meth:`inject` (acts the fault out) and :meth:`probe` (returns
        the schedule entry)."""
        if point not in POINTS:
            raise ValueError(
                f"unknown injection point {point!r}; valid points: "
                f"{', '.join(POINTS)}")
        with self._lock:
            hit = self._hits.get(point, 0)
            self._hits[point] = hit + 1
            rule = self._match(point, hit)
            if rule is not None:
                self.fired.append((point, hit, rule.kind))
        if rule is not None and not self._quiet:
            tevents.emit("fault_injected", point=point, hit=hit,
                         kind=rule.kind, arg=rule.arg)
            tevents.counter("faults.injected")
            tevents.counter(f"faults.{rule.kind}")
        return rule, hit

    def probe(self, point: str):
        """Schedule-compilation seam: consume one invocation of
        ``point`` and return ``(kind, arg)`` when a rule fires, else
        ``None`` — no exception, no stall. The SSP straggle/membership
        compilers call this once per (tick, shard) cell in fixed order,
        so the same plan always compiles the same schedule (the
        property the bitwise-replay acceptance rests on)."""
        rule, _ = self._consume(point)
        if rule is None:
            return None
        return rule.kind, rule.arg

    def inject(self, point: str, payload=None):
        """The one call every injection point makes. Returns ``payload``
        (possibly corrupted); may raise or stall per the plan."""
        rule, hit = self._consume(point)
        if rule is None:
            return payload
        if rule.kind in _SCHEDULING_KINDS:
            # scheduling kinds act inside the compiled SSP program, not
            # at an I/O seam — an inject() here records the fire (the
            # replay ledger stays complete) and passes through
            return payload
        if rule.kind == "oserror":
            raise InjectedOSError(
                f"[fault] injected transient OSError at {point}#{hit}")
        if rule.kind == "hang":
            self._sleep(rule.arg if rule.arg is not None
                        else DEFAULT_HANG_SECONDS)
            return payload
        if rule.kind == "kill":
            raise InjectedKill(
                f"[fault] injected thread death at {point}#{hit}")
        # corrupt
        if payload is None:
            raise InjectedCorruptionError(
                f"[fault] injected corruption detected in flight at "
                f"{point}#{hit}")
        return self._corrupt(point, hit, payload,
                             n_bytes=int(rule.arg or DEFAULT_CORRUPT_BYTES))

    def _corrupt(self, point: str, hit: int, payload, *, n_bytes: int):
        """Flip ``n_bytes`` bytes of ``payload`` at seed-deterministic
        positions — the same plan corrupts the same bits every replay."""
        buf = bytearray(payload)
        if not buf:
            return bytes(buf)
        rng = random.Random(_point_seed(self.plan.seed, point, hit))
        for _ in range(max(1, n_bytes)):
            buf[rng.randrange(len(buf))] ^= 0xFF
        return bytes(buf)

    def record(self, fires) -> list:
        """Mirror externally-observed fires into this registry's
        ledger — the SSP schedule compilers probe a FRESH plan-pure
        QUIET registry (so restarts recompile identically without
        re-emitting), and the fires reach the chaos verdict and the
        telemetry JSONL exactly once here: a (point, hit, kind) triple
        already in the ledger (a restart's recompilation of the same
        schedule) is skipped. Returns the newly recorded fires."""
        with self._lock:
            seen = set(self.fired)
            new = [f for f in fires if f not in seen]
            self.fired.extend(new)
        for point, hit, kind in new:
            tevents.emit("fault_injected", point=point, hit=hit,
                         kind=kind, arg=None)
            tevents.counter("faults.injected")
            tevents.counter(f"faults.{kind}")
        return new

    def hits(self, point: str) -> int:
        with self._lock:
            return self._hits.get(point, 0)

    def summary(self) -> dict:
        with self._lock:
            return {"plan": self.plan.spec(),
                    "hits": dict(self._hits),
                    "fired": [{"point": p, "hit": h, "kind": k}
                              for p, h, k in self.fired]}


# ---- the process-global registry (telemetry-style lifecycle) ----------

_LOCK = threading.Lock()
_REGISTRY: FaultRegistry | None = None


def configure(spec: str | FaultPlan | None | bool = None,
              *, sleep=time.sleep) -> FaultRegistry | None:
    """Select the process-global registry. ``spec=None`` falls back to
    ``$TDA_FAULT_PLAN``; unset/empty disables injection (the default).
    ``spec=False`` force-disables, ignoring the env var. Each configure
    starts a FRESH registry (invocation counters at zero), so two runs
    under the same plan replay the identical fault sequence."""
    global _REGISTRY
    if spec is False:
        plan = None
    elif isinstance(spec, FaultPlan):
        plan = spec
    else:
        raw = spec or os.environ.get(ENV_PLAN) or None
        plan = FaultPlan.parse(raw) if raw else None
    with _LOCK:
        _REGISTRY = FaultRegistry(plan, sleep=sleep) if plan else None
        return _REGISTRY


def active() -> FaultRegistry | None:
    return _REGISTRY


def enabled() -> bool:
    return _REGISTRY is not None


def inject(point: str, payload=None):
    """Module-level injection point — a single global read when no plan
    is configured (the always-on cost at every I/O seam)."""
    reg = _REGISTRY
    if reg is None:
        return payload
    return reg.inject(point, payload)


def probe(point: str):
    """Module-level schedule probe (see :meth:`FaultRegistry.probe`):
    ``(kind, arg)`` when a rule fires on this invocation, else ``None``
    — and always ``None`` with no plan configured, so an unfaulted SSP
    run compiles empty straggle/membership schedules."""
    reg = _REGISTRY
    if reg is None:
        return None
    return reg.probe(point)
