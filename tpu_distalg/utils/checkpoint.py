"""Checkpoint / resume.

The reference has none (SURVEY.md §5): training state lives only in driver
RAM and the only artifacts are PNG plots. Here any pytree of arrays (model,
optimizer state, step counter) can be saved per-N-steps and restored as one
msgpack file per step (flax serialization, atomic rename). Note ``save``
gathers every leaf to this host via ``np.asarray`` — fine for the replicated
model/optimizer state these workloads carry; use orbax directly for
multi-host sharded checkpoints of device-resident datasets.

Durability contract (chaos-tested, tests/test_faults.py):

  * ``save`` appends a CRC32 footer, fsyncs the tmp file before the
    atomic ``os.replace`` and the directory after it — a torn write
    that still happens to msgpack-parse is DETECTED on restore as
    :class:`CorruptCheckpointError` instead of silently resuming from
    garbage, and a power cut cannot lose the rename;
  * transient ``OSError`` during the write is retried in place via
    :func:`telemetry.supervisor.supervised` before it becomes anyone
    else's problem;
  * ``run_segmented``'s resume quarantines a corrupt NEWEST checkpoint
    and falls back to the next-older step in-process — recovery does
    not require spending a ``run_with_restarts`` cycle;
  * a preemption request (SIGTERM/SIGINT via ``faults.preempt``) exits
    at the next segment boundary, AFTER that segment's checkpoint is
    durably saved, with the distinct ``PREEMPTED_RC`` — the resumed run
    is bitwise-identical to an uninterrupted one.

Fault-injection points: ``ckpt:write`` (the payload bytes about to hit
disk), ``ckpt:read`` (the bytes just read), ``segment:run`` (before
each compiled segment) — see ``tpu_distalg/faults/registry.py``.
"""

from __future__ import annotations

import contextlib
import os
import re
import struct
import zlib
from typing import Any

import jax
import numpy as np

from tpu_distalg import faults
from tpu_distalg.faults import preempt
from tpu_distalg.telemetry import events as tevents

_STEP_RE = re.compile(r"^step_(\d+)\.msgpack$")

# footer = magic + little-endian CRC32 of the payload bytes. The magic
# starts with NUL so no legacy msgpack stream ends with it by accident
# (msgpack never emits a bare trailing NUL run of this shape).
_CRC_MAGIC = b"\x00TDACRC1"
_CRC_FOOTER_LEN = len(_CRC_MAGIC) + 4

# transient-disk-fault retry schedule for the write path: short and
# fixed — a real outage longer than this is run_with_restarts' job
SAVE_RETRIES = 2
SAVE_BACKOFF_SECONDS = 0.05


class CorruptCheckpointError(ValueError):
    """A checkpoint file exists but will not deserialize or fails its
    CRC — e.g. it was half-written by the same crash the watchdog
    exists to survive (the atomic rename + fsync in :func:`save`
    prevents this for clean process deaths, but not for disk faults).
    Carries the offending ``path`` so the resume fallback (and
    :func:`run_with_restarts`) can quarantine it and resume from the
    previous step instead of dying on a retryable condition."""

    def __init__(self, path: str, msg: str):
        super().__init__(msg)
        self.path = path


def _fsync_dir(directory: str) -> None:
    """fsync the directory so the rename itself is durable (an atomic
    replace whose dirent update is lost to a power cut resumes from the
    WRONG step). Best-effort: some filesystems refuse directory fds."""
    try:
        fd = os.open(directory, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def save(ckpt_dir: str, tree: Any, step: int) -> str:
    """Write ``tree`` at ``ckpt_dir/step_<step>.msgpack``: CRC32 footer,
    fsync, atomic rename, directory fsync — with transient ``OSError``
    retried (:data:`SAVE_RETRIES` attempts, fixed backoff)."""
    from flax import serialization

    from tpu_distalg.telemetry.supervisor import supervised

    os.makedirs(ckpt_dir, exist_ok=True)
    host_tree = jax.tree.map(np.asarray, tree)
    payload = serialization.msgpack_serialize(host_tree)
    # footer CRC is of the TRUE payload: an injected/real torn write
    # corrupts the body after this point and the mismatch is caught on
    # restore — the exact silent-resume-from-garbage hole being closed
    footer = _CRC_MAGIC + struct.pack("<I", zlib.crc32(payload) & 0xFFFFFFFF)
    path = os.path.join(ckpt_dir, f"step_{step}.msgpack")
    tmp = path + ".tmp"

    def write_once():
        body = faults.inject("ckpt:write", payload=payload)
        with open(tmp, "wb") as f:
            f.write(body)
            f.write(footer)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        _fsync_dir(ckpt_dir)

    supervised(write_once, phase="ckpt:write", retries=SAVE_RETRIES,
               backoff=SAVE_BACKOFF_SECONDS,
               backoff_cap=SAVE_BACKOFF_SECONDS, jitter=0.0,
               retry_on=(OSError,), failure_counter="ckpt.write_failures",
               log=lambda m: None)
    return path


def _strip_crc_footer(path: str, raw: bytes) -> bytes:
    """Validate + strip the CRC footer; legacy footerless files pass
    through (their only guard is msgpack parseability, as before)."""
    if len(raw) >= _CRC_FOOTER_LEN and \
            raw[-_CRC_FOOTER_LEN:-4] == _CRC_MAGIC:
        body = raw[:-_CRC_FOOTER_LEN]
        (want,) = struct.unpack("<I", raw[-4:])
        got = zlib.crc32(body) & 0xFFFFFFFF
        if got != want:
            raise CorruptCheckpointError(
                path,
                f"corrupt checkpoint {path}: CRC32 mismatch "
                f"(stored {want:#010x}, computed {got:#010x}) — the "
                f"file was torn or bit-rotted after writing; delete or "
                f"quarantine it to resume from an earlier step")
        return body
    return raw


def list_steps(ckpt_dir: str) -> list[int]:
    """Every on-disk checkpoint step, ascending (public: the cluster
    coordinator's WAL truncation keeps segments for exactly the kept
    checkpoints, so fallback-to-older-step can still roll forward)."""
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(
        int(m.group(1))
        for name in os.listdir(ckpt_dir)
        if (m := _STEP_RE.match(name))
    )


def latest_step(ckpt_dir: str) -> int | None:
    steps = list_steps(ckpt_dir)
    return max(steps) if steps else None


def restore(ckpt_dir: str, step: int | None = None) -> tuple[Any, int]:
    """Load (tree, step); ``step=None`` loads the newest checkpoint.
    The CRC footer (when present) is verified BEFORE parsing, so a torn
    write that still happens to msgpack-parse cannot slip through."""
    from flax import serialization

    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step}.msgpack")
    with open(path, "rb") as f:
        raw = f.read()
    # injected read-side corruption lands BEFORE the CRC check, so the
    # detection path is the one being exercised
    raw = faults.inject("ckpt:read", payload=raw)
    payload = _strip_crc_footer(path, raw)
    try:
        tree = serialization.msgpack_restore(payload)
    except Exception as e:
        raise CorruptCheckpointError(
            path,
            f"corrupt checkpoint {path} ({type(e).__name__}: {e}); delete "
            f"it to resume from an earlier step"
        ) from e
    return tree, step


def quarantine(path: str, *, logger=None) -> bool:
    """Rename a corrupt checkpoint to ``<path>.corrupt`` so the next
    resume sees the previous step. Tolerates the concurrent-process
    race (another restart already quarantined or pruned it —
    ``FileNotFoundError`` counts as done). Returns False only when the
    rename fails for a reason that needs a human."""
    try:
        # tda: ignore[TDA030] -- recovery rename of an ALREADY-corrupt
        # file, not a durable publish: a failure here is caught below
        # and reported, and injecting at it would shift the ckpt:write
        # hit counts every recorded chaos plan replays against
        os.replace(path, path + ".corrupt")
    except FileNotFoundError:
        return True  # a concurrent process beat us to it
    except OSError as os_err:
        (logger or print)(
            f"could not quarantine corrupt checkpoint {path} "
            f"({os_err}); manual cleanup required")
        return False
    tevents.emit("quarantine", path=path)
    tevents.counter("quarantines")
    return True


def restore_newest_with_fallback(ckpt_dir: str, *, logger=None):
    """The resume read path: try the newest checkpoint; a corrupt one is
    quarantined IN-PROCESS and the next-older step is tried — recovery
    from the crash-corrupts-newest-checkpoint scenario costs zero
    restart budget. Returns ``(payload, step)`` or ``None`` when no
    restorable checkpoint remains (fresh start). Public: the serving
    layer's artifact loader degrades through the same path
    (``serve/artifacts.py``)."""
    while True:
        step = latest_step(ckpt_dir)
        if step is None:
            return None
        try:
            return restore(ckpt_dir, step)
        except CorruptCheckpointError as e:
            if not quarantine(e.path, logger=logger):
                raise
            (logger or print)(
                f"[quarantine] corrupt checkpoint {e.path} -> .corrupt; "
                f"falling back to the previous step in-process")
        except FileNotFoundError:
            # pruned/quarantined under us by a concurrent process
            # between the listing and the open — re-list and retry
            continue


def encode_tag(tag: str) -> np.ndarray:
    """msgpack round-trips arrays, not str — the byte-encoded workload
    tag every segmented loop (here and ``membership.run_elastic``)
    stores and compares. One codec, so the tag contract cannot drift
    between the tick-indexed and window-indexed loops."""
    return np.frombuffer(tag.encode(), dtype=np.uint8)


def decode_tag(payload, default: str) -> str:
    """Inverse of :func:`encode_tag`; ``default`` for legacy payloads
    written before tags existed."""
    if "tag" in payload:
        return np.asarray(
            payload["tag"]).tobytes().decode(errors="replace")
    return default


def preempt_boundary_exit(step: int, tag: str) -> None:
    """The shared preemption contract of every segmented loop: once a
    request is pending, exit at the boundary AFTER the durable save —
    emit the record here (the signal handler only sets a flag) and
    raise :class:`~tpu_distalg.faults.Preempted` (rc 75, never caught
    by the restart budget). No-op without a pending request."""
    if not preempt.requested():
        return
    tevents.emit("preempted", step=step, tag=tag,
                 signals=list(preempt.signals_seen()))
    tevents.counter("preemptions")
    raise preempt.Preempted(step=step)


def run_segmented(
    checkpoint_dir: str,
    checkpoint_every: int,
    n_iterations: int,
    make_seg_fn,
    run_seg,
    state0,
    *,
    tag: str = "",
    keep: int = 3,
    stop_when=None,
    span_fields: dict | None = None,
):
    """Generic segmented/resumable training loop — the machinery behind
    every workload's ``checkpoint_dir`` option.

    Runs ``n_iterations`` total steps as compiled segments of
    ``checkpoint_every``; after each segment the (state, accs-so-far) is
    saved and a non-finite guard trips with a clear error. An existing
    checkpoint resumes from its absolute step; because every builder
    threads the absolute step offset into its PRNG (``t0``), segmented
    and straight-through runs are bitwise-identical. A corrupt newest
    checkpoint is quarantined and the next-older step resumes instead
    (see :func:`restore_newest_with_fallback`).

    ``make_seg_fn(seg_len)`` builds (and caches per distinct length) the
    compiled segment; ``run_seg(fn, state, t0)`` executes it and returns
    ``(new_state, accs)``; ``state0`` is the initial carry pytree.
    ``tag`` names the workload — stored in every checkpoint and compared
    on resume (along with the state leaves' shapes/dtypes), so resuming
    the wrong workload's directory fails loudly instead of silently
    continuing from foreign weights. ``stop_when(state)`` (optional) is
    checked after every segment AND on resume: fixpoint workloads
    (k-means converge mode, closure, ALS-to-tolerance) stop as soon as
    their convergence predicate holds instead of burning no-op segments
    to ``n_iterations`` — the segment bodies must make post-convergence
    segments no-ops (carry their convergence signal in ``state``) so
    segmented and straight runs stay bitwise-identical. Returns
    ``(state, accs_concat, start_step)``. ``span_fields`` ride on every
    ``train:segment`` span (what the builder knows about the segments
    and the loop does not, e.g. SSGD's ``draw_form``).

    Preemption: once ``faults.preempt`` has a pending request (SIGTERM/
    SIGINT), the loop raises :class:`~tpu_distalg.faults.Preempted` at
    the NEXT segment boundary — after that segment's checkpoint is
    durably on disk — so the process exits with the distinct
    ``PREEMPTED_RC`` and a re-run resumes bitwise-identically.
    """
    if checkpoint_every < 1:
        raise ValueError(
            f"checkpoint_every must be >= 1, got {checkpoint_every}"
        )
    leaves0, treedef = jax.tree.flatten(state0)
    start = 0
    accs_parts = []
    state = state0
    restored = restore_newest_with_fallback(checkpoint_dir)
    if restored is not None:
        payload, start = restored
        if start > n_iterations:
            raise ValueError(
                f"checkpoint in {checkpoint_dir} is at step {start}, "
                f"past n_iterations={n_iterations}; use a fresh "
                f"directory or raise n_iterations"
            )
        # legacy pre-tag payloads ({'w','accs'}) also lack 'state', so
        # the check below always rejects them: old checkpoints need a
        # fresh directory, not a silent cross-format resume
        saved_tag = decode_tag(payload, tag)
        sig = [(tuple(np.asarray(v).shape), str(np.asarray(v).dtype))
               for v in payload.get("state", [])]
        want = [(tuple(np.asarray(x).shape), str(np.asarray(x).dtype))
                for x in leaves0]
        if "state" not in payload or saved_tag != tag or sig != want:
            raise ValueError(
                f"checkpoint in {checkpoint_dir} is incompatible: it "
                f"holds workload {saved_tag!r} with state {sig}, but "
                f"this run is {tag!r} with state {want} — it was "
                f"written by a different workload, config, or framework "
                f"version; use a fresh directory"
            )
        state = jax.tree.unflatten(
            treedef, [np.asarray(v) for v in payload["state"]]
        )
        accs_parts = [np.asarray(payload["accs"])]

    from tpu_distalg.utils import metrics

    # this process's devices the state lies on (no mesh reaches this
    # loop): the train:* spans sample their memory at both ends
    devices = sorted({s.device for x in leaves0 if isinstance(x, jax.Array)
                      for s in x.addressable_shards}, key=lambda d: d.id)
    seg_fns = {}
    t = start
    while t < n_iterations:
        if stop_when is not None and stop_when(state):
            break
        seg = min(checkpoint_every, n_iterations - t)
        # a new segment length builds, and its first call traces and
        # compiles (or loads from the cache): that first segment sits
        # under train:build, the steady ones stand alone. The spans
        # mark at both edges, so the telemetry heartbeat names the
        # phase if a segment wedges (device hang) instead of staying
        # mute
        with contextlib.ExitStack() as build:
            if seg not in seg_fns:
                build.enter_context(tevents.span(
                    "train:build", devices, tag=tag, seg=seg))
                seg_fns[seg] = make_seg_fn(seg)
            with tevents.span("train:segment", devices, tag=tag, t0=t,
                              steps=seg, **(span_fields or {})):
                faults.inject("segment:run")
                state, accs = run_seg(seg_fns[seg], state, t)
                metrics.guard_finite(
                    state, f"training state after step {t + seg}"
                )
        t += seg
        # what the checkpoint holds, sized without fetching it
        held = metrics.nbytes(state, accs_parts, accs)
        with tevents.span("train:checkpoint", devices, step=t,
                          bytes=held):
            accs_parts.append(np.asarray(accs))
            save(
                checkpoint_dir,
                {"tag": encode_tag(tag),
                 "state": [np.asarray(x) for x in jax.tree.leaves(state)],
                 "accs": np.concatenate(accs_parts)},
                step=t,
            )
            prune(checkpoint_dir, keep=keep)
        tevents.emit("checkpoint_saved", step=t, tag=tag)
        tevents.counter("checkpoints_saved")
        if t < n_iterations:
            # boundary exit AFTER the durable save (the helper no-ops
            # without a pending request; a finished run never fakes a
            # preemption)
            preempt_boundary_exit(t, tag)
    accs = (np.concatenate(accs_parts) if accs_parts
            else np.zeros((0,), np.float32))
    return state, accs, start


def run_with_restarts(run_once, max_restarts: int = 0, *, logger=None):
    """Job-level auto-restart: the task-retry analogue of what Spark
    gives the reference silently (task retry + lineage recomputation —
    e.g. the cached RDD at ``/root/reference/optimization/ssgd.py:86``
    is rebuilt by lineage if an executor dies; SURVEY.md §5 "failure
    detection").

    ``run_once()`` is invoked up to ``1 + max_restarts`` times; any
    ``Exception`` (a device crash, or :func:`run_segmented`'s
    non-finite-state guard trip) triggers a retry. Recovery comes from
    pairing with a ``checkpoint_dir``: every workload's segmented
    runner resumes from the newest checkpoint on disk, so a retry
    replays only the failed segment — and because segment sampling is
    keyed on absolute step ids, the recovered run is bitwise-identical
    to an uninterrupted one. Without a checkpoint dir each retry
    starts from step 0 (still useful for transient device faults).
    Deterministic failures (a genuine NaN the guard keeps re-hitting)
    exhaust the retries and re-raise the LAST error. Configuration
    errors (``ValueError``/``TypeError``/``FileNotFoundError`` — e.g.
    an incompatible checkpoint directory) fail identically every time,
    so they are never retried; ``KeyboardInterrupt``/``SystemExit``
    (which includes a graceful :class:`~tpu_distalg.faults.Preempted`
    boundary exit — preemption must not burn the restart budget) are
    never caught. The one retryable ``ValueError`` is
    :class:`CorruptCheckpointError`: the offending file is quarantined
    (renamed ``*.corrupt``) and the retry resumes from the previous
    step — a checkpoint corrupted by the very crash being survived must
    not kill the watchdog. (``run_segmented``'s own resume already
    falls back in-process; this path covers corruption detected by
    DIRECT ``restore`` callers and explicit-step loads.)
    """
    if max_restarts < 0:
        raise ValueError(f"max_restarts must be >= 0, got {max_restarts}")
    attempt = 0
    while True:
        try:
            return run_once()
        except CorruptCheckpointError as e:
            # quarantine retries do NOT consume the restart budget: a
            # crash that also corrupts the newest checkpoint would
            # otherwise spend attempt 1 on the crash and die on the
            # corrupt file at max_restarts=1 — the exact scenario this
            # path exists for. The loop still terminates: each pass
            # renames one distinct on-disk file, and restore() can only
            # trip on files that exist. max_restarts=0 means "no
            # recovery of any kind" and still raises.
            if max_restarts == 0:
                raise
            if not quarantine(e.path, logger=logger):
                raise
            (logger or print)(
                f"[quarantine] corrupt checkpoint {e.path} -> .corrupt; "
                f"resuming from the previous step (restart budget "
                f"untouched: {attempt}/{max_restarts} used)"
            )
        except (ValueError, TypeError, FileNotFoundError):
            raise  # deterministic config error — retrying cannot help
        except Exception as e:  # noqa: BLE001 — anything restartable
            attempt += 1
            if attempt > max_restarts:
                tevents.emit("restart_budget_exhausted",
                             attempts=attempt - 1, of=max_restarts,
                             error=f"{type(e).__name__}: {e}")
                raise
            tevents.emit("restart", attempt=attempt, of=max_restarts,
                         error=f"{type(e).__name__}: {e}")
            tevents.counter("restarts")
            (logger or print)(
                f"[restart {attempt}/{max_restarts}] "
                f"{type(e).__name__}: {e} — re-running (resumes from "
                f"the latest checkpoint if one exists)"
            )


def prune(ckpt_dir: str, keep: int = 3) -> None:
    """Delete all but the newest ``keep`` checkpoints. Tolerates a
    concurrent restart's prune racing this one (``FileNotFoundError``
    means the file is already gone — the desired state)."""
    if not os.path.isdir(ckpt_dir):
        return
    steps = sorted(
        int(m.group(1))
        for name in os.listdir(ckpt_dir)
        if (m := _STEP_RE.match(name))
    )
    for s in steps[:-keep] if keep else steps:
        try:
            os.remove(os.path.join(ckpt_dir, f"step_{s}.msgpack"))
        except FileNotFoundError:
            pass
