"""The prefetch pipeline engine — gather ∥ H2D ∥ compute.

The streamed SSGD trainer proved the shape (``models/ssgd_stream.py``,
PR 1): the host gather of the next-next batch runs on a background
producer thread behind a maxsize-1 queue, the H2D ``device_put`` of the
next batch is dispatched before the current step's compute, and the
steady-state rate is ``max(gather, H2D, compute)`` — not their serial
sum. This module is that machinery extracted for EVERY workload that
consumes a :class:`~tpu_distalg.data.sharded.ShardedDataset`.

Invariants the extraction preserves (they are the bitwise contract):

  * block order and content are identical to the serial path — the
    producer gathers ``ids[0], ids[1], ...`` in order, so a consumer's
    trajectory is unchanged by prefetching;
  * host residency is bounded at two gathered batches beyond the one in
    compute (one staged-ready in the queue + the producer's in-flight
    gather);
  * a producer-side exception is forwarded through the queue and
    re-raised in the consumer; on any exit the producer is halted and
    joined (``Prefetcher`` is a context manager, and
    :func:`stream_staged` is a generator whose ``finally`` closes it —
    iterate under ``contextlib.closing`` when you may exit early);
  * a producer thread that DIES without posting anything (a bug, an
    injected ``faults.InjectedKill``) cannot block the consumer
    forever: :meth:`Prefetcher.get` waits in bounded intervals and
    checks producer liveness between them, raising
    :class:`ProducerDiedError` instead of hanging the run.
"""

from __future__ import annotations

import queue
import threading

import numpy as np

from tpu_distalg import faults
from tpu_distalg.telemetry import events as tevents


class ProducerDiedError(RuntimeError):
    """The prefetch producer thread exited without posting the item (or
    an error) the consumer is waiting on — silent thread death, the one
    failure a plain blocking ``Queue.get`` turns into an eternal hang.
    A plain ``RuntimeError`` so ``run_with_restarts`` retries it."""


class Prefetcher:
    """One-deep background producer: ``produce(i)`` for
    ``i in range(n_items)`` lands in arrival order behind a maxsize-1
    queue; :meth:`get` returns the next item or re-raises the
    producer's exception. Use as a context manager — ``__exit__`` halts
    and joins the thread whatever state the queue is in."""

    # liveness-check cadence for get(): long enough to cost nothing on
    # the healthy path, short enough that a dead producer is a prompt,
    # named error instead of a wedged run
    POLL_SECONDS = 0.1

    def __init__(self, produce, n_items: int,
                 name: str = "tda-data-prefetch"):
        self._produce = produce
        self._n = int(n_items)
        self._halt = threading.Event()
        self._q: queue.Queue = queue.Queue(maxsize=1)
        self._thread = (threading.Thread(
            target=self._run, daemon=True, name=name)
            if self._n else None)

    def _offer(self, item) -> bool:
        while not self._halt.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _run(self):
        try:
            for i in range(self._n):
                if not self._offer(self._produce(i)):
                    return
        except faults.InjectedKill:
            # die SILENTLY — no error posted. This is the chaos model
            # of a producer killed mid-flight; the consumer's liveness
            # guard in get() must turn it into ProducerDiedError.
            return
        except BaseException as e:  # noqa: BLE001 — re-raised in get()
            self._offer(e)

    def get(self):
        """Next item, or re-raise the producer's forwarded exception.
        Bounded-interval wait with a producer-liveness check: a dead
        producer raises :class:`ProducerDiedError` instead of blocking
        forever (a HUNG-but-alive producer is still waited on — that is
        the heartbeat watchdog's jurisdiction, not this guard's)."""
        while True:
            try:
                item = self._q.get(timeout=self.POLL_SECONDS)
                break
            except queue.Empty:
                th = self._thread
                if th is None or not th.is_alive():
                    # one last non-blocking drain: the producer may have
                    # posted its final item between our timeout and the
                    # liveness check
                    try:
                        item = self._q.get_nowait()
                        break
                    except queue.Empty:
                        tevents.counter("faults.producer_deaths_detected")
                        what = ("was never started" if th is None
                                else f"{th.name} died")
                        raise ProducerDiedError(
                            f"prefetch producer thread {what} without "
                            f"posting an item or an error; the batch it "
                            f"owed will never arrive — restart the "
                            f"stream (run_with_restarts recovers this)"
                        ) from None
        if isinstance(item, BaseException):
            raise item
        return item

    def __enter__(self):
        if self._thread is not None:
            self._thread.start()
        return self

    def __exit__(self, *exc):
        self._halt.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
        return False


def stream_staged(dataset, ids: np.ndarray):
    """Yield one staged device batch per step of ``ids`` ``(T, S, ns)``.

    Host backends (virtual/streamed): the producer thread gathers
    batch t+2 while batch t+1's ``device_put`` is in flight and the
    consumer computes on batch t — the double-buffered loop
    ``ssgd_stream`` ran inline, now behind a generator (``put`` of the
    NEXT batch is dispatched before the CURRENT batch is yielded to the
    consumer's compute). Resident backend: device-side block takes,
    dispatched one ahead for symmetry.

    Each step updates the liveness mark (``data:stream``); on
    exhaustion one ``data_pipeline`` event records the batch/byte
    totals for ``tda report``.
    """
    n_steps = len(ids)
    if dataset.backend == "resident":
        for i in range(n_steps):
            tevents.mark("data:stream", emit_event=False)
            yield dataset.stage(ids[i])
        return
    total_bytes = 0
    with Prefetcher(lambda i: dataset.gather(ids[i]), n_steps) as pf:
        staged = dataset.put(pf.get()) if n_steps else None
        for i in range(n_steps):
            tevents.mark("data:stream", emit_event=False)
            nxt = dataset.put(pf.get()) if i + 1 < n_steps else None
            total_bytes += int(np.prod(staged.shape)) * dataset.itemsize
            yield staged
            staged = nxt
    tevents.emit("data_pipeline", backend=dataset.backend,
                 steps=n_steps, bytes=total_bytes)


def make_host_block_sampler(seed: int, n_shards: int, n_blocks: int,
                            n_sampled: int):
    """Build ONCE the jitted 'fused_gather' block draw: threefry is
    platform-deterministic, so these ids equal the ones the resident
    path draws inside its scan — the property that keeps streamed
    trajectories bitwise-equal to resident ones. Runs on the default
    backend (a process pinned to ``JAX_PLATFORMS=tpu`` has no CPU
    backend to ask for). Returns ``draw(ts) -> (T, n_shards,
    n_sampled)`` local block ids on the host; the jit is cached per
    distinct segment length (building it per call would recompile the
    sampler inside timed/checkpointed loops)."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import sampling
    from tpu_distalg.utils import prng

    key = prng.root_key(seed)
    f = jax.jit(jax.vmap(lambda t: sampling.sample_block_ids(
        jax.random.fold_in(key, t), n_shards, n_blocks, n_sampled)))

    def draw(ts: np.ndarray) -> np.ndarray:
        return np.asarray(f(jnp.asarray(ts, jnp.int32)))

    return draw
