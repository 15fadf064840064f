"""Streamed host→device SSGD — REAL datasets bigger than HBM.

The resident fused samplers (``models/ssgd.py``) cap the dataset at
HBM. This module lifts the cap (r4 verdict "what's missing" #1): a
dataset of ARBITRARY bytes sitting in host RAM or on disk
(``np.memmap``) trains at any size — the Spark capability the reference
leans on when an RDD exceeds executor memory and partitions spill/stream
from disk
(``/root/reference/optimization/ssgd.py:86``'s ``.cache()`` is a hint,
not a requirement).

TPU-native shape of the answer:

  * the dataset is packed ONCE on host into the exact layout the
    'fused_gather' kernel consumes (``pallas_kernels.pack_augmented
    (as_numpy=True)``) — bf16-packed host bytes are what go over the
    wire, so H2D traffic per step is ``fraction × |X|`` bytes, same as
    the resident path's HBM traffic;
  * per step, the SAME without-replacement block draw as 'fused_gather'
    (``sampling.sample_block_ids``, threefry keyed on the absolute step
    id — platform-deterministic, so host-side draws equal device-side
    draws bit for bit) picks block ids, the host gathers those rows
    with one fancy-index memcpy, and ``jax.device_put`` stages them
    ASYNCHRONOUSLY onto the mesh (sharded over the data axis);
  * the staging of step t+1 is enqueued BEFORE step t's gradient is
    dispatched (double buffering), and the HOST GATHER runs on a
    background prefetch thread — since PR 2 both live in the data
    subsystem (``tpu_distalg/data``: ``ShardedDataset`` owns the
    storage/gather/put, ``pipeline.stream_staged`` the producer →
    maxsize-1 queue → put loop; at most two gathered batches resident
    beyond the one in compute): gather(t+2), H2D(t+1) and compute(t)
    genuinely overlap, so the steady-state rate is max(gather, H2D,
    compute) — not their serial sum (before round 6 the gather ran
    synchronously on the dispatch thread, which for a disk-memmap >RAM
    dataset made it gather + min(H2D, compute));
  * the device step feeds the staged blocks to the SAME kernel the
    resident path runs (``fused_grad_sum_gathered`` with the identity
    block index), so the weight trajectory is bitwise-identical to
    'fused_gather' on a resident copy of the same packed matrix
    (asserted in tests/test_ssgd_stream.py).

Checkpoint/resume: sampling is keyed on absolute step ids, so
segmented runs through ``checkpoint_dir`` are bitwise-identical to
straight runs, like every other sampler.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tpu_distalg.data import ShardedDataset, make_host_block_sampler
from tpu_distalg.models.ssgd import (
    SSGDConfig,
    TrainResult,
    fused_gather_geometry,
)
from tpu_distalg.ops import logistic, pallas_kernels
from tpu_distalg.parallel import DATA_AXIS, data_parallel, \
    mesh_on_tpu, tree_allreduce_sum
from tpu_distalg.utils import metrics, prng


def pack_host(X, y, mesh: Mesh, config: SSGDConfig):
    """Pack (X, y) into the fused layout as a HOST numpy array in the
    device dtype — never device-resident. Same layout/shuffle as
    :func:`ssgd.prepare_fused`, so a resident copy of the result trains
    bitwise-identically under 'fused_gather'."""
    n_shards = mesh.shape[DATA_AXIS]
    n = np.asarray(y).shape[0]
    return pallas_kernels.pack_augmented(
        np.asarray(X), np.asarray(y), np.ones(n, np.float32),
        dtype=jnp.dtype(config.x_dtype), pack=config.fused_pack,
        block_rows=config.gather_block_rows * n_shards,
        shuffle_seed=config.shuffle_seed, as_numpy=True)


def make_host_sampler(seed: int, n_shards: int, n_blocks: int,
                      n_sampled: int):
    """The host-CPU 'fused_gather' block draw — now the data
    subsystem's ``pipeline.make_host_block_sampler`` (kept as an alias:
    the sampler IS the bitwise-equality contract and callers reference
    it here)."""
    return make_host_block_sampler(seed, n_shards, n_blocks, n_sampled)


def host_block_ids(config: SSGDConfig, n_shards: int, n_blocks: int,
                   n_sampled: int, ts: np.ndarray) -> np.ndarray:
    """One-shot convenience wrapper over :func:`make_host_sampler`."""
    return make_host_sampler(config.seed, n_shards, n_blocks,
                             n_sampled)(ts)


def make_step_fn(mesh: Mesh, config: SSGDConfig, meta: dict,
                 n_sampled: int):
    """Jitted ``step(staged, w) -> w`` over one staged block batch
    (S, n_sampled·bp, pack·d_total): the resident kernel with the
    identity block index — a contiguous read of exactly the staged
    minibatch — then the shared update rule (``ssgd.py:105``)."""
    on_tpu = mesh_on_tpu(mesh)
    d_t = meta["d_total"]
    col_keep = (jnp.arange(d_t) < meta["y_col"]).astype(jnp.float32)
    kern = functools.partial(
        pallas_kernels.fused_grad_sum_gathered,
        pack=meta["pack"], d_total=d_t, y_col=meta["y_col"],
        v_col=meta["v_col"],
        gather_block_rows=config.gather_block_rows,
        interpret=not on_tpu)
    idx = jnp.arange(n_sampled, dtype=jnp.int32)

    def _local(Xb, w):
        g, cnt = kern(Xb[0], w, idx)
        return tree_allreduce_sum((g * col_keep, cnt))

    grad_fn = data_parallel(
        _local, mesh,
        in_specs=(P(DATA_AXIS, None, None), P()),
        out_specs=(P(), P()))

    def step(staged, w):
        g, cnt = grad_fn(staged, w)
        n_batch = jnp.maximum(cnt, 1.0)
        reg = logistic.reg_gradient(
            w, config.reg_type, config.elastic_alpha)
        return w - config.eta * (g / n_batch + config.lam * reg)

    return jax.jit(step)


class StreamTrainer:
    """The double-buffered host→device training loop over a packed
    host (or memmap) matrix. Build once, then :meth:`run` segments.
    Storage, gather, H2D staging and the prefetch pipeline live in the
    data subsystem (``tpu_distalg/data/`` — this trainer is where the
    machinery was proven before being promoted); what remains here is
    the SSGD-specific step/eval logic."""

    def __init__(self, X2_host, meta: dict, mesh: Mesh,
                 config: SSGDConfig, X_test=None, y_test=None):
        n_shards = mesh.shape[DATA_AXIS]
        n2 = X2_host.shape[0]
        if n2 % n_shards:
            raise ValueError(
                f"packed rows {n2} not divisible by {n_shards} shards "
                "— pack with block_rows=gather_block_rows*n_shards "
                "(pack_host does)")
        self.meta = meta
        self.mesh = mesh
        self.config = config
        self.bp = config.gather_block_rows // meta["pack"]
        self.n_shards = n_shards
        self.dataset = ShardedDataset(X2_host, mesh,
                                      block_rows=self.bp, meta=meta)
        self.X2 = self.dataset.storage
        self.n2_local = self.dataset.n2_local
        # same quantization (and warning) as the resident path
        n_blocks, n_sampled = fused_gather_geometry(
            config, meta, n_shards)
        if n_blocks != self.dataset.n_blocks:
            raise ValueError(
                f"meta n_padded={meta['n_padded']} disagrees with the "
                f"host matrix ({n2} packed rows)")
        self.n_blocks, self.n_sampled = n_blocks, n_sampled
        self._draw = make_host_sampler(config.seed, n_shards, n_blocks,
                                       n_sampled)
        self.step_fn = make_step_fn(mesh, config, meta, n_sampled)
        self.shard_spec = self.dataset.shard_spec
        self._touch = self.dataset._touch
        # CPU-mesh emulation on few host cores starves the rendezvous
        # when several multi-device programs are in flight (collective
        # thunks BLOCK pool workers; a 1-core host then never schedules
        # the remaining participants) — run one step at a time there.
        # Pipelining is a hardware-rig concern anyway.
        self._serialize = not self.dataset.on_tpu
        self.eval_fn = None
        if config.eval_test:
            if X_test is None:
                raise ValueError("eval_test=True needs X_test/y_test")
            from tpu_distalg.parallel import partition

            d_t = meta["d_total"]
            Xt = np.asarray(X_test, np.float32)
            Xt = np.pad(Xt, ((0, 0), (0, d_t - Xt.shape[1])))
            # replicate onto the mesh AND pin the eval to per-device
            # local compute via shard_map: left to GSPMD, a jit over
            # replicated operands may still partition the matmul and
            # insert collectives — and any collective program
            # dispatched concurrently with the pipelined step/touch
            # programs can deadlock a rendezvous on backends that
            # start programs out of order (seen on the CPU mesh)
            Xt = partition.put(Xt, "X_test", "ssgd_stream", mesh)
            yt = partition.put(y_test, "y_test", "ssgd_stream", mesh)
            self.eval_fn = jax.jit(data_parallel(
                lambda a, b, w: metrics.binary_accuracy(a @ w, b),
                mesh, in_specs=(P(), P(), P()), out_specs=P(),
            ))
            self._eval_args = (Xt, yt)
        self.h2d_bytes_per_step = self.dataset.h2d_bytes_per_step(
            n_sampled)

    def _gather(self, ids_step: np.ndarray) -> np.ndarray:
        """Host-side gather of one step's sampled blocks — now
        ``ShardedDataset.gather`` (kept for the tests that probe the
        stages individually)."""
        return self.dataset.gather(ids_step)

    def _put(self, gathered: np.ndarray):
        """Async H2D staging — now ``ShardedDataset.put``."""
        return self.dataset.put(gathered)

    def _stage(self, ids_step: np.ndarray):
        """Serial gather+put of one step's batch (no prefetch, on
        purpose: the shape an H2D roofline is read from)."""
        return self.dataset.stage(ids_step)

    def run(self, w, t0: int, n_steps: int, acc0=0.0):
        """``n_steps`` double-buffered steps from absolute step ``t0``;
        returns ``(w, accs)`` with the scan path's eval_every/last-acc
        semantics (``acc0`` carries the last computed accuracy across
        segment boundaries). Device values only are carried — no host
        sync until the final fetch.

        The host gather runs on the data subsystem's prefetch pipeline
        (``data/pipeline.stream_staged``): a background producer thread
        behind a maxsize-1 queue, so gather(t+2) ∥ H2D(t+1) ∥
        compute(t) and host residency is bounded at two gathered
        batches beyond the one in compute. Block order and content are
        identical to the serial path, so the weight trajectory stays
        bitwise-equal to the resident 'fused_gather' sampler. A
        producer-side exception is re-raised here; on any exit the
        producer is stopped and joined (``contextlib.closing``)."""
        from tpu_distalg.telemetry import events as tevents

        cfg = self.config
        ts = np.arange(t0, t0 + n_steps)
        ids = self._draw(ts)
        accs = []
        last_acc = jnp.float32(acc0)
        with contextlib.closing(self.dataset.stream(ids)) as batches:
            for i, staged in enumerate(batches):
                tevents.mark("ssgd_stream:step", emit_event=False)
                w = self.step_fn(staged, w)
                if self._serialize:
                    jax.block_until_ready(w)
                if self.eval_fn is not None:
                    if ts[i] % cfg.eval_every == 0:
                        last_acc = self.eval_fn(*self._eval_args, w)
                    accs.append(last_acc)
                else:
                    accs.append(last_acc)
        return w, jnp.stack(accs) if accs else jnp.zeros((0,))


def train(X2_host, meta: dict, mesh: Mesh, config: SSGDConfig,
          X_test=None, y_test=None, w0=None, *,
          checkpoint_dir: str | None = None,
          checkpoint_every: int = 500) -> TrainResult:
    """End-to-end streamed run (optionally checkpointed/segmented —
    bitwise-identical to a straight run, sampling is keyed on absolute
    step ids)."""
    from tpu_distalg.telemetry import events as tevents

    tevents.mark("ssgd_stream:train", emit_event=False)
    trainer = StreamTrainer(X2_host, meta, mesh, config, X_test, y_test)
    if w0 is None:
        d = (X_test.shape[1] if X_test is not None
             else meta["y_col"])
        w0 = jnp.zeros((meta["d_total"],), jnp.float32).at[:d].set(
            logistic.init_weights(prng.root_key(config.init_seed), d))
    d = meta["y_col"]  # original feature width inside the packed row
    if checkpoint_dir is None:
        w, accs = trainer.run(w0, 0, config.n_iterations)
        metrics.guard_finite(w, "streamed SSGD weights")
        return TrainResult(w=w[:d], accs=accs)

    from tpu_distalg.utils import checkpoint as ckpt

    def run_seg(seg_len, state, t0):
        w, accs = trainer.run(jnp.asarray(state["w"]), t0, seg_len,
                              acc0=float(np.asarray(state["acc"])))
        return ({"w": w, "acc": (accs[-1] if len(accs)
                                 else state["acc"])},
                np.asarray(accs))

    state, accs, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=lambda seg: seg,  # the "compiled segment" is its length
        run_seg=run_seg,
        state0={"w": w0, "acc": jnp.float32(0.0)}, tag="ssgd_stream")
    return TrainResult(w=jnp.asarray(state["w"])[:d],
                       accs=jnp.asarray(accs))
