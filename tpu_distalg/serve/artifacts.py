"""Checkpoint → servable model: the artifact side of the serving layer.

The training workloads already persist their converged state through
``utils/checkpoint.py`` (tag + state leaves + CRC footer); this module
turns those files — or in-memory arrays — into :class:`ServedModel`\\ s
the :class:`~tpu_distalg.serve.server.Server` can answer requests from:

  * LR-family tags (``lr``/``ssgd``/``ma``/``bmuf``/``easgd``/
    ``local_sgd``): probability scoring, payload = one (d,) feature row;
  * ``kmeans_*``: nearest-center assignment, payload = one (dim,) point;
  * ``als``: top-k item recommendation, payload = one user id. The
    headline path: user factor rows × the item-factor matrix through
    the fused Pallas matmul+top-k kernel (``ops/pallas_topk.py``) — the
    full score vector never materializes in HBM — with the item factors
    sharded over the mesh MODEL axis (``parallel/sharding.py`` specs)
    and each shard's k candidates merged through the comms layer's ring
    pair exchange (``comms.ring_allgather``: ``8·B·k·(S−1)`` wire bytes
    per batch, vs ``4·B·N·(S−1)/S`` for the dense all-gather baseline
    kept as ``merge='dense'``).

Every predictor compiles ONE program at the server's fixed
``max_batch`` shape and pads every batch to it, so batched and
unbatched submissions run the identical compiled function — the
padding-inert / bitwise-reply contract the tests pin.

Artifact-load degradation: a checkpoint whose read is corrupted in
flight (the ``ckpt:read`` chaos seam, or a real torn read) is RE-READ
once — transient corruption never demotes the served model — and only
persistent corruption falls back through the quarantine path to an
older step, exactly like training resume does.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from tpu_distalg.telemetry import events as tevents

#: checkpoint tags whose first state leaf is a weight vector servable
#: as a logistic scorer
_LR_TAG_ROOTS = ("lr", "ssgd", "ma", "bmuf", "easgd", "local_sgd")


@dataclasses.dataclass
class ServedModel:
    """One servable model: ``make_predict(max_batch)`` builds (once per
    batch shape — the server uses exactly one) the padded-batch
    predictor ``predict(payloads) -> [reply, ...]`` that owns the
    jit-stable padding and the single per-batch host sync."""

    name: str
    kind: str                     # "lr" | "kmeans" | "als"
    make_predict: object
    source: str = "memory"
    meta: dict = dataclasses.field(default_factory=dict)
    _cache: dict = dataclasses.field(default_factory=dict, repr=False)

    def predictor(self, max_batch: int):
        fn = self._cache.get(max_batch)
        if fn is None:
            fn = self._cache[max_batch] = self.make_predict(max_batch)
        return fn

    def predict_batch(self, payloads, max_batch: int):
        return self.predictor(max_batch)(payloads)

    def predict_one(self, payload, max_batch: int):
        """Unbatched reference: one request through the SAME padded
        compiled program a full batch uses (the bitwise-equality
        contract's other half)."""
        return self.predict_batch([payload], max_batch)[0]


def _stack_pad(payloads, shape: tuple, dtype, max_batch: int,
               what: str) -> np.ndarray:
    """Stack per-request payloads into the fixed (max_batch, *shape)
    batch — zero rows pad the tail (inert: replies are sliced back to
    the true request count; every predictor is row-independent)."""
    if len(payloads) > max_batch:
        raise ValueError(
            f"{what}: batch of {len(payloads)} exceeds max_batch="
            f"{max_batch}")
    out = np.zeros((max_batch,) + shape, dtype)
    for r, p in enumerate(payloads):
        arr = np.asarray(p, dtype)
        if arr.shape != shape:
            raise ValueError(
                f"{what}: payload {r} has shape {arr.shape}, "
                f"want {shape}")
        out[r] = arr
    return out


# --------------------------------------------------------------- models


def lr_model(w, name: str = "lr", *, source: str = "memory"
             ) -> ServedModel:
    """Logistic scorer from a trained weight vector: reply = P(y=1)
    for one (d,) feature row."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import logistic

    w_dev = jnp.asarray(np.asarray(w), jnp.float32)
    d = int(w_dev.shape[0])

    def make_predict(max_batch: int):
        fn = jax.jit(lambda X: logistic.predict_proba(X, w_dev))

        def predict(payloads):
            X = _stack_pad(payloads, (d,), np.float32, max_batch,
                           f"lr:{name}")
            out = np.asarray(fn(X))  # the ONE host sync for this batch
            return [out[r] for r in range(len(payloads))]

        return predict

    return ServedModel(name=name, kind="lr", make_predict=make_predict,
                       source=source, meta={"d": d})


def kmeans_model(centers, name: str = "kmeans", *,
                 source: str = "memory") -> ServedModel:
    """Cluster assignment from trained centers: reply = nearest-center
    index (int32) for one (dim,) point."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import kmeans as kops

    c_dev = jnp.asarray(np.asarray(centers), jnp.float32)
    k, dim = int(c_dev.shape[0]), int(c_dev.shape[1])

    def make_predict(max_batch: int):
        fn = jax.jit(lambda X: kops.assign_clusters(X, c_dev))

        def predict(payloads):
            X = _stack_pad(payloads, (dim,), np.float32, max_batch,
                           f"kmeans:{name}")
            out = np.asarray(fn(X))
            return [out[r] for r in range(len(payloads))]

        return predict

    return ServedModel(name=name, kind="kmeans",
                       make_predict=make_predict, source=source,
                       meta={"k": k, "dim": dim})


def _true_rows(M: np.ndarray) -> int:
    """Count of leading rows up to the last non-zero one — recovers the
    TRUE item/user count from a checkpointed factor matrix whose tail
    was zero-padded for sharding (padded rows solve to exactly zero;
    a genuinely all-zero trained factor row is measure-zero)."""
    nz = np.flatnonzero(np.any(np.asarray(M) != 0, axis=1))
    return int(nz[-1]) + 1 if len(nz) else 0


def _true_rows_device(M) -> int:
    """:func:`_true_rows` for a device-resident factor matrix: the
    reduction runs on device and only the resulting SCALAR crosses to
    the host — the old spelling's ``np.asarray(M)`` gathered the whole
    matrix, defeating the device-side handoff."""
    import jax.numpy as jnp

    nz = jnp.any(M != 0, axis=1)
    last = jnp.max(jnp.where(nz, jnp.arange(M.shape[0]) + 1, 0))
    return int(last)


def als_model(U, V, mesh, *, k_top: int = 10, merge: str = "sparse",
              use_fused: bool | None = None, block_items: int = 1024,
              n_items: int | None = None, name: str = "als",
              source: str = "memory") -> ServedModel:
    """Top-k recommendation from ALS factors: payload = one user id
    (int scalar), reply = ``(scores (k_top,) f32, item_ids (k_top,)
    int32)`` in ``lax.top_k`` order.

    The item factors are sharded over the mesh MODEL axis: each shard
    scores only its (N/S, k) slice — through the fused Pallas
    matmul+top-k kernel on TPU (``use_fused=None`` auto-picks; the
    interpret-mode kernel cannot beat native XLA on hosts) — and the
    per-shard candidates merge via ``merge``:

      * ``'sparse'`` (default): ``comms.ring_allgather`` of each
        shard's (value, index) pairs — ``8·B·k_top·(S−1)`` wire bytes
        per batch — then a replicated two-key sort;
      * ``'dense'``: all-gather of the full local score blocks
        (``4·B·N·(S−1)/S`` wire bytes) then a global ``lax.top_k`` —
        the baseline the sparse accounting is measured against.

    ``n_items`` overrides the true catalogue size when the caller knows
    it; by default the zero-padded tail of V is detected and masked so
    padded items can never outscore real ones.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from tpu_distalg.ops import pallas_topk as pt
    from tpu_distalg.parallel import (
        MODEL_AXIS, comms, mesh_on_tpu, partition)

    if merge not in ("sparse", "dense"):
        raise ValueError(f"merge must be 'sparse' or 'dense', "
                         f"got {merge!r}")
    # device-resident factors (the in-memory train→serve handoff —
    # bench, chaos, a Server built on the training result) stay on
    # device: the train→serve layout change runs as a device-side
    # reshard below instead of the old np.asarray gather + re-put
    dev_in = isinstance(U, jax.Array) and isinstance(V, jax.Array)
    if dev_in:
        U = jnp.asarray(U, jnp.float32)
        V = jnp.asarray(V, jnp.float32)
    else:
        U = np.asarray(U, np.float32)
        V = np.asarray(V, np.float32)
    if U.shape[1] != V.shape[1]:
        raise ValueError(
            f"U {U.shape} vs V {V.shape}: factor ranks differ")
    if n_items is not None:
        n_true = int(n_items)
    elif dev_in:
        n_true = _true_rows_device(V)  # one scalar D2H, not a gather
    else:
        n_true = _true_rows(V)
    if not 0 < n_true <= V.shape[0]:
        raise ValueError(
            f"n_items={n_true} invalid for V with {V.shape[0]} rows")
    if k_top < 1:
        raise ValueError(f"k_top must be >= 1, got {k_top}")
    on_tpu = mesh_on_tpu(mesh)
    fused = on_tpu if use_fused is None else bool(use_fused)
    n_model = int(mesh.shape[MODEL_AXIS])
    # pad items so every model shard holds an equal slice; padded rows
    # are zero AND index-masked (>= n_true scores -inf) — doubly inert
    n_pad = -(-V.shape[0] // n_model) * n_model
    if n_pad != V.shape[0]:
        pad = ((0, n_pad - V.shape[0]), (0, 0))
        V = jnp.pad(V, pad) if dev_in else np.pad(V, pad)
    local_n = n_pad // n_model

    if dev_in:
        # the train-layout → serve-layout seam, device-side: U
        # all-gathers to replicated, V slices to its model shards —
        # the collective program arXiv:2112.01075 argues for, with
        # the wire bytes accounted in the reshard.* counters
        placed = partition.reshard({"U": U, "V": V},
                                   "als_train", "als_serve", mesh)
    else:
        # host factors (a disk artifact): one H2D per leaf direct to
        # the serve layout
        placed = partition.place({"U": U, "V": V}, "als_serve", mesh)
    U_dev, V_dev = placed["U"], placed["V"]

    def _score(q, Vl, off, nv):
        if fused:
            return pt.fused_matmul_topk(
                q, Vl, off, nv, k=k_top, block_items=block_items,
                interpret=not on_tpu)
        return pt.xla_matmul_topk(q, Vl, off, nv, k=k_top)

    if n_model == 1:
        def topk_fn(ids, Uq, Vl):
            return _score(Uq[ids], Vl, 0, n_true)

        fn = jax.jit(topk_fn)
        wire_per_req = 0
    elif merge == "sparse":
        def body(ids, Uq, Vl):
            off = lax.axis_index(MODEL_AXIS) * local_n
            nv = jnp.clip(n_true - off, 0, local_n)
            v, i = _score(Uq[ids], Vl, off, nv)
            all_v, all_i = comms.ring_allgather((v, i), MODEL_AXIS,
                                                n_model)
            return pt.merge_topk_pairs(all_v, all_i, k=k_top)

        # the ring pair exchange + origin-order merge IS replicated by
        # construction (every shard gathers the same pairs and sorts
        # identically); the static checker can't see through ppermute,
        # so the check is off — same call shape as spmd.data_parallel
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P(), P(MODEL_AXIS, None)),
            out_specs=(P(), P()), check_vma=False))
        wire_per_req = 8 * k_top * (n_model - 1)
    else:
        def body(ids, Uq, Vl):
            off = lax.axis_index(MODEL_AXIS) * local_n
            q = Uq[ids]
            scores = jnp.matmul(q, Vl.T)
            pos = jnp.arange(local_n, dtype=jnp.int32)[None, :] + off
            scores = jnp.where(pos < n_true, scores, -jnp.inf)
            full = lax.all_gather(scores, MODEL_AXIS, axis=1,
                                  tiled=True)
            vals, idx = lax.top_k(full, k_top)
            return vals, idx.astype(jnp.int32)

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P(), P(MODEL_AXIS, None)),
            out_specs=(P(), P()), check_vma=False))
        wire_per_req = 4 * n_pad * (n_model - 1) // n_model

    def make_predict(max_batch: int):
        wire_per_batch = wire_per_req * max_batch

        def predict(payloads):
            ids = _stack_pad(payloads, (), np.int32, max_batch,
                             f"als:{name}")
            vals, idx = jax.device_get(fn(ids, U_dev, V_dev))
            if wire_per_batch:
                tevents.counter("serve.merge_bytes_wire",
                                wire_per_batch)
            return [(vals[r], idx[r]) for r in range(len(payloads))]

        return predict

    return ServedModel(
        name=name, kind="als", make_predict=make_predict, source=source,
        meta={"n_items": n_true, "n_users": int(U.shape[0]),
              "rank": int(U.shape[1]), "k_top": k_top, "merge": merge,
              "fused": fused, "n_model": n_model,
              "merge_wire_bytes_per_request": wire_per_req})


# ------------------------------------------------------ checkpoint load


def _restore_with_reread(path: str):
    """Load the newest checkpoint, degrading gracefully: a corrupt READ
    (the ``ckpt:read`` seam flips bytes in flight) is re-read once —
    the file on disk is usually intact — and only persistent corruption
    falls back through the quarantine path to an older step."""
    from tpu_distalg.utils import checkpoint as ckpt

    try:
        return ckpt.restore(path)
    except ckpt.CorruptCheckpointError:
        tevents.counter("serve.artifact_reread")
        tevents.emit("serve_artifact_reread", path=path)
        try:
            return ckpt.restore(path)
        except ckpt.CorruptCheckpointError:
            out = ckpt.restore_newest_with_fallback(path)
            if out is None:
                raise FileNotFoundError(
                    f"no restorable checkpoint under {path}") from None
            return out


def load_artifact_state(path: str) -> tuple:
    """The jax-free half of :func:`load_artifact`: restore the newest
    checkpoint (with the re-read degradation), verify the tagged
    format, and return ``(tag_root, state_leaves, step)`` raw. The
    cluster serving replicas (``cluster/serve.py``) ride this — they
    score with host numpy kernels and must not pull a jax mesh into
    every replica process just to read weights."""
    payload, step = _restore_with_reread(path)
    if "tag" not in payload or "state" not in payload:
        raise ValueError(
            f"checkpoint under {path} predates the tagged format — "
            f"re-train with a current build to serve it")
    tag = np.asarray(payload["tag"]).tobytes().decode(errors="replace")
    state = [np.asarray(x) for x in payload["state"]]
    root = tag.split(":", 1)[0]
    tevents.emit("serve_artifact_loaded", path=path, tag=tag, step=step)
    return root, state, step


def load_artifact(path: str, mesh, *, name: str | None = None,
                  k_top: int = 10, merge: str = "sparse",
                  use_fused: bool | None = None,
                  block_items: int = 1024) -> ServedModel:
    """Open a training checkpoint directory as a :class:`ServedModel`,
    dispatching on the checkpoint's workload tag (the same tag
    ``run_segmented`` verifies on resume). The ``tda serve --artifact``
    path — pair it with the ``artifact_path:`` line the training CLIs
    print."""
    root, state, _step = load_artifact_state(path)
    if root in _LR_TAG_ROOTS:
        return lr_model(state[0], name=name or root, source=path)
    if root.startswith("kmeans"):
        return kmeans_model(state[0], name=name or "kmeans",
                            source=path)
    if root == "als":
        return als_model(state[0], state[1], mesh, k_top=k_top,
                         merge=merge, use_fused=use_fused,
                         block_items=block_items,
                         name=name or "als", source=path)
    raise ValueError(
        f"checkpoint under {path} holds workload {root!r} — no serving "
        f"adapter for it (servable: {', '.join(_LR_TAG_ROOTS)}, "
        f"kmeans_*, als)")
