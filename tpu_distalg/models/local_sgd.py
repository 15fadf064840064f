"""Shared local-update harness for the periodic-averaging optimizer family.

MA (``/root/reference/optimization/ma.py``), BMUF (``bmuf.py``) and EASGD
(``easgd.py``) share one machinery (SURVEY.md §2.1 rows 3-5): per-replica
local models take minibatch-SGD steps on their own shard, then a global
round combines them. The reference keeps per-replica models as a keyed RDD
joined against sampled points (``ma.py:99-102``) and runs one Spark job per
round; here each replica's local loop is a ``lax.scan`` *inside* a
``shard_map`` body — local steps never touch the interconnect, and only the
round-level combine is a collective, exactly mirroring the reference's
job-per-round boundary (SURVEY.md §3.2).

Semantics quirks reproduced behind flags (SURVEY.md §7 hard part #6):
  * the reference reuses the SAME minibatch for all 5 local steps of a round
    (seed ``42+t`` inside the local loop, ``ma.py:98-99``) — default;
    ``resample_per_local_step=True`` gives each local step a fresh draw;
  * BMUF's block-momentum ``delta_w`` is initialised *random*, not zero
    (``bmuf.py:95``) — ``random_delta_init`` flag;
  * EASGD does NOT resync local models to the center each round
    (``easgd.py:95-106`` has no resync line, unlike ``ma.py:96``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpu_distalg.ops import logistic, sampling
from tpu_distalg.parallel import (
    DATA_AXIS,
    data_parallel,
    mesh_on_tpu,
    parallelize,
    tree_allreduce_mean,
)
from tpu_distalg.utils import metrics, prng


@dataclasses.dataclass(frozen=True)
class LocalSGDConfig:
    """Knob names follow ``ma.py:19-23`` / ``bmuf.py:19-25`` /
    ``easgd.py:19-25``."""

    n_iterations: int = 300          # global rounds
    n_local_iterations: int = 5      # local steps per round
    eta: float = 0.1
    mini_batch_fraction: float = 0.1
    # round-level combine: 'average' (MA) | 'bmuf' | 'easgd'
    global_update: str = "average"
    resync: bool = True              # broadcast center to replicas each round
    elastic_alpha: float = 0.0       # EASGD α = η·ρ (easgd.py:24)
    mu: float = 0.9                  # BMUF momentum (bmuf.py:24)
    zeta: float = 0.1                # BMUF block learning rate (bmuf.py:25)
    beta: float | None = None        # EASGD center rate; None → n_replicas·α
    resample_per_local_step: bool = False
    random_delta_init: bool = True   # BMUF delta_w ~ U[-1,1) (bmuf.py:95)
    seed: int = 42
    init_seed: int = 7
    eval_test: bool = True
    # TPU perf knobs (not in the reference) — the flagship SSGD treatment
    # applied to the local-update family. 'bernoulli' = XLA mask over all
    # rows (reference sample() semantics); 'fused_gather' = the packed
    # traffic-proportional Pallas kernel: each replica's local step DMAs
    # only its sampled gather_block_rows-row blocks (same grad_sum
    # contract, block-cluster sampling — see ssgd.SAMPLERS);
    # 'fused_train' = 'fused_gather' with each round's n_local steps
    # fused into ONE megakernel launch per replica (weights in VMEM,
    # update + elastic pull in-kernel). Unlike SSGD's megakernel this
    # composes with dp>1 — local steps touch no interconnect; the
    # round-end pmean is unchanged.
    sampler: str = "bernoulli"
    x_dtype: str = "float32"
    fused_pack: int = 16
    gather_block_rows: int = 1024
    shuffle_seed: int | None = None
    # round-combine sync schedule (parallel/comms.py): 'dense' (bitwise
    # the pre-comms pmean — the default), 'bucketed', 'hier', 'bf16',
    # 'int8' (native int8 wire), 'topk[:frac]' (error-feedback
    # residuals in the scan state). bucketed/int8 run the
    # double-buffered bucket overlap pipeline by default ('@seq'
    # disables — bitwise-identical either way; a no-op for the
    # single-bucket topk/hier). The ONE collective of
    # this family is the round-end model average, so every sampler
    # (megakernel included) composes with it.
    comm: str = "dense"
    # synchronization discipline (parallel/ssp.py): 'bsp' (lock-step
    # round combine — bitwise the pre-SSP trainer, the default) or
    # 'ssp[:s[:decay]]': the combine runs once per s-round window,
    # replicas straggled by the seeded 'shard:straggle' plan skip
    # rounds instead of stalling the mesh, and the merge is a
    # STALENESS-WEIGHTED model average (weight decay**windows-stale)
    # feeding the usual MA/BMUF/EASGD center update. 'shard:leave'
    # plan rules drive elastic membership epochs. Composes with the
    # 'bernoulli' sampler; the fused kernels stay BSP.
    sync: str = "bsp"


@dataclasses.dataclass
class TrainResult:
    w: jax.Array
    ws: jax.Array  # final per-replica models (n_replicas, D)
    accs: jax.Array

    @property
    def final_acc(self) -> float:
        return float(self.accs[-1])


def _make_local_rounds(config: LocalSGDConfig, sync=None):
    """shard_map body: resync (maybe), run L local steps on the local
    shard, then pmean the round's model average across replicas — the
    ``treeAggregate``/n combine (``ma.py:104-106``) as ONE collective
    over the data axis, so the center update needs no gather.

    With ``sync`` (a ``comms.CommSync``) the round-end average runs the
    comm schedule instead of the raw pmean, and the body threads the
    flat error-feedback residual ``res`` + absolute round id ``t``."""

    def local_steps(X, y, masks, ws_local, w):
        # X (rows, D) local block; masks (L, rows); ws_local (1, D); w (D,)
        w_l = w if config.resync else ws_local[0]

        def local_step(w_l, mask):
            g_sum, cnt = logistic.grad_sum(X, y, w_l, mask)
            g_mean = g_sum / jnp.maximum(cnt, 1.0)  # update_local_w ma.py:39-43
            w_l = (
                w_l
                - config.eta * g_mean
                - config.elastic_alpha * (w_l - w)  # easgd.py:41-45
            )
            return w_l, None

        w_l, _ = jax.lax.scan(local_step, w_l, masks)
        return w_l

    if sync is None:
        def local_rounds(X, y, masks, ws_local, w):
            w_l = local_steps(X, y, masks, ws_local, w)
            return w_l[None, :], tree_allreduce_mean(w_l)

        return local_rounds

    def local_rounds_comm(X, y, masks, ws_local, w, t, res):
        w_l = local_steps(X, y, masks, ws_local, w)
        w_avg, res = sync.reduce_mean(w_l, res, t)
        return w_l[None, :], w_avg, res

    return local_rounds_comm


def _derive_beta(config: LocalSGDConfig, n_replicas: int) -> float:
    return (config.beta if config.beta is not None
            else n_replicas * config.elastic_alpha)  # easgd.py:25


def _comm_sync(mesh, config: LocalSGDConfig, d: int):
    """The round combine's CommSync: ONE (D,) leaf — the per-replica
    model being averaged (cf. ssgd's (grad, count) pair)."""
    import jax

    from tpu_distalg.parallel import comms

    return comms.make_sync(
        config.comm, mesh, jax.ShapeDtypeStruct((d,), jnp.float32))


def _make_combine(config: LocalSGDConfig, beta: float):
    """Round-level combine shared by the XLA and fused builders — the
    ONE place the MA/BMUF/EASGD center updates live, so the two sampler
    paths cannot drift apart. Returns ``(w, delta) = combine(w, w_avg,
    delta)``."""

    def combine(w, w_avg, delta):
        if config.global_update == "average":
            return w_avg, delta
        if config.global_update == "bmuf":
            delta = config.mu * delta + config.zeta * (w_avg - w)
            return w + delta, delta  # bmuf.py:113-114
        if config.global_update == "easgd":
            return (1 - beta) * w + beta * w_avg, delta  # easgd.py:106
        raise ValueError(config.global_update)

    return combine


def _check_sync_sampler(config: LocalSGDConfig) -> None:
    from tpu_distalg.parallel import ssp as pssp

    spec = pssp.SyncSpec.parse(config.sync)
    if spec.is_ssp and config.sampler != "bernoulli":
        raise ValueError(
            f"sync={config.sync!r} (stale-synchronous) composes with "
            f"the 'bernoulli' sampler — got sampler="
            f"{config.sampler!r}; the fused kernels stay BSP")


def make_ssp_train_fn(mesh: Mesh, config: LocalSGDConfig,
                      n_padded: int, d: int, *,
                      active: tuple[bool, ...], n_win_seg: int,
                      total_rounds: int):
    """SSP window scan for the local-update family: ``s`` ROUNDS of
    ``L`` local steps each between combines. A replica straggled by the
    seeded schedule skips the round (real interference compute runs
    instead); the window-end merge is a staleness-weighted MODEL
    average — every active replica's model enters with weight
    ``decay**windows_stale`` (0 = it worked this window and was free at
    the boundary) — feeding the usual MA/BMUF/EASGD center update. With
    ``resync``, replicas adopt the fresh center at the window start
    unless straggled there (a busy replica keeps its stale model — that
    IS the staleness being weighted).

    Call as ``fn(X, y, valid, X_test, y_test, w0, ws0, delta0,
    clocks0, stale0, res0, extra_seg, win0)``; returns ``(w, ws,
    delta, clocks, stale, res, win_accs, ages_max, ages_mean,
    gated)``."""
    import numpy as np

    from jax import lax

    from tpu_distalg.parallel import comms
    from tpu_distalg.parallel import ssp as pssp

    spec = pssp.SyncSpec.parse(config.sync)
    s = spec.staleness
    L = config.n_local_iterations
    n_replicas = mesh.shape[DATA_AXIS]
    beta = _derive_beta(config, n_replicas)
    sync = _comm_sync(mesh, config, d)
    combine = _make_combine(config, beta)
    key = prng.root_key(config.seed)
    active_np = np.asarray(active, bool)
    big = jnp.int32(1 << 30)

    def window_body(X, y, masks, w, ws_local, clocks, stale, res,
                    extra, roundv, winid):
        my = lax.axis_index(DATA_AXIS)
        act = jnp.asarray(active_np)
        act_me = act[my]
        w_l = ws_local[0]
        # resync adoption at the window start — a replica straggled at
        # the boundary keeps its old model (the staleness the merge
        # weights); EASGD never resyncs (easgd.py:95-106)
        if config.resync:
            adopt = act & (extra[0] == 0)
        else:
            adopt = jnp.zeros_like(act)
        w_l = jnp.where(adopt[my], w, w_l)
        max_c = jnp.max(jnp.where(act, clocks, -big))
        clocks_adj = jnp.where(adopt, max_c, clocks)
        min_known = jnp.min(jnp.where(act, clocks_adj, big))

        def one_round(carry, xs):
            w_l, my_clock, gated_ct = carry
            masks_r, extra_r, rv = xs
            # pad rounds pay no interference (cf. ssgd's tick body)
            eu = jnp.where(rv, extra_r[my], 0)
            gated = (my_clock - min_known) >= jnp.int32(s)
            do = rv & act_me & (eu == 0) & jnp.logical_not(gated)
            dummy = pssp.straggle_work(eu, 1.0)

            def local_step(w_i, mask):
                g_sum, cnt = logistic.grad_sum(X, y, w_i, mask)
                g_mean = g_sum / jnp.maximum(cnt, 1.0)
                return (w_i - config.eta * g_mean
                        - config.elastic_alpha * (w_i - w)), None

            w_new, _ = jax.lax.scan(local_step, w_l, masks_r)
            w_l = pssp.entangle(
                jnp.where(do, w_new, w_l), dummy)
            my_clock = my_clock + do.astype(clocks.dtype)
            gated_ct = gated_ct + (rv & act_me & gated).astype(
                jnp.int32)
            return (w_l, my_clock, gated_ct), None

        (w_l, my_clock, my_gated), _ = lax.scan(
            one_round, (w_l, clocks_adj[my], jnp.int32(0)),
            (masks, extra, roundv))

        clocks_new = comms.psum(
            jnp.zeros_like(clocks).at[my].set(my_clock))
        gated = comms.psum(my_gated)
        stepped = clocks_new > clocks_adj
        fresh = act & stepped & jnp.logical_not(extra[-1] > 0)
        stale_new = jnp.where(fresh, 0, stale + 1)
        wts = pssp.staleness_weights(
            stale_new, act, act, spec.decay)
        wsum = jnp.sum(wts)
        contrib = wts[my] * w_l
        (contrib,), res_new = sync.reduce((contrib,), res, winid)
        w_avg = contrib / jnp.maximum(wsum, jnp.float32(1e-12))
        ages_obs = jnp.where(act, stale_new, 0)
        n_act = jnp.sum(act.astype(jnp.float32))
        ages_max = jnp.max(ages_obs).astype(jnp.float32)
        ages_mean = (jnp.sum(ages_obs.astype(jnp.float32))
                     / jnp.maximum(n_act, 1.0))
        return (w_l[None], w_avg, clocks_new, stale_new, res_new,
                ages_max, ages_mean, gated)

    window_fn = data_parallel(
        window_body, mesh,
        in_specs=(
            P("data", None),        # X rows
            P("data"),              # y
            P(None, None, "data"),  # masks (s, L, rows)
            P(),                    # center w
            P("data", None),        # per-replica models (R, D)
            P(), P(),               # clocks, stale (replicated)
            P("data", None),        # error-feedback residual
            P(), P(), P(),          # extra (s, S), round validity, win
        ),
        out_specs=(P("data", None), P(), P(), P(), P("data", None),
                   P(), P(), P()),
    )

    def round_masks(valid, t):
        if config.resample_per_local_step:
            draws = [
                sampling.bernoulli_mask(
                    key, t * L + li, n_padded,
                    config.mini_batch_fraction, valid)
                for li in range(L)
            ]
            return jnp.stack(draws)
        mask = sampling.bernoulli_mask(
            key, t, n_padded, config.mini_batch_fraction, valid)
        return jnp.broadcast_to(mask, (L, n_padded))

    def train(X, y, valid, X_test, y_test, w0, ws0, delta0, clocks0,
              stale0, res0, extra_seg, win0):
        def win_step(carry, xs):
            w, ws, delta, clocks, stale, res = carry
            i, extra_w = xs
            winid = (win0 + i).astype(jnp.int32)
            ts = winid * s + jnp.arange(s)
            masks = jax.vmap(lambda t: round_masks(valid, t))(ts)
            roundv = ts < total_rounds
            (ws, w_avg, clocks, stale, res, amax, amean,
             gated) = window_fn(X, y, masks, w, ws, clocks, stale,
                                res, extra_w, roundv, winid)
            w, delta = combine(w, w_avg, delta)
            acc = (metrics.binary_accuracy(X_test @ w, y_test)
                   if config.eval_test else jnp.float32(0))
            return ((w, ws, delta, clocks, stale, res),
                    (acc, amax, amean, gated))

        carry, (accs, amax, amean, gated) = jax.lax.scan(
            win_step, (w0, ws0, delta0, clocks0, stale0, res0),
            (jnp.arange(n_win_seg), extra_seg))
        return (*carry, accs, amax, amean, gated)

    return jax.jit(train)


def _train_ssp(
    X_train, y_train, X_test, y_test, mesh: Mesh,
    config: LocalSGDConfig,
    *,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 100,
) -> TrainResult:
    """SSP driver for the local-update family — the ssgd driver's
    shape over (w, ws, delta, clocks, stale, res) state, elastic via
    :func:`membership.run_elastic` (a resume at a different shard
    count re-derives per-replica state from the replicated center)."""
    import numpy as np

    from tpu_distalg.models.ssgd import window_accs_to_ticks
    from tpu_distalg.parallel import comms, membership, partition
    from tpu_distalg.parallel import ssp as pssp

    spec = pssp.SyncSpec.parse(config.sync)
    s = spec.staleness
    T = config.n_iterations
    D = X_train.shape[1]
    n_shards = int(mesh.shape[DATA_AXIS])
    Xs = parallelize(X_train, mesh, dtype=jnp.dtype(config.x_dtype))
    ys = parallelize(y_train, mesh)
    X_te, y_te = jnp.asarray(X_test), jnp.asarray(y_test)
    k_init = prng.root_key(config.init_seed)
    w0 = np.asarray(logistic.init_weights(
        jax.random.fold_in(k_init, 0), D), np.float32)
    ws0 = np.asarray(jax.random.uniform(
        jax.random.fold_in(k_init, 1), (n_shards, D),
        minval=-1.0, maxval=1.0), np.float32)
    if config.global_update == "bmuf" and config.random_delta_init:
        delta0 = np.asarray(jax.random.uniform(
            jax.random.fold_in(k_init, 2), (D,),
            minval=-1.0, maxval=1.0), np.float32)
    else:
        delta0 = np.zeros((D,), np.float32)
    n_win, padded = pssp.window_grid(T, s)
    extra = pssp.compile_straggle_schedule(padded, n_shards)
    extra[T:] = 0  # pad rounds don't exist: no interference, no busy
    extra = extra.reshape(n_win, s, n_shards)
    sync = _comm_sync(mesh, config, D)

    def fresh_shard_state(w_host):
        """Per-replica state derived from the replicated center — the
        renegotiation story: a rejoining replica starts at the center,
        residuals are re-zeroed (flushed into the last merge)."""
        w_host = np.asarray(w_host, np.float32)
        return (np.tile(w_host, (n_shards, 1)),
                np.asarray(sync.init_state()))

    def renegotiate(saved_leaves, saved_shards, start_win):
        del saved_shards, start_win
        w = np.asarray(saved_leaves[0], np.float32)
        ws_new, res_new = fresh_shard_state(w)
        return (w, ws_new,
                np.asarray(saved_leaves[2], np.float32),   # delta
                np.asarray(membership.redistribute_clocks(
                    saved_leaves[3], n_shards), np.int32),
                np.zeros((n_shards,), np.int32),           # stale
                res_new)

    def make_seg_fn(active, n_win_seg):
        return make_ssp_train_fn(
            mesh, config, Xs.n_padded, D, active=active,
            n_win_seg=n_win_seg, total_rounds=T)

    def on_epoch(state, prev, cur):
        """A shard re-entering the active set is CURRENT, not a
        straggler: its clock froze while it was away (history, not
        staleness), and for EASGD (resync=False) no in-program adopt
        exists to bump it — left alone, the frozen clock would become
        min_known and the gate would serialize the whole mesh onto the
        rejoiner. Its model's genuine staleness is still carried (and
        merge-weighted) by `stale`, which only resets once it does
        fresh work."""
        w, ws, delta, clocks, stale, res = state
        clocks = np.asarray(clocks, np.int32).copy()
        rejoined = [k for k in range(n_shards)
                    if cur.active[k] and not prev.active[k]]
        if rejoined:
            cont = [k for k in range(n_shards)
                    if cur.active[k] and prev.active[k]]
            top = int(clocks[cont].max()) if cont \
                else int(clocks.max())
            clocks[rejoined] = top
        return (w, ws, delta, clocks, stale, res)

    def run_seg(fn, state, win0, n_win_seg, epoch):
        del epoch
        # idempotent rule-table placement: device-resident state in
        # the table layout passes through untouched (the old
        # np.asarray + device_put spelling paid a host round trip
        # every segment); restored/renegotiated host leaves take one
        # H2D direct to their final layout
        st = partition.ensure(
            {"w": state[0] if isinstance(state[0], jax.Array)
             else np.asarray(state[0], np.float32),
             "ws": state[1],
             "delta": state[2] if isinstance(state[2], jax.Array)
             else np.asarray(state[2], np.float32),
             "clocks": state[3], "stale": state[4], "res": state[5]},
            "local_sgd", mesh)
        out = fn(Xs.data, ys.data, Xs.mask, X_te, y_te,
                 st["w"], st["ws"], st["delta"], st["clocks"],
                 st["stale"], st["res"],
                 jnp.asarray(extra[win0:win0 + n_win_seg]),
                 jnp.int32(win0))
        return out[:6], out[6:]

    # state layout: (w, ws, delta, clocks, stale, res)
    state0 = (w0, ws0, delta0, np.zeros((n_shards,), np.int32),
              np.zeros((n_shards,), np.int32),
              np.asarray(sync.init_state()))

    state, outs, start, epochs = membership.run_elastic(
        checkpoint_dir, max(1, checkpoint_every // s), n_win,
        n_shards, make_seg_fn=make_seg_fn, run_seg=run_seg,
        state0=state0, renegotiate=renegotiate, on_epoch=on_epoch,
        # spec.spec() in the tag: window indexing and merge weights
        # depend on (s, decay) — a different --sync must reject, not
        # silently reinterpret the saved window progress
        tag=(f"local_sgd:{spec.spec()}:{config.global_update}"
             f":comm={config.comm}"),
        ticks_per_window=s)

    w = jnp.asarray(np.asarray(state[0], np.float32))
    ws = jnp.asarray(np.asarray(state[1], np.float32))
    metrics.guard_finite((w, ws), "local-SGD (ssp) models")
    accs = window_accs_to_ticks(outs[0], s, T) if outs \
        else np.zeros((T,), np.float32)
    stats = pssp.observed_staleness(
        outs[1] if outs else [], outs[2] if outs else [])
    pssp.emit_ssp_counters(
        spec, stats,
        straggle_ticks=int(np.count_nonzero(extra)),
        gated_ticks=int(np.asarray(outs[3]).sum()) if outs else 0,
        epochs=len(epochs))
    comms.emit_sync_counters(sync, n_win - start)
    return TrainResult(w=w, ws=ws, accs=jnp.asarray(accs))


def make_train_fn(mesh: Mesh, config: LocalSGDConfig, n_padded: int,
                  *, d: int | None = None):
    """Build the jitted round scan. With ``config.comm != 'dense'``
    pass ``d`` (model width); the returned fn is then called as
    ``fn(X, y, valid, X_test, y_test, w0, ws0, delta0, res0, t0=0)`` →
    ``(w, ws, delta, res, accs)``."""
    n_replicas = mesh.shape[DATA_AXIS]
    beta = _derive_beta(config, n_replicas)
    L = config.n_local_iterations
    key = prng.root_key(config.seed)

    sync = None
    if config.comm != "dense":
        if d is None:
            raise ValueError(
                f"comm={config.comm!r} needs the model width: call "
                "make_train_fn(mesh, config, n_padded, d=D) "
                "(local_sgd.train does this for you)"
            )
        sync = _comm_sync(mesh, config, d)
        local_fn = data_parallel(
            _make_local_rounds(config, sync),
            mesh,
            in_specs=(
                P("data", None),   # X rows
                P("data"),         # y
                P(None, "data"),   # masks (L, rows)
                P("data", None),   # per-replica models (R, D)
                P(),               # center w
                P(),               # absolute round id
                P("data", None),   # error-feedback residual (R, E)
            ),
            out_specs=(P("data", None), P(), P("data", None)),
        )
    else:
        local_fn = data_parallel(
            _make_local_rounds(config),
            mesh,
            in_specs=(
                P("data", None),   # X rows
                P("data"),         # y
                P(None, "data"),   # masks (L, rows)
                P("data", None),   # per-replica models (R, D) → (1, D) local
                P(),               # center w
            ),
            out_specs=(P("data", None), P()),
        )

    def round_masks(valid, t):
        if config.resample_per_local_step:
            draws = [
                sampling.bernoulli_mask(
                    key, t * L + l, n_padded,
                    config.mini_batch_fraction, valid,
                )
                for l in range(L)
            ]
            return jnp.stack(draws)
        # reference parity: one draw per round, reused by every local step
        # (sample(False, frac, 42+t) inside the local loop, ma.py:98-99)
        mask = sampling.bernoulli_mask(
            key, t, n_padded, config.mini_batch_fraction, valid
        )
        return jnp.broadcast_to(mask, (L, n_padded))

    combine = _make_combine(config, beta)

    if sync is not None:
        def train(X, y, valid, X_test, y_test, w0, ws0, delta0, res0,
                  t0=0):
            def round_step(carry, t):
                w, ws, delta, res = carry
                masks = round_masks(valid, t)
                ws, w_avg, res = local_fn(X, y, masks, ws, w, t, res)
                w, delta = combine(w, w_avg, delta)
                acc = (
                    metrics.binary_accuracy(X_test @ w, y_test)
                    if config.eval_test
                    else jnp.float32(0)
                )
                return (w, ws, delta, res), acc

            (w, ws, delta, res), accs = jax.lax.scan(
                round_step, (w0, ws0, delta0, res0),
                jnp.arange(config.n_iterations) + t0,
            )
            return w, ws, delta, res, accs

        return jax.jit(train)

    def train(X, y, valid, X_test, y_test, w0, ws0, delta0, t0=0):
        def round_step(carry, t):
            w, ws, delta = carry
            masks = round_masks(valid, t)
            ws, w_avg = local_fn(X, y, masks, ws, w)
            w, delta = combine(w, w_avg, delta)
            acc = (
                metrics.binary_accuracy(X_test @ w, y_test)
                if config.eval_test
                else jnp.float32(0)
            )
            return (w, ws, delta), acc

        # absolute round ids (t0 offset): segmented checkpoint/resume
        # draws identical minibatch masks to a straight-through run
        (w, ws, delta), accs = jax.lax.scan(
            round_step, (w0, ws0, delta0),
            jnp.arange(config.n_iterations) + t0,
        )
        return w, ws, delta, accs

    return jax.jit(train)


def make_train_fn_fused(mesh: Mesh, config: LocalSGDConfig, meta: dict):
    """Fused-kernel local rounds: every replica's local step runs the
    traffic-proportional gathered Pallas kernel on ITS OWN packed shard
    (``pallas_kernels.fused_grad_sum_gathered`` — the same one-HBM-pass
    kernel as SSGD's flagship sampler; the local step's ``grad_sum``
    contract is identical, only the combine differs). Local steps touch
    no interconnect; the round-end pmean is the only collective —
    exactly the reference's job-per-round boundary (``ma.py:98-106``).

    All (round, local-step, shard) block draws happen in one batched
    threefry before the scan; the id array is sharded over the data axis
    so each replica carries only its own draw column.
    """
    import functools

    from tpu_distalg.models.ssgd import fused_gather_geometry
    from tpu_distalg.ops import pallas_kernels

    on_tpu = mesh_on_tpu(mesh)
    d_t = meta["d_total"]
    col_keep = (jnp.arange(d_t) < meta["y_col"]).astype(jnp.float32)
    n_shards = mesh.shape[DATA_AXIS]
    n_blocks, n_sampled = fused_gather_geometry(config, meta, n_shards)
    L = config.n_local_iterations
    beta = _derive_beta(config, n_replicas=n_shards)
    key = prng.root_key(config.seed)
    sync = (_comm_sync(mesh, config, d_t)
            if config.comm != "dense" else None)
    kern = functools.partial(
        pallas_kernels.fused_grad_sum_gathered,
        pack=meta["pack"], d_total=d_t, y_col=meta["y_col"],
        v_col=meta["v_col"],
        gather_block_rows=config.gather_block_rows,
        interpret=not on_tpu,
    )

    def prep_idx(ts):
        """(T, L, S, ns) sampled block ids via the shared
        without-replacement draw (``sampling.sample_block_ids``), keyed
        on (absolute round id, local-step index, shard); without
        resampling the one per-round draw is broadcast over L (reference
        parity: the same minibatch serves every local step of a round,
        ``ma.py:98-99``)."""
        from tpu_distalg.ops import sampling

        n_draws = L if config.resample_per_local_step else 1

        def draw_round(t):
            return jax.vmap(
                lambda l: sampling.sample_block_ids(
                    jax.random.fold_in(jax.random.fold_in(key, t), l),
                    n_shards, n_blocks, n_sampled,
                )
            )(jnp.arange(n_draws))

        idx = jax.vmap(draw_round)(ts)
        return jnp.broadcast_to(
            idx, (ts.shape[0], L, n_shards, n_sampled))

    if config.sampler == "fused_train":
        mega_kern = functools.partial(
            pallas_kernels.fused_train_gathered,
            pack=meta["pack"], d_total=d_t, y_col=meta["y_col"],
            v_col=meta["v_col"],
            gather_block_rows=config.gather_block_rows,
            eta=config.eta, alpha=config.elastic_alpha,
            interpret=not on_tpu,
        )

        def _local_models(X2, idx_round, ws_local, w):
            # X2 (n2_local, P·D); idx_round (L, 1, ns) — this shard's
            # draws. The whole L-step local loop is ONE megakernel
            # launch: weights live in VMEM, the SGD update and the
            # elastic pull run in-kernel (fused_train_gathered); the
            # center is fixed for the round, exactly easgd.py:41-45 /
            # ma.py:98-102 semantics
            w_l = w if config.resync else ws_local[0]
            pk = meta["pack"]
            wt = mega_kern(
                X2, jnp.tile(w_l, (pk,))[:, None], idx_round[:, 0, :],
                center_tile=jnp.tile(w, (pk,))[:, None],
            )
            return wt[:d_t, 0]
    else:
        def _local_models(X2, idx_round, ws_local, w):
            # X2 (n2_local, P·D); idx_round (L, 1, ns) — this shard's
            # draws
            w_l = w if config.resync else ws_local[0]

            def local_step(w_l, idx_l):
                g, cnt = kern(X2, w_l, idx_l[0])
                g_mean = (g * col_keep) / jnp.maximum(cnt, 1.0)
                w_l = (
                    w_l
                    - config.eta * g_mean
                    - config.elastic_alpha * (w_l - w)  # easgd.py:41-45
                )
                return w_l, None

            w_l, _ = jax.lax.scan(local_step, w_l, idx_round)
            return w_l

    if sync is not None:
        def local_rounds(X2, idx_round, ws_local, w, t, res):
            w_l = _local_models(X2, idx_round, ws_local, w)
            # the one collective of this family: the round-end average,
            # under the comm schedule with the residual threaded
            w_avg, res = sync.reduce_mean(w_l, res, t)
            return w_l[None, :], w_avg, res

        local_fn = data_parallel(
            local_rounds, mesh,
            in_specs=(
                P("data", None),          # packed rows
                P(None, "data", None),    # (L, S, ns) draws → (L, 1, ns)
                P("data", None),          # per-replica models
                P(),                      # center w
                P(),                      # absolute round id
                P("data", None),          # error-feedback residual
            ),
            out_specs=(P("data", None), P(), P("data", None)),
        )
    else:
        def local_rounds(X2, idx_round, ws_local, w):
            w_l = _local_models(X2, idx_round, ws_local, w)
            return w_l[None, :], tree_allreduce_mean(w_l)

        local_fn = data_parallel(
            local_rounds, mesh,
            in_specs=(
                P("data", None),          # packed rows
                P(None, "data", None),    # (L, S, ns) draws → (L, 1, ns)
                P("data", None),          # per-replica models
                P(),                      # center w
            ),
            out_specs=(P("data", None), P()),
        )

    combine = _make_combine(config, beta)

    if sync is not None:
        def train(X2, X_test, y_test, w0, ws0, delta0, res0, t0=0):
            ts = jnp.arange(config.n_iterations) + t0
            idx_all = prep_idx(ts)                # (T, L, S, ns)

            def round_step(carry, x):
                t, idx_round = x
                w, ws, delta, res = carry
                ws, w_avg, res = local_fn(X2, idx_round, ws, w, t, res)
                w, delta = combine(w, w_avg, delta)
                acc = (
                    metrics.binary_accuracy(X_test @ w, y_test)
                    if config.eval_test
                    else jnp.float32(0)
                )
                return (w, ws, delta, res), acc

            (w, ws, delta, res), accs = jax.lax.scan(
                round_step, (w0, ws0, delta0, res0), (ts, idx_all)
            )
            return w, ws, delta, res, accs

        return jax.jit(train)

    def train(X2, X_test, y_test, w0, ws0, delta0, t0=0):
        ts = jnp.arange(config.n_iterations) + t0
        idx_all = prep_idx(ts)                    # (T, L, S, ns)

        def round_step(carry, idx_round):
            w, ws, delta = carry
            ws, w_avg = local_fn(X2, idx_round, ws, w)
            w, delta = combine(w, w_avg, delta)
            acc = (
                metrics.binary_accuracy(X_test @ w, y_test)
                if config.eval_test
                else jnp.float32(0)
            )
            return (w, ws, delta), acc

        (w, ws, delta), accs = jax.lax.scan(
            round_step, (w0, ws0, delta0), idx_all
        )
        return w, ws, delta, accs

    return jax.jit(train)


def prepare_fused(X_train, y_train, mesh: Mesh, config: LocalSGDConfig):
    """One-time setup for the fused sampler (mirrors
    ``ssgd.prepare_fused``): pack (X, y, validity) into the kernel
    layout, shard over the data axis, build augmented initial state and
    the jitted round scan. Returns ``(fn, X2, w0, ws0, delta0, meta)``;
    call as ``fn(X2, X_test_padded, y_test, w0, ws0, delta0)``."""
    import numpy as np

    from tpu_distalg.ops import pallas_kernels
    from tpu_distalg.parallel import partition

    n_shards = mesh.shape[DATA_AXIS]
    D = X_train.shape[1]
    n = X_train.shape[0]
    X2, meta = pallas_kernels.pack_augmented(
        np.asarray(X_train), np.asarray(y_train), np.ones(n, np.float32),
        dtype=jnp.dtype(config.x_dtype),
        pack=config.fused_pack,
        block_rows=config.gather_block_rows * n_shards,
        shuffle_seed=config.shuffle_seed,
    )
    X2 = partition.put(X2, "X2", "local_sgd", mesh)
    d_t = meta["d_total"]
    n_replicas = n_shards
    k_init = prng.root_key(config.init_seed)
    w0 = jnp.zeros((d_t,), jnp.float32).at[:D].set(
        logistic.init_weights(jax.random.fold_in(k_init, 0), D)
    )
    # per-replica init ~ U[-1,1) in the true columns (ma.py:86); the
    # y/v/pad columns stay zero forever (zeroed grad, zero elastic pull)
    ws0 = jnp.zeros((n_replicas, d_t), jnp.float32).at[:, :D].set(
        jax.random.uniform(
            jax.random.fold_in(k_init, 1), (n_replicas, D),
            minval=-1.0, maxval=1.0,
        )
    )
    if config.global_update == "bmuf" and config.random_delta_init:
        delta0 = jnp.zeros((d_t,), jnp.float32).at[:D].set(
            jax.random.uniform(
                jax.random.fold_in(k_init, 2), (D,),
                minval=-1.0, maxval=1.0,
            )
        )
    else:
        delta0 = jnp.zeros((d_t,))
    fn = make_train_fn_fused(mesh, config, meta)
    return fn, X2, w0, ws0, delta0, meta


def _train_comm(mesh, config: LocalSGDConfig, d, data_args, w0, ws0,
                delta0, *, make_fn, checkpoint_dir, checkpoint_every,
                tag, crop, fn=None):
    """Comm-schedule round driver shared by the XLA and fused paths:
    the carry/checkpoint state is ``(w, ws, delta, residual)`` — the
    error-feedback residual is per-replica like ``ws`` and persists
    across segments for bitwise resume."""
    from tpu_distalg.parallel import comms, partition
    from tpu_distalg.utils import metrics as _metrics

    sync = _comm_sync(mesh, config, d)
    res0 = partition.put(sync.init_state(), "res", "local_sgd", mesh)

    if checkpoint_dir is None:
        fn = fn if fn is not None else make_fn(config.n_iterations)
        w, ws, _, _, accs = fn(*data_args, w0, ws0, delta0, res0)
        comms.emit_sync_counters(sync, config.n_iterations)
        _metrics.guard_finite((w, ws), "local-SGD models")
        return TrainResult(w=w[:crop], ws=ws[:, :crop], accs=accs)

    from tpu_distalg.utils import checkpoint as ckpt

    def run_seg(seg_fn, state, t0):
        w, ws, delta, res = state
        ws = partition.put(ws, "ws", "local_sgd", mesh)
        res = partition.put(res, "res", "local_sgd", mesh)
        w, ws, delta, res, accs = seg_fn(
            *data_args, jnp.asarray(w), ws, jnp.asarray(delta), res,
            t0=t0)
        return (w, ws, delta, res), accs

    (w, ws, delta, res), accs, start = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=make_fn, run_seg=run_seg,
        state0=(w0, ws0, delta0, res0),
        tag=f"{tag}:comm={config.comm}",
    )
    # only the rounds THIS process ran (resume skips the rest)
    comms.emit_sync_counters(sync, config.n_iterations - start)
    return TrainResult(
        w=jnp.asarray(w)[:crop], ws=jnp.asarray(ws)[:, :crop],
        accs=jnp.asarray(accs),
    )


def _train_fused(
    X_train, y_train, X_test, y_test, mesh: Mesh,
    config: LocalSGDConfig,
    *,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 100,
) -> TrainResult:
    import numpy as np

    D = X_train.shape[1]
    fn, X2, w0, ws0, delta0, meta = prepare_fused(
        X_train, y_train, mesh, config)
    X_te = jnp.asarray(
        np.pad(np.asarray(X_test, np.float32),
               ((0, 0), (0, meta["d_total"] - D)))
    )
    y_te = jnp.asarray(y_test)

    if config.comm != "dense":
        return _train_comm(
            mesh, config, meta["d_total"], (X2, X_te, y_te),
            w0, ws0, delta0,
            make_fn=lambda seg: make_train_fn_fused(
                mesh, dataclasses.replace(config, n_iterations=seg),
                meta),
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            tag=f"local_sgd:{config.global_update}:{config.sampler}",
            crop=D, fn=fn,
        )

    if checkpoint_dir is None:
        w, ws, _, accs = fn(X2, X_te, y_te, w0, ws0, delta0)
        metrics.guard_finite((w, ws), "local-SGD (fused) models")
        return TrainResult(w=w[:D], ws=ws[:, :D], accs=accs)

    from tpu_distalg.parallel import partition
    from tpu_distalg.utils import checkpoint as ckpt

    def run_seg(seg_fn, state, t0):
        w, ws, delta = state
        ws = partition.put(ws, "ws", "local_sgd", mesh)
        w, ws, delta, accs = seg_fn(
            X2, X_te, y_te, jnp.asarray(w), ws, jnp.asarray(delta),
            t0=t0,
        )
        return (w, ws, delta), accs

    (w, ws, delta), accs, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=lambda seg: make_train_fn_fused(
            mesh, dataclasses.replace(config, n_iterations=seg), meta),
        run_seg=run_seg,
        state0=(w0, ws0, delta0),
        tag=f"local_sgd:{config.global_update}:{config.sampler}",
    )
    return TrainResult(
        w=jnp.asarray(w)[:D], ws=jnp.asarray(ws)[:, :D],
        accs=jnp.asarray(accs),
    )


def train(
    X_train, y_train, X_test, y_test, mesh: Mesh,
    config: LocalSGDConfig = LocalSGDConfig(),
    *,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 100,
) -> TrainResult:
    """End-to-end local-update training; optionally checkpointed.

    With ``checkpoint_dir``, rounds run in compiled segments and the
    full carry ``(w, ws, delta)`` — center model, per-replica models and
    the BMUF momentum — is saved after each (same machinery as SSGD,
    ``utils.checkpoint.run_segmented``); segmented and straight-through
    runs are bitwise-identical because round PRNG keys use absolute
    round ids.
    """
    from tpu_distalg.models.ssgd import check_sampler
    from tpu_distalg.telemetry import events as tevents

    # progress mark: the heartbeat names this phase if a round wedges
    # (checkpointed runs also mark per segment inside run_segmented)
    tevents.mark(f"local_sgd:{config.global_update}", emit_event=False)
    check_sampler(config)
    _check_sync_sampler(config)
    from tpu_distalg.parallel import ssp as _pssp

    if _pssp.SyncSpec.parse(config.sync).is_ssp:
        return _train_ssp(
            X_train, y_train, X_test, y_test, mesh, config,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every)
    if config.sampler != "bernoulli":
        return _train_fused(
            X_train, y_train, X_test, y_test, mesh, config,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
        )
    Xs = parallelize(X_train, mesh, dtype=jnp.dtype(config.x_dtype))
    ys = parallelize(y_train, mesh)
    D = X_train.shape[1]
    n_replicas = mesh.shape[DATA_AXIS]
    k_init = prng.root_key(config.init_seed)
    w0 = logistic.init_weights(jax.random.fold_in(k_init, 0), D)
    # per-replica init ~ U[-1,1): ma.py:86 parallelize(2*ranf((n_slices,D+1))-1)
    ws0 = jax.random.uniform(
        jax.random.fold_in(k_init, 1), (n_replicas, D), minval=-1.0, maxval=1.0
    )
    if config.global_update == "bmuf" and config.random_delta_init:
        delta0 = jax.random.uniform(
            jax.random.fold_in(k_init, 2), (D,), minval=-1.0, maxval=1.0
        )
    else:
        delta0 = jnp.zeros((D,))
    X_te, y_te = jnp.asarray(X_test), jnp.asarray(y_test)

    if config.comm != "dense":
        return _train_comm(
            mesh, config, D,
            (Xs.data, ys.data, Xs.mask, X_te, y_te), w0, ws0, delta0,
            make_fn=lambda seg: make_train_fn(
                mesh, dataclasses.replace(config, n_iterations=seg),
                Xs.n_padded, d=D),
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            tag=f"local_sgd:{config.global_update}",
            crop=D,
        )

    if checkpoint_dir is None:
        fn = make_train_fn(mesh, config, Xs.n_padded)
        w, ws, _, accs = fn(
            Xs.data, ys.data, Xs.mask, X_te, y_te, w0, ws0, delta0,
        )
        metrics.guard_finite((w, ws), "local-SGD models")
        return TrainResult(w=w, ws=ws, accs=accs)

    from tpu_distalg.parallel import partition
    from tpu_distalg.utils import checkpoint as ckpt

    def run_seg(fn, state, t0):
        w, ws, delta = state
        # restored per-replica models arrive as host arrays — the
        # rule table re-shards them (one H2D direct to final layout)
        ws = partition.put(ws, "ws", "local_sgd", mesh)
        w, ws, delta, accs = fn(
            Xs.data, ys.data, Xs.mask, X_te, y_te,
            jnp.asarray(w), ws, jnp.asarray(delta), t0=t0,
        )
        return (w, ws, delta), accs

    (w, ws, delta), accs, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=lambda seg: make_train_fn(
            mesh, dataclasses.replace(config, n_iterations=seg),
            Xs.n_padded),
        run_seg=run_seg,
        state0=(w0, ws0, delta0),
        tag=f"local_sgd:{config.global_update}",
    )
    return TrainResult(
        w=jnp.asarray(w), ws=jnp.asarray(ws), accs=jnp.asarray(accs)
    )
