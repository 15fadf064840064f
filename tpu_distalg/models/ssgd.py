"""SSGD — synchronous minibatch SGD (the north-star workload).

Re-design of ``/root/reference/optimization/ssgd.py``: per iteration the
reference Bernoulli-samples a minibatch (``sample(False, 0.1, 42+t)``,
``:97``), ships the model via broadcast, tree-aggregates the pair
``(Σ grad, count)`` (``:99-103``) and updates on the driver (``:105``) —
1500 Spark jobs for 1500 steps. Here the whole schedule is one XLA program:

  * the minibatch is a Bernoulli *mask* with static shape (SURVEY.md §7 hard
    part #2), drawn topology-independently from the partitionable PRNG;
  * the aggregation is one fused psum of the (gradient, count) pytree over
    the mesh data axis (ICI AllReduce, no driver);
  * the 1500-step loop is a ``lax.scan`` — zero host round-trips.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpu_distalg.ops import logistic, sampling
from tpu_distalg.parallel import (
    data_parallel,
    mesh_on_tpu,
    parallelize,
    tree_allreduce_sum,
)
from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import names
from tpu_distalg.utils import metrics, prng


# The ways to draw and score a mini-batch, stated once: SSGDConfig.sampler
# takes one of these, :func:`check_sampler` refuses any other name, and
# ``tda ssgd --sampler`` offers them (``cli.py``; the local-update family
# takes the same three).
#   'bernoulli'    the XLA reference: a reference-parity mask over ALL rows
#                  (sample() semantics, ssgd.py:97), two passes over a dense
#                  X. What the tests and tests_tpu/ compare the kernels
#                  with, and the one path every contract composes with:
#                  SSP, every comm schedule, the tp split's plain form.
#   'fused_gather' the traffic-proportional kernel: whole
#                  gather_block_rows-row blocks are drawn XLA-side and ONLY
#                  those blocks are copied (block-cluster sampling: i.i.d.
#                  per-row equivalent when rows are i.i.d. or pack-time
#                  shuffled), one launch and one psum a step. The mesh's
#                  path (cell lr30_400m_dp4) and the one that takes rows of
#                  indices (INDEX_ROW_FORMATS: cells lrhash39_46m_frac01,
#                  lrwide11_150m_frac01, lrpairs3728_350k_frac01), the comm
#                  schedules, SSP and feature_sharded.
#   'fused_train'  'fused_gather' with the WHOLE schedule fused into one
#                  kernel launch per mega_steps segment (weights live in
#                  VMEM, update runs in-kernel): fastest path (cells
#                  lr30_100m_bigbatch, lr30_100m_smallbatch), but
#                  single-data-shard only (no per-step psum), lam=0 only,
#                  eval at segment boundaries only.
# A table larger than HBM is not a sampler: models/ssgd_stream.train stages
# the sampled blocks of REAL bytes (host RAM / disk memmap) host→device per
# step, bitwise 'fused_gather' on a resident copy, and
# :func:`prepare_fused_synthetic` makes seeded rows on the device.
SAMPLERS = ("bernoulli", "fused_gather", "fused_train")


@dataclasses.dataclass(frozen=True)
class SSGDConfig:
    """Knob names follow ``ssgd.py:17-21``."""

    n_iterations: int = 1500
    eta: float = 0.1
    mini_batch_fraction: float = 0.1
    lam: float = 0.0
    reg_type: str = "l2"
    elastic_alpha: float = 0.0  # α of elastic_net (ssgd.py:46-47)
    seed: int = 42
    init_seed: int = 7
    eval_test: bool = True
    # evaluate test accuracy only every N steps (others report the last
    # computed value) — keeps convergence observable in benchmark-scale
    # runs without paying a test matvec per step
    eval_every: int = 1
    # TPU perf knobs (not in the reference):
    x_dtype: str = "float32"    # 'bfloat16' halves HBM traffic for X
    # one of SAMPLERS, which says what each is and who runs it.
    # Precision note: with x_dtype='bfloat16' the fused kernels cast the
    # residual AND the selector-replicated weights to bf16 (the XLA bf16
    # path keeps both f32) — a small extra deviation; convergence to the
    # reference band is verified on-TPU (tests_tpu/, chip_smoke.py)
    sampler: str = "bernoulli"
    fused_pack: int = 16        # rows packed per sublane row ('fused_*')
    gather_block_rows: int = 1024   # rows per sampled block ('fused_*')
    mega_steps: int = 125       # steps per kernel launch ('fused_train')
    shuffle_seed: int | None = None  # pack-time row shuffle ('fused_*')
    # shard the FEATURE dim over the mesh model axis (tensor parallelism):
    # the forward matvec psums partial X_l·w_l over 'model', the gradient
    # contraction psums over 'data' only, and w lives sharded P('model')
    feature_sharded: bool = False
    # gradient-sync schedule (parallel/comms.py): 'dense' (bitwise the
    # pre-comms psum — the default), 'bucketed' (ppermute-chunk ring),
    # 'hier' (reduce-scatter intra-group / ring across groups /
    # all-gather), 'bf16', 'int8' (NATIVE int8 ring: seeded stochastic
    # rounding, int8 on the wire in both phases), 'topk[:frac]'
    # (sparse_allreduce with error-feedback residuals carried in the
    # scan state). bucketed/int8 run the double-buffered bucket
    # OVERLAP pipeline by default — the exchange of bucket b hides
    # behind bucket b−1's unpack and the reg-gradient math; append
    # '@seq' (e.g. 'int8@seq') for the sequential A/B reference
    # (bitwise-identical, slower; a no-op for the single-bucket
    # topk/hier). Composes with samplers 'bernoulli' and
    # 'fused_gather'; the megakernel ('fused_train': no per-step
    # collective exists to compress) and feature_sharded reject
    # non-dense comm.
    comm: str = "dense"
    # synchronization discipline (parallel/ssp.py): 'bsp' (classic
    # lock-step, one collective per step — bitwise the pre-SSP trainer,
    # the default) or 'ssp[:s[:decay]]' (stale-synchronous: shards run
    # up to s steps ahead of the slowest peer, the gradient merge runs
    # once per s-tick window with staleness-weighted delayed-gradient
    # application, and a device-resident clock vector — combined via
    # the comms layer — gates only bound-violating shards, so a
    # straggler no longer serializes every step). Seeded
    # 'shard:straggle'/'shard:leave' fault-plan rules compile into the
    # deterministic straggler/membership schedules; same plan => a
    # bitwise-identical replay. SSP composes with the 'bernoulli' and
    # 'fused_gather' samplers and any --comm schedule; the megakernel
    # and feature_sharded stay BSP.
    sync: str = "bsp"


@dataclasses.dataclass
class TrainResult:
    w: jax.Array
    accs: jax.Array

    @property
    def final_acc(self) -> float:
        return float(self.accs[-1])


def _comm_sync(mesh, config, d: int):
    """The trainer's one :class:`~tpu_distalg.parallel.comms.CommSync`:
    built identically wherever it is needed (scan builder, train(),
    telemetry accounting) from the (Σ grad, count) sync pytree."""
    import jax

    from tpu_distalg.parallel import comms

    example = (jax.ShapeDtypeStruct((d,), jnp.float32),
               jax.ShapeDtypeStruct((), jnp.float32))
    return comms.make_sync(config.comm, mesh, example)


def _ssp_comm_sync(mesh, config, d: int):
    """The SSP merge's CommSync: ONE (D,) leaf — the staleness-weighted
    delta contribution (the clock vector rides a separate dense psum;
    integer clocks must stay exact under every schedule)."""
    import jax

    from tpu_distalg.parallel import comms

    return comms.make_sync(
        config.comm, mesh, jax.ShapeDtypeStruct((d,), jnp.float32))


def _eval_acc(config: SSGDConfig, w, t, last_acc, X_test, y_test):
    """A step's test accuracy: every step, every ``eval_every`` steps
    (the last one carried between), or never."""
    if not config.eval_test:
        return jnp.float32(0)
    if config.eval_every == 1:
        return metrics.binary_accuracy(X_test @ w, y_test)
    return jax.lax.cond(
        t % config.eval_every == 0,
        lambda w: metrics.binary_accuracy(X_test @ w, y_test),
        lambda w: last_acc,
        w,
    )


def _build_scan_comm(config: SSGDConfig, sample_and_grad, prep_xs=None):
    """Comm-schedule variant of :func:`_build_scan`:
    ``sample_and_grad(X, y, valid, w, payload, t, res)`` → (Σ grad,
    count, res', reg); the flat error-feedback residual rides in the
    scan carry (zero-width for stateless schedules) and is returned so
    checkpointed runs can persist it — a dropped residual would silently
    void the top-k convergence correction. ``reg`` is the
    regularization gradient, computed INSIDE the sync's overlap window
    (``sync.reduce(..., compute=...)``): it is the step's one piece of
    update math independent of the reduced gradient, so the comm layer
    schedules the exchange's wire time behind it."""
    if config.eval_every < 1:
        raise ValueError(
            f"eval_every must be >= 1, got {config.eval_every}"
        )

    def train(X, y, valid, X_test, y_test, w0, res0, t0=0, acc0=0.0):
        ts = jnp.arange(config.n_iterations) + t0
        xs = (ts, prep_xs(ts)) if prep_xs is not None else (ts, ts)

        def step(carry, x):
            w, last_acc, res = carry
            t, payload = x
            g, cnt, res, reg = sample_and_grad(
                X, y, valid, w, payload, t, res)
            with jax.named_scope(names.SSGD_UPDATE):
                n_batch = jnp.maximum(cnt, 1.0)  # guard empty sample
                w = w - config.eta * (g / n_batch + config.lam * reg)
                acc = _eval_acc(config, w, t, last_acc, X_test, y_test)
            return (w, acc, res), acc

        (w, _, res), accs = jax.lax.scan(
            step, (w0, jnp.float32(acc0), res0), xs
        )
        return w, accs, res

    return jax.jit(train)


def _build_scan(config: SSGDConfig, sample_and_grad, prep_xs=None):
    """Shared step/scan builder: ``sample_and_grad(X, y, valid, w, x)`` →
    global (Σ grad, count); update rule and eval are identical for every
    sampler (``ssgd.py:105`` semantics).

    ``prep_xs(ts)`` (optional) maps the absolute step ids to the per-step
    scan inputs — used by 'fused_gather' to draw EVERY step's sampled
    block ids in one batched PRNG call before the scan (per-step
    ``jax.random`` traffic inside a scan costs more than the minibatch
    gradient itself at small batch sizes)."""

    if config.eval_every < 1:
        raise ValueError(
            f"eval_every must be >= 1, got {config.eval_every}"
        )

    def train(X, y, valid, X_test, y_test, w0, t0=0, acc0=0.0):
        # absolute step ids (t0 offset): segmented checkpoint/resume runs
        # sample identical minibatches to a straight-through run; acc0
        # carries the last computed accuracy across segment boundaries
        # when eval_every > 1
        ts = jnp.arange(config.n_iterations) + t0
        xs = (ts, prep_xs(ts)) if prep_xs is not None else (ts, ts)

        def step(carry, x):
            w, last_acc = carry
            t, payload = x
            g, cnt = sample_and_grad(X, y, valid, w, payload)
            with jax.named_scope(names.SSGD_UPDATE):
                n_batch = jnp.maximum(cnt, 1.0)  # guard empty sample
                reg = logistic.reg_gradient(
                    w, config.reg_type, config.elastic_alpha
                )
                w = w - config.eta * (
                    g / n_batch + config.lam * reg)  # ssgd.py:105
                acc = _eval_acc(config, w, t, last_acc, X_test, y_test)
            return (w, acc), acc

        (w, _), accs = jax.lax.scan(
            step, (w0, jnp.float32(acc0)), xs
        )
        return w, accs

    return jax.jit(train)


def make_train_fn(mesh: Mesh, config: SSGDConfig, n_padded: int,
                  *, d: int | None = None):
    """Build the jitted scan over ``n_iterations`` SSGD steps.

    With ``config.comm != 'dense'`` the gradient sync runs the
    comm-schedule path: pass ``d`` (the feature width, i.e. ``w``'s
    length — the comm layer sizes its residual/byte accounting off it)
    and call the returned fn as ``fn(X, y, valid, X_test, y_test, w0,
    res0, t0=0, acc0=0.0)`` → ``(w, accs, res)``."""
    check_sampler(config)
    if config.sampler != "bernoulli":
        raise ValueError(
            f"sampler={config.sampler!r} packs labels into X — build via "
            "make_train_fn_fused(mesh, config, meta) with meta from "
            "pallas_kernels.pack_augmented (feature_sharded: "
            "make_train_fn_fused_tp), or use ssgd.train()"
        )
    _check_comm_sampler(config)
    if config.feature_sharded:
        return _make_train_fn_tp(mesh, config, n_padded)
    if config.comm != "dense":
        return _make_train_fn_comm(mesh, config, n_padded, d)

    def _local_grad(X, y, mask, w):
        g, cnt = logistic.grad_sum(X, y, w, mask)
        return tree_allreduce_sum((g, cnt))

    grad_fn = data_parallel(
        _local_grad,
        mesh,
        in_specs=(P("data", None), P("data"), P("data"), P()),
        out_specs=(P(), P()),
    )
    key = prng.root_key(config.seed)

    def sample_and_grad(X, y, valid, w, t):
        mask = sampling.bernoulli_mask(
            key, t, n_padded, config.mini_batch_fraction, valid
        )
        return grad_fn(X, y, mask, w)

    return _build_scan(config, sample_and_grad)


def check_sampler(config) -> None:
    """The one refusal of a sampler's name, made by every public entry
    (the local-update family's too) before any check of a format, a
    mesh or a schedule."""
    if config.sampler not in SAMPLERS:
        raise ValueError(
            f"unknown sampler {config.sampler!r}: one of {SAMPLERS}")


def _check_comm_sampler(config: SSGDConfig) -> None:
    """Reject schedule/sampler combinations that have no per-step
    collective to re-schedule, up front and with the remedy named."""
    if config.comm == "dense":
        return
    if config.feature_sharded:
        raise ValueError(
            "comm != 'dense' does not compose with feature_sharded "
            "(the tp split's model-axis matvec psum is activation "
            "traffic, not a gradient sync); run the comm schedules on "
            "a pure-dp mesh"
        )
    if config.sampler == "fused_train":
        raise ValueError(
            f"comm={config.comm!r} applies to the per-step gradient "
            "sync, which sampler='fused_train' does not expose (it "
            "fuses whole segments into one launch with no per-step "
            "collective) — use 'bernoulli' or 'fused_gather'"
        )


def _check_sync_sampler(config: SSGDConfig) -> None:
    """Reject sync/sampler combinations up front, remedy named."""
    from tpu_distalg.parallel import ssp as pssp

    spec = pssp.SyncSpec.parse(config.sync)
    if not spec.is_ssp:
        return
    if config.sampler == "fused_train" or config.feature_sharded:
        raise ValueError(
            f"sync={config.sync!r} (stale-synchronous) composes with "
            f"the 'bernoulli' and 'fused_gather' samplers on a pure-dp "
            f"mesh — got sampler={config.sampler!r} "
            f"feature_sharded={config.feature_sharded}; 'fused_train' "
            f"(no per-window collective exists inside the megakernel) "
            f"and the tp split stay BSP")


def make_ssp_train_fn(mesh: Mesh, config: SSGDConfig, n_padded: int,
                      d: int, *, active: tuple[bool, ...],
                      n_win_seg: int, total_ticks: int,
                      meta: dict | None = None):
    """The SSP window scan: one compiled fn per (active set, segment
    window count), called per epoch segment by :func:`_train_ssp`.

    Call as ``fn(X, y, valid, X_test, y_test, w0, clocks0, pend0,
    basegen0, wl0, accd0, res0, extra_seg, win0)`` where ``extra_seg``
    is the segment's ``(n_win_seg, s, S)`` straggle schedule slice and
    ``win0`` the absolute window offset; returns ``(w, clocks, pend,
    basegen, wl, accd, res, win_accs, ages_max, ages_mean, gated)``.

    Per window: shards with no undelivered progress ADOPT the fresh
    center (base generation = this window); each of the ``s`` ticks is
    a LOCAL SGD step — no collective — skipped when the seeded straggle
    schedule claims the tick or the clock gate trips (conservative SSP
    gate: own clock minus the window-start active minimum ≥ the bound);
    at the boundary, shards not straggling deliver their accumulated
    update, weighted ``decay**age`` (age = windows since their base
    model — delayed-gradient application), the clock vector is combined
    through the comms layer, and the center moves by the weighted
    average. A shard straggled AT the boundary keeps accumulating and
    delivers later at a staler weight — nothing is ever waited for,
    nothing is ever lost.

    With ``meta`` (from ``pallas_kernels.pack_augmented``) the local
    tick gradient runs the 'fused_gather' block-gather kernel instead
    of the XLA bernoulli-mask path, and the carry layout is
    UNCHANGED (``ssp_init_state`` at ``d = meta['d_total']``): the
    window/merge/gate algebra is sampler-independent, so at ``s=1``
    on one shard the trajectory is bitwise the BSP fused trainer's
    (the parity pin).
    """
    import numpy as np

    from tpu_distalg.parallel import DATA_AXIS, comms
    from tpu_distalg.parallel import ssp as pssp

    spec = pssp.SyncSpec.parse(config.sync)
    s = spec.staleness
    sync = _ssp_comm_sync(mesh, config, d)
    key = prng.root_key(config.seed)
    active_np = np.asarray(active, bool)
    big = jnp.int32(1 << 30)
    n_shards_m = int(mesh.shape[DATA_AXIS])

    if meta is None:
        payload_spec = P(None, "data")       # (s, rows) bernoulli masks

        def tick_grad(X, y, w_l, payload_t):
            return logistic.grad_sum(X, y, w_l, payload_t)

        def window_payload(ts, valid):
            return jax.vmap(
                lambda t: sampling.bernoulli_mask(
                    key, t, n_padded, config.mini_batch_fraction,
                    valid))(ts)
    else:
        from tpu_distalg.ops import pallas_kernels

        d_t = meta["d_total"]
        col_keep = (jnp.arange(d_t) < meta["y_col"]).astype(
            jnp.float32)
        n_blocks, n_sampled = fused_gather_geometry(
            config, meta, n_shards_m)
        kern = functools.partial(
            pallas_kernels.fused_grad_sum_gathered,
            pack=meta["pack"], d_total=d_t, y_col=meta["y_col"],
            v_col=meta["v_col"],
            gather_block_rows=config.gather_block_rows,
            interpret=not mesh_on_tpu(mesh))
        payload_spec = P(None, "data", None)  # (s, S, ns) draws

        def tick_grad(X2, y, w_l, payload_t):
            del y                            # packed into X2
            g, cnt = kern(X2, w_l, payload_t[0])
            return g * col_keep, cnt

        def window_payload(ts, valid):
            del valid                        # validity rides X2
            return jax.vmap(
                lambda t: sampling.sample_block_ids(
                    jax.random.fold_in(key, t),
                    n_shards_m, n_blocks, n_sampled))(ts)

    def window_body(X, y, payloads, w, clocks, pend, basegen, wl,
                    accd, res, extra, tickv, winid):
        from jax import lax

        my = lax.axis_index(DATA_AXIS)
        act = jnp.asarray(active_np)
        act_me = act[my]
        wl = wl[0]
        accd = accd[0]
        # shards with nothing pending adopt the fresh center: their
        # base model is THIS window's merged state (age 0 at delivery)
        adopt = act & jnp.logical_not(pend)
        basegen = jnp.where(adopt, winid, basegen)
        max_c = jnp.max(jnp.where(act, clocks, -big))
        # an adopting shard holds the freshest model — its clock jumps
        # to the head of the pack so a historical lag (a rejoiner's
        # absence) cannot trip the gate against CURRENT staleness
        clocks_adj = jnp.where(adopt, max_c, clocks)
        min_known = jnp.min(jnp.where(act, clocks_adj, big))
        wl = jnp.where(act_me & jnp.logical_not(pend[my]), w, wl)
        accd = jnp.where(act_me & jnp.logical_not(pend[my]),
                         jnp.zeros_like(accd), accd)

        def tick(carry, xs):
            w_l, acc, my_clock, gated_ct = carry
            payload_t, extra_t, tv = xs
            # pad ticks (tv False, past total_ticks) pay NO
            # interference: the BSP A/B arm never runs them, so a
            # straggle cell landing in the padding would bias the
            # measured speedup against SSP
            eu = jnp.where(tv, extra_t[my], 0)
            gated = (my_clock - min_known) >= jnp.int32(s)
            do = (tv & act_me & (eu == 0)
                  & jnp.logical_not(gated))
            # the compiled-in straggler: real FLOPs on this shard only,
            # entangled below so the delay sits on the critical path
            dummy = pssp.straggle_work(eu, 1.0)
            g, cnt = tick_grad(X, y, w_l, payload_t)
            reg = logistic.reg_gradient(
                w_l, config.reg_type, config.elastic_alpha)
            upd = config.eta * (g / jnp.maximum(cnt, 1.0)
                                + config.lam * reg)
            dof = do.astype(jnp.float32)
            w_l = pssp.entangle(w_l - dof * upd, dummy)
            acc = acc - dof * upd
            my_clock = my_clock + do.astype(clocks.dtype)
            gated_ct = gated_ct + (tv & act_me & gated).astype(
                jnp.int32)
            return (w_l, acc, my_clock, gated_ct), None

        (wl, accd, my_clock, my_gated), _ = lax.scan(
            tick, (wl, accd, clocks_adj[my], jnp.int32(0)),
            (payloads, extra, tickv))

        # the clock vector, combined via the comms layer (ints ride the
        # dense path of any schedule — a compressed count would corrupt
        # the staleness math for no byte win)
        clocks_new = comms.psum(
            jnp.zeros_like(clocks).at[my].set(my_clock))
        gated = comms.psum(my_gated)
        stepped = clocks_new > clocks_adj
        pend2 = (pend | stepped) & act
        boundary_busy = extra[-1] > 0
        deliver = pend2 & jnp.logical_not(boundary_busy) & act
        ages = jnp.maximum(winid - basegen, 0)
        wts = pssp.staleness_weights(ages, act, deliver, spec.decay)
        wsum = jnp.sum(wts)
        contrib = wts[my] * accd
        (summed,), res_new = sync.reduce((contrib,), res, winid)
        # a merge nobody delivered to is a NO-OP, not an epsilon
        # division: the collective still ran (SPMD requires it), but a
        # stateful schedule (topk) flushed its error-feedback residual
        # into `summed` — applying that over the 1e-12 clamp would
        # multiply it by 1e12, and keeping res_new would silently lose
        # the flushed mass. Discard both: the residual rides to the
        # next merge exactly as if the boundary never fired.
        delivered_any = wsum > 0
        w_new = w + jnp.where(
            delivered_any,
            summed / jnp.maximum(wsum, jnp.float32(1e-12)), 0.0)
        res_new = jnp.where(delivered_any, res_new, res)
        ages_obs = jnp.where(deliver, ages, 0)
        n_del = jnp.sum(deliver.astype(jnp.float32))
        ages_max = jnp.max(ages_obs).astype(jnp.float32)
        ages_mean = (jnp.sum(ages_obs.astype(jnp.float32))
                     / jnp.maximum(n_del, 1.0))
        pend_out = pend2 & jnp.logical_not(deliver)
        accd = jnp.where(deliver[my], jnp.zeros_like(accd), accd)
        return (w_new, clocks_new, pend_out, basegen, wl[None],
                accd[None], res_new, ages_max, ages_mean, gated)

    window_fn = data_parallel(
        window_body, mesh,
        in_specs=(
            P("data", None),    # X rows (or the packed X2)
            P("data"),          # y (a dummy on the fused paths)
            payload_spec,       # per-tick sampling payload
            P(),                # center w
            P(), P(), P(),      # clocks, pend, basegen (replicated)
            P("data", None),    # per-shard local models (S, D)
            P("data", None),    # per-shard accumulated deltas (S, D)
            P("data", None),    # error-feedback residual (S, E)
            P(), P(), P(),      # extra (s, S), tick validity, winid
        ),
        out_specs=(P(), P(), P(), P(), P("data", None),
                   P("data", None), P("data", None), P(), P(), P()),
    )

    def train(X, y, valid, X_test, y_test, w0, clocks0, pend0,
              basegen0, wl0, accd0, res0, extra_seg, win0):
        def win_step(carry, xs):
            w, clocks, pend, basegen, wl, accd, res = carry
            i, extra_w = xs
            winid = (win0 + i).astype(jnp.int32)
            ts = winid * s + jnp.arange(s)
            payloads = window_payload(ts, valid)
            tickv = ts < total_ticks
            (w, clocks, pend, basegen, wl, accd, res, amax, amean,
             gated) = window_fn(X, y, payloads, w, clocks, pend,
                                basegen, wl, accd, res, extra_w,
                                tickv, winid)
            acc = (metrics.binary_accuracy(X_test @ w, y_test)
                   if config.eval_test else jnp.float32(0))
            return ((w, clocks, pend, basegen, wl, accd, res),
                    (acc, amax, amean, gated))

        carry0 = (w0, clocks0, pend0, basegen0, wl0, accd0, res0)
        carry, (accs, amax, amean, gated) = jax.lax.scan(
            win_step, carry0, (jnp.arange(n_win_seg), extra_seg))
        return (*carry, accs, amax, amean, gated)

    return jax.jit(train)


def ssp_init_state(mesh: Mesh, config: SSGDConfig, d: int, *,
                   w=None, clocks=None, win0: int = 0):
    """Host-side SSP carry for :func:`make_ssp_train_fn`, in call
    order: ``(w, clocks, pending, base_gen, local_models,
    accumulated_deltas, ef_residual)``. The ONE place the state layout
    lives — the training driver's step-0 state, its cross-geometry
    renegotiation AND the bench's timing arm all build here, so a
    carry change can never leave a hand-rolled copy behind."""
    import numpy as np

    from tpu_distalg.parallel import DATA_AXIS

    n_shards = int(mesh.shape[DATA_AXIS])
    sync = _ssp_comm_sync(mesh, config, d)
    w = (np.zeros((d,), np.float32) if w is None
         else np.asarray(w, np.float32))
    clocks = (np.zeros((n_shards,), np.int32) if clocks is None
              else np.asarray(clocks, np.int32))
    return (w, clocks,
            np.zeros((n_shards,), bool),                 # pending
            np.full((n_shards,), int(win0), np.int32),   # base gen
            np.tile(w, (n_shards, 1)),                   # local models
            np.zeros((n_shards, d), np.float32),         # accumulated Δ
            np.asarray(sync.init_state()))               # EF residual


def make_bsp_straggler_fn(mesh: Mesh, config: SSGDConfig,
                          n_padded: int, extra):
    """The speedup bench's BSP arm: the classic per-step
    (Σ grad, count) psum trainer — same sampling and update math as
    :func:`make_train_fn`'s default path, so the trajectory is BITWISE
    the plain BSP one — with the compiled straggle schedule's
    interference compute entangled on each shard's gradient BEFORE the
    collective. The per-tick psum is a barrier, so every shard's delay
    is paid serially by the whole mesh: exactly the cost the SSP
    window structure removes, measured instead of claimed.
    ``extra`` is the (n_ticks, n_shards) schedule from
    :func:`ssp.compile_straggle_schedule`. Returns
    ``fn(X, y, valid, X_test, y_test, w0)`` → ``(w, accs)``."""
    from jax import lax

    from tpu_distalg.parallel import DATA_AXIS
    from tpu_distalg.parallel import ssp as pssp

    key = prng.root_key(config.seed)
    extra_arr = jnp.asarray(extra, jnp.int32)

    def _local_grad(X, y, mask, w, extra_t):
        my = lax.axis_index(DATA_AXIS)
        dummy = pssp.straggle_work(extra_t[my], 1.0)
        g, cnt = logistic.grad_sum(X, y, w, mask)
        # the entangle puts the interference on the collective's
        # critical path; values are untouched (identity), so BSP under
        # a straggle plan stays bitwise BSP — only slower
        g = pssp.entangle(g, dummy)
        return tree_allreduce_sum((g, cnt))

    grad_fn = data_parallel(
        _local_grad, mesh,
        in_specs=(P("data", None), P("data"), P("data"), P(), P()),
        out_specs=(P(), P()),
    )

    def prep_xs(ts):
        return jnp.take(extra_arr, ts, axis=0)

    def sample_and_grad(X, y, valid, w, payload):
        t, extra_t = payload
        mask = sampling.bernoulli_mask(
            key, t, n_padded, config.mini_batch_fraction, valid)
        return grad_fn(X, y, mask, w, extra_t)

    return _build_scan(config, sample_and_grad,
                       prep_xs=lambda ts: (ts, prep_xs(ts)))


def window_accs_to_ticks(win_accs, s: int, n_ticks: int):
    """Expand per-window accuracies to the per-tick history every other
    trainer reports: tick t carries the last merge's accuracy (0 before
    the first merge), the final tick the final merge's — the
    ``fused_train`` eval-at-boundary idiom, window-shaped. Pure, so
    segmented and straight runs assemble identical histories."""
    import numpy as np

    win_accs = np.asarray(win_accs, np.float32)
    if win_accs.size == 0 or n_ticks <= 0:
        # degenerate runs (n_iterations=0 still executes one fully
        # masked window) report an empty history like the BSP paths
        return np.zeros((max(0, n_ticks),), np.float32)
    prev = np.concatenate([[np.float32(0.0)], win_accs[:-1]])
    accs = np.repeat(prev, s)
    accs[s - 1::s] = win_accs
    accs = accs[:n_ticks]
    accs[-1] = win_accs[-1]
    return accs


def _train_ssp(
    X_train, y_train, X_test, y_test, mesh: Mesh, config: SSGDConfig,
    *,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 500,
) -> TrainResult:
    """Stale-synchronous training driver (``sync='ssp[:s[:decay]]'``):
    windows of ``s`` ticks between merges, seeded straggle/membership
    schedules compiled from the active fault plan, elastic epochs via
    :func:`membership.run_elastic` (checkpointed at window granularity;
    a resume on a different shard count renegotiates the ring instead
    of rejecting). The trajectory is a pure function of (config, data,
    plan), so a replay under the same plan is bitwise-identical."""
    import numpy as np

    from tpu_distalg.parallel import DATA_AXIS, comms, membership
    from tpu_distalg.parallel import partition
    from tpu_distalg.parallel import ssp as pssp

    spec = pssp.SyncSpec.parse(config.sync)
    s = spec.staleness
    T = config.n_iterations
    d_orig = X_train.shape[1]
    n_shards = int(mesh.shape[DATA_AXIS])
    if config.sampler == "fused_gather":
        # the packed-kernel SSP path: same carry (ssp_init_state at
        # d_total), same window/merge algebra — only the local tick
        # gradient runs the fused kernel (PR 9's named leftover)
        _, X2, w0j, meta = prepare_fused(X_train, y_train, mesh,
                                         config)
        d = meta["d_total"]
        w0 = np.asarray(w0j, np.float32)
        data_x = X2
        # labels/validity ride inside the packed X2; the dummies only
        # satisfy the window program's sharded-arg signature
        data_y = jnp.zeros((n_shards,), jnp.float32)
        data_valid = jnp.zeros((n_shards,), jnp.float32)
        n_padded = meta["n_padded"]
        X_te = jnp.asarray(
            np.pad(np.asarray(X_test, np.float32),
                   ((0, 0), (0, d - d_orig))))
        y_te = jnp.asarray(y_test)
        tag = (f"ssgd:{config.sampler}:{spec.spec()}:"
               f"comm={config.comm}")
    else:
        meta = None
        d = d_orig
        Xs = parallelize(X_train, mesh,
                         dtype=jnp.dtype(config.x_dtype))
        ys = parallelize(y_train, mesh)
        data_x, data_y, data_valid = Xs.data, ys.data, Xs.mask
        n_padded = Xs.n_padded
        X_te, y_te = jnp.asarray(X_test), jnp.asarray(y_test)
        w0 = np.asarray(logistic.init_weights(
            prng.root_key(config.init_seed), d), np.float32)
        # the pre-fused tag spelling: existing bernoulli checkpoint
        # directories keep resuming
        tag = f"ssgd:{spec.spec()}:comm={config.comm}"
    n_win, padded_ticks = pssp.window_grid(T, s)
    extra = pssp.compile_straggle_schedule(padded_ticks, n_shards)
    extra[T:] = 0  # pad ticks don't exist: no interference, no busy
    extra = extra.reshape(n_win, s, n_shards)
    sync = _ssp_comm_sync(mesh, config, d)

    def fresh_state(w_host, clocks, win0: int):
        """Full state from the replicated center — both the step-0
        state and the cross-geometry redistribution (every epoch
        boundary is a resync point, so per-shard state is DERIVED, not
        resharded). Layout lives in :func:`ssp_init_state`."""
        return ssp_init_state(mesh, config, d, w=w_host,
                              clocks=clocks, win0=win0)

    def renegotiate(saved_leaves, saved_shards, start_win):
        del saved_shards
        return fresh_state(
            saved_leaves[0],
            membership.redistribute_clocks(saved_leaves[1], n_shards),
            start_win)

    def make_seg_fn(active, n_win_seg):
        return make_ssp_train_fn(
            mesh, config, n_padded, d, active=active,
            n_win_seg=n_win_seg, total_ticks=T, meta=meta)

    def run_seg(fn, state, win0, n_win_seg, epoch):
        del epoch
        # idempotent table placement (parallel/partition.py): state
        # that is already device-resident in the rule-table layout
        # passes through untouched — the old np.asarray + device_put
        # spelling paid a full host round trip EVERY segment
        w = state[0] if isinstance(state[0], jax.Array) \
            else np.asarray(state[0], np.float32)
        st = partition.ensure(
            {"w": w, "clocks": state[1], "pend": state[2],
             "basegen": state[3], "wl": state[4], "accd": state[5],
             "res": state[6]},
            "ssgd", mesh)
        out = fn(data_x, data_y, data_valid, X_te, y_te,
                 st["w"], st["clocks"], st["pend"], st["basegen"],
                 st["wl"], st["accd"], st["res"],
                 jnp.asarray(extra[win0:win0 + n_win_seg]),
                 jnp.int32(win0))
        state = out[:7]
        accs, amax, amean, gated = out[7:]
        return state, (accs, amax, amean, gated)

    state, outs, start, epochs = membership.run_elastic(
        checkpoint_dir, max(1, checkpoint_every // s), n_win, n_shards,
        make_seg_fn=make_seg_fn, run_seg=run_seg,
        state0=fresh_state(w0, np.zeros(n_shards, np.int32), 0),
        renegotiate=renegotiate,
        # the sync spec is part of the tag: windows are indexed in
        # s-tick units and merge weights depend on decay, so a resume
        # under a DIFFERENT bound would silently reinterpret the saved
        # progress — it must reject like any other workload mismatch
        # (and the fused samplers carry their own tag: the augmented
        # weight layout is not the XLA path's)
        tag=tag,
        ticks_per_window=s)

    w = jnp.asarray(np.asarray(state[0], np.float32))[:d_orig]
    metrics.guard_finite(w, "SSGD (ssp) weights")
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
    return TrainResult(w=w, accs=jnp.asarray(accs))


def _make_train_fn_comm(mesh: Mesh, config: SSGDConfig, n_padded: int,
                        d: int | None):
    """Bernoulli-sampler scan with the comm-schedule gradient sync:
    identical sampling and update math to :func:`make_train_fn`'s
    default path — only the (Σ grad, count) allreduce goes through
    :mod:`tpu_distalg.parallel.comms`."""
    if d is None:
        raise ValueError(
            f"comm={config.comm!r} needs the feature width: call "
            "make_train_fn(mesh, config, n_padded, d=X.shape[1]) "
            "(ssgd.train does this for you)"
        )
    sync = _comm_sync(mesh, config, d)

    def _local_grad(X, y, mask, w, t, res):
        g, cnt = logistic.grad_sum(X, y, w, mask)
        # the reg gradient is the update's one sync-independent term —
        # handing it to the comm layer as the overlap thunk lets the
        # scheduler hide the exchange behind it
        (g, cnt), res, reg = sync.reduce(
            (g, cnt), res, t,
            compute=lambda: logistic.reg_gradient(
                w, config.reg_type, config.elastic_alpha))
        return g, cnt, res, reg

    grad_fn = data_parallel(
        _local_grad,
        mesh,
        in_specs=(P("data", None), P("data"), P("data"), P(), P(),
                  P("data", None)),
        out_specs=(P(), P(), P("data", None), P()),
    )
    key = prng.root_key(config.seed)

    def sample_and_grad(X, y, valid, w, payload, t, res):
        del payload  # == t on the bernoulli path
        mask = sampling.bernoulli_mask(
            key, t, n_padded, config.mini_batch_fraction, valid
        )
        return grad_fn(X, y, mask, w, t, res)

    return _build_scan_comm(config, sample_and_grad)


def _make_train_fn_tp(mesh: Mesh, config: SSGDConfig, n_padded: int):
    """dp×tp SSGD: rows sharded over 'data', features over 'model'.

    Forward: z = psum_model(X_l·w_l) — a tensor-parallel matvec; backward:
    g_l = psum_data(X_lᵀ·resid) — each model shard owns its feature slice
    of the gradient and of w. Caller pads the feature dim to a multiple of
    the model-axis size (zero columns are inert).
    """
    from tpu_distalg.parallel import DATA_AXIS, MODEL_AXIS, comms

    key = prng.root_key(config.seed)

    def _local_grad(X, y, mask, w):
        z = comms.psum(X @ w, MODEL_AXIS)          # (rows_l,) TP matvec
        resid = (jax.nn.sigmoid(z) - y) * mask
        g = comms.psum(X.T @ resid, DATA_AXIS)     # my feature slice
        cnt = comms.psum(jnp.sum(mask), DATA_AXIS)
        return g, cnt

    grad_fn = data_parallel(
        _local_grad,
        mesh,
        in_specs=(
            P("data", "model"), P("data"), P("data"), P("model"),
        ),
        out_specs=(P("model"), P()),
    )

    def sample_and_grad(X, y, valid, w, t):
        mask = sampling.bernoulli_mask(
            key, t, n_padded, config.mini_batch_fraction, valid
        )
        return grad_fn(X, y, mask, w)

    return _build_scan(config, sample_and_grad)


def fused_gather_geometry(config: SSGDConfig, meta: dict, n_shards: int):
    """Per-shard block-sampling geometry of the 'fused_gather' sampler:
    (blocks per shard, blocks sampled per shard per step). Single source
    of truth for the bytes a step moves. A table of ragged rows
    (``row_format`` pairs) is a grid of blocks of pair slots, which
    ``gather_block_rows`` does not size."""
    if meta.get("row_format") == "pairs":
        from tpu_distalg.models import ssgd_pairs

        return ssgd_pairs.blocks_geometry(config, meta, n_shards)
    if config.gather_block_rows % meta["pack"]:
        # the kernel raises the same constraint at trace time; catching it
        # here keeps the derived n_blocks/n_sampled from silently using a
        # truncated block size
        raise ValueError(
            f"gather_block_rows={config.gather_block_rows} must be a "
            f"multiple of pack={meta['pack']}"
        )
    bp = config.gather_block_rows // meta["pack"]
    n2_local = (meta["n_padded"] // meta["pack"]) // n_shards
    n_blocks = n2_local // bp
    if n_blocks * bp != n2_local:
        raise ValueError(
            f"gather_block_rows={config.gather_block_rows} must divide "
            f"the per-shard row count {n2_local * meta['pack']}; re-pack "
            f"with block_rows a multiple of gather_block_rows × n_shards"
        )
    n_sampled = max(1, round(config.mini_batch_fraction * n_blocks))
    warn_quantized_fraction(
        "fused_gather", n_blocks, n_sampled, config.mini_batch_fraction,
        "lower gather_block_rows or fused_pack for a finer grid")
    return n_blocks, n_sampled


def warn_quantized_fraction(prefix: str, n_blocks: int, n_sampled: int,
                            frac: float, remedy: str) -> None:
    """Warn when the block grid quantizes the configured minibatch
    fraction by more than 25% — shared by every block-cluster sampler
    so the tolerance and message cannot drift between them."""
    eff = n_sampled / n_blocks
    if abs(eff - frac) > 0.25 * frac:
        warnings.warn(
            f"{prefix}: {n_blocks} blocks/shard quantizes the minibatch "
            f"fraction to {eff:.3f} (configured {frac}); {remedy}",
            stacklevel=3,
        )


def make_train_fn_fused(mesh: Mesh, config: SSGDConfig, meta: dict):
    """Scan builder for the packed-layout samplers (and, by ``meta``,
    for rows of indices).

    'fused_gather': the traffic-proportional kernel
    (``fused_grad_sum_gathered``) — samples ``frac·n_blocks`` block ids
    XLA-side each step and DMAs only those (runs under interpret on CPU
    too). The kernel sits inside ``shard_map`` over the data axis with
    (Σg, count) psum'd across shards; the carried weight vector is the
    augmented (d_total,) layout and the y/v/pad columns are re-zeroed
    every step (their gradient entries are kernel garbage).
    'fused_train': :func:`_make_train_fn_mega`.
    """
    from jax import lax

    from tpu_distalg.ops import pallas_kernels
    from tpu_distalg.parallel import DATA_AXIS

    check_sampler(config)
    if meta.get("row_format", "packed") == "pairs":
        # ragged rows of (feature, value) pairs: models/ssgd_pairs.py
        from tpu_distalg.models import ssgd_pairs

        return ssgd_pairs.make_train_fn(mesh, config, meta)
    if meta.get("row_format", "packed") in INDEX_ROW_FORMATS:
        # the loader's meta decides: rows that are indices have their
        # own two passes and share everything round them
        return _make_train_fn_hashed(mesh, config, meta)
    if config.sampler == "bernoulli":
        raise ValueError(
            "sampler='bernoulli' masks dense rows, not the packed "
            "layout — build via make_train_fn(mesh, config, n_padded), "
            "or use ssgd.train()")
    on_tpu = mesh_on_tpu(mesh)
    d_t = meta["d_total"]
    col_keep = (jnp.arange(d_t) < meta["y_col"]).astype(jnp.float32)
    n_shards = mesh.shape[DATA_AXIS]
    _check_comm_sampler(config)
    sync = (_comm_sync(mesh, config, d_t)
            if config.comm != "dense" else None)

    if config.sampler == "fused_train":
        return _make_train_fn_mega(mesh, config, meta, on_tpu, n_shards)
    # geometry warns when n_blocks quantizes the fraction coarsely
    n_blocks, n_sampled = fused_gather_geometry(config, meta, n_shards)
    key = prng.root_key(config.seed)
    kern = functools.partial(
        pallas_kernels.fused_grad_sum_gathered,
        pack=meta["pack"], d_total=d_t, y_col=meta["y_col"],
        v_col=meta["v_col"],
        gather_block_rows=config.gather_block_rows,
        interpret=not on_tpu,
    )

    def prep_xs(ts):
        # ALL (step, shard) block draws in one batched threefry —
        # the shared without-replacement draw
        # (sampling.sample_block_ids), per-round key = fold_in(key,
        # absolute step id)
        with jax.named_scope(names.SSGD_DRAW):
            return jax.vmap(
                lambda t: sampling.sample_block_ids(
                    jax.random.fold_in(key, t),
                    n_shards, n_blocks, n_sampled,
                )
            )(ts)                                    # (T, S, ns)

    if sync is not None:
        def _local_grad(X2, w, idx_shards, t, res):
            shard = lax.axis_index(DATA_AXIS)
            idx = lax.dynamic_index_in_dim(
                idx_shards, shard, keepdims=False
            )
            with jax.named_scope(names.SSGD_KERNEL):
                g, cnt = kern(X2, w, idx)
                g = g * col_keep
            with jax.named_scope(names.SSGD_SYNC):
                (g, cnt), res, reg = sync.reduce(
                    (g, cnt), res, t,
                    compute=lambda: logistic.reg_gradient(
                        w, config.reg_type, config.elastic_alpha))
            return g, cnt, res, reg
    else:
        def _local_grad(X2, w, idx_shards):
            shard = lax.axis_index(DATA_AXIS)
            idx = lax.dynamic_index_in_dim(
                idx_shards, shard, keepdims=False
            )
            with jax.named_scope(names.SSGD_KERNEL):
                g, cnt = kern(X2, w, idx)
                g = g * col_keep
            with jax.named_scope(names.SSGD_SYNC):
                return tree_allreduce_sum((g, cnt))

    if sync is not None:
        grad_fn = data_parallel(
            _local_grad,
            mesh,
            in_specs=(P("data", None), P(), P(), P(),
                      P("data", None)),
            out_specs=(P(), P(), P("data", None), P()),
        )

        def sample_and_grad(X2, y, valid, w, x, t, res):
            del y, valid  # labels/validity ride inside the packed X2
            return grad_fn(X2, w, x, t, res)

        return _build_scan_comm(config, sample_and_grad,
                                prep_xs=prep_xs)

    grad_fn = data_parallel(
        _local_grad,
        mesh,
        in_specs=(P("data", None), P(), P()),
        out_specs=(P(), P()),
    )

    def sample_and_grad(X2, y, valid, w, x):
        del y, valid  # labels/validity ride inside the packed X2
        return grad_fn(X2, w, x)

    return _build_scan(config, sample_and_grad, prep_xs=prep_xs)


def _make_train_fn_mega(mesh: Mesh, config: SSGDConfig, meta: dict,
                        on_tpu: bool, n_shards: int):
    """'fused_train' scan builder: the whole schedule in
    ``pallas_kernels.fused_train_gathered`` megakernel launches of
    ``mega_steps`` SGD steps each (weights in VMEM, update in-kernel).

    Sampling is IDENTICAL to 'fused_gather' (same
    ``sampling.sample_block_ids`` draw keyed on the absolute step id, so
    checkpoint/resume stays bitwise) and the update math is the same
    f32-master/bf16-selector structure, so the two samplers agree to
    float rounding — asserted by ``tests/test_mega_kernel.py``. The
    per-step psum is the one thing a single launch cannot express, hence
    the single-data-shard restriction.
    """
    from tpu_distalg.ops import pallas_kernels

    n_blocks, n_sampled = fused_gather_geometry(config, meta, n_shards)
    if n_shards != 1:
        raise ValueError(
            "sampler='fused_train' fuses the whole schedule into one "
            "kernel launch, so there is no per-step cross-shard psum: "
            "it is the single-data-shard (dp=1) specialization. Use "
            "'fused_gather' on multi-shard data meshes."
        )
    if config.lam != 0.0:
        raise ValueError(
            "sampler='fused_train' supports lam=0 only (the reference "
            "default, ssgd.py:21); use 'fused_gather' for regularized "
            "runs"
        )
    if config.mega_steps < 1:
        raise ValueError(
            f"mega_steps must be >= 1, got {config.mega_steps}"
        )
    T = config.n_iterations
    mega = min(config.mega_steps, T)
    if T % mega:
        raise ValueError(
            f"sampler='fused_train' needs n_iterations ({T}) divisible "
            f"by mega_steps ({mega})"
        )
    if config.eval_test and config.eval_every != mega:
        raise ValueError(
            "sampler='fused_train' evaluates at kernel-segment "
            f"boundaries only: set eval_every == mega_steps ({mega}) "
            "or eval_test=False"
        )
    d_t = meta["d_total"]
    key = prng.root_key(config.seed)
    kern = functools.partial(
        pallas_kernels.fused_train_gathered,
        pack=meta["pack"], d_total=d_t, y_col=meta["y_col"],
        v_col=meta["v_col"],
        gather_block_rows=config.gather_block_rows,
        eta=config.eta, interpret=not on_tpu,
    )

    def train(X2, y, valid, X_test, y_test, w0, t0=0, acc0=0.0):
        del y, valid  # labels/validity ride inside the packed X2
        ts = jnp.arange(T) + t0
        with jax.named_scope(names.SSGD_DRAW):
            idx = jax.vmap(
                lambda t: sampling.sample_block_ids(
                    jax.random.fold_in(key, t), 1, n_blocks, n_sampled)
            )(ts).reshape(T // mega, mega, n_sampled)
        w_tile0 = jnp.tile(w0, (meta["pack"],))[:, None]

        def seg(wt, idx_seg):
            with jax.named_scope(names.SSGD_KERNEL):
                wt = kern(X2, wt, idx_seg)
            with jax.named_scope(names.SSGD_UPDATE):
                acc = (
                    metrics.binary_accuracy(X_test @ wt[:d_t, 0], y_test)
                    if config.eval_test else jnp.float32(0)
                )
            return wt, acc

        w_tile, seg_accs = jax.lax.scan(seg, w_tile0, idx)
        w = w_tile[:d_t, 0]
        if config.eval_test:
            # eval_every-style history: position t carries the last acc
            # computed at or before t (segment ends), seeded with acc0
            prev = jnp.concatenate(
                [jnp.asarray(acc0, jnp.float32).reshape(1),
                 seg_accs[:-1]]
            )
            accs = jnp.repeat(prev, mega).at[mega - 1::mega].set(
                seg_accs)
        else:
            accs = jnp.zeros((T,), jnp.float32)
        return w, accs

    return jax.jit(train)


def prepare_fused_tp(X_train, y_train, mesh: Mesh, config: SSGDConfig):
    """dp×tp setup for the gathered kernel: the feature dim is sharded
    over the mesh model axis. Each model shard packs ITS OWN feature
    slice (padded to equal width) with the y/v columns replicated into
    every slice — their weight entries are pinned to zero, so partial
    matvecs never double-count them and every shard can extract y/v
    locally. Returns ``(fn, X2, w0, meta)``; the global augmented weight
    layout is the concatenation of the per-shard ``(d_total,)`` slices,
    sharded ``P('model')``.
    """
    import numpy as np

    from tpu_distalg.ops import pallas_kernels
    from tpu_distalg.parallel import DATA_AXIS, MODEL_AXIS, partition

    n_data = mesh.shape[DATA_AXIS]
    n_model = mesh.shape[MODEL_AXIS]
    d_orig = X_train.shape[1]
    n = X_train.shape[0]
    X_np = np.asarray(X_train, np.float32)
    d_pad = (-d_orig) % n_model
    if d_pad:
        X_np = np.pad(X_np, ((0, 0), (0, d_pad)))
    d_l = X_np.shape[1] // n_model

    packs, meta = [], None
    for m in range(n_model):
        # same n/shuffle_seed per slice → identical row permutation and
        # padding, so slot (i, p) holds the SAME row in every slice
        X2_m, meta = pallas_kernels.pack_augmented(
            X_np[:, m * d_l:(m + 1) * d_l], np.asarray(y_train),
            np.ones(n, np.float32),
            dtype=jnp.dtype(config.x_dtype), pack=config.fused_pack,
            block_rows=config.gather_block_rows * n_data,
            shuffle_seed=config.shuffle_seed,
        )
        packs.append(np.asarray(X2_m))
    X2 = partition.put(np.concatenate(packs, axis=1), "X2",
                       "ssgd_tp", mesh)
    d_t = meta["d_total"]
    meta = dict(meta, n_model=n_model, d_local=d_l, d_orig=d_orig)
    w_init = logistic.init_weights(prng.root_key(config.init_seed), d_orig)
    w_init = np.pad(np.asarray(w_init), (0, d_pad))
    w0 = np.zeros((n_model * d_t,), np.float32)
    for m in range(n_model):
        w0[m * d_t: m * d_t + d_l] = w_init[m * d_l:(m + 1) * d_l]
    w0 = partition.put(w0, "w", "ssgd_tp", mesh)
    fn = make_train_fn_fused_tp(mesh, config, meta)
    return fn, X2, w0, meta


def tp_augment_test_matrix(X_test, meta: dict):
    """Map test features into the concatenated per-shard augmented
    layout (zeros at every y/v/pad position — the matching weight
    entries are held at zero, so the padded matvec equals the original)."""
    import numpy as np

    d_t, d_l, n_model = meta["d_total"], meta["d_local"], meta["n_model"]
    X_np = np.asarray(X_test, np.float32)
    n = X_np.shape[0]
    out = np.zeros((n, n_model * d_t), np.float32)
    for m in range(n_model):
        width = min(d_l, max(0, X_np.shape[1] - m * d_l))
        out[:, m * d_t: m * d_t + width] = \
            X_np[:, m * d_l: m * d_l + width]
    return jnp.asarray(out)


def make_train_fn_fused_tp(mesh: Mesh, config: SSGDConfig, meta: dict):
    """dp×tp scan builder for the gathered kernel — the two-pass split.

    The one-pass kernel cannot feature-shard: the residual needs the
    GLOBAL matvec ``z = Σ_m X_m·w_m``. So each step runs
    ``fused_forward_gathered`` (partial z + local y/v on this shard's
    feature slice), one ``comms.psum(z, 'model')``, then
    ``fused_backward_gathered`` (residᵀ·X on the slice) — the sampled
    blocks are read TWICE, i.e. 2× the per-chip HBM bytes of pure dp at
    equal chip count. Measured on the v5e chip (1M×128 benchmark
    geometry, model=1 so the split cost is isolated and collectives are
    free): two-pass 7557 steps/s vs one-pass 8510 — 0.89×, because at
    this scale the step is dispatch/overhead-bound rather than
    bandwidth-bound; in the bandwidth-bound regime (≥100M rows) the
    byte ratio makes it →0.5×. Use dp×tp for CAPACITY (feature width
    beyond one chip's HBM) — pure dp is the throughput-optimal layout
    for this workload (SURVEY.md §2.3).
    """
    import functools

    from tpu_distalg.ops import pallas_kernels
    from tpu_distalg.parallel import DATA_AXIS, MODEL_AXIS, comms

    on_tpu = mesh_on_tpu(mesh)
    d_t = meta["d_total"]
    Pk = meta["pack"]
    n_shards = mesh.shape[DATA_AXIS]
    col_keep = (jnp.arange(d_t) < meta["y_col"]).astype(jnp.float32)
    n_blocks, n_sampled = fused_gather_geometry(config, meta, n_shards)
    key = prng.root_key(config.seed)
    fwd = functools.partial(
        pallas_kernels.fused_forward_gathered,
        pack=Pk, d_total=d_t, y_col=meta["y_col"], v_col=meta["v_col"],
        gather_block_rows=config.gather_block_rows, interpret=not on_tpu,
    )
    bwd = functools.partial(
        pallas_kernels.fused_backward_gathered,
        pack=Pk, d_total=d_t,
        gather_block_rows=config.gather_block_rows, interpret=not on_tpu,
    )

    def prep_xs(ts):
        return jax.vmap(
            lambda t: sampling.sample_block_ids(
                jax.random.fold_in(key, t), n_shards, n_blocks, n_sampled,
            )
        )(ts)                                        # (T, S, ns)

    def _local_grad(X2, w_l, idx_local):
        idx = idx_local[0]                           # (ns,)
        zyv = fwd(X2, w_l, idx)                      # (ns·bp, 3P)
        z = comms.psum(zyv[:, :Pk], MODEL_AXIS)      # TP matvec
        y, v = zyv[:, Pk:2 * Pk], zyv[:, 2 * Pk:]    # local (replicated)
        resid = (jax.nn.sigmoid(z) - y) * v
        g_l = bwd(X2, resid, idx) * col_keep         # my feature slice
        g_l = comms.psum(g_l, DATA_AXIS)
        cnt = comms.psum(jnp.sum(v), DATA_AXIS)
        return g_l, cnt

    grad_fn = data_parallel(
        _local_grad, mesh,
        in_specs=(
            P("data", "model"),      # concatenated per-slice packs
            P("model"),              # concatenated augmented weights
            P("data", None),         # (S, ns) draws → (1, ns) local
        ),
        out_specs=(P("model"), P()),
    )

    def sample_and_grad(X2, y, valid, w, x):
        del y, valid                 # packed into X2
        return grad_fn(X2, w, x)

    return _build_scan(config, sample_and_grad, prep_xs=prep_xs)


def fused_train_segment_lengths(checkpoint_dir, checkpoint_every: int,
                                n_iterations: int) -> set[int]:
    """The distinct compiled-segment lengths a checkpointed run will
    execute, INCLUDING a resume from whatever step is on disk — shared
    by the up-front fused_train guard and the CLI's mega_steps
    auto-pick so both validate the lengths that will actually run."""
    from tpu_distalg.utils import checkpoint as ckpt

    if checkpoint_every < 1:
        # run_segmented raises the same downstream; failing here keeps
        # the while loop below from spinning on a zero-length segment
        raise ValueError(
            f"checkpoint_every must be >= 1, got {checkpoint_every}"
        )
    start = (ckpt.latest_step(checkpoint_dir) or 0) if checkpoint_dir \
        else 0
    lens: set[int] = set()
    t = min(start, n_iterations)
    while t < n_iterations:
        seg = min(checkpoint_every, n_iterations - t)
        lens.add(seg)
        t += seg
    return lens


def _train_span(config: SSGDConfig, **fields):
    """An unsegmented run: the one call of the compiled schedule
    (trace and compile with it the first time) and the fetch of the
    finite-weights guard that ends it."""
    return tevents.span("ssgd:train", sampler=config.sampler,
                        steps=config.n_iterations, **fields)


def _draw_fields(config: SSGDConfig, meta: dict, mesh: Mesh) -> dict:
    """What the training spans of a fused run say about its block draw:
    the form ``sampling.sample_block_ids`` takes at this geometry
    (``tda report`` prints it)."""
    from tpu_distalg.parallel import DATA_AXIS

    with warnings.catch_warnings():      # the builder has warned already
        warnings.simplefilter("ignore")
        n_blocks, n_sampled = fused_gather_geometry(
            config, meta, mesh.shape[DATA_AXIS])
    return {"draw_form": sampling.draw_form(n_blocks, n_sampled)}


def _acc_carrying_run_seg(*data_args, w_put=None):
    """Segment runner shared by the XLA, fused and fused-tp checkpoint
    paths: state = (w, last_acc); the final emitted accuracy IS the
    carried last-acc, so resuming with ``acc0`` keeps eval_every>1
    histories bitwise-equal across segment boundaries. ``w_put``
    re-places restored host weights per the workload's rule table
    (the tp path's model-sharded w)."""

    def run_seg(fn, state, t0):
        w, acc0 = state
        w = jnp.asarray(w) if w_put is None else w_put(w)
        w, accs = fn(*data_args, w, t0=t0, acc0=jnp.asarray(acc0))
        return (w, accs[-1]), accs

    return run_seg


def train(
    X_train, y_train, X_test, y_test, mesh: Mesh,
    config: SSGDConfig = SSGDConfig(),
    *,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 500,
) -> TrainResult:
    """End-to-end training; optionally checkpointed/resumable.

    With ``checkpoint_dir``, training runs in compiled segments of
    ``checkpoint_every`` steps; after each segment the (w, step, accs)
    state is saved (msgpack) and a non-finite-weights guard trips with a
    clear error (the NaN hazard SURVEY.md §5 flags in the reference is
    impossible to see there — it has no guards at all). An existing
    checkpoint in the directory resumes from its absolute step; segmented
    and straight-through runs produce bitwise-identical weights.
    """
    import numpy as np

    from tpu_distalg.parallel import MODEL_AXIS, partition

    check_sampler(config)
    _check_comm_sampler(config)
    _check_sync_sampler(config)
    from tpu_distalg.parallel import ssp as _pssp

    if _pssp.SyncSpec.parse(config.sync).is_ssp:
        return _train_ssp(
            X_train, y_train, X_test, y_test, mesh, config,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every)
    if config.sampler != "bernoulli":
        if config.feature_sharded:
            if config.sampler != "fused_gather":
                raise ValueError(
                    "feature_sharded composes with sampler="
                    "'fused_gather' or 'bernoulli', not "
                    f"'{config.sampler}'"
                )
            return _train_fused_tp(
                X_train, y_train, X_test, y_test, mesh, config,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
            )
        return _train_fused(
            X_train, y_train, X_test, y_test, mesh, config,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
        )

    d_orig = X_train.shape[1]
    n_model = mesh.shape[MODEL_AXIS]
    if config.feature_sharded:
        # zero feature columns are inert: zero grad slice, zero w slice
        d_pad = (-d_orig) % n_model
        if d_pad:
            X_train = np.pad(np.asarray(X_train), ((0, 0), (0, d_pad)))
            X_test = np.pad(np.asarray(X_test), ((0, 0), (0, d_pad)))

    Xs = parallelize(
        X_train, mesh, dtype=jnp.dtype(config.x_dtype)
    )
    X_data = Xs.data
    if config.feature_sharded:
        X_data = partition.put(X_data, "X_data",
                               "ssgd_feature_sharded", mesh)
    ys = parallelize(y_train, mesh)
    w0 = logistic.init_weights(
        prng.root_key(config.init_seed), X_train.shape[1]
    )
    if config.feature_sharded:
        w0 = partition.put(w0, "w", "ssgd_feature_sharded", mesh)
    X_te, y_te = jnp.asarray(X_test), jnp.asarray(y_test)

    if config.comm != "dense":
        return _train_comm(
            mesh, config, d_orig,
            (X_data, ys.data, Xs.mask, X_te, y_te), w0,
            make_fn=lambda seg: make_train_fn(
                mesh, dataclasses.replace(config, n_iterations=seg),
                Xs.n_padded, d=d_orig),
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            tag=f"ssgd:{config.sampler}",
            crop=d_orig,
        )

    if checkpoint_dir is None:
        fn = make_train_fn(mesh, config, Xs.n_padded)
        with _train_span(config):
            w, accs = fn(X_data, ys.data, Xs.mask, X_te, y_te, w0)
            metrics.guard_finite(w, "SSGD weights")
        return TrainResult(w=w[:d_orig], accs=accs)

    from tpu_distalg.utils import checkpoint as ckpt

    (w, _), accs, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=lambda seg: make_train_fn(
            mesh, dataclasses.replace(config, n_iterations=seg),
            Xs.n_padded),
        run_seg=_acc_carrying_run_seg(
            X_data, ys.data, Xs.mask, X_te, y_te),
        state0=(w0, jnp.float32(0)),
        tag=f"ssgd:{config.sampler}",
    )
    return TrainResult(w=jnp.asarray(w)[:d_orig], accs=jnp.asarray(accs))


def _train_comm(mesh, config, d, data_args, w0, *, make_fn,
                checkpoint_dir, checkpoint_every, tag, crop, fn=None,
                span_fields=None):
    """Comm-schedule training driver shared by the XLA and fused paths:
    the scan carry/checkpoint state is ``(w, last_acc, residual)`` —
    the flat error-feedback residual persists across segments, so a
    resumed top-k run replays bitwise (satellite-tested round-trip)."""
    from tpu_distalg.parallel import comms, partition

    sync = _comm_sync(mesh, config, d)
    res0 = partition.put(sync.init_state(), "res", "ssgd", mesh)

    if checkpoint_dir is None:
        fn = fn if fn is not None else make_fn(config.n_iterations)
        with _train_span(config, **(span_fields or {})):
            w, accs, _ = fn(*data_args, w0, res0)
            metrics.guard_finite(w, "SSGD weights")
        comms.emit_sync_counters(sync, config.n_iterations)
        return TrainResult(w=w[:crop], accs=accs)

    from tpu_distalg.utils import checkpoint as ckpt

    def run_seg(fn, state, t0):
        w, acc0, res = state
        res = partition.put(res, "res", "ssgd", mesh)
        w, accs, res = fn(*data_args, jnp.asarray(w), res, t0=t0,
                          acc0=jnp.asarray(acc0))
        return (w, accs[-1], res), accs

    (w, _, _), accs, start = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=make_fn,
        run_seg=run_seg,
        state0=(w0, jnp.float32(0), res0),
        tag=f"{tag}:comm={config.comm}",
        span_fields=span_fields,
    )
    # count only the syncs THIS process ran — a resumed run performed
    # n_iterations - start, not the full schedule
    comms.emit_sync_counters(sync, config.n_iterations - start)
    return TrainResult(w=jnp.asarray(w)[:crop], accs=jnp.asarray(accs))


def prepare_fused(X_train, y_train, mesh: Mesh, config: SSGDConfig):
    """One-time setup of :func:`_train_fused` (and of callers that time
    the steps themselves): pack (X, y, validity) into the fused kernel's layout, shard it over
    the data axis, build the augmented initial weights and the jitted
    scan. Returns ``(fn, X2, w0, meta)``; call as
    ``fn(X2, dummy, dummy, X_test_padded, y_test, w0)``.
    """
    import numpy as np

    from tpu_distalg.ops import pallas_kernels
    from tpu_distalg.parallel import DATA_AXIS, partition

    n_shards = mesh.shape[DATA_AXIS]
    d_orig = X_train.shape[1]
    n = X_train.shape[0]
    with tevents.span("ssgd:prepare", mesh.local_devices, rows=n):
        with tevents.span("ssgd:pack", rows=n):
            X2, meta = pallas_kernels.pack_augmented(
                np.asarray(X_train), np.asarray(y_train),
                np.ones(n, np.float32),
                dtype=jnp.dtype(config.x_dtype),
                pack=config.fused_pack,
                block_rows=config.gather_block_rows * n_shards,
                shuffle_seed=config.shuffle_seed,
            )
        with tevents.span("ssgd:h2d", mesh.local_devices,
                          rows=meta["n_padded"], bytes=int(X2.nbytes)):
            X2 = partition.put(X2, "X2", "ssgd", mesh)
            X2.block_until_ready()   # the span is the copy, not its enqueue
        w0 = jnp.zeros((meta["d_total"],), jnp.float32).at[:d_orig].set(
            logistic.init_weights(prng.root_key(config.init_seed), d_orig)
        )
        fn = make_train_fn_fused(mesh, config, meta)
    return fn, X2, w0, meta


def prepare_fused_synthetic(
    n_rows: int, n_features: int, mesh: Mesh, config: SSGDConfig,
    *, data_seed: int = 0, separation: float = 2.0,
    chunk_rows: int = 1 << 20,
):
    """Scale-out variant of :func:`prepare_fused`: the packed design
    matrix is synthesized ON DEVICE, shard by shard — host memory use is
    O(1) in ``n_rows``, which is what a 1B-row table requires. The
    reference materializes its whole
    matrix on the driver (``/root/reference/optimization/ssgd.py:86``);
    ``parallelize``/``pack_augmented`` mirror that and top out at host
    RAM. Rows here are generated from a counter-based per-row PRNG
    (``datasets.synthetic_two_class_rows``), so content is
    topology-independent and no shuffle is needed (rows are i.i.d. by
    construction — block-cluster sampling is exactly row sampling).

    Generation runs in ``chunk_rows`` chunks inside a ``lax.map`` so the
    f32 intermediates stay chunk-sized; only the final dtype-cast packed
    array occupies HBM. Returns ``(fn, X2, w0, meta)`` like
    :func:`prepare_fused`.
    """
    import numpy as np

    from jax import lax

    from tpu_distalg.ops import pallas_kernels
    from tpu_distalg.parallel import DATA_AXIS, partition
    from tpu_distalg.utils import datasets as dsets

    n_shards = mesh.shape[DATA_AXIS]
    pk = config.fused_pack
    d = n_features + 1  # + bias column (ssgd.py:83-84)
    d_t, y_col, v_col = pallas_kernels.packed_dims(d, pk)
    mult = max(config.gather_block_rows, pk) * n_shards
    n_t = n_rows + ((-n_rows) % mult)
    n_local = n_t // n_shards
    chunk = min(chunk_rows, n_local)
    while chunk and (n_local % chunk or chunk % pk):
        chunk //= 2
    if chunk == 0:
        raise ValueError(
            f"cannot chunk n_local={n_local} rows by pack={pk}"
        )
    n_chunks = n_local // chunk
    make_rows = dsets.synthetic_two_class_rows(
        n_features, data_seed, separation)
    dtype = jnp.dtype(config.x_dtype)

    def body():
        s = lax.axis_index(DATA_AXIS)

        def gen_chunk(c):
            ids = s * n_local + c * chunk + jnp.arange(chunk)
            X, y = make_rows(ids)
            valid = (ids < n_rows).astype(jnp.float32)
            cols = [X, jnp.ones((chunk, 1)), y[:, None], valid[:, None]]
            if d_t > d + 2:
                cols.append(jnp.zeros((chunk, d_t - d - 2)))
            rows = jnp.concatenate(cols, axis=1).astype(dtype)
            return rows.reshape(chunk // pk, pk * d_t)

        chunks = lax.map(gen_chunk, jnp.arange(n_chunks))
        return chunks.reshape(n_local // pk, pk * d_t)

    spec = P(DATA_AXIS, None)
    f = jax.shard_map(body, mesh=mesh, in_specs=(), out_specs=spec)
    X2 = jax.jit(f, out_shardings=partition.leaf_sharding(
        "ssgd", "X2", mesh))()
    meta = dict(pack=pk, d_total=d_t, y_col=y_col, v_col=v_col,
                n_padded=n_t)
    w0 = jnp.zeros((d_t,), jnp.float32).at[:d].set(
        logistic.init_weights(prng.root_key(config.init_seed), d)
    )
    fn = make_train_fn_fused(mesh, config, meta)
    return fn, X2, w0, meta


def tp_extract_weights(w, meta: dict):
    """Original-layout weights from the concatenated per-shard augmented
    vector (inverse of :func:`prepare_fused_tp`'s placement)."""
    import numpy as np

    d_t, d_l = meta["d_total"], meta["d_local"]
    w_np = np.asarray(w)
    parts = [w_np[m * d_t: m * d_t + d_l] for m in range(meta["n_model"])]
    return jnp.asarray(np.concatenate(parts)[: meta["d_orig"]])


def _train_fused_tp(
    X_train, y_train, X_test, y_test, mesh: Mesh, config: SSGDConfig,
    *,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 500,
) -> TrainResult:
    """dp×tp training with the gathered kernel (two-pass split — see
    :func:`make_train_fn_fused_tp` for the measured cost vs pure dp)."""
    fn, X2, w0, meta = prepare_fused_tp(X_train, y_train, mesh, config)
    X_te = tp_augment_test_matrix(X_test, meta)
    y_te = jnp.asarray(y_test)
    dummy = jnp.zeros((1,), jnp.float32)
    if checkpoint_dir is None:
        with _train_span(config):
            w, accs = fn(X2, dummy, dummy, X_te, y_te, w0)
            metrics.guard_finite(w, "SSGD (fused tp) weights")
        return TrainResult(w=tp_extract_weights(w, meta), accs=accs)

    from tpu_distalg.parallel import partition
    from tpu_distalg.utils import checkpoint as ckpt

    (w, _), accs, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=lambda seg: make_train_fn_fused_tp(
            mesh, dataclasses.replace(config, n_iterations=seg), meta),
        run_seg=_acc_carrying_run_seg(
            X2, dummy, dummy, X_te, y_te,
            w_put=lambda w: partition.put(w, "w", "ssgd_tp", mesh)),
        state0=(w0, jnp.float32(0)),
        tag=f"ssgd:{config.sampler}:tp",
    )
    return TrainResult(
        w=tp_extract_weights(jnp.asarray(w), meta),
        accs=jnp.asarray(accs),
    )


def _train_fused(
    X_train, y_train, X_test, y_test, mesh: Mesh, config: SSGDConfig,
    *,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 500,
) -> TrainResult:
    """Packed-layout training ('fused_gather', 'fused_train'): pack
    once, then read only the sampled blocks of the packed matrix.

    The packed layout bakes labels and row validity into X
    (``pallas_kernels.pack_augmented``), so the scan carries an augmented
    (d_total,) weight vector; eval pads X_test with matching zero columns
    (the y/v entries of w are held at zero each step, so the padded
    matvec equals the unpadded one).

    With ``checkpoint_dir``, training runs in compiled segments exactly
    like the XLA-sampler path: the only carry is the augmented weight
    vector, and both fused samplers key their draw off the ABSOLUTE step
    id (``fold_in(key, t)``), so segmented resume is bitwise-equal to a
    straight run.
    """
    import numpy as np

    d_orig = X_train.shape[1]
    fn, X2, w0, meta = prepare_fused(X_train, y_train, mesh, config)
    X_te = jnp.asarray(
        np.pad(np.asarray(X_test, np.float32),
               ((0, 0), (0, meta["d_total"] - d_orig)))
    )
    y_te = jnp.asarray(y_test)
    dummy = jnp.zeros((1,), jnp.float32)
    draw = _draw_fields(config, meta, mesh)
    if config.comm != "dense":
        return _train_comm(
            mesh, config, meta["d_total"],
            (X2, dummy, dummy, X_te, y_te), w0,
            make_fn=lambda seg: make_train_fn_fused(
                mesh, dataclasses.replace(config, n_iterations=seg),
                meta),
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            tag=f"ssgd:{config.sampler}",
            crop=d_orig, fn=fn, span_fields=draw,
        )
    if checkpoint_dir is None:
        with _train_span(config, **draw):
            w, accs = fn(X2, dummy, dummy, X_te, y_te, w0)
            metrics.guard_finite(w, "SSGD (fused) weights")
        return TrainResult(w=w[:d_orig], accs=accs)

    from tpu_distalg.utils import checkpoint as ckpt

    if config.sampler == "fused_train":
        # each checkpoint segment re-enters _make_train_fn_mega with
        # n_iterations=segment length and mega=min(mega_steps, segment):
        # validate EVERY segment length up front — including those of a
        # RESUMED run (start from the newest checkpoint, which may not
        # be a multiple of the current checkpoint_every) — so a run
        # cannot die mid-way on the builder's divisibility /
        # eval-boundary checks after hours of training
        for seg in sorted(fused_train_segment_lengths(
                checkpoint_dir, checkpoint_every, config.n_iterations)):
            mega = min(config.mega_steps, seg)
            if seg % mega:
                raise ValueError(
                    f"sampler='fused_train': checkpoint segment of "
                    f"{seg} steps is not divisible by mega_steps "
                    f"({config.mega_steps}); choose checkpoint_every "
                    f"and n_iterations as multiples of mega_steps"
                )
            if config.eval_test and config.eval_every != mega:
                raise ValueError(
                    f"sampler='fused_train' with eval_test: a "
                    f"checkpoint segment of {seg} steps evaluates at "
                    f"its launch boundary mega=min(mega_steps, seg)="
                    f"{mega}, but eval_every={config.eval_every} — "
                    f"make n_iterations and checkpoint_every multiples "
                    f"of mega_steps (so no short remainder segment "
                    f"exists) and set eval_every == mega_steps, or "
                    f"eval_test=False"
                )

    (w, _), accs, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=lambda seg: make_train_fn_fused(
            mesh, dataclasses.replace(config, n_iterations=seg), meta),
        run_seg=_acc_carrying_run_seg(X2, dummy, dummy, X_te, y_te),
        state0=(w0, jnp.float32(0)),
        tag=f"ssgd:{config.sampler}",
        span_fields=draw,
    )
    return TrainResult(w=jnp.asarray(w)[:d_orig], accs=jnp.asarray(accs))


# ---- rows that are indices and not columns ------------------------------
#
# The second row format: a row is ``nnz`` int32 slots of a table of float32
# weights and a label (``ops/pallas_hashed.py`` documents the block layout
# and the two passes). ``meta["row_format"]`` says which table: ``hashed``,
# ``2 ** hash_bits`` slots that every field's values are mixed into, or
# ``indexed``, the fields' ranges end to end (``meta["cardinalities"]``),
# every value its own weight. Everything round the passes is the packed
# rows': the block grid of ``fused_gather_geometry``, the draw,
# ``_build_scan``'s step, the psum. The third format, ``pairs``: a row is
# a list of (feature, value) pairs of its own length, float32 values
# (``ops/pairs.py``, ``models/ssgd_pairs.py``); it shares the same
# things and the refusals of :func:`_check_hashed_config`.

INDEX_ROW_FORMATS = ("hashed", "indexed", "pairs")


@dataclasses.dataclass
class HashedResult:
    """A run over rows of indices, hashed or indexed: the model vector
    (table, bias, zeros), and what it scores on rows the table does not
    hold."""

    w: jax.Array
    accs: jax.Array
    heldout_acc: float
    heldout_log_loss: float
    forms: str = ""       # :func:`describe_forms`: what ``tda ssgd`` prints

    @property
    def final_acc(self) -> float:
        return self.heldout_acc


def hashed_geometry(config: SSGDConfig, meta: dict):
    from tpu_distalg.ops import pallas_hashed

    return pallas_hashed.HashedGeometry(
        nnz=meta["nnz"], hash_bits=meta["hash_bits"],
        block_rows=config.gather_block_rows,
        field_sizes=tuple(meta["cardinalities"])
        if meta["row_format"] == "indexed" else ())


def _check_hashed_config(config: SSGDConfig,
                         row_format: str = "hashed") -> None:
    """The one place that says which trainers take rows of indices
    (``row_format`` hashed, indexed or pairs): the per-step
    block-sampled one ('fused_gather'), dense BSP. The others read a
    row as columns."""
    from tpu_distalg.parallel import ssp as pssp

    check_sampler(config)
    rows = f"{row_format} rows"

    why = {
        "bernoulli": "masks every row of a dense matrix each step",
        "fused_train": "keeps a packed step's 40 weights in the "
                       "megakernel's VMEM; a weight table (2**hash_bits "
                       "slots hashed, a slot a feature indexed or in "
                       "pairs) and a psum a step do not fit one launch",
    }
    if config.sampler != "fused_gather":
        raise ValueError(
            f"{rows}: sampler={config.sampler!r} cannot take the "
            f"format ({why[config.sampler]}); use sampler='fused_gather'")
    if config.feature_sharded:
        raise ValueError(
            f"{rows}: feature_sharded splits packed columns over "
            f"the model axis and cannot take the format; a weight table "
            f"sharded over chips is not built yet (ROADMAP R4m)")
    if config.comm != "dense":
        raise ValueError(
            f"{rows}: comm={config.comm!r} cannot take the format "
            f"yet: the schedules of parallel/comms.py have not met a "
            f"gradient as long as a weight table (ROADMAP R4m); use "
            f"'dense'")
    if pssp.SyncSpec.parse(config.sync).is_ssp:
        raise ValueError(
            f"{rows}: sync={config.sync!r} cannot take the format: "
            f"the guarantee is BSP (no slot updated from stale weights)")


def hashed_field_plan(config: SSGDConfig, meta: dict):
    """Which form each field of a hashed or indexed ``meta`` takes
    (``pallas_hashed.field_plan`` over the dictionaries its loader
    states), or ``None``: every field of a hashed table by address."""
    from tpu_distalg.ops import pallas_hashed

    return pallas_hashed.field_plan(hashed_geometry(config, meta),
                                    meta.get("dictionaries"))


def _hashed_fields(config: SSGDConfig, meta: dict, mesh: Mesh) -> dict:
    """What the spans of a hashed or indexed run say (``tda report``
    prints it): the format, the table's bytes, the passes' form and how
    many fields take each form (by value, by address in VMEM, in HBM;
    an ``xla`` pass reads every field the same way and counts none),
    and of the fields in HBM how many scatter into an accumulator of
    their whole range in VMEM on ``mesh`` and how many through XLA
    (``pallas_hashed.field_scatter_form``)."""
    from tpu_distalg.ops import pallas_hashed

    geom = hashed_geometry(config, meta)
    form = geom.pass_form
    plan = hashed_field_plan(config, meta)
    n_dict = len(plan.dict_fields) if plan else 0
    n_hbm = len(plan.hbm_fields) if plan else 0
    n_addr = 0 if form == "xla" else meta["nnz"] - n_dict - n_hbm
    on_tpu = mesh_on_tpu(mesh)
    scatter = [pallas_hashed.field_scatter_form(geom.field_sizes[f], on_tpu)
               for f in (plan.hbm_fields if plan else ())]
    return {"row_format": meta["row_format"], "nnz": meta["nnz"],
            "hash_bits": meta["hash_bits"],
            "table_bytes": 4 * geom.n_slots, "gather_form": form,
            "scatter_form": form, "dict_fields": n_dict,
            "addr_fields": meta["nnz"] - n_dict - n_hbm,
            "dict_values": plan.n_values if plan else 0,
            "fields_dict": n_dict, "fields_vmem": n_addr,
            "fields_hbm": n_hbm,
            "fields_hbm_scatter_vmem": scatter.count("vmem"),
            "fields_hbm_scatter_xla": scatter.count("xla")}


def describe_forms(config: SSGDConfig, meta: dict) -> str:
    """One line that says which table the rows index and which form
    each field's share of the two passes takes (``pallas_hashed.
    pass_form`` / ``field_form``, from sizes alone)."""
    from tpu_distalg.ops import pallas_hashed

    geom = hashed_geometry(config, meta)
    plan = hashed_field_plan(config, meta)
    head = (f"row format {meta['row_format']}: {geom.n_slots} weights "
            f"({4 * geom.n_slots / 1e6:.1f} MB), passes {geom.pass_form}")
    if plan is None:
        return head + (": every field by address" if geom.pass_form
                       == "vmem" else ": every field through XLA")
    if plan.addr_groups is None:
        return head + (f": fields by value {list(plan.dict_fields)}, by "
                       f"address {list(plan.addr_fields)}")
    return head + (
        f": fields by value {list(plan.dict_fields)}, by address in VMEM "
        f"{[list(g.fields) for g in plan.addr_groups]} (a group a "
        f"table of at most 2**{pallas_hashed.VMEM_BITS} slots), in HBM "
        f"{list(plan.hbm_fields)}")


def _make_train_fn_hashed(mesh: Mesh, config: SSGDConfig, meta: dict):
    """:func:`make_train_fn_fused` for a hashed or indexed ``meta``:
    the same scan (``fn(X, dummy, dummy, dummy, dummy, w0, t0=,
    acc0=)``), the same draw, update and psum; only the local gradient
    is new. The carried ``w`` is ``f32[geom.w_len]``: table (``2 **
    hash_bits`` slots, or a slot a feature), bias, zeros. The
    scan scores nothing (there is no dense test matrix to multiply):
    :func:`evaluate_hashed` scores held-out rows between segments."""
    from jax import lax

    from tpu_distalg.ops import pallas_hashed
    from tpu_distalg.parallel import DATA_AXIS

    _check_hashed_config(config, meta["row_format"])
    geom = hashed_geometry(config, meta)
    plan = hashed_field_plan(config, meta)
    n_shards = mesh.shape[DATA_AXIS]
    n_blocks, n_sampled = fused_gather_geometry(config, meta, n_shards)
    interpret = not mesh_on_tpu(mesh)
    n_rows, B = meta["n_rows"], geom.block_rows
    key = prng.root_key(config.seed)

    def prep_xs(ts):
        with jax.named_scope(names.SSGD_DRAW):
            return jax.vmap(
                lambda t: sampling.sample_block_ids(
                    jax.random.fold_in(key, t),
                    n_shards, n_blocks, n_sampled))(ts)     # (T, S, ns)

    def _local_grad(X, w, idx_shards):
        shard = lax.axis_index(DATA_AXIS)
        ids = lax.dynamic_index_in_dim(idx_shards, shard, keepdims=False)
        with jax.named_scope(names.SSGD_GATHER):
            m = pallas_hashed.margins(X, w, ids, geom, plan=plan,
                                      interpret=interpret)
            y = pallas_hashed.labels(X, ids, geom)
            row = ((shard * n_blocks + ids) * B)[:, None] \
                + jnp.arange(B)[None, :]
            valid = (row < n_rows).astype(jnp.float32)
            r = (jax.nn.sigmoid(m) - y) * valid
        with jax.named_scope(names.SSGD_SCATTER):
            g = pallas_hashed.slot_sums(X, r, ids, geom, plan=plan,
                                        interpret=interpret)
            cnt = jnp.sum(valid)
        with jax.named_scope(names.SSGD_SYNC):
            return tree_allreduce_sum((g, cnt))

    grad_fn = data_parallel(
        _local_grad, mesh,
        in_specs=(P("data", None, None), P(), P()),
        out_specs=(P(), P()))

    def sample_and_grad(X, y, valid, w, x):
        del y, valid                 # labels and validity ride in X
        return grad_fn(X, w, x)

    return _build_scan(dataclasses.replace(config, eval_test=False),
                       sample_and_grad, prep_xs=prep_xs)


@functools.lru_cache(maxsize=8)
def hashed_table_fn(mesh: Mesh, n_rows: int, n_padded: int, geom,
                    cardinalities: tuple, rows_kw: tuple = ()):
    """The compiled loader of one geometry: the seed is its argument,
    so a second seed costs no compile."""
    import math

    from jax import lax

    from tpu_distalg.parallel import DATA_AXIS, partition

    n_shards = mesh.shape[DATA_AXIS]
    B, F, nnz = geom.block_rows, geom.fields_held, geom.nnz
    n_local = n_padded // n_shards
    n_blocks = n_local // B
    per = math.gcd(n_blocks, 16)                 # blocks a chunk
    chunk, n_chunks = B * per, n_blocks // per
    make_rows = _row_generator(geom.row_format, cardinalities,
                            geom.hash_bits, rows_kw)

    def body(seed):
        s = lax.axis_index(DATA_AXIS)
        bias = make_rows.planted_bias(seed)

        def one(c):
            ids = s * n_local + c * chunk + jnp.arange(chunk)
            slots, y = make_rows(ids, seed, bias)
            # a padding row keeps its slots (any slot is a safe
            # address); the trainer tells it by its id
            cols = jnp.concatenate(
                [slots, y.astype(jnp.int32)[:, None],
                 jnp.zeros((chunk, F - nnz - 1), jnp.int32)], axis=1)
            return cols.reshape(per, B, F).transpose(0, 2, 1)

        return lax.map(one, jnp.arange(n_chunks)).reshape(n_blocks, F, B)

    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(),
                      out_specs=P(DATA_AXIS, None, None)),
        out_shardings=partition.leaf_sharding("ssgd", "X", mesh))


def _row_generator(row_format: str, cardinalities, hash_bits: int, rows_kw):
    """The generator of either format's rows (``utils/datasets.py``)."""
    from tpu_distalg.utils import datasets as dsets

    if row_format == "indexed":
        return dsets.indexed_click_rows(cardinalities, **dict(rows_kw))
    return dsets.hashed_click_rows(cardinalities, hash_bits,
                                   **dict(rows_kw))


def build_hashed_table(n_rows: int, nnz: int, hash_bits: int, mesh: Mesh,
                       config: SSGDConfig, *, data_seed: int = 0,
                       cardinalities=None, row_format: str = "hashed",
                       **rows_kw):
    """The loader of rows of indices: ``n_rows`` seeded click-log rows
    (``datasets.hashed_click_rows`` or, for ``row_format='indexed'``,
    ``indexed_click_rows``, which ``rows_kw`` reach) made ON DEVICE,
    shard by shard, as ``int32[n_blocks, fields_held,
    gather_block_rows]``, and the ``meta`` that states the format: for
    an indexed table (``hash_bits`` 0) the fields' ``cardinalities`` are
    their ranges of the table, ``offsets`` where each starts, and every
    field of at most 65 536 values states its range as its dictionary.
    Returns ``(X, meta)``."""
    from tpu_distalg.ops import pallas_hashed
    from tpu_distalg.parallel import DATA_AXIS
    from tpu_distalg.utils import datasets as dsets

    if row_format == "pairs":
        raise ValueError("row_format 'pairs': ragged rows have their own "
                         "loader, models/ssgd_pairs.build_table")
    if row_format not in INDEX_ROW_FORMATS:
        raise ValueError(f"row_format {row_format!r}: one of "
                         f"{INDEX_ROW_FORMATS}")
    cards = tuple(cardinalities
                  or dsets.click_field_cardinalities(nnz))
    if len(cards) != nnz:
        raise ValueError(f"{len(cards)} cardinalities for {nnz} fields")
    indexed = row_format == "indexed"
    geom = pallas_hashed.HashedGeometry(
        nnz=nnz, hash_bits=hash_bits, block_rows=config.gather_block_rows,
        field_sizes=cards if indexed else ())
    mult = geom.block_rows * mesh.shape[DATA_AXIS]
    # pack 1: fused_gather_geometry's block grid counts rows
    meta = dict(row_format=row_format, nnz=nnz, hash_bits=hash_bits,
                pack=1, n_rows=n_rows, n_padded=n_rows + (-n_rows) % mult,
                d_total=geom.w_len, n_slots=geom.n_slots, cardinalities=cards,
                rows_kw=tuple(sorted(rows_kw.items())),
                dictionaries=dsets.indexed_field_dictionaries(cards)
                if indexed
                else dsets.click_field_dictionaries(cards, hash_bits))
    if indexed:
        meta["offsets"] = geom.offsets
    devices = mesh.local_devices
    with tevents.span("ssgd:prepare", devices, rows=n_rows,
                      bytes=meta["n_padded"] * geom.row_bytes,
                      **_hashed_fields(config, meta, mesh)):
        with tevents.span("ssgd:generate", devices,
                          rows=meta["n_padded"]):
            X = hashed_table_fn(mesh, n_rows, meta["n_padded"], geom,
                                cards, meta["rows_kw"])(
                jnp.int32(data_seed))
            X.block_until_ready()
            tevents.current().fields["bytes"] = metrics.nbytes(X)
    return X, meta


def prepare_hashed_synthetic(n_rows: int, nnz: int, hash_bits: int,
                             mesh: Mesh, config: SSGDConfig, *,
                             data_seed: int = 0, cardinalities=None,
                             row_format: str = "hashed", **rows_kw):
    """:func:`prepare_fused_synthetic` for rows of indices: returns
    ``(fn, X, w0, meta)``, the weights zero as the source's."""
    from tpu_distalg.parallel import partition

    X, meta = build_hashed_table(
        n_rows, nnz, hash_bits, mesh, config, data_seed=data_seed,
        cardinalities=cardinalities, row_format=row_format, **rows_kw)
    # placed as the trainer returns it: the first call and every later
    # one (a next segment's, a benchmark window's) are one program
    w0 = partition.put(jnp.zeros((meta["d_total"],), jnp.float32), "w",
                       "ssgd", mesh)
    return make_train_fn_fused(mesh, config, meta), X, w0, meta


def evaluate_hashed(w, meta: dict, *, data_seed: int = 0,
                    n: int = 1 << 16):
    """``(accuracy, log-loss)`` of the model vector ``w`` on ``n`` rows
    the table of ``meta`` does not hold (ids past its padded end),
    float32."""
    make_rows = _row_generator(meta["row_format"], meta["cardinalities"],
                            meta["hash_bits"], meta["rows_kw"])
    n_slots = meta["n_slots"]

    @jax.jit
    def score(w, seed):
        slots, y = make_rows(meta["n_padded"] + jnp.arange(n), seed)
        m = jnp.sum(w[:n_slots][slots], axis=1) + w[n_slots]
        loss = jnp.mean(jax.nn.softplus(m) - y * m)
        return jnp.mean(((m > 0) == (y > 0.5)).astype(jnp.float32)), loss

    acc, loss = score(jnp.asarray(w, jnp.float32), jnp.int32(data_seed))
    return float(acc), float(loss)


def run_index_rows(fn, X, w0, meta: dict, mesh: Mesh, config: SSGDConfig,
                   fields: dict, *, tag: str, what: str,
                   checkpoint_dir: str | None, checkpoint_every: int):
    """The run of a table whose rows ride in ``X`` alone (hashed,
    indexed, pairs), straight through or in checkpointed segments:
    ``(w, accs)``."""
    dummy = jnp.zeros((1,), jnp.float32)
    fields = dict(_draw_fields(config, meta, mesh), **fields)
    if checkpoint_dir is None:
        with _train_span(config, **fields):
            w, accs = fn(X, dummy, dummy, dummy, dummy, w0)
            metrics.guard_finite(w, what)
        return w, accs
    from tpu_distalg.parallel import partition
    from tpu_distalg.utils import checkpoint as ckpt

    (w, _), accs, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=lambda seg: make_train_fn_fused(
            mesh, dataclasses.replace(config, n_iterations=seg), meta),
        run_seg=_acc_carrying_run_seg(X, dummy, dummy, dummy, dummy),
        state0=(w0, partition.put(jnp.float32(0), "acc0", "ssgd", mesh)),
        tag=tag, span_fields=fields,
    )
    return w, accs


def train_hashed(n_rows: int, nnz: int, hash_bits: int, mesh: Mesh,
                 config: SSGDConfig, *, data_seed: int = 0,
                 cardinalities=None, row_format: str = "hashed",
                 checkpoint_dir: str | None = None,
                 checkpoint_every: int = 500) -> HashedResult:
    """End-to-end training on rows of indices (``tda ssgd
    --hashed-rows`` / ``--indexed-rows``): the loader's table, the
    block-sampled BSP trainer, held-out rows scored at the end;
    checkpointed and resumable like :func:`train`."""
    fn, X, w0, meta = prepare_hashed_synthetic(
        n_rows, nnz, hash_bits, mesh, config, data_seed=data_seed,
        cardinalities=cardinalities, row_format=row_format)
    w, accs = run_index_rows(
        fn, X, w0, meta, mesh, config, _hashed_fields(config, meta, mesh),
        tag=f"ssgd:{row_format}:{nnz}x{hash_bits or meta['d_total']}",
        what="SSGD (hashed) weights", checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every)
    with tevents.span("ssgd:heldout"):
        acc, loss = evaluate_hashed(w, meta, data_seed=data_seed)
    return HashedResult(w=jnp.asarray(w), accs=jnp.asarray(accs),
                        heldout_acc=acc, heldout_log_loss=loss,
                        forms=describe_forms(config, meta))
