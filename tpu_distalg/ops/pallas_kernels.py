"""Pallas TPU kernels for the hot SSGD path.

The XLA-fused SSGD step reads X from HBM twice per iteration — once for the
forward matvec ``X·w`` and once for the gradient contraction ``Xᵀ·resid``
(``tpu_distalg.ops.logistic.grad_sum``) — and the step is bandwidth-bound.
:func:`fused_grad_sum_gathered` fuses forward, masking and backward into
ONE pass over the SAMPLED blocks of X, the only remaining HBM traffic.

The design is driven by TPU layout constraints (/opt/skills/guides/
pallas_guide.md), discovered the hard way across kernel generations;
the first three are gone (PR 57: PERF.md §6 has their last chip
readings) and are kept here as what they taught:

  v1: separate (n, 1) y/mask operands. A (rows, 1) array is physically
     lane-padded 128-wide on TPU, so each "tiny" stream moved as many
     bytes as X itself; per-call feature padding also re-copied X
     every step.
  v2: y/validity folded into X as two ordinary columns, Bernoulli mask
     drawn from the on-core PRNG — one X pass, but every per-row value
     ((B,1) shapes) still wasted 127/128 of each VPU register row.
  v3: P consecutive rows packed per sublane row,
     X2 = X.reshape(n/P, P·D) — the layout that stayed
     (:func:`pack_augmented`). All per-row values live in (rows, P)
     shapes. The forward matvec becomes one matmul against a block-
     diagonal replication of w; label/validity extraction are two more
     selector blocks of the same constant matrix (single fused (P·D, 3P)
     operand — one extra DMA per grid step, not three); the backward
     contraction runs on the MXU with a (P, P·D) tile-shaped accumulator
     whose diagonal band is folded outside the kernel. The deliberate P×
     FLOP overhead buys layout sanity: the MXU is idle in a bandwidth-
     bound step. It still streamed 100% of X to sample ``fraction`` of
     it.

  v4 (:func:`fused_grad_sum_gathered`, production): moves the sampling
     into the *grid*: the caller draws ``frac·n_blocks`` block ids
     XLA-side and the kernel copies exactly those blocks, ids
     scalar-prefetched (a ring of VMEM slots since PR 27, see
     ``_ring_fetch``) — HBM traffic ≈ fraction × |X| per step.
     (Row-granular gathers are NOT the answer: random access serializes
     on TPU.)
  v5 (:func:`fused_train_gathered`, production on one data shard): v4
     with the whole schedule of a segment in one launch.

The gathered kernels' current readings, cell by cell, are in PERF.md §5.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_distalg.ops.pallas_api import pl, pltpu


def packed_dims(d: int, pack: int):
    """Static packed-layout geometry shared by :func:`pack_augmented`
    (host packing) and on-device synthesis: total padded column count
    ``d_t`` (features + y + valid + zero-pad, rounded so ``pack·d_t`` is
    a lane-tile multiple) and the y/valid column positions."""
    import numpy as np

    y_col, v_col = d, d + 1
    lane_q = 128 // int(np.gcd(pack, 128))   # smallest D granularity
    d_t = d + 2 + ((-(d + 2)) % lane_q)
    assert (pack * d_t) % 128 == 0           # lane_q rounding guarantees it
    return int(d_t), y_col, v_col


def pack_augmented(X, y, valid, *, dtype=jnp.bfloat16, pack: int = 16,
                   block_rows: int = 8192, shuffle_seed: int | None = None,
                   as_numpy: bool = False):
    """Pack (X, y, valid) for the gathered kernels
    (:func:`fused_grad_sum_gathered`) — done ONCE, outside the training scan.

    Layout: ``[features… | y | valid | zero-pad]`` per row, row i of the
    augmented matrix at packed position ``[i // pack, (i % pack)·D …]``.
    The total column count D is padded so that ``pack·D`` is a lane-tile
    multiple and rows to a ``block_rows`` multiple (zero rows carry
    valid=0 and are inert).  ``shuffle_seed`` permutes rows once at pack
    time so the gathered sampler's block-cluster draws are exchangeable
    with row-level draws even when the input rows are ordered.  Returns
    ``(X2, meta)`` where ``X2`` has shape (n_padded/pack, pack·D) and
    ``meta`` is the static dict of (pack, d_total, y_col, v_col,
    n_padded).
    """
    import numpy as np

    X = np.asarray(X, np.float32)
    if shuffle_seed is not None:
        perm = np.random.default_rng(shuffle_seed).permutation(X.shape[0])
        X, y = X[perm], np.asarray(y)[perm]
        valid = np.asarray(valid)[perm]
    n, d = X.shape
    d_t, y_col, v_col = packed_dims(d, pack)
    n_t = n + ((-n) % max(block_rows, pack))
    out = np.zeros((n_t, d_t), np.float32)
    out[:n, :d] = X
    out[:n, y_col] = np.asarray(y, np.float32)
    out[:n, v_col] = np.asarray(valid, np.float32)[:n]
    out2 = out.reshape(n_t // pack, pack * d_t)
    # as_numpy: HOST-resident packed matrix in the device dtype
    # (ml_dtypes bf16 is a numpy dtype) — the streamed >HBM path packs
    # once on host and DMAs sampled blocks per step (ssgd_stream)
    X2 = (out2.astype(jnp.dtype(dtype)) if as_numpy
          else jnp.asarray(out2, dtype))
    meta = dict(pack=pack, d_total=d_t, y_col=y_col, v_col=v_col,
                n_padded=n_t)
    return X2, meta


# The gathered kernels' block loop (v4's ``_grad_kernel_gathered`` and
# v5's ``_train_kernel_gathered`` share it). PERF.md section 6, "PR 27",
# has the chip readings behind both halves:
#
#   the ring: X2 stays in HBM and the kernel copies each grid cell's
#   sampled block into one of a few VMEM slots itself (``_ring_scratch``), the
#   next cells' copies started before this cell's is waited for, so
#   that one copy is always in flight behind the one that is landing
#   (the BlockSpec pipeline starts block i+1 only once block i has
#   landed, and the gap between the two is paid on every block);
#
#   the body: x2 is the MXU's latched operand in BOTH passes. The
#   forward is ``Cᵀ (3P, P·D) · x2ᵀ``, so z, y, v are sublane slices
#   of one dense (3P, rows) tile, the sigmoid runs over full vector
#   registers and the residual is born (P, rows), the shape the
#   backward ``resid · x2`` wants: no lane slices, no transpose, and
#   no per-block vector-to-scalar count.

# VMEM the ring's slots may take together (of the 100 MB the calls allow)
_RING_BYTES = 48 * 1024 * 1024
# packed rows of a block the body takes at a time: bounds the (3P, rows)
# f32 tile of the middle to a few vector registers
_CHUNK_ROWS = 512


def _chunk_rows(bp: int) -> int:
    """Largest divisor of ``bp`` (a multiple of 8) within ``_CHUNK_ROWS``."""
    return max(r for r in range(8, min(bp, _CHUNK_ROWS) + 1, 8)
               if bp % r == 0)


def _ring_fetch(idx_ref, x_hbm, xbuf, sems, g, n_cells: int):
    """Start the copies that keep the ring full, wait for grid cell
    ``g``'s block and return the slot it landed in. ``idx_ref`` holds
    the block id of every cell, flattened in grid order, so the ids are
    read ahead across a step boundary of the megakernel."""
    slots, bp = xbuf.shape[0], xbuf.shape[1]

    def copy(cell):
        row0 = pl.multiple_of(idx_ref[cell] * bp, bp)
        slot = cell % slots
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(row0, bp), :], xbuf.at[slot], sems.at[slot])

    @pl.when(g == 0)
    def _fill():
        for cell in range(min(slots - 1, n_cells)):
            copy(cell).start()

    ahead = g + (slots - 1)

    # the slot it overwrites held cell g-1's block, done with
    @pl.when(ahead < n_cells)
    def _next():
        copy(ahead).start()

    copy(g).wait()
    return xbuf.at[g % slots]


def _block_grad(x_ref, ct_ref, acc_ref, cnt_ref, *, pack: int):
    """One resident block's share of the step: ``acc_ref`` (P, P·D) +=
    the residual-weighted row sums (the tile whose diagonal band is the
    gradient), ``cnt_ref`` (P, rows) += the valid flags. Logits,
    sigmoid, residual and both accumulations in float32; x2 and the
    residual enter the MXU in x2's dtype."""
    P = pack
    bp = x_ref.shape[0]
    rows = cnt_ref.shape[1]
    g = cnt = None
    for r0 in range(0, bp, rows):
        x2 = x_ref[r0:r0 + rows, :]                 # (rows, P·D)
        zyv = jax.lax.dot_general(                  # (3P, rows)
            ct_ref[:], x2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        z, y, v = zyv[:P], zyv[P:2 * P], zyv[2 * P:]
        resid = ((jax.nn.sigmoid(z) - y) * v).astype(x2.dtype)
        gk = jnp.dot(resid, x2, preferred_element_type=jnp.float32)
        g = gk if g is None else g + gk
        cnt = v if cnt is None else cnt + v
    acc_ref[:] += g                                 # (P, P·D)
    cnt_ref[:] += cnt


def _gathered_shapes(name: str, X2, pack: int, d_total: int,
                     gather_block_rows: int) -> int:
    """Validate the packed table against the block geometry; returns
    ``bp``, the packed rows of a block."""
    P, D = pack, d_total
    n2, pd = X2.shape
    bp = gather_block_rows // P
    if (pd != P * D or (P * D) % 128 or gather_block_rows % P
            or bp == 0 or n2 % bp):
        raise ValueError(
            f"{name}: X2 {X2.shape} incompatible with "
            f"pack={P}, d_total={D}, gather_block_rows={gather_block_rows}"
        )
    if bp % 8:
        # TPU tiling: the block's sublane dim must be a multiple of 8
        raise ValueError(
            f"gather_block_rows={gather_block_rows} gives {bp} packed "
            f"rows per block; need a multiple of 8·pack={8 * P} rows"
        )
    return bp


def _ring_scratch(bp: int, pd: int, dtype):
    """The ring's slots and their semaphores (the first two scratch
    operands of both gathered kernels). The depth follows from the
    block's static shape: three slots (two copies in flight; a fourth
    reads the same on the chip) where ``_RING_BYTES`` holds them, never
    fewer than two."""
    block_bytes = bp * pd * jnp.dtype(dtype).itemsize
    slots = max(2, min(3, _RING_BYTES // block_bytes))
    return [pltpu.VMEM((slots, bp, pd), dtype),
            pltpu.SemaphoreType.DMA((slots,))]


def _grad_kernel_gathered(idx_ref, x_hbm, ct_ref, gacc_ref, cnt_out_ref,
                          xbuf, sems, acc_ref, cnt_ref, *, pack: int,
                          n_sampled: int):
    """v4 body: NO on-core sampling — the sampling already happened in
    the *grid*: cell i copies block ``idx_ref[i]`` (scalar-prefetched
    sampled block ids) and no other, so only the minibatch's blocks ever
    leave HBM. Every resident row counts (modulo the packed validity
    column). The block loop is the shared ring + body above."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)

    x_ref = _ring_fetch(idx_ref, x_hbm, xbuf, sems, i, n_sampled)
    _block_grad(x_ref, ct_ref, acc_ref, cnt_ref, pack=pack)

    @pl.when(i == n_sampled - 1)
    def _done():
        gacc_ref[:] = acc_ref[:]
        cnt_out_ref[0, 0] = jnp.sum(cnt_ref[:])


@functools.partial(
    jax.jit,
    static_argnames=("pack", "d_total", "y_col", "v_col",
                     "gather_block_rows", "interpret"),
)
def fused_grad_sum_gathered(X2, w_aug, block_idx, *, pack: int,
                            d_total: int, y_col: int, v_col: int,
                            gather_block_rows: int = 1024,
                            interpret: bool = False):
    """Traffic-proportional (Σ gradient, count): ONE pass over only the
    SAMPLED blocks of X (v4).

    A kernel that samples on the core still streams 100% of X to
    sample a ``fraction`` of it — HBM traffic 1/fraction× what the
    algorithm needs. Here the minibatch is drawn at *block* granularity:
    the caller samples ``block_idx`` (ids of ``gather_block_rows``-row
    blocks, XLA-side PRNG) and the kernel copies exactly those blocks
    (a ring of VMEM slots fed by the scalar-prefetched ids), so traffic
    ≈ fraction × |X| per step. Row-level random gathers are NOT the
    answer on TPU — they serialize; whole-block DMA keeps transfers
    wide.

    Semantics: block-cluster sampling — sampling whole blocks of
    consecutive rows instead of i.i.d. rows (Spark's per-partition
    ``sample`` is the same kind of partition-clustered approximation,
    reference ``ssgd.py:97``). For i.i.d. or pre-shuffled rows
    (``pack_augmented(shuffle_seed=...)``) the sampled-gradient
    distribution is identical to row-level sampling at equal batch size.

    No on-core PRNG → runs under ``interpret=True`` on CPU.
    Returns the (d_total,) gradient (garbage y/v/pad entries — zero via
    the meta col mask) and the kept-row count.
    """
    P, D = pack, d_total
    bp = _gathered_shapes("fused_grad_sum_gathered", X2, P, D,
                          gather_block_rows)
    CT = build_selector_t(w_aug, pack=P, d_total=D, y_col=y_col,
                          v_col=v_col, dtype=X2.dtype)
    n_sampled = block_idx.shape[0]
    kernel = functools.partial(_grad_kernel_gathered, pack=P,
                               n_sampled=n_sampled)
    gacc, cnt = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_sampled,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),      # X2 stays in HBM
                pl.BlockSpec((3 * P, P * D), lambda i, s: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((P, P * D), lambda i, s: (0, 0)),
                pl.BlockSpec((1, 1), lambda i, s: (0, 0),
                             memory_space=pltpu.SMEM),
            ],
            scratch_shapes=_ring_scratch(bp, P * D, X2.dtype) + [
                pltpu.VMEM((P, P * D), jnp.float32),
                pltpu.VMEM((P, _chunk_rows(bp)), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((P, P * D), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
        name="_grad_kernel_gathered",
    )(block_idx.astype(jnp.int32), X2, CT)
    g = jnp.einsum("ccj->j", gacc.reshape(P, P, D))
    return g, cnt[0, 0]


def _train_kernel_gathered(idx_ref, x_hbm, msel_ref, s_ref, st_ref,
                           ew3_ref, eyv_ref, w0_ref, ctr_ref, wout_ref,
                           xbuf, sems, ct_ref, wm_ref, acc_ref, cnt_ref,
                           *, pack: int, eta: float, alpha: float,
                           n_steps: int, n_sampled: int, sel_dtype,
                           skip_update: bool = False):
    """v5 body: T SGD steps in ONE kernel launch (see
    :func:`fused_train_gathered`). Grid (T, n_sampled); the weight
    master ``wm`` (1, P·D) f32 and the selector ``ct`` (3P, P·D) in X2's
    dtype live in VMEM scratch across ALL grid steps, so between-step
    cost is zero — no kernel relaunch, no XLA glue, no HBM round-trip
    for the model state. The ring of sampled blocks runs on across a
    step's end (the update does not depend on the next step's blocks).

    The in-kernel update avoids cross-lane transposes (expensive
    relayouts on TPU) by expressing the gradient fold and the selector
    rebuild as small matmuls/reductions against constant operands:
      y    (P, D)    = (acc ⊙ Msel) · S      — per-slot diagonal band
      grow (1, D)    = Σ_sublanes y          — the gradient, lane-major
      Δw   (1, P·D)  = grow · Sᵀ             — tiled to every slot
      Cᵀ             = bf16(wm ⊙ Ew3ᵀ) + EyEvᵀ — selector rebuilt in place
    """
    t = pl.program_id(0)
    i = pl.program_id(1)
    last_step = t == n_steps - 1

    def rebuild_selector():
        ct_ref[:] = (
            jnp.broadcast_to(wm_ref[:], ct_ref.shape) * ew3_ref[:]
        ).astype(sel_dtype) + eyv_ref[:]

    @pl.when((t == 0) & (i == 0))
    def _first():
        wm_ref[:] = w0_ref[:]
        rebuild_selector()

    @pl.when(i == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)

    x_ref = _ring_fetch(idx_ref, x_hbm, xbuf, sems, t * n_sampled + i,
                        n_steps * n_sampled)
    _block_grad(x_ref, ct_ref, acc_ref, cnt_ref, pack=pack)

    if skip_update:
        # roofline ablation (bench-only): the full gradient pass with
        # the serialized end-of-step update chain removed — the A/B
        # against the real kernel prices that chain exactly
        @pl.when(last_step & (i == n_sampled - 1))
        def _done_abl():
            wout_ref[:] = wm_ref[:]

        return

    @pl.when(i == n_sampled - 1)
    def _update():
        nb = jnp.maximum(jnp.sum(cnt_ref[:]), 1.0)  # empty-sample guard
        yband = jnp.dot(acc_ref[:] * msel_ref[:], s_ref[:],
                        preferred_element_type=jnp.float32)  # (P, D)
        grow = jnp.sum(yband, axis=0, keepdims=True)          # (1, D)
        wm = wm_ref[:] - (eta / nb) * jnp.dot(
            grow, st_ref[:], preferred_element_type=jnp.float32)
        if alpha:
            # EASGD elastic pull toward the round-start center
            # (easgd.py:41-45); both tails are zero, so no column mask
            wm = wm - alpha * (wm_ref[:] - ctr_ref[:])
        wm_ref[:] = wm
        rebuild_selector()

    @pl.when(last_step & (i == n_sampled - 1))
    def _done():
        wout_ref[:] = wm_ref[:]


@functools.partial(
    jax.jit,
    static_argnames=("pack", "d_total", "y_col", "v_col",
                     "gather_block_rows", "eta", "alpha", "interpret",
                     "skip_update"),
)
def fused_train_gathered(X2, w_tile0, block_idx, *, pack: int,
                         d_total: int, y_col: int, v_col: int,
                         gather_block_rows: int, eta: float,
                         alpha: float = 0.0, center_tile=None,
                         interpret: bool = False,
                         skip_update: bool = False):
    """T block-sampled SGD steps in ONE pallas_call (v5, "megakernel").

    The v4 kernel (:func:`fused_grad_sum_gathered`) made HBM traffic
    proportional to the minibatch, but still paid a fixed per-STEP cost:
    one Mosaic launch (~8 µs) plus the XLA update glue (~3 µs) against
    ~33 µs of DMA at bench scale — ~25% of the step. Here the grid is
    ``(T, n_sampled)``: the weight master and the selector live in
    VMEM scratch across the whole schedule, the SGD update runs
    in-kernel at each block-row boundary, and the launch cost amortizes
    over T steps. Per-step work collapses to the minibatch DMA.

    Semantics are EXACTLY the per-step 'fused_gather' path for the
    ``lam=0``, single-data-shard case (the per-step psum is the one
    thing a single kernel cannot do — use 'fused_gather' for dp>1):
    same block-cluster sampling (the caller draws ``block_idx`` with the
    same PRNG), same f32 weight master quantizing to a bf16 selector per
    step, same ``w −= η·g_masked/max(cnt,1)`` update with the y/v/pad
    columns held at zero (baked into the Ew3 mask — valid because the
    augmented w0 tail is zero and its gradient is masked).

    ``w_tile0``: (P·D, 1) f32, the augmented weights tiled per slot
    (``jnp.tile(w_aug, P)[:, None]``). ``block_idx``: (T, n_sampled)
    int32. Returns the final (P·D, 1) weight tile; row j of any slot c
    (``tile[c*D+j, 0]``) is ``w_aug[j]``. (Inside, the master is the
    same tile as a row, (1, P·D): the selector is built transposed.)

    ``alpha``/``center_tile`` add the EASGD elastic pull
    ``w −= α·(w − center)`` per step (``easgd.py:41-45``) — the center
    is fixed for the whole launch, which is exactly a local-SGD round's
    contract (the local-update family fuses its ``n_local`` steps into
    one launch per round; valid at dp>1 because local steps touch no
    interconnect).

    Roofline decomposition (PR 27, one v5e, the benchmark's bigbatch
    shapes: blocks of 512 x 640 bf16 = 655 360 B, 1221 of 12 208 a
    step, µs a block; PERF.md section 6 has the table). The kernel
    before that PR: 1.177 whole, 1.062 with the block pinned (body
    only), 0.875 with the body emptied (copies only): the BODY bound
    it, a serial chain of a forward that streamed x2 through the MXU
    (0.57), a middle of lane slices, a sigmoid over eighth-filled
    registers, a transpose and a scalar count (0.44), and the backward
    (0.33). Streaming a 16-row register through the MXU costs 16
    cycles, latching it about 4, so this body latches x2 in both
    passes: 0.54 pinned. Under it the copies bind: 0.875 with the
    BlockSpec pipeline's one copy in flight behind the landing one
    (0.948 with this body), **0.872 with the ring** (three or four
    slots read the same, two read 0.958) = 751 GB/s, 92% of 819, what a
    sequential read reaches: the order of the blocks costs nothing. The
    end-of-step update chain is 0.5 µs a step (``skip_update=True``
    A/B) and now runs under the next step's copies. A block cannot cost
    less than its bytes; a layout that moves fewer (64 of its 80 B a
    row are needed) is the next step, not a schedule.
    """
    P, D = pack, d_total
    bp = _gathered_shapes("fused_train_gathered", X2, P, D,
                          gather_block_rows)
    T, n_sampled = block_idx.shape

    # constant operands of the in-kernel update (built once per trace;
    # XLA hoists them out of any enclosing scan)
    colmask = (jnp.arange(D) < y_col).astype(jnp.float32)      # (D,)
    eyeP = jnp.eye(P, dtype=jnp.float32)
    # Msel (P, P·D): 1 at [c, c·D+j] for kept j — the diagonal band of
    # the acc tile, with the y/v/pad gradient columns zeroed
    msel = (eyeP[:, :, None] * colmask[None, None, :]).reshape(P, P * D)
    # S (P·D, D): identity stacked P times — folds (·, P·D) to (·, D);
    # its transpose tiles (·, D) to (·, P·D)
    s_tile = jnp.tile(jnp.eye(D, dtype=jnp.float32), (P, 1))
    # Ew3ᵀ (3P, P·D): w-selector ones in the first P rows (colmasked
    # columns); zeros over the Ey/Ev rows
    ew3 = jnp.concatenate(
        [msel, jnp.zeros((2 * P, P * D), jnp.float32)], axis=0)
    # EyEvᵀ (3P, P·D) in X2's dtype: the selector of a zero w
    eyv = build_selector_t(jnp.zeros((D,), jnp.float32), pack=P,
                           d_total=D, y_col=y_col, v_col=v_col,
                           dtype=X2.dtype)

    if center_tile is None:
        center_tile = jnp.zeros((P * D, 1), jnp.float32)
    kernel = functools.partial(
        _train_kernel_gathered, pack=P, eta=eta, alpha=alpha,
        n_steps=T, n_sampled=n_sampled, sel_dtype=X2.dtype,
        skip_update=skip_update)
    whole = lambda t, i, s: (0, 0)  # noqa: E731 — resident constants
    wout = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T, n_sampled),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),     # X2 stays in HBM
                pl.BlockSpec((P, P * D), whole),       # Msel
                pl.BlockSpec((P * D, D), whole),       # S
                pl.BlockSpec((D, P * D), whole),       # Sᵀ
                pl.BlockSpec((3 * P, P * D), whole),   # Ew3ᵀ
                pl.BlockSpec((3 * P, P * D), whole),   # EyEvᵀ
                pl.BlockSpec((1, P * D), whole),       # w_tile0 as a row
                pl.BlockSpec((1, P * D), whole),       # center tile, too
            ],
            out_specs=pl.BlockSpec((1, P * D), whole),
            scratch_shapes=_ring_scratch(bp, P * D, X2.dtype) + [
                pltpu.VMEM((3 * P, P * D), X2.dtype),   # Cᵀ
                pltpu.VMEM((1, P * D), jnp.float32),    # weight master
                pltpu.VMEM((P, P * D), jnp.float32),    # grad acc
                pltpu.VMEM((P, _chunk_rows(bp)), jnp.float32),  # counts
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((1, P * D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
        name="_train_kernel_gathered",
    )(block_idx.astype(jnp.int32).reshape(-1), X2, msel, s_tile,
      s_tile.T, ew3, eyv, w_tile0.reshape(1, P * D),
      center_tile.reshape(1, P * D))
    return wout.reshape(P * D, 1)


def _fwd_kernel_gathered(idx_ref, x_ref, c_ref, zyv_ref):
    """Forward half of the two-pass dp×tp split (see
    :func:`fused_forward_gathered`): one selector matmul per sampled
    block, output streamed per block — no accumulator."""
    del idx_ref
    zyv_ref[:] = jnp.dot(x_ref[:], c_ref[:],
                         preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("pack", "d_total", "y_col", "v_col",
                     "gather_block_rows", "interpret"),
)
def fused_forward_gathered(X2, w_aug, block_idx, *, pack: int,
                           d_total: int, y_col: int, v_col: int,
                           gather_block_rows: int = 1024,
                           interpret: bool = False):
    """Forward-only pass over the SAMPLED blocks: returns
    ``zyv (n_sampled·bp, 3P)`` = [z | y | v] per packed row slot.

    Exists for the dp×tp composition of the gathered sampler
    (SURVEY.md §2.3 row 6): with the feature dim sharded over the mesh
    model axis the residual needs the GLOBAL matvec, so the one-pass
    kernel splits into forward (this) → ``psum(z, 'model')`` → backward
    (:func:`fused_backward_gathered`). Each model shard packs its own
    feature slice WITH the y/v columns replicated (their weight entries
    are pinned to zero, so the partial z never double-counts them) and
    extracts y/v locally — only z crosses the interconnect. The split
    reads the sampled blocks twice; see ``ssgd.SSGDConfig`` for the
    measured cost of that versus pure dp.
    """
    P, D = pack, d_total
    n2, pd = X2.shape
    bp = gather_block_rows // P
    if (pd != P * D or (P * D) % 128 or gather_block_rows % P
            or bp == 0 or n2 % bp or bp % 8):
        raise ValueError(
            f"fused_forward_gathered: X2 {X2.shape} incompatible with "
            f"pack={P}, d_total={D}, gather_block_rows={gather_block_rows}"
        )
    C = build_selector(w_aug, pack=P, d_total=D, y_col=y_col,
                       v_col=v_col, dtype=X2.dtype)
    n_s = block_idx.shape[0]
    zyv = pl.pallas_call(
        _fwd_kernel_gathered,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_s,),
            in_specs=[
                pl.BlockSpec((bp, P * D), lambda i, s: (s[i], 0)),
                pl.BlockSpec((P * D, 3 * P), lambda i, s: (0, 0)),
            ],
            out_specs=pl.BlockSpec((bp, 3 * P), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_s * bp, 3 * P), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(block_idx.astype(jnp.int32), X2, C)
    return zyv


def _bwd_kernel_gathered(idx_ref, x_ref, r_ref, gacc_ref, acc_ref,
                         *, pack: int):
    """Backward half: accumulate residᵀ·x2 over the sampled blocks (the
    resid blocks arrive in sampled order, indexed by the grid step)."""
    del idx_ref
    P = pack
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x2 = x_ref[:]
    acc_ref[:] += jax.lax.dot_general(
        r_ref[:].astype(x2.dtype), x2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        gacc_ref[:] = acc_ref[:]


@functools.partial(
    jax.jit,
    static_argnames=("pack", "d_total", "gather_block_rows", "interpret"),
)
def fused_backward_gathered(X2, resid, block_idx, *, pack: int,
                            d_total: int, gather_block_rows: int = 1024,
                            interpret: bool = False):
    """Backward pass of the dp×tp split: ``g = Σ residᵀ·x2`` over the
    sampled blocks, returning the (d_total,) gradient slice for THIS
    model shard's features. ``resid (n_sampled·bp, P)`` must be in the
    same sampled-block order :func:`fused_forward_gathered` emitted
    (slot r of block i at row ``i·bp + r``)."""
    P, D = pack, d_total
    n2, pd = X2.shape
    bp = gather_block_rows // P
    if (pd != P * D or (P * D) % 128 or gather_block_rows % P
            or bp == 0 or n2 % bp or bp % 8):
        raise ValueError(
            f"fused_backward_gathered: X2 {X2.shape} incompatible with "
            f"pack={P}, d_total={D}, gather_block_rows={gather_block_rows}"
        )
    n_s = block_idx.shape[0]
    if resid.shape != (n_s * bp, P):
        raise ValueError(
            f"resid {resid.shape} != ({n_s * bp}, {P}) sampled layout"
        )
    kernel = functools.partial(_bwd_kernel_gathered, pack=P)
    gacc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_s,),
            in_specs=[
                pl.BlockSpec((bp, P * D), lambda i, s: (s[i], 0)),
                pl.BlockSpec((bp, P), lambda i, s: (i, 0)),
            ],
            out_specs=pl.BlockSpec((P, P * D), lambda i, s: (0, 0)),
            scratch_shapes=[pltpu.VMEM((P, P * D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((P, P * D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(block_idx.astype(jnp.int32), X2, resid)
    return jnp.einsum("ccj->j", gacc.reshape(P, P, D))


def build_selector(w_aug, *, pack: int, d_total: int, y_col: int,
                   v_col: int, dtype=jnp.bfloat16):
    """The fused constant operand C = [Wbig | Ey | Ev], (P·D, 3P):
    ``Wbig[c·D+j, c] = w[j]`` (block-diagonal replication of the weight
    vector — the matvec as a matmul), ``Ey[c·D+y_col, c] = 1`` and
    ``Ev[c·D+v_col, c] = 1`` (per-slot label/validity selectors).
    Rebuilt from ``w`` each step in XLA (~P·D·3P elements, negligible
    next to the X pass)."""
    P, D = pack, d_total
    eyeP = jnp.eye(P, dtype=dtype)
    w_col = w_aug.reshape(-1, 1).astype(dtype)
    wbig = (eyeP[:, None, :] * w_col[None, :, :]).reshape(P * D, P)
    ey = (eyeP[:, None, :] * jax.nn.one_hot(y_col, D, dtype=dtype)[
        None, :, None]).reshape(P * D, P)
    ev = (eyeP[:, None, :] * jax.nn.one_hot(v_col, D, dtype=dtype)[
        None, :, None]).reshape(P * D, P)
    return jnp.concatenate([wbig, ey, ev], axis=1)


def build_selector_t(w_aug, *, pack: int, d_total: int, y_col: int,
                     v_col: int, dtype=jnp.bfloat16):
    """:func:`build_selector`'s operand as the gathered kernels latch
    it, Cᵀ (3P, P·D): row c is ``w`` in slot c's columns, row P+c the
    one at ``c·D+y_col``, row 2P+c the one at ``c·D+v_col``. Built in
    this shape (a transpose of C folded into the dot is a form XLA:CPU
    has no bf16 kernel for, and the kernels run interpreted there)."""
    P, D = pack, d_total
    eyeP = jnp.eye(P, dtype=dtype)
    rows = [w_aug.reshape(-1).astype(dtype),
            jax.nn.one_hot(y_col, D, dtype=dtype),
            jax.nn.one_hot(v_col, D, dtype=dtype)]
    return jnp.concatenate(
        [(eyeP[:, :, None] * r[None, None, :]).reshape(P, P * D)
         for r in rows], axis=0)


