"""Fused matmul + top-k retrieval kernel for the online serving layer.

The ALS recommendation query is ``top-k over q · Vᵀ`` — a (B, d) batch
of user factor vectors scored against the (N, d) item-factor matrix.
The naive XLA spelling materializes the full (B, N) score matrix in HBM
(``B·N·4`` bytes written, then read back by ``lax.top_k``'s sort); at
retrieval scale N is the catalogue (16k-10M items) and the score matrix
is pure traffic — every row is reduced to k winners immediately.

:func:`fused_matmul_topk` keeps the reduction on-chip: the grid walks
the item axis in ``block_items``-row tiles of V, each grid step runs
one MXU matmul ``q · V_blockᵀ → (B, bn)`` and folds the block's scores
into a running (B, k) best-candidates buffer held in VMEM scratch — the
full score vector never exists anywhere, in HBM *or* VMEM. HBM traffic
is exactly one pass over V (the irreducible operand) plus the O(B·k)
result.

Selection semantics are PINNED to ``jax.lax.top_k``: values descending,
ties broken toward the LOWER item index. The in-kernel merge earns the
tie rule explicitly — each of the k selection rounds takes the max
score and, among equal scores, the minimum candidate index — so the
fused kernel, the XLA reference (:func:`xla_matmul_topk`) and the
sharded candidate merge (:func:`merge_topk_pairs`) are exactly
interchangeable (tests/test_serve.py pins equality, crafted ties
included).

Sharding: the kernel scores a LOCAL slice of V; ``index_offset`` maps
local rows to global item ids and ``n_valid`` masks the padded tail to
-inf, so a model-axis shard calls it on its own (N/S, d) slice and
contributes k (value, index) pairs to the cross-shard merge
(``serve/artifacts.py`` rides ``comms.ring_allgather`` — ``8·B·k·(S−1)``
wire bytes instead of an O(N) dense gather).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_distalg.ops.pallas_api import pl, pltpu


_NEG_INF = float("-inf")
_IDX_SENTINEL = 2**31 - 1


def _topk_kernel(s_ref, q_ref, v_ref, val_ref, idx_ref, cand_v, cand_i,
                 *, k: int, kp: int, bn: int):
    """One grid step: score a (bn, d) tile of V against the whole (B, d)
    query block, then merge into the running (B, kp) best buffer.

    ``cand_v``/``cand_i`` scratch is (B, kp + bn): columns [:kp] carry
    the running top-k (slots >= k stay at the -inf/sentinel fill and are
    never selected while a real candidate remains), columns [kp:] are
    refilled with this block's scores. The merge is k unrolled selection
    rounds — max value, min index among ties, then mask the winner —
    which is exactly ``lax.top_k``'s (value desc, index asc) order.
    """
    i = pl.program_id(0)
    B = q_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        cand_v[:, :kp] = jnp.full((B, kp), _NEG_INF, jnp.float32)
        cand_i[:, :kp] = jnp.full((B, kp), _IDX_SENTINEL, jnp.int32)

    # MXU: q (B, d) · v (bn, d)ᵀ → (B, bn) scores for this item tile
    scores = jax.lax.dot_general(
        q_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # local item position within this shard's padded V slice
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, bn), 1) + i * bn
    valid = pos < s_ref[1]                 # n_valid local rows
    gidx = pos + s_ref[0]                  # global item id (shard offset)
    cand_v[:, kp:] = jnp.where(valid, scores, _NEG_INF)
    cand_i[:, kp:] = jnp.where(valid, gidx, _IDX_SENTINEL)

    cv, ci = cand_v[:], cand_i[:]
    new_v = jnp.full((B, kp), _NEG_INF, jnp.float32)
    new_i = jnp.full((B, kp), _IDX_SENTINEL, jnp.int32)
    colk = jax.lax.broadcasted_iota(jnp.int32, (B, kp), 1)
    for j in range(k):
        m = jnp.max(cv, axis=1, keepdims=True)
        sel = jnp.min(
            jnp.where(cv == m, ci, _IDX_SENTINEL), axis=1, keepdims=True)
        new_v = jnp.where(colk == j, m, new_v)
        new_i = jnp.where(colk == j, sel, new_i)
        # real candidate indices are unique; only the exhausted case
        # selects the sentinel, and masking every sentinel then is inert
        cv = jnp.where(ci == sel, _NEG_INF, cv)
    cand_v[:, :kp] = new_v
    cand_i[:, :kp] = new_i

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        val_ref[:] = cand_v[:, :kp]
        idx_ref[:] = cand_i[:, :kp]


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_items", "interpret"),
)
def fused_matmul_topk(Q, V, index_offset, n_valid, *, k: int,
                      block_items: int = 1024, interpret: bool = False):
    """Top-k of ``Q · Vᵀ`` without materializing the score matrix.

    ``Q`` (B, d) f32 queries, ``V`` (Nl, d) f32 item factors (a local
    shard slice is fine). ``index_offset`` (traced scalar) maps local V
    rows to global item ids; ``n_valid`` (traced scalar) is the count of
    REAL local rows — rows at or past it (zero padding) are masked to
    -inf and can never be selected. Returns ``(values (B, k) f32,
    indices (B, k) int32)`` in ``lax.top_k`` order (value descending,
    ties toward the lower index). When fewer than k valid items exist,
    the tail is (-inf, 2³¹−1).

    Geometry is padded internally: B to a sublane multiple, d to a lane
    multiple, Nl to a ``block_items`` multiple (``block_items`` itself
    must be a lane multiple) — all padding provably inert (zero rows
    masked by ``n_valid``; zero feature columns contribute 0 to every
    dot product).
    """
    B, d = Q.shape
    nl, dv = V.shape
    if dv != d:
        raise ValueError(f"Q {Q.shape} vs V {V.shape}: feature dims differ")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if block_items % 128:
        raise ValueError(
            f"block_items must be a 128 multiple, got {block_items}")
    kp = -(-k // 128) * 128
    bn = block_items
    b_pad = (-B) % 8
    d_pad = (-d) % 128
    n_pad = (-nl) % bn
    if b_pad or d_pad:
        Q = jnp.pad(Q.astype(jnp.float32), ((0, b_pad), (0, d_pad)))
    else:
        Q = Q.astype(jnp.float32)
    if n_pad or d_pad:
        V = jnp.pad(V.astype(jnp.float32), ((0, n_pad), (0, d_pad)))
    else:
        V = V.astype(jnp.float32)
    Bp, dt = Q.shape
    nt = V.shape[0]

    s = jnp.stack([jnp.asarray(index_offset, jnp.int32),
                   jnp.asarray(n_valid, jnp.int32)])
    kernel = functools.partial(_topk_kernel, k=k, kp=kp, bn=bn)
    vals, idx = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nt // bn,),
            in_specs=[
                pl.BlockSpec((Bp, dt), lambda i, s: (0, 0)),
                pl.BlockSpec((bn, dt), lambda i, s: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((Bp, kp), lambda i, s: (0, 0)),
                pl.BlockSpec((Bp, kp), lambda i, s: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((Bp, kp + bn), jnp.float32),
                pltpu.VMEM((Bp, kp + bn), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bp, kp), jnp.float32),
            jax.ShapeDtypeStruct((Bp, kp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(s, Q, V)
    vals, idx = vals[:B, :k], idx[:B, :k]
    # exhausted slots (fewer than k valid items) keep the index of an
    # already-taken candidate after the in-kernel masking — normalize
    # the -inf tail to the sentinel, matching xla_matmul_topk
    return vals, jnp.where(vals == _NEG_INF, _IDX_SENTINEL, idx)


@functools.partial(jax.jit, static_argnames=("k",))
def xla_matmul_topk(Q, V, index_offset, n_valid, *, k: int):
    """The XLA reference/fallback: full ``(B, Nl)`` score matrix then
    ``lax.top_k`` — same contract as :func:`fused_matmul_topk` (global
    ids via ``index_offset``, padded rows masked by ``n_valid``, ties
    toward the lower index). This is also the serving predictor on
    non-TPU backends, where the interpret-mode kernel cannot compete
    with native XLA."""
    scores = jnp.matmul(Q.astype(jnp.float32), V.astype(jnp.float32).T)
    col = jnp.arange(V.shape[0], dtype=jnp.int32)
    scores = jnp.where(col[None, :] < n_valid, scores, _NEG_INF)
    if k > V.shape[0]:
        # honor the fused kernel's fewer-than-k tail contract
        pad = k - V.shape[0]
        scores = jnp.pad(scores, ((0, 0), (0, pad)),
                         constant_values=_NEG_INF)
        col = jnp.pad(col, (0, pad), constant_values=_IDX_SENTINEL)
    vals, local = jax.lax.top_k(scores, k)
    gidx = col[local] + jnp.asarray(index_offset, jnp.int32)
    gidx = jnp.where(vals == _NEG_INF, _IDX_SENTINEL, gidx)
    return vals, gidx


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk_pairs(all_vals, all_idx, *, k: int):
    """Merge per-shard top-k candidate pairs into the global top-k.

    ``all_vals``/``all_idx`` are (S, B, K) — shard-major stacks as
    returned by ``comms.ring_allgather`` of each shard's local
    (values, indices). Sorted by (value descending, index ascending) via
    a two-key ``lax.sort``, so the result is exactly what
    :func:`xla_matmul_topk` over the concatenated catalogue returns —
    shard windows are disjoint, so no index appears twice. Replicated
    inputs give replicated (bitwise-identical) outputs; no collective
    runs here."""
    S, B, K = all_vals.shape
    v = jnp.moveaxis(all_vals, 0, 1).reshape(B, S * K)
    i = jnp.moveaxis(all_idx, 0, 1).reshape(B, S * K)
    neg_v, idx = jax.lax.sort((-v, i), num_keys=2)
    return -neg_v[:, :k], idx[:, :k]
