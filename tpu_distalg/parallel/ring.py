"""Ring pipelines over the mesh data axis — sequence/context parallelism.

The reference has no sequences or attention (SURVEY.md §5: longest
"sequence" is a 31-feature row), but the communication layer of a TPU
framework must scale to long-context workloads (ring attention /
all-to-all sequence parallelism), so these are first-class here:

  * ``ring_allgather_matmul`` — A·Bᵀ where both operands are row-sharded:
    B blocks rotate around the ring (``ppermute`` over ICI) while partial
    products accumulate, overlapping communication with MXU compute — the
    standard ICI pipeline (cf. the scaling-book collective-matmul recipe).
  * ``ring_attention`` — exact blockwise attention with online softmax
    accumulation (Liu et al. ring attention; Milakov-Gimelshein online
    softmax): Q stays put, K/V blocks rotate; memory per chip is
    O(S_local²) instead of O(S²), so sequence length scales linearly with
    the ring size. Multi-head and causal decoding are supported — the
    full surface a decoder block needs.
  * ``ulysses_attention`` — DeepSpeed-Ulysses sequence parallelism: one
    ``all_to_all`` re-shards sequence→heads, every chip runs dense
    attention on its own heads over the FULL sequence, and the inverse
    ``all_to_all`` restores sequence sharding. Cheaper in collective
    volume than the ring when the head count divides the axis; the ring
    wins on peak memory (Ulysses materialises full-sequence K/V).

All are shard_map bodies: run them inside ``data_parallel`` with
sequence-sharded operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tpu_distalg.parallel.mesh import DATA_AXIS


def _ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


def ring_allgather_matmul(a_local, b_local, axis_name: str = DATA_AXIS):
    """Per-shard rows of A·Bᵀ with B row-sharded: (Sa_l, d) x (Sb, d)ᵀ.

    Each of the n ring steps multiplies the resident B block (MXU) while the
    next block is in flight (XLA overlaps the ppermute with the dot).
    Returns the (Sa_l, Sb) block of the full product owned by this shard.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    sb = b_local.shape[0]

    def body(i, carry):
        b, out = carry
        # the block currently resident came from shard (my - i) mod n
        src = (my - i) % n
        part = jnp.dot(a_local, b.T, preferred_element_type=jnp.float32)
        out = lax.dynamic_update_slice(out, part, (0, src * sb))
        b = lax.ppermute(b, axis_name, _ring_perm(n))
        return b, out

    out0 = jnp.zeros((a_local.shape[0], n * sb), dtype=jnp.float32)
    _, out = lax.fori_loop(0, n, body, (b_local, out0))
    return out


def _online_update(qh, o, m, l, kh, vh, scale, mask):
    """One online-softmax accumulation step over a resident K/V chunk.

    ``qh``: (H, Sq, d); ``kh, vh``: (H_kv, C, d) with H divisible by
    H_kv — grouped-query KV heads are consumed through a zero-copy
    grouped einsum view (query heads [hk·g, hk·g+g) read KV head hk;
    no KV replication). State ``o``: (H, Sq, d), ``m, l``: (H, Sq).
    ``mask``: (Sq, C) boolean (True = attend) or None. Fully-masked
    rows are handled safely: while ``m`` is still −inf the rescale
    factor and probabilities are forced to 0 instead of
    exp(−inf − −inf) = NaN.
    """
    h, s_q, d_ = qh.shape
    h_kv, c = kh.shape[0], kh.shape[1]
    g = h // h_kv
    if g == 1:
        scores = jnp.einsum(
            "hqd,hkd->hqk", qh, kh, preferred_element_type=jnp.float32
        ) * scale
    else:
        scores = jnp.einsum(
            "hgqd,hkd->hgqk", qh.reshape(h_kv, g, s_q, d_), kh,
            preferred_element_type=jnp.float32,
        ).reshape(h, s_q, c) * scale
    if mask is not None:
        scores = jnp.where(mask[None], scores, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    safe = ~jnp.isneginf(m_new)
    alpha = jnp.where(safe, jnp.exp(m - m_new), 0.0)
    p = jnp.where(
        safe[..., None], jnp.exp(scores - m_new[..., None]), 0.0
    )
    l = l * alpha + jnp.sum(p, axis=-1)
    pv = p.astype(vh.dtype)
    if g == 1:
        upd = jnp.einsum("hqk,hkd->hqd", pv, vh,
                         preferred_element_type=jnp.float32)
    else:
        upd = jnp.einsum(
            "hgqk,hkd->hgqd", pv.reshape(h_kv, g, s_q, c), vh,
            preferred_element_type=jnp.float32,
        ).reshape(h, s_q, d_)
    o = o * alpha[..., None] + upd
    return o, m_new, l


def zigzag_order(n_shards: int, n_rows: int):
    """Row permutation for the balanced causal ring layout: lay a
    global (S, ...) array out as ``x[zigzag_order(n, S)]`` and shard it
    over the ring; shard s then holds global chunks (s, 2n−1−s) — the
    position↔shard map :func:`ring_attention` ``layout='zigzag'``
    expects. ``S`` must divide into 2n equal chunks."""
    if n_rows % (2 * n_shards):
        raise ValueError(
            f"zigzag_order: {n_rows} rows not divisible by "
            f"2·n_shards={2 * n_shards}"
        )
    import numpy as np

    c = n_rows // (2 * n_shards)
    parts = []
    for s in range(n_shards):
        parts.append(np.arange(s * c, (s + 1) * c))
        parts.append(np.arange((2 * n_shards - 1 - s) * c,
                               (2 * n_shards - s) * c))
    return np.concatenate(parts)


def zigzag_inverse(n_shards: int, n_rows: int):
    """Inverse permutation: ``out[zigzag_order] = zigzag_out`` →
    ``zigzag_out[zigzag_inverse]`` is in natural position order."""
    import numpy as np

    p = zigzag_order(n_shards, n_rows)
    inv = np.empty_like(p)
    inv[p] = np.arange(len(p))
    return inv


def ring_attention(q, k, v, axis_name: str = DATA_AXIS, *,
                   scale: float | None = None,
                   kv_chunk: int | None = None,
                   causal: bool = False,
                   use_flash: bool = False,
                   flash_interpret: bool = False,
                   flash_block_q: int = 2048,
                   flash_block_kv: int = 2048,
                   layout: str = "contiguous"):
    """Exact attention over a sequence sharded around the ring.

    ``q, k, v``: (S_local, d) single-head or (S_local, H, d) multi-head
    per shard, sequence-sharded in ring order (shard i holds global
    positions [i·S_local, (i+1)·S_local)). K/V blocks rotate; each
    arrival updates the online-softmax state (running max m, normalizer
    l, accumulator o) so the result is exactly ``softmax(QKᵀ/√d)·V`` over
    the FULL sequence, per head.

    ``causal=True`` applies the decoder mask on GLOBAL positions: query
    p attends to keys ≤ p. Blocks that arrive from a later shard are
    fully masked and skipped outright (``lax.cond`` around the compute —
    the ppermute still runs, keeping the ring in lockstep). The skip
    saves the FLOPs but not the wall-clock imbalance: shard n−1 computes
    n partial blocks while shard 0 computes 1, idling ~half the ring's
    FLOP capacity at n=8. ``layout='zigzag'`` fixes that: each shard
    holds global chunks (s, 2n−1−s) — lay data out with
    :func:`zigzag_order` / undo with :func:`zigzag_inverse` — and each
    ring step decomposes into chunk-pairs of which ONE is statically
    all-attend, one statically skipped, and two conditional, so every
    shard computes exactly 2n+1 chunk-pair tiles (≈2n·c² FLOPs, c the
    half-chunk length) per pass REGARDLESS of position — vs the
    contiguous layout's shard-dependent 1…n full blocks (the striped/
    zigzag context-parallel schedule; cf. llama-3-style zigzag
    sharding). Zigzag requires ``causal=True`` (balanced already when
    non-causal), even local length, and supersedes ``kv_chunk`` (use
    flash blocks to bound memory).

    ``kv_chunk`` bounds the materialised score tile: the resident K/V
    block is processed in flash-attention-style chunks of that many keys
    (a ``lax.scan`` applying the same online-softmax update), so peak
    memory is O(S_local · kv_chunk) per head instead of O(S_local²) — at
    S_local = 32k a full score block is 4 GB and out of HBM, while
    kv_chunk = 1024 keeps it at 128 MB. ``None`` processes whole blocks
    (fine for short sequences; fewer, larger MXU calls).

    ``use_flash=True`` swaps the XLA update for the Pallas flash kernel
    (``ops.pallas_attention.flash_attention_block``): the whole
    QKᵀ→softmax→·V pipeline runs per VMEM-resident tile — same algebra
    and f32 accumulation, much less HBM traffic. Needs head-dim a
    multiple of 128 and block-divisible lengths, supersedes
    ``kv_chunk``. DIFFERENTIABLE end-to-end at flash speed: the custom
    VJP saves (O, logsumexp) from the forward ring and runs a SECOND
    ring of Pallas backward kernels
    (``ops.pallas_attention.flash_attention_backward_block``) — K/V
    blocks rotate again, each step recomputes P from the saved stats
    per VMEM tile and emits (dQ partial, dK/dV of the resident block);
    the dK/dV accumulators travel WITH their blocks so after n steps
    each shard holds its own finished cotangent. Same algebra and f32
    accumulation as differentiating the XLA path, so the gradients are
    exact. Set ``flash_interpret=True`` on CPU meshes (tests).
    """
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring layout {layout!r}")
    if layout == "zigzag":
        if not causal:
            raise ValueError(
                "layout='zigzag' exists to balance the CAUSAL ring; "
                "non-causal rings are balanced already"
            )
        if kv_chunk is not None:
            raise ValueError(
                "layout='zigzag' does not compose with kv_chunk; use "
                "use_flash=True (tiled in VMEM) to bound memory"
            )
        return _ring_attention_zigzag(
            q, k, v, axis_name=axis_name, scale=scale,
            use_flash=use_flash, flash_interpret=flash_interpret,
            bq=flash_block_q, bkv=flash_block_kv,
        )
    if use_flash:
        from tpu_distalg.ops.pallas_attention import BWD_BLOCK_MAX

        bwd_bq = min(flash_block_q, BWD_BLOCK_MAX)
        bwd_bkv = min(flash_block_kv, BWD_BLOCK_MAX)
        impl = functools.partial(
            _ring_attention_impl, axis_name=axis_name, scale=scale,
            kv_chunk=kv_chunk, causal=causal,
            flash_interpret=flash_interpret,
            flash_block_q=flash_block_q, flash_block_kv=flash_block_kv,
        )

        @jax.custom_vjp
        def flash_fn(q, k, v):
            return impl(q, k, v, use_flash=True)

        def _fwd(q, k, v):
            out, lse = impl(q, k, v, use_flash=True, return_stats=True)
            return out, (q, k, v, out, lse)

        def _bwd(res, g):
            qq, kk, vv, out, lse = res
            return _ring_flash_backward(
                qq, kk, vv, out, lse, g, axis_name=axis_name,
                scale=scale, causal=causal,
                flash_interpret=flash_interpret,
                bq=bwd_bq, bkv=bwd_bkv,
            )

        flash_fn.defvjp(_fwd, _bwd)
        return flash_fn(q, k, v)
    return _ring_attention_impl(
        q, k, v, axis_name=axis_name, scale=scale, kv_chunk=kv_chunk,
        causal=causal, use_flash=False,
        flash_interpret=flash_interpret,
        flash_block_q=flash_block_q, flash_block_kv=flash_block_kv,
    )


def _ring_flash_backward(q, k, v, out, lse, g, *, axis_name, scale,
                         causal, flash_interpret, bq, bkv):
    """Ring of flash backward kernels — dK/dV accumulators ride along.

    Forward residuals: ``out`` (normalised, f32, caller layout) and
    ``lse`` (H, S_q, 1) — the FINAL ring-wide logsumexp, so every
    backward tile recomputes the true softmax P independently; no
    rescaling chain crosses ring steps. Each of the n steps feeds the
    resident K/V block and ITS travelling (dk, dv) accumulator through
    ``flash_attention_backward_block``; dQ accumulates locally. The
    rotation count is n, so every (block, accumulator) pair ends the
    loop back on its owner shard. Comm volume is 2× the forward ring
    (4 rotating buffers) — the standard ring-attention backward cost.
    """
    from tpu_distalg.ops.pallas_attention import (
        flash_attention_backward_block,
    )

    single = q.ndim == 2
    if single:
        q, k, v, out, g = (x[:, None, :] for x in (q, k, v, out, g))
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_q, h, d = q.shape
    s_local = k.shape[0]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    qh = jnp.moveaxis(q, 1, 0)                        # (H, Sq, d)
    kh0 = jnp.moveaxis(k, 1, 0)                       # (H_kv, Sl, d)
    vh0 = jnp.moveaxis(v, 1, 0)
    doh = jnp.moveaxis(g, 1, 0).astype(jnp.float32)
    oh = jnp.moveaxis(out, 1, 0).astype(jnp.float32)
    delta = jnp.sum(doh * oh, axis=-1, keepdims=True)  # (H, Sq, 1)

    def body(i, carry):
        kh, vh, dk, dv, dq = carry
        src = (my - i) % n

        def compute(args):
            dq, dk, dv = args
            dq_c, dk_c, dv_c = flash_attention_backward_block(
                qh, kh, vh, doh, lse, delta,
                my * s_q, src * s_local, scale=s, causal=causal,
                bq=bq, bkv=bkv, interpret=flash_interpret,
            )
            return dq + dq_c, dk + dk_c, dv + dv_c

        if causal:
            dq, dk, dv = lax.cond(
                src <= my, compute, lambda a: a, (dq, dk, dv))
        else:
            dq, dk, dv = compute((dq, dk, dv))
        perm = _ring_perm(n)
        kh = lax.ppermute(kh, axis_name, perm)
        vh = lax.ppermute(vh, axis_name, perm)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        return kh, vh, dk, dv, dq

    zeros = functools.partial(jnp.zeros, dtype=jnp.float32)
    _, _, dk, dv, dq = lax.fori_loop(
        0, n, body,
        (kh0, vh0, zeros(kh0.shape), zeros(vh0.shape),
         zeros((h, s_q, d))),
    )
    dq = jnp.moveaxis(dq, 0, 1).astype(q.dtype)
    dk = jnp.moveaxis(dk, 0, 1).astype(k.dtype)
    dv = jnp.moveaxis(dv, 0, 1).astype(v.dtype)
    if single:
        dq, dk, dv = (x[:, 0, :] for x in (dq, dk, dv))
    return dq, dk, dv


def _ring_attention_zigzag(q, k, v, *, axis_name, scale, use_flash,
                           flash_interpret, bq, bkv):
    if not use_flash:
        return _zigzag_impl(
            q, k, v, axis_name=axis_name, scale=scale, use_flash=False,
            flash_interpret=flash_interpret, bq=bq, bkv=bkv)
    impl = functools.partial(
        _zigzag_impl, axis_name=axis_name, scale=scale,
        flash_interpret=flash_interpret, bq=bq, bkv=bkv)

    @jax.custom_vjp
    def flash_fn(q, k, v):
        return impl(q, k, v, use_flash=True)

    def _fwd(q, k, v):
        out, lse = impl(q, k, v, use_flash=True, return_stats=True)
        return out, (q, k, v, out, lse)

    def _bwd(res, g):
        from tpu_distalg.ops.pallas_attention import BWD_BLOCK_MAX

        qq, kk, vv, out, lse = res
        return _zigzag_flash_backward(
            qq, kk, vv, out, lse, g, axis_name=axis_name, scale=scale,
            flash_interpret=flash_interpret,
            bq=min(bq, BWD_BLOCK_MAX), bkv=min(bkv, BWD_BLOCK_MAX))

    flash_fn.defvjp(_fwd, _bwd)
    return flash_fn(q, k, v)


def _zigzag_pairs(my, src, n, c):
    """Global start offsets of the per-step chunk-pairs.

    Shard s holds q/k chunks (s, 2n−1−s) of c rows each. Of the four
    (q-chunk, kv-chunk) pairs per ring step, (C,B) is STATICALLY all-
    masked (C = my ≤ n−1 < B = 2n−1−src) and (D,A) STATICALLY all-
    attend (D = 2n−1−my ≥ n > A = src), leaving two conditional pairs.
    Per full pass shard ``my`` computes n unconditional (D,A) pairs,
    my+1 (C,A) pairs and n−my (D,B) pairs = 2n+1 c²-tiles for EVERY
    shard (≈2n·c² FLOPs after the triangular pairs' tile skip) — the
    balance the contiguous layout lacks.
    """
    qc0 = my * c
    qd0 = (2 * n - 1 - my) * c
    ka0 = src * c
    kb0 = (2 * n - 1 - src) * c
    return qc0, qd0, ka0, kb0


def _zigzag_impl(q, k, v, *, axis_name, scale, use_flash,
                 flash_interpret, bq, bkv, return_stats=False):
    single = q.ndim == 2
    if single:
        q, k, v = (x[:, None, :] for x in (q, k, v))
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_q, h, d = q.shape
    if s_q % 2 or k.shape[0] != s_q:
        raise ValueError(
            f"zigzag ring: local length {s_q} must be even (two "
            f"chunks) and q/k lengths equal (got k {k.shape[0]})"
        )
    if h % k.shape[1]:
        raise ValueError(
            f"ring_attention: {h} query heads not divisible by "
            f"{k.shape[1]} KV heads"
        )
    c = s_q // 2
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    qh = jnp.moveaxis(q, 1, 0)                     # (H, 2c, d)
    qhC, qhD = qh[:, :c], qh[:, c:]

    if use_flash:
        from tpu_distalg.ops.pallas_attention import flash_attention_block

        def upd(qc, kc, vc, st, q0, k0, causal_pair):
            o, m, l = st
            o, m, l = flash_attention_block(
                qc, kc, vc, o, m[..., None], l[..., None], q0, k0,
                scale=s, causal=causal_pair, bq=bq, bkv=bkv,
                interpret=flash_interpret)
            return o, m[..., 0], l[..., 0]
    else:
        def upd(qc, kc, vc, st, q0, k0, causal_pair):
            mask = None
            if causal_pair:
                mask = ((q0 + jnp.arange(c))[:, None]
                        >= (k0 + jnp.arange(c))[None, :])
            return _online_update(qc, *st, kc, vc, s, mask)

    def body(i, carry):
        kh, vh, stC, stD = carry
        src = (my - i) % n
        qc0, qd0, ka0, kb0 = _zigzag_pairs(my, src, n, c)
        kA, vA = kh[:, :c], vh[:, :c]
        kB, vB = kh[:, c:], vh[:, c:]
        stC = lax.cond(
            src <= my,
            lambda st: upd(qhC, kA, vA, st, qc0, ka0, True),
            lambda st: st, stC)
        stD = upd(qhD, kA, vA, stD, qd0, ka0, False)
        stD = lax.cond(
            src >= my,
            lambda st: upd(qhD, kB, vB, st, qd0, kb0, True),
            lambda st: st, stD)
        perm = _ring_perm(n)
        return (lax.ppermute(kh, axis_name, perm),
                lax.ppermute(vh, axis_name, perm), stC, stD)

    def st0():
        return (jnp.zeros((h, c, d), jnp.float32),
                jnp.full((h, c), -jnp.inf, jnp.float32),
                jnp.zeros((h, c), jnp.float32))

    kh0 = jnp.moveaxis(k, 1, 0)
    vh0 = jnp.moveaxis(v, 1, 0)
    _, _, (oC, mC, lC), (oD, mD, lD) = lax.fori_loop(
        0, n, body, (kh0, vh0, st0(), st0()))
    o = jnp.concatenate([oC / lC[..., None], oD / lD[..., None]],
                        axis=1)
    out = jnp.moveaxis(o, 0, 1)                    # (2c, H, d)
    out = out[:, 0, :] if single else out
    if return_stats:
        lse = jnp.concatenate(
            [mC + jnp.log(lC), mD + jnp.log(lD)], axis=1)[..., None]
        return out, lse
    return out


def _zigzag_flash_backward(q, k, v, out, lse, g, *, axis_name, scale,
                           flash_interpret, bq, bkv):
    """Zigzag mirror of :func:`_ring_flash_backward`: the same three
    live chunk-pairs per step, dK/dV accumulators rotating with their
    blocks, dQ accumulating per local chunk."""
    from tpu_distalg.ops.pallas_attention import (
        flash_attention_backward_block,
    )

    single = q.ndim == 2
    if single:
        q, k, v, out, g = (x[:, None, :] for x in (q, k, v, out, g))
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_q, h, d = q.shape
    c = s_q // 2
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    qh = jnp.moveaxis(q, 1, 0)
    kh0 = jnp.moveaxis(k, 1, 0)
    vh0 = jnp.moveaxis(v, 1, 0)
    doh = jnp.moveaxis(g, 1, 0).astype(jnp.float32)
    oh = jnp.moveaxis(out, 1, 0).astype(jnp.float32)
    delta = jnp.sum(doh * oh, axis=-1, keepdims=True)  # (H, 2c, 1)
    qhC, qhD = qh[:, :c], qh[:, c:]
    doC, doD = doh[:, :c], doh[:, c:]
    lseC, lseD = lse[:, :c], lse[:, c:]
    dC, dD = delta[:, :c], delta[:, c:]

    def pair_bwd(qc, kc, vc, do_c, lse_c, delta_c, q0, k0, causal_pair):
        return flash_attention_backward_block(
            qc, kc, vc, do_c, lse_c, delta_c, q0, k0, scale=s,
            causal=causal_pair, bq=bq, bkv=bkv,
            interpret=flash_interpret)

    def body(i, carry):
        kh, vh, dk, dv, dqC, dqD = carry
        src = (my - i) % n
        qc0, qd0, ka0, kb0 = _zigzag_pairs(my, src, n, c)
        kA, vA = kh[:, :c], vh[:, :c]
        kB, vB = kh[:, c:], vh[:, c:]

        def ca(args):
            dqC, dk, dv = args
            dq_c, dk_c, dv_c = pair_bwd(qhC, kA, vA, doC, lseC, dC,
                                        qc0, ka0, True)
            return (dqC + dq_c, dk.at[:, :c].add(dk_c),
                    dv.at[:, :c].add(dv_c))

        dqC, dk, dv = lax.cond(
            src <= my, ca, lambda a: a, (dqC, dk, dv))
        dq_c, dk_c, dv_c = pair_bwd(qhD, kA, vA, doD, lseD, dD,
                                    qd0, ka0, False)
        dqD = dqD + dq_c
        dk = dk.at[:, :c].add(dk_c)
        dv = dv.at[:, :c].add(dv_c)

        def db(args):
            dqD, dk, dv = args
            dq_c, dk_c, dv_c = pair_bwd(qhD, kB, vB, doD, lseD, dD,
                                        qd0, kb0, True)
            return (dqD + dq_c, dk.at[:, c:].add(dk_c),
                    dv.at[:, c:].add(dv_c))

        dqD, dk, dv = lax.cond(
            src >= my, db, lambda a: a, (dqD, dk, dv))
        perm = _ring_perm(n)
        return (lax.ppermute(kh, axis_name, perm),
                lax.ppermute(vh, axis_name, perm),
                lax.ppermute(dk, axis_name, perm),
                lax.ppermute(dv, axis_name, perm), dqC, dqD)

    zeros = functools.partial(jnp.zeros, dtype=jnp.float32)
    _, _, dk, dv, dqC, dqD = lax.fori_loop(
        0, n, body,
        (kh0, vh0, zeros(kh0.shape), zeros(vh0.shape),
         zeros((h, c, d)), zeros((h, c, d))))
    dq = jnp.concatenate([dqC, dqD], axis=1)
    dq = jnp.moveaxis(dq, 0, 1).astype(q.dtype)
    dk = jnp.moveaxis(dk, 0, 1).astype(k.dtype)
    dv = jnp.moveaxis(dv, 0, 1).astype(v.dtype)
    if single:
        dq, dk, dv = (x[:, 0, :] for x in (dq, dk, dv))
    return dq, dk, dv


def _ring_attention_impl(q, k, v, *, axis_name, scale, kv_chunk,
                         causal, use_flash, flash_interpret,
                         flash_block_q, flash_block_kv,
                         return_stats=False):
    single = q.ndim == 2
    if single:
        q, k, v = (x[:, None, :] for x in (q, k, v))
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_q, h, d = q.shape
    if h % k.shape[1]:
        raise ValueError(
            f"ring_attention: {h} query heads not divisible by "
            f"{k.shape[1]} KV heads"
        )
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    qh = jnp.moveaxis(q, 1, 0)                     # (H, Sq, d)
    s_local = k.shape[0]
    if not use_flash and kv_chunk is not None and (
        kv_chunk < 1 or (kv_chunk < s_local and s_local % kv_chunk)
    ):
        # kv_chunk >= s_local harmlessly degrades to whole-block
        # processing (the tile bound is already satisfied); the flash
        # kernel tiles internally and never reads kv_chunk
        raise ValueError(
            f"kv_chunk={kv_chunk} must be >= 1 and divide the local "
            f"K/V length {s_local}"
        )
    q_pos = my * s_q + jnp.arange(s_q)             # global query positions

    if use_flash:
        from tpu_distalg.ops.pallas_attention import flash_attention_block

        def process_block(kh, vh, o, m, l, src):
            o, m, l = flash_attention_block(
                qh, kh, vh, o, m[..., None], l[..., None],
                my * s_q, src * s_local, scale=s, causal=causal,
                bq=flash_block_q, bkv=flash_block_kv,
                interpret=flash_interpret,
            )
            return o, m[..., 0], l[..., 0]
    else:
        def process_block(kh, vh, o, m, l, src):
            # kh, vh: (H_kv, S_local, d) — transposed ONCE before the
            # ring loop; ppermute commutes with the transpose, so
            # blocks rotate in this layout and no per-ring-step
            # relayout is paid. Grouped-query KV heads are consumed by
            # _online_update's grouped einsum view — the ring moves and
            # the update reads only H_kv heads, no replication
            if kv_chunk is None or kv_chunk >= s_local:
                mask = None
                if causal:
                    k_pos = src * s_local + jnp.arange(s_local)
                    mask = q_pos[:, None] >= k_pos[None, :]
                return _online_update(qh, o, m, l, kh, vh, s, mask)
            n_chunks = s_local // kv_chunk
            h_kv = kh.shape[0]
            kc = kh.reshape(h_kv, n_chunks, kv_chunk, d).transpose(
                1, 0, 2, 3)
            vc = vh.reshape(h_kv, n_chunks, kv_chunk, d).transpose(
                1, 0, 2, 3)

            def chunk_step(carry, xs):
                kcc, vcc, c = xs
                mask = None
                if causal:
                    k_pos = (src * s_local + c * kv_chunk
                             + jnp.arange(kv_chunk))
                    mask = q_pos[:, None] >= k_pos[None, :]
                return _online_update(qh, *carry, kcc, vcc, s, mask), None

            (o, m, l), _ = lax.scan(
                chunk_step, (o, m, l), (kc, vc, jnp.arange(n_chunks))
            )
            return o, m, l

    def body(i, carry):
        kh, vh, o, m, l = carry
        # the block currently resident came from shard (my - i) mod n
        src = (my - i) % n
        if causal:
            o, m, l = lax.cond(
                src <= my,
                lambda oml: process_block(kh, vh, *oml, src),
                lambda oml: oml,
                (o, m, l),
            )
        else:
            o, m, l = process_block(kh, vh, o, m, l, src)
        kh = lax.ppermute(kh, axis_name, _ring_perm(n))
        vh = lax.ppermute(vh, axis_name, _ring_perm(n))
        return kh, vh, o, m, l

    o0 = jnp.zeros((h, s_q, d), dtype=jnp.float32)
    m0 = jnp.full((h, s_q), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((h, s_q), dtype=jnp.float32)
    kh0 = jnp.moveaxis(k, 1, 0)                    # (H, S_local, d)
    vh0 = jnp.moveaxis(v, 1, 0)
    _, _, o, m, l = lax.fori_loop(0, n, body, (kh0, vh0, o0, m0, l0))
    out = jnp.moveaxis(o / l[..., None], 0, 1)     # (Sq, H, d)
    out = out[:, 0, :] if single else out
    if return_stats:
        # final ring-wide logsumexp per row, (H, Sq, 1) — the flash
        # backward's recompute anchor
        return out, (m + jnp.log(l))[..., None]
    return out


def softmax_attention(q, k, v, *, scale: float | None = None,
                      causal: bool = False, use_flash: bool = False,
                      flash_interpret: bool = False):
    """Dense reference attention, (S, H, d) × (T, H, d) → (S, H, d).

    Materialises the full (H, S, T) score tensor — the local compute of
    :func:`ulysses_attention` and the oracle the ring variants are tested
    against. ``use_flash=True`` runs the Pallas flash kernel instead
    (tiled, no (H, S, T) materialisation) — DIFFERENTIABLE via the same
    flash backward kernels as the ring path (one "ring step" with both
    offsets 0), so Ulysses-flash trains at flash speed too.
    """
    d = q.shape[-1]
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"softmax_attention: {q.shape[1]} query heads not "
            f"divisible by {k.shape[1]} KV heads"
        )
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    if use_flash:
        from tpu_distalg.ops.pallas_attention import (
            flash_attention_backward_block,
            flash_attention_block,
        )

        def _flash_fwd_stats(q_, k_, v_):
            qh = jnp.moveaxis(q_, 1, 0)               # (H, S, d)
            h, s_q, _ = qh.shape
            o, m, l = flash_attention_block(
                qh, jnp.moveaxis(k_, 1, 0), jnp.moveaxis(v_, 1, 0),
                jnp.zeros((h, s_q, d), jnp.float32),
                jnp.full((h, s_q, 1), -jnp.inf, jnp.float32),
                jnp.zeros((h, s_q, 1), jnp.float32),
                0, 0, scale=s, causal=causal, interpret=flash_interpret,
            )
            return jnp.moveaxis(o / l, 0, 1), m + jnp.log(l)

        @jax.custom_vjp
        def flash_fn(q_, k_, v_):
            return _flash_fwd_stats(q_, k_, v_)[0]

        def _fwd(q_, k_, v_):
            out, lse = _flash_fwd_stats(q_, k_, v_)
            return out, (q_, k_, v_, out, lse)

        def _bwd(res, g):
            q_, k_, v_, out, lse = res
            doh = jnp.moveaxis(g, 1, 0).astype(jnp.float32)
            oh = jnp.moveaxis(out, 1, 0).astype(jnp.float32)
            delta = jnp.sum(doh * oh, axis=-1, keepdims=True)
            dq, dk, dv = flash_attention_backward_block(
                jnp.moveaxis(q_, 1, 0), jnp.moveaxis(k_, 1, 0),
                jnp.moveaxis(v_, 1, 0), doh, lse, delta, 0, 0,
                scale=s, causal=causal, interpret=flash_interpret,
            )
            return (jnp.moveaxis(dq, 0, 1).astype(q_.dtype),
                    jnp.moveaxis(dk, 0, 1).astype(k_.dtype),
                    jnp.moveaxis(dv, 0, 1).astype(v_.dtype))

        flash_fn.defvjp(_fwd, _bwd)
        return flash_fn(q, k, v)
    # grouped-query heads consumed through a zero-copy grouped einsum
    # view, like _online_update — no KV replication on any path
    s_q, h, _ = q.shape
    t, h_kv = k.shape[0], k.shape[1]
    g = h // h_kv
    scores = jnp.einsum(
        "qhgd,khd->hgqk", q.reshape(s_q, h_kv, g, d), k,
        preferred_element_type=jnp.float32,
    ).reshape(h, s_q, t) * s
    if causal:
        mask = jnp.arange(s_q)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(mask[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "hgqk,khd->qhgd", p.astype(v.dtype).reshape(h_kv, g, s_q, t), v,
        preferred_element_type=jnp.float32,
    ).reshape(s_q, h, d)


def ulysses_attention(q, k, v, axis_name: str = DATA_AXIS, *,
                      scale: float | None = None, causal: bool = False,
                      use_flash: bool = False,
                      flash_interpret: bool = False):
    """DeepSpeed-Ulysses sequence-parallel attention.

    ``q, k, v``: (S_local, H, d) sequence-sharded. One ``all_to_all``
    re-shards to (S, H_local, d) — every chip holds the FULL sequence for
    H/n of the heads — attention runs locally per head (positions
    are global, so ``causal`` needs no cross-shard bookkeeping), and the
    inverse exchange restores (S_local, H, d). Exact; requires H
    divisible by the axis size. ``use_flash=True`` runs the local
    attention through the Pallas flash kernel (no full score tensor),
    DIFFERENTIABLE via :func:`softmax_attention`'s flash VJP — the
    cotangents flow back through the inverse exchanges; otherwise peak
    memory is O(S²·H/n) — prefer :func:`ring_attention` when that
    binds.
    """
    qh = alltoall_seq_to_head(q, axis_name)
    kh = alltoall_seq_to_head(k, axis_name)
    vh = alltoall_seq_to_head(v, axis_name)
    o = softmax_attention(qh, kh, vh, scale=scale, causal=causal,
                          use_flash=use_flash,
                          flash_interpret=flash_interpret)
    return alltoall_head_to_seq(o, axis_name)


def _seq_to_head_impl(x, axis_name):
    n = lax.axis_size(axis_name)
    s_l, h, d = x.shape
    if h % n:
        raise ValueError(
            f"alltoall_seq_to_head: head count {h} must be divisible by "
            f"the '{axis_name}' axis size {n}"
        )
    x = x.reshape(s_l, n, h // n, d)
    out = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=0,
                         tiled=False)
    return out.reshape(n * s_l, h // n, d)


def _head_to_seq_impl(x, axis_name):
    n = lax.axis_size(axis_name)
    s, h_l, d = x.shape
    if s % n:
        raise ValueError(
            f"alltoall_head_to_seq: sequence length {s} must be "
            f"divisible by the '{axis_name}' axis size {n}"
        )
    x = x.reshape(n, s // n, h_l, d)
    out = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=1,
                         tiled=False)
    return out.reshape(s // n, n * h_l, d)


# Both exchanges are global orthogonal permutations, so each one's VJP
# is exactly the inverse exchange — declared via custom_vjp because the
# automatic transpose of all_to_all(tiled=False) through the enclosing
# reshapes currently fails Mosaic/XLA verification under shard_map.

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def alltoall_seq_to_head(x, axis_name: str = DATA_AXIS):
    """DeepSpeed-Ulysses-style exchange: (S_local, H, d) sequence-sharded →
    (S, H_local, d) head-sharded, in one all_to_all over the axis."""
    return _seq_to_head_impl(x, axis_name)


alltoall_seq_to_head.defvjp(
    lambda x, axis_name: (_seq_to_head_impl(x, axis_name), None),
    lambda axis_name, _, g: (_head_to_seq_impl(g, axis_name),),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def alltoall_head_to_seq(x, axis_name: str = DATA_AXIS):
    """Inverse of :func:`alltoall_seq_to_head`: (S, H_local, d)
    head-sharded → (S_local, H, d) sequence-sharded, in one all_to_all.
    ``alltoall_head_to_seq(alltoall_seq_to_head(x))`` is the identity."""
    return _head_to_seq_impl(x, axis_name)


alltoall_head_to_seq.defvjp(
    lambda x, axis_name: (_head_to_seq_impl(x, axis_name), None),
    lambda axis_name, _, g: (_seq_to_head_impl(g, axis_name),),
)
