"""Ring-pipeline correctness: sequence-parallel results must equal the
single-device dense computation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpu_distalg.parallel import data_parallel, parallelize
from tpu_distalg.parallel.ring import (
    alltoall_head_to_seq,
    alltoall_seq_to_head,
    ring_allgather_matmul,
    ring_attention,
    ulysses_attention,
)


def _dense_attention(q, k, v, causal=False):
    """NumPy oracle: (S, H, d) multi-head (or (S, d) single-head)
    softmax(QKᵀ/√d)·V with an optional causal mask on positions."""
    single = q.ndim == 2
    if single:
        q, k, v = (x[:, None, :] for x in (q, k, v))
    d = q.shape[-1]
    scores = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    if causal:
        mask = np.arange(q.shape[0])[:, None] >= np.arange(k.shape[0])
        scores = np.where(mask[None], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    out = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)
    return out[:, 0, :] if single else out


def test_ring_allgather_matmul(mesh8):
    rng = np.random.default_rng(0)
    S, d = 64, 16
    A = rng.normal(size=(S, d)).astype(np.float32)
    B = rng.normal(size=(S, d)).astype(np.float32)
    As, Bs = parallelize(A, mesh8), parallelize(B, mesh8)

    f = data_parallel(
        ring_allgather_matmul, mesh8,
        in_specs=(P("data", None), P("data", None)),
        out_specs=P("data", None),
    )
    out = np.asarray(jax.jit(f)(As.data, Bs.data))
    np.testing.assert_allclose(out, A @ B.T, rtol=1e-4, atol=1e-4)


def test_ring_attention_matches_dense(mesh8):
    rng = np.random.default_rng(1)
    S, d = 128, 32
    q = rng.normal(size=(S, d)).astype(np.float32)
    k = rng.normal(size=(S, d)).astype(np.float32)
    v = rng.normal(size=(S, d)).astype(np.float32)

    # dense reference
    scores = (q @ k.T) / np.sqrt(d)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    expect = (p / p.sum(-1, keepdims=True)) @ v

    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    f = data_parallel(
        ring_attention, mesh8,
        in_specs=(P("data", None),) * 3,
        out_specs=P("data", None),
    )
    out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


def test_ring_attention_long_sequence_stability(mesh8):
    """Large logits: online softmax must not overflow (the same stability
    class of bug as the reference's sigmoid, SURVEY.md §5)."""
    rng = np.random.default_rng(2)
    S, d = 64, 8
    q = (rng.normal(size=(S, d)) * 30).astype(np.float32)
    k = (rng.normal(size=(S, d)) * 30).astype(np.float32)
    v = rng.normal(size=(S, d)).astype(np.float32)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    f = data_parallel(
        ring_attention, mesh8,
        in_specs=(P("data", None),) * 3,
        out_specs=P("data", None),
    )
    out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
    assert np.isfinite(out).all()


def test_alltoall_seq_to_head(mesh8):
    rng = np.random.default_rng(3)
    S, H, d = 64, 8, 4
    x = rng.normal(size=(S, H, d)).astype(np.float32)
    xs = parallelize(x, mesh8)
    f = data_parallel(
        alltoall_seq_to_head, mesh8,
        in_specs=(P("data", None, None),),
        out_specs=P(None, "data", None),
    )
    out = np.asarray(jax.jit(f)(xs.data))
    assert out.shape == (S, H, d)
    np.testing.assert_allclose(out, x, rtol=1e-6)


def test_ring_attention_kv_chunked_matches_unchunked(mesh8):
    """Flash-style kv chunking is a pure memory optimization: results
    match whole-block processing and the dense reference."""
    import functools

    rng = np.random.default_rng(3)
    S, d = 128, 16
    q = rng.normal(size=(S, d)).astype(np.float32)
    k = rng.normal(size=(S, d)).astype(np.float32)
    v = rng.normal(size=(S, d)).astype(np.float32)
    scores = (q @ k.T) / np.sqrt(d)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    expect = (p / p.sum(-1, keepdims=True)) @ v

    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    for chunk in (4, 8, 16):  # S_local = 16 over 8 shards
        f = data_parallel(
            functools.partial(ring_attention, kv_chunk=chunk), mesh8,
            in_specs=(P("data", None),) * 3,
            out_specs=P("data", None),
        )
        out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
        np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


def test_ring_attention_kv_chunk_validation(mesh8):
    import functools

    import pytest

    rng = np.random.default_rng(4)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    qs = parallelize(x, mesh8)
    f = data_parallel(
        functools.partial(ring_attention, kv_chunk=3), mesh8,
        in_specs=(P("data", None),) * 3,
        out_specs=P("data", None),
    )
    with pytest.raises(ValueError, match="kv_chunk"):
        jax.jit(f)(qs.data, qs.data, qs.data)


def test_ring_attention_kv_chunk_oversized_degrades(mesh8):
    """kv_chunk larger than S_local processes whole blocks (the tile
    bound is already met) instead of erroring."""
    import functools

    rng = np.random.default_rng(5)
    S, d = 64, 8
    q = rng.normal(size=(S, d)).astype(np.float32)
    k = rng.normal(size=(S, d)).astype(np.float32)
    v = rng.normal(size=(S, d)).astype(np.float32)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    f = data_parallel(
        functools.partial(ring_attention, kv_chunk=4096), mesh8,
        in_specs=(P("data", None),) * 3,
        out_specs=P("data", None),
    )
    g = data_parallel(
        ring_attention, mesh8,
        in_specs=(P("data", None),) * 3,
        out_specs=P("data", None),
    )
    np.testing.assert_allclose(
        np.asarray(jax.jit(f)(qs.data, ks.data, vs.data)),
        np.asarray(jax.jit(g)(qs.data, ks.data, vs.data)),
        rtol=1e-6)


def test_ring_attention_multihead_matches_dense(mesh8):
    rng = np.random.default_rng(6)
    S, H, d = 64, 4, 16
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k = rng.normal(size=(S, H, d)).astype(np.float32)
    v = rng.normal(size=(S, H, d)).astype(np.float32)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    f = data_parallel(
        ring_attention, mesh8,
        in_specs=(P("data", None, None),) * 3,
        out_specs=P("data", None, None),
    )
    out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
    np.testing.assert_allclose(
        out, _dense_attention(q, k, v), rtol=2e-4, atol=2e-4)


def test_ring_attention_causal_matches_dense(mesh8):
    """Decoder mask on GLOBAL positions: cross-shard blocks from later
    shards contribute nothing; the own-shard block is triangular."""
    import functools

    rng = np.random.default_rng(7)
    S, d = 64, 8
    q = rng.normal(size=(S, d)).astype(np.float32)
    k = rng.normal(size=(S, d)).astype(np.float32)
    v = rng.normal(size=(S, d)).astype(np.float32)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    f = data_parallel(
        functools.partial(ring_attention, causal=True), mesh8,
        in_specs=(P("data", None),) * 3,
        out_specs=P("data", None),
    )
    out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
    np.testing.assert_allclose(
        out, _dense_attention(q, k, v, causal=True), rtol=2e-4, atol=2e-4)


def test_ring_attention_causal_multihead_chunked(mesh8):
    """causal x multi-head x kv_chunk all compose: the chunked mask is
    offset by chunk position inside the rotating block."""
    import functools

    rng = np.random.default_rng(8)
    S, H, d = 128, 2, 8
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k = rng.normal(size=(S, H, d)).astype(np.float32)
    v = rng.normal(size=(S, H, d)).astype(np.float32)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    expect = _dense_attention(q, k, v, causal=True)
    for chunk in (4, 8):  # S_local = 16 over 8 shards
        f = data_parallel(
            functools.partial(ring_attention, causal=True,
                              kv_chunk=chunk), mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )
        out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
        np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


def test_alltoall_head_to_seq_roundtrip(mesh8):
    rng = np.random.default_rng(9)
    S, H, d = 64, 8, 4
    x = rng.normal(size=(S, H, d)).astype(np.float32)
    xs = parallelize(x, mesh8)

    def roundtrip(x_local):
        return alltoall_head_to_seq(alltoall_seq_to_head(x_local))

    f = data_parallel(
        roundtrip, mesh8,
        in_specs=(P("data", None, None),),
        out_specs=P("data", None, None),
    )
    out = np.asarray(jax.jit(f)(xs.data))
    np.testing.assert_allclose(out, x, rtol=1e-6)


def test_ulysses_attention_matches_dense(mesh8):
    import functools

    rng = np.random.default_rng(10)
    S, H, d = 64, 8, 16  # H == axis size: one head per chip
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k = rng.normal(size=(S, H, d)).astype(np.float32)
    v = rng.normal(size=(S, H, d)).astype(np.float32)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    for causal in (False, True):
        f = data_parallel(
            functools.partial(ulysses_attention, causal=causal), mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )
        out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
        np.testing.assert_allclose(
            out, _dense_attention(q, k, v, causal=causal),
            rtol=2e-4, atol=2e-4)


def test_ulysses_matches_ring(mesh8):
    """The two sequence-parallel strategies are exact: they agree with
    each other bit-for-tolerance on the same inputs."""
    import functools

    rng = np.random.default_rng(11)
    S, H, d = 64, 8, 8
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k = rng.normal(size=(S, H, d)).astype(np.float32)
    v = rng.normal(size=(S, H, d)).astype(np.float32)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    outs = []
    for fn in (functools.partial(ring_attention, causal=True),
               functools.partial(ulysses_attention, causal=True)):
        f = data_parallel(
            fn, mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )
        outs.append(np.asarray(jax.jit(f)(qs.data, ks.data, vs.data)))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)


def test_attention_gradients_match_dense(mesh8):
    """Both sequence-parallel attentions are trainable: reverse-mode
    gradients flow through the ring's ppermute/fori_loop and through
    Ulysses' custom-VJP exchanges (each all_to_all is an orthogonal
    permutation — its VJP is the inverse exchange), matching the dense
    oracle's gradients."""
    import functools

    rng = np.random.default_rng(12)
    S, H, d = 64, 8, 8
    q, k, v = (rng.normal(size=(S, H, d)).astype(np.float32)
               for _ in range(3))
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))

    def dense_loss(q_, k_, v_):
        s = np.sqrt(np.float32(d))
        sc = jnp.einsum("qhd,khd->hqk", q_, k_) / s
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.sum(jnp.einsum("hqk,khd->qhd", p, v_) ** 2)

    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    for fn in (functools.partial(ring_attention, causal=True),
               functools.partial(ulysses_attention, causal=True)):
        f = data_parallel(
            fn, mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )

        def loss(q_, k_, v_):
            return jnp.sum(f(q_, k_, v_) ** 2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
            qs.data, ks.data, vs.data)
        for got, want in zip(g, gd):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_flash_ring_gradients_match_xla_path(mesh8):
    """use_flash is trainable: its custom VJP runs the backward through
    the exact XLA ring, so gradients equal the XLA path's gradients
    (which themselves match the dense oracle)."""
    import functools

    rng = np.random.default_rng(17)
    S, H, d = 1024, 2, 128
    q, k, v = (rng.normal(size=(S, H, d)).astype(np.float32)
               for _ in range(3))
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    grads = []
    for kw in (dict(), dict(use_flash=True, flash_interpret=True,
                            flash_block_q=128, flash_block_kv=128)):
        f = data_parallel(
            functools.partial(ring_attention, causal=True, **kw),
            mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )

        def loss(q_, k_, v_):
            return jnp.sum(f(q_, k_, v_) ** 2)

        grads.append(jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
            qs.data, ks.data, vs.data))
    for got, want in zip(grads[1], grads[0]):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_ulysses_flash_gradients_match_dense(mesh8):
    """Ulysses with use_flash is trainable end-to-end: the flash
    backward kernels run as softmax_attention's custom VJP and the
    cotangents flow back through the inverse all_to_all exchanges,
    matching the dense oracle's gradients."""
    import functools

    rng = np.random.default_rng(19)
    S, H, d = 512, 8, 128
    q, k, v = (rng.normal(size=(S, H, d)).astype(np.float32)
               for _ in range(3))
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))

    def dense_loss(q_, k_, v_):
        s = np.sqrt(np.float32(d))
        sc = jnp.einsum("qhd,khd->hqk", q_, k_) / s
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.sum(jnp.einsum("hqk,khd->qhd", p, v_) ** 2)

    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    f = data_parallel(
        functools.partial(ulysses_attention, causal=True,
                          use_flash=True, flash_interpret=True),
        mesh8,
        in_specs=(P("data", None, None),) * 3,
        out_specs=P("data", None, None),
    )

    def loss(q_, k_, v_):
        return jnp.sum(f(q_, k_, v_) ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
        qs.data, ks.data, vs.data)
    for got, want in zip(g, gd):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_flash_ring_gradients_noncausal_multitile(mesh8):
    """Non-causal flash backward with multi-tile grids per ring step
    (s_local=256 over 128-blocks → 2×2 backward tiles) AND grouped
    query heads (H=2, H_kv=1): exercises the dq/dkv accumulator
    init-store across inner grid axes, the dkv kernel's group-folded
    inner axis, and the no-causal-skip path at once."""
    import functools

    rng = np.random.default_rng(20)
    S, H, H_kv, d = 2048, 2, 1, 128
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k, v = (rng.normal(size=(S, H_kv, d)).astype(np.float32)
            for _ in range(2))
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    grads = []
    for kw in (dict(), dict(use_flash=True, flash_interpret=True,
                            flash_block_q=128, flash_block_kv=128)):
        f = data_parallel(
            functools.partial(ring_attention, **kw), mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )

        def loss(q_, k_, v_):
            return jnp.sum(f(q_, k_, v_) ** 2)

        grads.append(jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
            qs.data, ks.data, vs.data))
    for got, want in zip(grads[1], grads[0]):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_flash_backward_block_halves_to_divisor():
    """The backward wrapper must halve a non-dividing block down to a
    divisor instead of raising (regression: the removed XLA-backward
    fallback handled any length): s=384 with bq=bkv=256 halves to 128,
    and the halved-block gradients equal the directly-sized ones."""
    from tpu_distalg.ops.pallas_attention import (
        flash_attention_backward_block,
        flash_attention_block,
    )

    rng = np.random.default_rng(21)
    H, S, d = 1, 384, 128
    qh, kh, vh = (jnp.asarray(rng.normal(size=(H, S, d)), jnp.float32)
                  for _ in range(3))
    o0 = jnp.zeros((H, S, d), jnp.float32)
    m0 = jnp.full((H, S, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((H, S, 1), jnp.float32)
    o, m, l = flash_attention_block(
        qh, kh, vh, o0, m0, l0, 0, 0, scale=1.0 / np.sqrt(d),
        causal=True, bq=128, bkv=128, interpret=True)
    lse = m + jnp.log(l)
    out = o / l
    do = jnp.asarray(rng.normal(size=(H, S, d)), jnp.float32)
    delta = jnp.sum(do * out, axis=-1, keepdims=True)
    # independent oracle: autodiff through dense causal attention (NOT
    # another kernel config, which would compare the halved kernel to
    # itself)
    def dense(q_, k_, v_):
        sc = jnp.einsum("hqd,hkd->hqk", q_, k_) / np.sqrt(d)
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v_)

    _, vjp = jax.vjp(dense, qh, kh, vh)
    want = vjp(do)
    got = flash_attention_backward_block(
        qh, kh, vh, do, lse, delta, 0, 0, scale=1.0 / np.sqrt(d),
        causal=True, bq=256, bkv=256,  # 256 ∤ 384 -> halves to 128
        interpret=True)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ring_attention_flash_matches_dense(mesh8):
    """The Pallas flash kernel path (interpret mode on CPU) is the same
    online-softmax algebra: matches the dense oracle and the XLA path
    for causal and full attention. Small flash blocks force MULTI-tile
    grids per ring step (s_local=512 over bq=bkv=128 → 4×4 tiles), so
    the j==0 carry load / last-j store and the causal tile-skip guard
    are exercised, not just the 1×1 degenerate grid."""
    import functools

    rng = np.random.default_rng(13)
    S, H, d = 4096, 2, 128
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k = rng.normal(size=(S, H, d)).astype(np.float32)
    v = rng.normal(size=(S, H, d)).astype(np.float32)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    for causal in (False, True):
        f = data_parallel(
            functools.partial(ring_attention, causal=causal,
                              use_flash=True, flash_interpret=True,
                              flash_block_q=128, flash_block_kv=128),
            mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )
        out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
        np.testing.assert_allclose(
            out, _dense_attention(q, k, v, causal=causal),
            rtol=2e-4, atol=2e-4, err_msg=f"causal={causal}")


def test_ulysses_attention_flash_matches_dense(mesh8):
    """Ulysses with the flash kernel as its local attention (interpret
    mode; default 2048-tile blocks degrade to one tile at S=512)."""
    import functools

    rng = np.random.default_rng(14)
    S, H, d = 512, 8, 128
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k = rng.normal(size=(S, H, d)).astype(np.float32)
    v = rng.normal(size=(S, H, d)).astype(np.float32)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    for causal in (False, True):
        f = data_parallel(
            functools.partial(ulysses_attention, causal=causal,
                              use_flash=True, flash_interpret=True),
            mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )
        out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
        np.testing.assert_allclose(
            out, _dense_attention(q, k, v, causal=causal),
            rtol=2e-4, atol=2e-4, err_msg=f"causal={causal}")


def test_ring_attention_flash_gqa_matches_dense(mesh8):
    """Grouped-query attention through the flash kernel: query head h
    reads KV head h // group straight from the block index map — the
    oracle is dense attention with KV heads repeated."""
    import functools

    rng = np.random.default_rng(15)
    S, H, H_kv, d = 1024, 8, 2, 128
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k = rng.normal(size=(S, H_kv, d)).astype(np.float32)
    v = rng.normal(size=(S, H_kv, d)).astype(np.float32)
    k_rep = np.repeat(k, H // H_kv, axis=1)
    v_rep = np.repeat(v, H // H_kv, axis=1)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    for causal in (False, True):
        f = data_parallel(
            functools.partial(ring_attention, causal=causal,
                              use_flash=True, flash_interpret=True,
                              flash_block_q=128, flash_block_kv=128),
            mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )
        out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
        np.testing.assert_allclose(
            out, _dense_attention(q, k_rep, v_rep, causal=causal),
            rtol=2e-4, atol=2e-4, err_msg=f"causal={causal}")


def test_ring_attention_gqa_xla_path_matches_dense(mesh8):
    """GQA on the XLA path too: the ring rotates only the H_kv heads
    and broadcasts per resident block; Ulysses broadcasts in its local
    attention. Both match the repeated-KV dense oracle."""
    import functools

    rng = np.random.default_rng(16)
    # Ulysses additionally needs H_kv divisible by the axis size (the
    # KV exchange head-shards), so 16 query / 8 KV heads over 8 shards
    S, H, H_kv, d = 64, 16, 8, 16
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k = rng.normal(size=(S, H_kv, d)).astype(np.float32)
    v = rng.normal(size=(S, H_kv, d)).astype(np.float32)
    k_rep = np.repeat(k, H // H_kv, axis=1)
    v_rep = np.repeat(v, H // H_kv, axis=1)
    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    for fn in (functools.partial(ring_attention, causal=True),
               functools.partial(ring_attention, causal=True,
                                 kv_chunk=4),
               functools.partial(ulysses_attention, causal=True)):
        f = data_parallel(
            fn, mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )
        out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))
        np.testing.assert_allclose(
            out, _dense_attention(q, k_rep, v_rep, causal=True),
            rtol=2e-4, atol=2e-4)


def test_gqa_gradients_match_repeated_kv_oracle(mesh8):
    """GQA backward: dk/dv cotangents group-sum over the query heads
    sharing each KV head. Checked for the XLA ring AND the flash VJP
    against the dense repeated-KV oracle (whose dk/dv are summed over
    the repeats)."""
    import functools

    rng = np.random.default_rng(18)
    S, H, H_kv, d = 1024, 4, 2, 128  # s_local=128: bkv's lane minimum
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k = rng.normal(size=(S, H_kv, d)).astype(np.float32)
    v = rng.normal(size=(S, H_kv, d)).astype(np.float32)
    g = H // H_kv

    def dense_loss(q_, k_, v_):
        kr = jnp.repeat(k_, g, axis=1)
        vr = jnp.repeat(v_, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q_, kr) / np.sqrt(np.float32(d))
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.sum(jnp.einsum("hqk,khd->qhd", p, vr) ** 2)

    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    qs, ks, vs = (parallelize(x, mesh8) for x in (q, k, v))
    for kw in (dict(), dict(use_flash=True, flash_interpret=True,
                            flash_block_q=64, flash_block_kv=128)):
        f = data_parallel(
            functools.partial(ring_attention, causal=True, **kw),
            mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )

        def loss(q_, k_, v_):
            return jnp.sum(f(q_, k_, v_) ** 2)

        got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
            qs.data, ks.data, vs.data)
        for a, b in zip(got, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                err_msg=f"kw={kw}")


def test_zigzag_causal_ring_matches_dense(mesh8):
    """layout='zigzag' (shard s holds global chunks (s, 2n-1-s)): the
    balanced causal ring equals the dense oracle after undoing the
    layout, on the XLA path and the flash path."""
    import functools

    from tpu_distalg.parallel.ring import zigzag_inverse, zigzag_order

    rng = np.random.default_rng(22)
    S, H, d = 2048, 2, 128
    q, k, v = (rng.normal(size=(S, H, d)).astype(np.float32)
               for _ in range(3))
    expect = _dense_attention(q, k, v, causal=True)
    p = zigzag_order(8, S)
    inv = zigzag_inverse(8, S)
    qs, ks, vs = (parallelize(x[p], mesh8) for x in (q, k, v))
    for kw in (dict(), dict(use_flash=True, flash_interpret=True,
                            flash_block_q=128, flash_block_kv=128)):
        f = data_parallel(
            functools.partial(ring_attention, causal=True,
                              layout="zigzag", **kw),
            mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )
        out = np.asarray(jax.jit(f)(qs.data, ks.data, vs.data))[inv]
        np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4,
                                   err_msg=f"kw={kw}")


def test_zigzag_gradients_match_dense(mesh8):
    """Zigzag backward matches the dense oracle's gradients after
    undoing the layout, on BOTH paths: the flash custom VJP (three
    chunk-pair kernels per step, dK/dV accumulators riding the ring)
    and plain autodiff through the XLA _zigzag_impl's cond/fori
    structure. GQA composes (H=2 query, 1 KV head)."""
    import functools

    from tpu_distalg.parallel.ring import zigzag_inverse, zigzag_order

    rng = np.random.default_rng(23)
    S, H, H_kv, d = 2048, 2, 1, 128
    q = rng.normal(size=(S, H, d)).astype(np.float32)
    k, v = (rng.normal(size=(S, H_kv, d)).astype(np.float32)
            for _ in range(2))
    g = H // H_kv

    def dense_loss(q_, k_, v_):
        kr = jnp.repeat(k_, g, axis=1)
        vr = jnp.repeat(v_, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q_, kr) / np.sqrt(np.float32(d))
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.sum(jnp.einsum("hqk,khd->qhd", pr, vr) ** 2)

    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    p = zigzag_order(8, S)
    inv = zigzag_inverse(8, S)
    qs, ks, vs = (parallelize(x[p], mesh8) for x in (q, k, v))
    for kw in (dict(use_flash=True, flash_interpret=True,
                    flash_block_q=128, flash_block_kv=128),
               dict()):
        f = data_parallel(
            functools.partial(ring_attention, causal=True,
                              layout="zigzag", **kw),
            mesh8,
            in_specs=(P("data", None, None),) * 3,
            out_specs=P("data", None, None),
        )

        def loss(q_, k_, v_):
            return jnp.sum(f(q_, k_, v_) ** 2)

        got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
            qs.data, ks.data, vs.data)
        for a, b in zip(got, gd):
            np.testing.assert_allclose(
                np.asarray(a)[inv], np.asarray(b), rtol=1e-4,
                atol=1e-4, err_msg=f"kw={kw}")


def test_zigzag_layout_validation(mesh8):
    import functools

    import pytest

    from tpu_distalg.parallel.ring import zigzag_order

    rng = np.random.default_rng(24)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    qs = parallelize(x, mesh8)
    for kw, msg in ((dict(layout="zigzag"), "zigzag"),
                    (dict(layout="zigzag", causal=True, kv_chunk=4),
                     "kv_chunk"),
                    (dict(layout="spiral"), "layout")):
        f = data_parallel(
            functools.partial(ring_attention, **kw), mesh8,
            in_specs=(P("data", None),) * 3,
            out_specs=P("data", None),
        )
        with pytest.raises(ValueError, match=msg):
            jax.jit(f)(qs.data, qs.data, qs.data)
    with pytest.raises(ValueError, match="divisible"):
        zigzag_order(8, 100)
