"""On-TPU numerical validation of the fused Pallas kernels.

The CPU suite verifies packing layout, selector algebra and the gathered
kernel end-to-end in interpret mode; what it cannot verify is real-Mosaic
convergence. These tests close that gap against the reference goldens
(``/root/reference/optimization/ssgd.py:122-130``, final acc 0.929825).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import ssgd
from tpu_distalg.ops import logistic
from tpu_distalg.ops import pallas_kernels as pk
from tpu_distalg.utils import prng


def test_fused_gather_convergence(tpu_mesh, cancer_data):
    """sampler='fused_gather' (block-gather kernel) reaches the
    reference's SSGD quality band on breast-cancer; fine-grained blocks
    so the 398-row task has real stochasticity."""
    res = ssgd.train(
        *cancer_data, tpu_mesh,
        ssgd.SSGDConfig(n_iterations=1500, sampler="fused_gather",
                        fused_pack=4, gather_block_rows=32,
                        shuffle_seed=0),
    )
    assert res.final_acc >= 0.92, res.final_acc


def test_fused_gather_gradient_expectation(tpu_mesh):
    """The v4 block-gather kernel's gradient is an unbiased estimator
    (block-cluster sampling over i.i.d. rows): the mean normalized
    gradient over many steps matches the full-batch mean gradient
    within standard-error tolerance."""
    rng = np.random.default_rng(1)
    n, d = 1 << 16, 30
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    X2, meta = pk.pack_augmented(X, y, np.ones(n, np.float32),
                                 dtype=jnp.float32, pack=16,
                                 block_rows=1024)
    w = np.zeros(meta["d_total"], np.float32)
    w[:d] = rng.normal(size=(d,)).astype(np.float32) * 0.1
    w_j = jnp.asarray(w)
    n_blocks = meta["n_padded"] // 1024
    n_sampled = max(1, round(0.1 * n_blocks))
    T = 800
    key = prng.root_key(0)
    kern = functools.partial(
        pk.fused_grad_sum_gathered, pack=16, d_total=meta["d_total"],
        y_col=meta["y_col"], v_col=meta["v_col"], gather_block_rows=1024)

    @jax.jit
    def mean_grad():
        keys = jax.vmap(lambda t: jax.random.fold_in(key, t))(
            jnp.arange(T))
        bits = jax.vmap(lambda k: jax.random.bits(k, (n_blocks,)))(keys)
        idx = jnp.argsort(bits, axis=-1)[:, :n_sampled].astype(jnp.int32)

        def step(acc, ix):
            g, cnt = kern(X2, w_j, ix)
            return acc + g / jnp.maximum(cnt, 1.0), ()
        acc, _ = jax.lax.scan(step, jnp.zeros((meta["d_total"],)), idx)
        return acc / T

    gm = np.asarray(mean_grad())[:d]
    g_full, cnt = logistic.grad_sum(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(w[:d]), jnp.ones(n))
    gf = np.asarray(g_full / cnt)
    se = float(np.std(X) * 0.5 / np.sqrt(0.1 * n * T))
    np.testing.assert_allclose(gm, gf, atol=20 * se)


def test_fused_train_convergence(tpu_mesh, cancer_data):
    """sampler='fused_train' (whole-schedule megakernel, Mosaic path):
    reaches the reference band; the trajectory legitimately differs
    from fused_gather's by f32 reduction order (measured 0.95 here vs
    0.9298 — both inside the LR/SSGD golden band)."""
    import warnings

    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message="fused_gather:")
        res = ssgd.train(
            *cancer_data, tpu_mesh,
            ssgd.SSGDConfig(n_iterations=1500, sampler="fused_train",
                            mega_steps=125, eval_every=125,
                            fused_pack=4, gather_block_rows=32,
                            shuffle_seed=0),
        )
    assert res.final_acc >= 0.92, res.final_acc


def test_local_fused_train_convergence(tpu_mesh, cancer_data):
    """MA with megakernel local rounds on the real Mosaic path."""
    import warnings

    from tpu_distalg.models import ma

    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message="fused_gather:")
        res = ma.train(*cancer_data, tpu_mesh, ma.MAConfig(
            n_iterations=300, sampler="fused_train",
            gather_block_rows=64, fused_pack=4, shuffle_seed=0))
    # reference MA golden 0.8538 (ma.py:131); measured 0.8947 on TPU
    assert res.final_acc >= 0.85, res.final_acc


def test_gathered_kernels_at_the_cells_block_shape(tpu_mesh):
    """Both gathered kernels compiled at the benchmark cells' block
    shape (``pack`` 16, ``d_total`` 40, 8192-row bfloat16 blocks = 655 KB,
    a table of 256) on the program's own draws, against a float32
    ``jax.numpy`` gradient that rounds where the kernels round (the
    weights to the bfloat16 selector, the residual to bfloat16 before
    the MXU) and nowhere else; then a local-SGD round as MA / EASGD
    launch it (``alpha`` 0 and > 0 with a centre) against its per-step
    form. MA's guard until it has a cell of its own (ROADMAP R1)."""
    from tpu_distalg.ops import sampling

    P, gbr, n_blocks, n_sampled, T, eta = 16, 8192, 256, 25, 6, 0.1
    d = 31
    D, y_col, v_col = pk.packed_dims(d, P)
    assert D == 40
    kx, ky, kv, kw_ = jax.random.split(prng.root_key(11), 4)
    n = n_blocks * gbr
    X = jax.random.normal(kx, (n, d - 1), jnp.float32)
    w_true = jax.random.normal(kw_, (d - 1,), jnp.float32)
    y = (X @ w_true + jax.random.normal(ky, (n,)) > 0).astype(jnp.float32)
    valid = (jax.random.uniform(kv, (n,)) < 0.97).astype(jnp.float32)
    flat = jnp.concatenate(
        [X, jnp.ones((n, 1)), y[:, None], valid[:, None],
         jnp.zeros((n, D - d - 2))], axis=1).astype(jnp.bfloat16)
    del X
    X2 = flat.reshape(n // P, P * D)
    kw = dict(pack=P, d_total=D, y_col=y_col, v_col=v_col,
              gather_block_rows=gbr)
    keep = (jnp.arange(D) < y_col).astype(jnp.float32)
    idx = jax.vmap(lambda t: sampling.sample_block_ids(
        jax.random.fold_in(prng.root_key(42), t), 1, n_blocks,
        n_sampled))(jnp.arange(T)).reshape(T, n_sampled)
    w0 = jnp.zeros((D,), jnp.float32).at[:d].set(
        0.1 * jax.random.normal(prng.root_key(5), (d,)))
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def ref_grad(flat, w, ids):
        rows = flat.reshape(n_blocks, gbr, D)[ids].reshape(-1, D).astype(
            jnp.float32)
        # reduce_precision, not a cast there and back: XLA drops that
        # pair (xla_allow_excess_precision) and the reference would
        # round nowhere
        to_bf16 = functools.partial(jax.lax.reduce_precision,
                                    exponent_bits=8, mantissa_bits=7)
        z = jnp.dot(rows, to_bf16(w * keep), precision=hi)
        v = rows[:, v_col]
        resid = to_bf16((jax.nn.sigmoid(z) - rows[:, y_col]) * v)
        return jnp.dot(resid, rows, precision=hi), jnp.sum(v)

    def step(w, g, cnt, alpha=0.0, centre=0.0):
        return (w - eta * g * keep / jnp.maximum(cnt, 1.0)
                - alpha * (w - centre))

    # one launch of the v4 kernel against the float32 gradient
    g, cnt = pk.fused_grad_sum_gathered(X2, w0, idx[0], **kw)
    g_ref, cnt_ref = ref_grad(flat, w0, idx[0])
    assert float(cnt) == float(cnt_ref) > 0.9 * n_sampled * gbr
    scale = float(jnp.abs(g_ref * keep).max())
    # (a residual that rounds the other way to bfloat16 is 4e-3 of
    # itself; a bfloat16 table read as the wrong rows is 1)
    err = float(jnp.abs((g - g_ref) * keep).max())
    assert err < 2e-4 * scale, (err, scale)

    # T steps of the megakernel against T float32 steps. The in-kernel
    # fold of the gradient tile is two float32 matmuls at the MXU's
    # default precision (as before PR 27): 1.5e-3 of the movement on
    # the chip, 2e-6 interpreted on the CPU. A wrong block is 1.
    tile = lambda w: jnp.tile(w, (P,))[:, None]  # noqa: E731
    wt = pk.fused_train_gathered(X2, tile(w0), idx, eta=eta, **kw)
    w_ref = w0
    for t in range(T):
        w_ref = step(w_ref, *ref_grad(flat, w_ref, idx[t]))
    wt = np.asarray(wt).reshape(P, D)
    assert np.abs(wt - wt[0]).max() == 0.0
    moved = float(jnp.abs(w_ref - w0).max())
    assert moved > 1e-3
    err = np.abs(wt[0] - np.asarray(w_ref)).max()
    assert err < 1e-2 * moved, (err, moved)

    # a local-SGD round (local_sgd._local_models): one launch with the
    # round's centre against per-step launches and the same pull (a
    # pull left out is 0.15 of the movement here)
    centre = w0 + 0.02 * keep
    for alpha in (0.0, 0.05):
        wt = pk.fused_train_gathered(
            X2, tile(w0), idx, eta=eta, alpha=alpha,
            center_tile=tile(centre), **kw)
        w_l = w0
        for t in range(T):
            g, cnt = pk.fused_grad_sum_gathered(X2, w_l, idx[t], **kw)
            w_l = step(w_l, g, cnt, alpha, centre)
        err = np.abs(np.asarray(wt)[:D, 0] - np.asarray(w_l)).max()
        assert err < 1e-2 * moved, (alpha, err, moved)


def test_flash_attention_matches_xla_path(tpu_mesh):
    """The Mosaic flash kernel and the XLA online-softmax ring agree on
    real hardware (both paths round scores through bf16 matmul passes)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tpu_distalg.parallel import DATA_AXIS, data_parallel
    from tpu_distalg.parallel.ring import ring_attention
    from tpu_distalg.utils import prng

    S, H, d = 2048, 4, 128
    key = prng.root_key(3)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (S, H, d),
                          jnp.bfloat16)
        for i in range(3)
    )
    outs = []
    for kw in (dict(kv_chunk=512), dict(use_flash=True)):
        f = jax.jit(data_parallel(
            functools.partial(ring_attention, causal=True, **kw),
            tpu_mesh,
            in_specs=(P(DATA_AXIS, None, None),) * 3,
            out_specs=P(DATA_AXIS, None, None),
        ))
        outs.append(np.asarray(f(q, k, v)))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-2, atol=2e-2)
    assert np.isfinite(outs[1]).all()


def test_flash_backward_matches_xla_backward_on_tpu(tpu_mesh):
    """Round-4 flash backward on hardware: gradients through the Pallas
    backward kernels match the XLA ring path's gradients to the MXU
    default-precision noise band (~0.5% relative — both paths round
    f32 matmul operands to bf16, in different places)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tpu_distalg.parallel import DATA_AXIS, data_parallel
    from tpu_distalg.parallel.ring import ring_attention

    key = jax.random.PRNGKey(0)
    S, H, d = 2048, 4, 128
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (S, H, d))
               for i in range(3))
    grads = {}
    for name, kw in (("flash", dict(use_flash=True)),
                     ("xla", dict(kv_chunk=1024))):
        f = data_parallel(
            functools.partial(ring_attention, causal=True, **kw),
            tpu_mesh,
            in_specs=(P(DATA_AXIS, None, None),) * 3,
            out_specs=P(DATA_AXIS, None, None),
        )
        loss = lambda a, b, c: jnp.sum(f(a, b, c) ** 2)  # noqa: E731
        grads[name] = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(grads["flash"], grads["xla"]):
        a, b = np.asarray(a), np.asarray(b)
        rel = np.abs(a - b).max() / np.abs(b).max()
        assert rel < 1e-2, f"flash-vs-xla grad rel err {rel}"


def test_pagerank_pallas_scatter_matches_xla_on_tpu(tpu_mesh):
    """Round-4 Pallas scatter on hardware: the one-hot product (three
    exact bf16 pieces since PR 39, ``scatter_window``) keeps
    standard-mode ranks within f32 noise of the XLA segment_sum
    sweep."""
    import numpy as np

    from tpu_distalg.models import pagerank
    from tpu_distalg.ops import graph as gops
    from tpu_distalg.utils import datasets

    edges = datasets.erdos_renyi_edges(200_000, 8.0, seed=1)
    el = gops.prepare_edges(edges, 200_000)
    de = pagerank.prepare_device_edges(el, tpu_mesh)
    assert de.plan is not None
    outs = {}
    for sc in ("pallas", "xla"):
        cfg = pagerank.PageRankConfig(n_iterations=10, mode="standard",
                                      scatter=sc)
        fn = pagerank.make_run_fn(tpu_mesh, cfg, de.n_vertices,
                                  de.plan if sc == "pallas" else None)
        outs[sc] = np.asarray(fn(de.src, de.dst, de.w_e, de.emask,
                                 de.has_out, de.n_ref)[0])
    rel = (np.abs(outs["pallas"] - outs["xla"]).max()
           / outs["xla"].max())
    assert rel < 1e-5, f"pallas-vs-xla ranks rel err {rel}"


def test_pagerank_spmv_matches_xla_on_tpu(tpu_mesh):
    """Round-5 fused SpMV (Path E) on hardware: the whole gather+
    scatter kernel keeps standard-mode ranks within f32 noise of the
    XLA sweep at 200k vertices."""
    import numpy as np

    from tpu_distalg.models import pagerank
    from tpu_distalg.ops import graph as gops
    from tpu_distalg.utils import datasets

    edges = datasets.erdos_renyi_edges(200_000, 8.0, seed=1)
    el = gops.prepare_edges(edges, 200_000)
    spmv = pagerank.prepare_device_spmv(el, tpu_mesh)
    assert spmv is not None
    de = pagerank.prepare_device_edges(el, tpu_mesh, build_plan=False)
    outs = {}
    for sc in ("spmv", "xla"):
        cfg = pagerank.PageRankConfig(n_iterations=10, mode="standard",
                                      scatter=sc)
        fn = pagerank.make_run_fn(tpu_mesh, cfg, de.n_vertices, None,
                                  spmv if sc == "spmv" else None)
        outs[sc] = np.asarray(fn(de.src, de.dst, de.w_e, de.emask,
                                 de.has_out, de.n_ref)[0])
    rel = (np.abs(outs["spmv"] - outs["xla"]).max()
           / outs["xla"].max())
    assert rel < 1e-5, f"spmv-vs-xla ranks rel err {rel}"


def test_pagerank_sharded_by_range_matches_xla_on_four_chips():
    """Graph500 SCALE 22 on four chips through ``run_rmat``: drawn a
    quarter of the ids a chip, exchanged by destination range,
    deduplicated and planned where each range lives, ten fused sweeps
    (a chip writes its own range, the ranges all-gathered) against the
    XLA sweep on the same edges pulled to the host."""
    import numpy as np

    from tpu_distalg.models import pagerank
    from tpu_distalg.parallel import get_mesh
    from tpu_distalg.utils import datasets

    if len(jax.devices()) < 4:
        pytest.skip("the sharded sweep is a four-chip path")
    scale, seed = 22, 3_000_000_019
    mesh = get_mesh(data=4, model=1, devices=jax.devices()[:4])
    cfg = pagerank.PageRankConfig(n_iterations=10, mode="standard")
    got = np.asarray(pagerank.run_rmat(mesh, cfg, scale, 16, None,
                                       seed).ranks)
    src, dst = jax.jit(datasets.kronecker_edges(scale))(
        jnp.arange(16 << scale, dtype=jnp.uint32),
        np.uint32(seed & 0xFFFFFFFF))
    want = np.asarray(pagerank.run(
        np.stack([np.asarray(src), np.asarray(dst)], axis=1), mesh,
        pagerank.PageRankConfig(n_iterations=10, mode="standard",
                                scatter="xla"), 1 << scale).ranks)
    rel = np.abs(got - want).max() / want.max()
    assert rel < 1e-5, f"sharded spmv-vs-xla ranks rel err {rel}"
    assert abs(float(got.sum(dtype=np.float64)) - 1.0) < 1e-4


@pytest.mark.parametrize("ws,edges", [(224, 1 << 20), (440, 1 << 19)])
def test_pagerank_pipelined_kernel_is_segment_sum_bit_for_bit_on_tpu(
        ws, edges):
    """PR 45's chunk loop (a chunk's gather in one block with the chunk
    before's scatter) at the two cells' geometries, ``rg`` 512 with
    ``ws`` 224 and 440, compiled by Mosaic: (a) every vertex with at
    most one in-edge and contributions of full 24-bit significands,
    the table IS XLA's ``segment_sum`` of ``ranks[src] * w_e``, bit for
    bit, in one kernel call and in several (the drain and the empty
    first scatter at every border); (b) random destinations and
    contributions that are one power of two, every sum exact: bit for
    bit again, as PR 44's Step 0 read the sequential loop."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    v = 1 << 20
    rng = np.random.default_rng(ws)
    src = rng.integers(0, v, size=edges)

    def tables(dst, w_e, ranks):
        plan = ppr.plan_spmv(src, dst, w_e, v, rg=512)
        g = plan.geom
        assert (g.rg, g.ws, g.n_groups) == (512, ws, 16)
        assert ppr.spmv_overlap(g.rg) == "step"
        rt = jnp.asarray(ranks.reshape(-1, 128))
        steps = g.n_steps
        several = next(d for d in range(steps // 6, 1, -1)
                       if steps % d == 0)
        want = np.asarray(jax.jit(lambda s, d, w, r: jax.ops.segment_sum(
            r[s] * w, d, num_segments=v))(src, dst, w_e, ranks))
        for seg_steps in (g.seg_steps, several):
            got = np.asarray(ppr.spmv_table(
                plan.gbase, plan.sbase, rt, plan.src_lane, plan.src_row,
                plan.dst_row, plan.dst_lane, plan.w_e, rg=g.rg, ws=g.ws,
                r8=g.rows_out, blk=g.blk,
                seg_steps=seg_steps))[:g.r8].reshape(-1)
            yield steps // seg_steps, got, want

    once = rng.permutation(v)[:edges]
    for calls, got, want in tables(
            once, _full_mantissas(rng, edges) * 2.0 ** 40,
            _full_mantissas(rng, v)):
        assert (want != 0).sum() == edges
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32),
                                      err_msg=f"{calls} calls")
    for calls, got, want in tables(
            rng.integers(0, v, size=edges),
            np.full(edges, 1 / 16, np.float32),
            np.full(v, 1.0 / v, np.float32)):
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32),
                                      err_msg=f"{calls} calls")
    print(f"[pipelined spmv] rg 512 ws {ws}: the table is segment_sum "
          f"bit for bit over {edges} edges, in 1 and in {calls} calls")


def _scatter_products(c, row, lane, ws, pieces=3, interpret=False):
    """One small Pallas kernel, two windows of the same operands: the
    shipped one-hot scatter product (``scatter_window`` over the first
    ``pieces`` of ``split3``) and the ``Precision.HIGHEST`` product it
    replaced in PR 39 (six bf16 passes of the whole float32 operand)."""
    from jax.experimental import pallas as pl

    from tpu_distalg.ops import pallas_pagerank as ppr

    def kernel(c_ref, row_ref, lane_ref, new_ref, old_ref):
        c, row, lane = c_ref[...], row_ref[...], lane_ref[...]
        kept = ppr.split3(c)[:pieces]
        new_ref[...] = ppr.scatter_window(kept, row, lane, ws)
        row_iota = jax.lax.broadcasted_iota(jnp.int32, (ws, 128), 0)
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
        old = jnp.zeros((ws, 128), jnp.float32)
        for s in range(8):
            m = jnp.where(
                jnp.broadcast_to(row[s:s + 1, :], (ws, 128)) == row_iota,
                jnp.broadcast_to(c[s:s + 1, :], (ws, 128)), 0.0)
            onehot_t = (jnp.broadcast_to(lane[s:s + 1, :], (128, 128))
                        == lane_iota).astype(jnp.float32)
            old += jax.lax.dot_general(
                m, onehot_t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
        old_ref[...] = old

    window = jax.ShapeDtypeStruct((ws, 128), jnp.float32)
    new, old = pl.pallas_call(kernel, out_shape=[window, window],
                              interpret=interpret)(
        jnp.asarray(c), jnp.asarray(row), jnp.asarray(lane))
    return np.asarray(new), np.asarray(old)


def _full_mantissas(rng, shape):
    """Positive float32 with all 24 significand bits in use (the last
    one set), over the ten binades a rank x weight of the Graph500 cell
    spans."""
    mant = rng.integers(1 << 23, 1 << 24, size=shape) | 1
    return (mant * 2.0 ** rng.integers(-56, -46, size=shape)
            ).astype(np.float32)


@pytest.mark.parametrize("ws", [24, 72, 224])
def test_pagerank_scatter_product_is_float32_on_tpu(ws):
    """PR 39's product on the MXU itself: three single bf16 passes of
    the contribution's three exact pieces against the one-hot. (a) With
    every window cell written by at most one slot the window holds each
    contribution bit for bit, and so does the ``HIGHEST`` product; (b)
    with 1024 slots on eight cells (a star) the two agree within the
    reordering of one float32 sum, 2^-22 of the sum a cell; (c) the
    control, the same helper fed two pieces (hi, mid: a hi/lo split,
    ``Precision.HIGH``), fails (a): the test can tell the split the
    configuration forbids from this one."""
    rng = np.random.default_rng(ws)
    c = _full_mantissas(rng, (8, 128))
    cells = rng.permutation(ws * 128)[:1024].reshape(8, 128)
    row, lane = (cells // 128).astype(np.int32), (cells % 128).astype(
        np.int32)
    want = np.zeros((ws, 128), np.float32)
    want[row, lane] = c
    new, old = _scatter_products(c, row, lane, ws)
    np.testing.assert_array_equal(new.view(np.uint32),
                                  want.view(np.uint32))
    np.testing.assert_array_equal(old.view(np.uint32),
                                  want.view(np.uint32))
    two, _ = _scatter_products(c, row, lane, ws, pieces=2)
    short = int((two.view(np.uint32) != want.view(np.uint32)).sum())
    assert short > 900, short
    # (b) a star: every slot on one of eight cells
    hubs = rng.permutation(ws * 128)[:8]
    cells = hubs[rng.integers(0, 8, size=(8, 128))]
    row, lane = (cells // 128).astype(np.int32), (cells % 128).astype(
        np.int32)
    new, old = _scatter_products(c, row, lane, ws)
    total = np.zeros((ws, 128), np.float64)
    np.add.at(total, (row, lane), c.astype(np.float64))
    assert (new[total == 0] == 0).all() and (old[total == 0] == 0).all()
    bound = 2.0 ** -22 * total
    assert (np.abs(new - old) <= bound).all()
    assert (np.abs(new - total) <= bound).all()
    print(f"[scatter product] ws {ws}: star cells off the float64 sum by "
          f"{np.abs(new - total)[total > 0].max() / total.max():.3g} "
          f"(shipped), {np.abs(old - total)[total > 0].max() / total.max():.3g}"
          f" (HIGHEST) of the largest; two pieces left {short} of 1024 "
          f"single cells short")


def test_streamed_ssgd_bitwise_on_tpu(tpu_mesh, cancer_data):
    """Round-5 streamed >HBM path on hardware: host-side threefry
    draws + staged blocks reproduce the resident fused_gather weights
    BIT FOR BIT (the design contract, asserted on the real chip)."""
    import numpy as np

    from tpu_distalg.models import ssgd, ssgd_stream

    X_train, y_train, X_test, y_test = cancer_data
    cfg = ssgd.SSGDConfig(n_iterations=120, sampler="fused_gather",
                          gather_block_rows=32, fused_pack=4,
                          shuffle_seed=0, eval_every=40)
    resident = ssgd.train(X_train, y_train, X_test, y_test, tpu_mesh,
                          cfg)
    X2h, meta = ssgd_stream.pack_host(X_train, y_train, tpu_mesh, cfg)
    streamed = ssgd_stream.train(X2h, meta, tpu_mesh, cfg, X_test,
                                 y_test)
    np.testing.assert_array_equal(np.asarray(resident.w),
                                  np.asarray(streamed.w))


def test_fused_topk_matches_xla_topk_incl_ties(tpu_mesh):
    """The compiled fused matmul+top-k kernel against ``xla_matmul_topk``
    on hardware. Small-integer factors are exact in every matmul
    precision (the MXU's default pass rounds f32 operands to bf16), so
    the scores are bit-equal and crowded with ties: indices and values
    must match EXACTLY, ties toward the lower item id — including a
    shard offset, a padded tail and fewer-than-k valid items. Random
    f32 factors at the serving geometry then agree wherever the
    default-precision scores leave no near-tie."""
    from tpu_distalg.ops import pallas_topk as pt

    rng = np.random.default_rng(0)
    Q = rng.integers(-3, 4, size=(32, 64)).astype(np.float32)
    V = rng.integers(-3, 4, size=(5000, 64)).astype(np.float32)
    V[100] = V[7]        # crafted exact ties
    V[4000] = V[7]
    for off, nv, k in ((0, 5000, 10), (12345, 4100, 10), (0, 6, 10),
                       (0, 5000, 130)):
        fv, fi = pt.fused_matmul_topk(Q, V, off, nv, k=k)
        xv, xi = pt.xla_matmul_topk(Q, V, off, nv, k=k)
        np.testing.assert_array_equal(np.asarray(fi), np.asarray(xi),
                                      err_msg=f"{(off, nv, k)}")
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(xv),
                                      err_msg=f"{(off, nv, k)}")
    Q = rng.normal(size=(32, 64)).astype(np.float32)
    V = rng.normal(size=(16384, 64)).astype(np.float32)
    fv, fi = pt.fused_matmul_topk(Q, V, 0, 16384, k=10)
    xv, xi = pt.xla_matmul_topk(Q, V, 0, 16384, k=10)
    np.testing.assert_allclose(np.asarray(fv), np.asarray(xv),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(fi) == np.asarray(xi)).mean() >= 0.99


def test_tp_split_kernels_match_one_pass_kernel(tpu_mesh):
    """The two-pass dp x tp split (``fused_forward_gathered`` ->
    residual -> ``fused_backward_gathered``, what ``tda ssgd
    --mesh-shape NxM`` runs) against the one-pass gathered kernel on
    the same sampled blocks."""
    rng = np.random.default_rng(3)
    n, d = 1 << 14, 30
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    X2, meta = pk.pack_augmented(X, y, np.ones(n, np.float32),
                                 dtype=jnp.float32, pack=16,
                                 block_rows=1024)
    kw = dict(pack=16, d_total=meta["d_total"], gather_block_rows=1024)
    cols = dict(y_col=meta["y_col"], v_col=meta["v_col"])
    w = np.zeros(meta["d_total"], np.float32)
    w[:d] = rng.normal(size=d).astype(np.float32) * 0.1
    w = jnp.asarray(w)
    idx = jnp.asarray([3, 9, 0, 14], jnp.int32)
    g1, cnt1 = pk.fused_grad_sum_gathered(X2, w, idx, **kw, **cols)
    zyv = pk.fused_forward_gathered(X2, w, idx, **kw, **cols)
    P = 16
    z, yy, v = zyv[:, :P], zyv[:, P:2 * P], zyv[:, 2 * P:3 * P]
    resid = (jax.nn.sigmoid(z) - yy) * v
    g2 = pk.fused_backward_gathered(X2, resid, idx, **kw)
    assert float(cnt1) == float(jnp.sum(v)) == 4 * 1024
    keep = np.arange(meta["d_total"]) < meta["y_col"]
    np.testing.assert_allclose(np.asarray(g2)[keep],
                               np.asarray(g1)[keep],
                               rtol=1e-5, atol=1e-3)


def test_carry_leaves_have_one_shard_per_device(tpu_mesh):
    """Every leaf a trainer carries lands as its rule table says: one
    addressable shard on EACH device, of the table's shard shape —
    trivially on one chip, the real layout proof on four."""
    from tpu_distalg.models import als
    from tpu_distalg.parallel import get_mesh, partition
    from tpu_distalg.utils import datasets

    n_dev = len(jax.devices())

    def check(table, mesh, leaves):
        for name, arr in leaves.items():
            shards = arr.addressable_shards
            assert len(shards) == n_dev, (table, name, len(shards))
            assert len({s.device for s in shards}) == n_dev
            want = partition.leaf_sharding(
                table, name, mesh, shape=arr.shape
            ).shard_shape(arr.shape)
            assert {s.data.shape for s in shards} == {want}, (
                table, name, want)

    X, y = datasets.synthetic_two_class(1 << 16, 125, seed=0)
    X = datasets.add_bias_column(X)
    cfg = ssgd.SSGDConfig(n_iterations=20, eval_test=False,
                          x_dtype="bfloat16", sampler="fused_gather",
                          gather_block_rows=1024, shuffle_seed=0)
    fn, X2, w0, meta = ssgd.prepare_fused(X, y, tpu_mesh, cfg)
    dummy = jnp.zeros((1,), jnp.float32)
    w, _ = fn(X2, dummy, dummy,
              jnp.zeros((1, meta["d_total"]), jnp.float32),
              jnp.zeros((1,), jnp.float32), w0)
    assert bool(jnp.isfinite(w).all())
    check("ssgd", tpu_mesh, {"X2": X2, "w": w})

    # ALS on a (data x model) mesh when the device count allows, so
    # the item factors' model-axis rule engages
    mesh = (get_mesh(data=n_dev // 2, model=2) if n_dev % 2 == 0
            else tpu_mesh)
    acfg = als.ALSConfig(m=512, n=1024, k=16, n_iterations=2)
    rng = np.random.default_rng(0)
    R = partition.put(als.synthesize_rank_k(acfg), "R", "als_train",
                      mesh)
    U0 = partition.put(np.zeros((512, 16), np.float32), "U",
                       "als_train", mesh)
    V0 = partition.put(rng.random((1024, 16), dtype=np.float32), "V0",
                       "als_train", mesh)
    U, V, errs = als.make_fit_fn(mesh, acfg)(R, U0, V0)
    assert bool(jnp.isfinite(errs).all())
    check("als_train", mesh, {"R": R, "U": U, "V": V})


def test_block_draw_selection_equals_compiled_argsort(tpu_mesh,
                                                      monkeypatch):
    """The compiled selection of ``sampling.sample_block_ids`` against
    the compiled ``argsort`` slice at the benchmark cells' shapes (12
    and 1221 of 12 208 blocks, 250 steps, one and four shards), over
    the threefry words and over words crowded with ties and all-ones:
    a tie rule of the chip's sort or reduce that differs from the
    CPU's shows here and not as a ``w_rel_err``."""
    from tpu_distalg.ops import sampling

    key = jax.random.key(42)
    ts = jnp.arange(250) + 1_400_000_103
    real = jax.random.bits
    table = jnp.asarray([0, 7, 0xFFFFFFFF], jnp.uint32)

    def tied(k, shape):
        return table[real(k, shape) % np.uint32(3)]

    def restated(bits, t, n_shards, n_blocks, n_sampled):
        ks = jax.vmap(lambda s: jax.random.fold_in(
            jax.random.fold_in(key, t), s))(jnp.arange(n_shards))
        words = jax.vmap(lambda k: bits(k, (n_blocks,)))(ks)
        return jnp.argsort(words, axis=-1)[:, :n_sampled].astype(
            jnp.int32)

    for bits in (real, tied):
        monkeypatch.setattr(sampling.jax.random, "bits", bits)
        for shape in ((1, 12208, 12), (1, 12208, 1221),
                      (4, 12208, 1221), (4, 12208, 12)):
            got = jax.jit(jax.vmap(lambda t: sampling.sample_block_ids(
                jax.random.fold_in(key, t), *shape)))(ts)
            want = jax.jit(jax.vmap(
                lambda t: restated(bits, t, *shape)))(ts)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want),
                err_msg=f"{shape} {bits.__name__}")


def test_kmeans_lanes_table_fits_and_follows_reference(tpu_mesh):
    """The scale path at the HiBench ``huge`` shape (dim 20, k 10, 5
    generating clusters). At 100M points the resident table is the
    points and nothing else: 80 B a point, at most 9 GB on the device
    that holds it. At 1M points the compiled Mosaic pass follows a
    float32 NumPy Lloyd: from the same centres one iteration agrees to
    2e-5 of the spread where no point changes sides (at most 40 of 1M
    may: the two sides write the distance differently) and counts
    every point once."""
    from tpu_distalg.models import kmeans
    from tpu_distalg.utils import datasets

    from tpu_distalg.parallel import get_mesh

    tpu_mesh = get_mesh(data=1, devices=jax.devices()[:1])
    dev = jax.devices()[0]
    make_rows, _ = datasets.gaussian_mixture_rows(k=5, dim=20, spread=8.0)
    n = 1_000_000
    data, valid, lanes = kmeans.build_scaled(
        tpu_mesh, n, make_rows, 10, data_seed=7)
    pts = np.asarray(lanes.unpack(data))[:n]
    c = pts[np.random.default_rng(0).choice(n, 10, replace=False)]
    seg1 = kmeans.make_fit_seg_fn(
        tpu_mesh, kmeans.KMeansConfig(k=10, n_iterations=1), 1, lanes)
    for _ in range(3):
        got, _, _, counts = seg1(data, valid, jnp.asarray(c),
                                 jnp.float32(0), jnp.int32(0))
        d2 = ((pts[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        a = d2.argmin(1)
        want_counts = np.bincount(a, minlength=10)
        want = np.stack([pts[a == j].sum(0, dtype=np.float64) / max(
            want_counts[j], 1) for j in range(10)]).astype(np.float32)
        moved = int(np.abs(np.asarray(counts) - want_counts).sum())
        assert int(np.asarray(counts).sum()) == n
        assert moved <= 80, moved
        err = float(np.abs(np.asarray(got) - want).max() / 8.0)
        assert err < (2e-5 if moved == 0 else 2e-4), (err, moved)
        c = want
    del data
    before = dev.memory_stats()["bytes_in_use"]
    data, valid, lanes = kmeans.build_scaled(
        tpu_mesh, 100_000_000, make_rows, 10, data_seed=7)
    held = dev.memory_stats()["bytes_in_use"] - before
    assert data.nbytes == 1526 * 65536 * 80
    assert held <= 9e9 and dev.memory_stats()["peak_bytes_in_use"] <= 9e9, \
        dev.memory_stats()
    _, _, n_run, counts = kmeans.make_fit_seg_fn(
        tpu_mesh, kmeans.KMeansConfig(k=10, n_iterations=2), 2, lanes)(
            data, valid, jnp.asarray(c), jnp.float32(0), jnp.int32(0))
    assert int(n_run) == 2
    assert int(np.asarray(counts, np.int64).sum()) == 100_000_000


def test_kmeans_lanes_sums_are_float32_sums_on_the_mxu():
    """The compiled pass at the cell's block shape (dim 20, k 10,
    blocks of 512 sublane rows, 1M points, the last block half
    padding), from fixed centres: the assignment is the score pass's
    bit for bit, the 10 counts are its bincount exactly, and each of
    the 200 sums that the MXU adds up (three bfloat16 pieces a point
    against the 0/1 mask) is within float32 summation error of the
    float64 sum: 1e-6 of the sum of magnitudes, where the same points
    rounded to bfloat16 once miss by 5e-4 or more."""
    from tpu_distalg.ops import pallas_lloyd as lloyd

    k, dim, n = 10, 20, 1_000_000
    assert lloyd.sums_form(k, dim) == "mxu"
    geom = lloyd.lanes_geometry(dim, k)
    assert geom.block_rows == 512
    nb = -(-n // geom.block_points)
    rng = np.random.default_rng(29)
    shape = (nb * geom.block_points, dim)
    # all 24 significand bits in use, and the 16 that bfloat16 drops
    # always round down, so that the control's error cannot average out
    mant = ((rng.integers(1 << 7, 1 << 8, size=shape) << 16)
            | rng.integers(0x1000, 0x7000, size=shape)).astype(np.float64)
    pts = (mant * 2.0 ** rng.integers(-24, -19, size=shape)
           ).astype(np.float32)
    centers = pts[rng.choice(n, k, replace=False)]
    x4 = jax.vmap(geom.pack)(
        jnp.asarray(pts).reshape(nb, geom.block_points, dim))
    partial, assign = jax.block_until_ready(
        lloyd.lloyd_pass(x4, jnp.asarray(centers), n, assign=True))
    alone, = lloyd.lloyd_pass(x4, jnp.asarray(centers), n, stats=False,
                              assign=True)
    np.testing.assert_array_equal(np.asarray(assign), np.asarray(alone))
    sums, counts = map(np.asarray, lloyd.fold_stats(partial, k, dim))
    a = np.asarray(alone).reshape(-1)[:n]
    want = np.zeros((k, dim))
    mags = np.zeros((k, dim))
    np.add.at(want, a, pts[:n].astype(np.float64))
    np.add.at(mags, a, np.abs(pts[:n]).astype(np.float64))
    assert counts.dtype == np.int32
    np.testing.assert_array_equal(counts, np.bincount(a, minlength=k))
    err = np.abs(sums - want) / mags
    assert err.max() < 1e-6, err.max()
    rounded = np.asarray(jax.lax.reduce_precision(
        jnp.asarray(pts[:n]), exponent_bits=8, mantissa_bits=7))
    control = np.zeros((k, dim))
    np.add.at(control, a, rounded.astype(np.float64))
    assert (np.abs(control - want) / mags).min() > 5e-4
    # the float64 argmin agrees wherever the margin is beyond rounding
    d2 = ((pts[:n, None, :].astype(np.float64)
           - centers[None].astype(np.float64)) ** 2).sum(-1)
    two = np.sort(d2, axis=1)[:, :2]
    clear = two[:, 1] - two[:, 0] > 1e-3 * (1 + two[:, 1])
    assert clear.mean() > 0.95
    np.testing.assert_array_equal(a[clear], d2.argmin(1)[clear])


@pytest.mark.parametrize("k,dim,form", [
    (32, 32, "mxu"), (9, 17, "mxu"), (8, 16, "vpu"), (7, 5, "vpu")])
def test_kmeans_lanes_pass_at_other_shapes(k, dim, form):
    """The compiled pass in both forms away from the cell's shape (the
    planes in seven column tiles; k and dim odd; the masked adds on the
    VPU): 300 000 points in the geometry's own blocks, the last one
    padding in part; counts the score pass's bincount exactly, sums
    within float32 summation error of the float64 sums."""
    from tpu_distalg.ops import pallas_lloyd as lloyd

    n = 300_000
    assert lloyd.sums_form(k, dim) == form
    geom = lloyd.lanes_geometry(dim, k)
    nb = -(-n // geom.block_points)
    rng = np.random.default_rng(k * dim)
    pts = (rng.normal(size=(nb * geom.block_points, dim)) * 5
           ).astype(np.float32)
    centers = pts[rng.choice(n, k, replace=False)]
    x4 = jax.vmap(geom.pack)(
        jnp.asarray(pts).reshape(nb, geom.block_points, dim))
    partial, = lloyd.lloyd_pass(x4, jnp.asarray(centers), n)
    alone, = lloyd.lloyd_pass(x4, jnp.asarray(centers), n, stats=False,
                              assign=True)
    sums, counts = map(np.asarray, lloyd.fold_stats(partial, k, dim))
    a = np.asarray(alone).reshape(-1)[:n]
    want = np.zeros((k, dim))
    mags = np.zeros((k, dim))
    np.add.at(want, a, pts[:n].astype(np.float64))
    np.add.at(mags, a, np.abs(pts[:n]).astype(np.float64))
    assert counts.dtype == np.int32
    np.testing.assert_array_equal(counts, np.bincount(a, minlength=k))
    assert (np.abs(sums - want) <= 1e-6 * mags).all()


def _float64_nearest(pts, centers, chunk=8192):
    """(argmin, margin to the second nearest, smallest squared
    distance) of every row by float64 squared distances."""
    c = centers.astype(np.float64)
    c2 = (c * c).sum(1)
    arg, margin, least = [], [], []
    for i in range(0, len(pts), chunk):
        x = pts[i:i + chunk].astype(np.float64)
        d2 = (x * x).sum(1)[:, None] - 2.0 * x @ c.T + c2[None]
        two = np.partition(d2, 1, axis=1)[:, :2]
        arg.append(d2.argmin(1))
        margin.append(two[:, 1] - two[:, 0])
        least.append(two[:, 0])
    return (np.concatenate(arg), np.concatenate(margin),
            np.concatenate(least))


def _wide_case(dim, k, n, seed):
    """Mixture points in the wide geometry's own blocks (the last one
    padding in part), centres sampled from them, the compiled pass's
    assignment, sums, counts and its time."""
    import time

    from tpu_distalg.ops import pallas_lloyd_wide as wide

    geom = wide.wide_geometry(dim, k)
    p = geom.block_points
    nb = -(-n // p)
    rng = np.random.default_rng(seed)
    mus = rng.normal(size=(10, dim)) * 8.0
    pts = (mus[rng.integers(0, 10, nb * p)]
           + rng.normal(size=(nb * p, dim))).astype(np.float32)
    centers = pts[rng.choice(n, k, replace=False)]
    x3 = jax.vmap(geom.pack)(jnp.asarray(pts).reshape(nb, p, dim))
    c = jnp.asarray(centers)

    def run():
        a = wide.wide_assign(x3, c, geom=geom)
        return a, wide.wide_stats(x3, a, n, geom=geom)

    jax.block_until_ready(run())
    t0 = time.perf_counter()
    assign, (sums, counts) = jax.block_until_ready(run())
    ms = (time.perf_counter() - t0) * 1e3
    return (geom, pts[:n], centers, x3, np.asarray(assign).reshape(-1)[:n],
            np.asarray(sums), np.asarray(counts), ms)


def _check_wide_stats(pts, a, k, sums, counts, tol=None):
    """Counts exact; every sum within what a straight float32 sum of a
    cluster's m points may be off by, m x 6e-8 of the sum of magnitudes
    of the float64 sum, whatever the form (the scatter adds a cluster's
    points one by one into two accumulators; the one-hot's arrive 256 at
    a time, so a sum is hundreds of float32 additions of partial sums;
    one addend rounded to bfloat16 is off by 4e-3 of itself); and the
    largest such error within ``tol``: 1e-6, or 6e-8 times the root of
    the largest cluster's size where that is more (addends of one sign
    drift by about that). On one v5e (PR 31) it read 8.57e-7 under the
    scatter at 784 x 4096 (largest cluster 1271 points: the bare 1e-6
    would hold with no room; the one-hot at the same ids 1.03e-6), 1.5e-7
    to 7.5e-7 at the other scatter shapes (clusters of up to 7 to 882),
    9.0e-7 and 7.7e-7 under the one-hot at (96, 1024) and (49, 96)
    (898 and 3014)."""
    if tol is None:
        tol = max(1e-6, 6e-8 * float(np.sqrt(counts.max())))
    want = np.zeros((k, pts.shape[1]))
    mags = np.zeros((k, pts.shape[1]))
    np.add.at(want, a, pts.astype(np.float64))
    np.add.at(mags, a, np.abs(pts).astype(np.float64))
    assert counts.dtype == np.int32
    np.testing.assert_array_equal(counts, np.bincount(a, minlength=k))
    err = np.abs(sums - want)
    worst = float((err / (mags + 1e-30)).max())
    print(f"[wide sums] largest error over the sum of magnitudes "
          f"{worst:.3g} (tolerance {tol:g}), largest cluster "
          f"{int(counts.max())} points")
    assert worst <= tol, worst
    # (+ 2: the one-hot adds three products' sums a chunk)
    assert (err <= (counts[:, None] + 2) * 6e-8 * mags + 1e-30).all()


def test_kmeans_wide_pass_at_the_published_widths(monkeypatch):
    """The compiled wide pass at FAISS's MNIST8m widths (784 float32
    dimensions, 4096 centres, blocks of 512 points, 100 000 points)
    against a float64 argmin. Float32 accuracy: the scores are 1e5 and
    carry 0.01 to 0.05 of rounding, so a point whose two nearest
    centres lie closer than that may go to either (2 in 10 000 at the
    cell's size: PERF.md §6, PR 30); every other point goes where
    float64 sends it. Held as: at most 1 point in 1000 differs, and none
    whose margin is over 1e-6 of the scores' scale (0.15). Counts are
    the assignment's bincount exactly, adding up to n; sums within
    float32 summation error of the float64 sums. The control, operands
    of one bfloat16 piece (the MXU's default precision) in the same
    kernel, sends hundreds of points wrong whose margin is ten times
    that."""
    from tpu_distalg.ops import pallas_lloyd_wide as wide

    dim, k, n = 784, 4096, 100_000
    geom, pts, centers, x3, a, sums, counts, _ = _wide_case(dim, k, n, 3)
    assert (geom.dim_held, geom.block_points, geom.sums_form) == (
        784, 512, "scatter")
    # the six products as one contraction: 37 slabs a tile where six
    # products of 896 took 42
    assert (geom.dist_form, geom.dist_depth) == ("mxu6", 4736)
    want, margin, least = _float64_nearest(pts, centers)
    scale = (centers.astype(np.float64) ** 2).sum(1).max() * 3
    bad = a != want
    print(f"[wide widths] {int(bad.sum())} of {n} differ from float64, "
          f"largest margin among them {margin[bad].max(initial=0):.4g}, "
          f"scale {scale:.4g}")
    assert int(bad.sum()) <= n // 1000
    assert margin[bad].max(initial=0.0) < 1e-6 * scale
    _check_wide_stats(pts, a, k, sums, counts)
    assert int(counts.sum()) == n
    # the one-hot form at the same ids: the same counts, float32 sums
    # too (391 chunks' partial sums a cluster at worst: 1e-5 where the
    # smaller shapes hold 1e-6)
    a3 = jnp.asarray(a, jnp.int32)
    a3 = jnp.pad(a3, (0, x3.shape[0] * 512 - n)).reshape(-1, 1, 512)
    hot, hot_counts = jax.jit(
        lambda x, i: wide.onehot_stats(x, i, n, geom=geom))(x3, a3)
    _check_wide_stats(pts, a, k, np.asarray(hot), np.asarray(hot_counts),
                      tol=1e-5)

    real = wide.split3
    monkeypatch.setattr(wide, "split3", lambda x: real(x)[:1] + tuple(
        jnp.zeros_like(p) for p in real(x)[1:]))
    jax.clear_caches()
    try:
        low = np.asarray(wide.wide_assign(
            x3, jnp.asarray(centers), geom=geom)).reshape(-1)[:n]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    clear = margin > 1e-5 * scale
    wrong = int((low[clear] != want[clear]).sum())
    print(f"[wide widths] control: {int((low != want).sum())} differ, "
          f"{wrong} of them with a margin over {1e-5 * scale:.3g}")
    assert wrong > 100, wrong


@pytest.mark.parametrize("dim,k,form", [
    (96, 1024, "mxu"), (128, 1024, "scatter"), (96, 16384, "scatter"),
    (128, 16384, "scatter"), (49, 96, "mxu"),
    # the distance product's contraction at 3 slabs (six products of 128
    # took 6) and at 10 (12)
    (64, 4096, "scatter"), (200, 1024, "scatter")])
def test_kmeans_wide_pass_at_other_shapes(dim, k, form):
    """The geometry's choice away from the cell's shape: descriptor
    widths with a codebook of a thousand and of sixteen thousand centres
    and a dim that is not a multiple of 8, each with the form of the
    per-cluster sums that ``sums_form(k, dim)`` gives it (the one-hot
    product where k x dim is small, the scatter-add elsewhere). 60 000
    points; assignment against float64 wherever the margin is beyond
    rounding, counts exact, sums float32 sums. The pass's time and its
    share of the MXU's bfloat16 peak are printed and kept in
    ``chiprun_out/kmeans_wide_shapes.jsonl``: a reading, not an
    assertion."""
    import json
    import os

    n = 60_000
    geom, pts, centers, _, a, sums, counts, ms = _wide_case(
        dim, k, n, dim * k)
    assert geom.sums_form == form
    want, margin, _ = _float64_nearest(pts, centers)
    scale = (centers.astype(np.float64) ** 2).sum(1).max() * 3
    clear = margin > 2e-6 * scale
    assert clear.mean() > 0.99, clear.mean()
    np.testing.assert_array_equal(a[clear], want[clear])
    _check_wide_stats(pts, a, k, sums, counts)
    line = {"dim": dim, "k": k, "n": n, "ms_a_pass": ms,
            "mxu_share_pct": 2.0 * n * k * dim / (ms / 1e3) / 197e12 * 100,
            "stats_tile": geom.stats_tile, "centre_tile": geom.centre_tile,
            "dist_depth": geom.dist_depth, "sums_form": form}
    print(f"[wide shapes] {json.dumps(line)}")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kmeans_wide_shapes.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")


def test_kmeans_wide_scatter_tiles_the_centres():
    """Past ``ACC_BYTES`` an accumulator of the scatter holds a tile of
    the centres (1024 dimensions, 16384 centres: five tiles of 4088),
    compiled: 20 000 points under random ids against ``np.add.at``,
    counts exact."""
    from tpu_distalg.ops import pallas_lloyd_wide as wide

    dim, k, n = 1024, 16384, 20_000
    geom = wide.wide_geometry(dim, k)
    assert geom.sums_form == "scatter" and geom.scatter_tile == 4088
    p = geom.block_points
    nb = -(-n // p)
    rng = np.random.default_rng(5)
    pts = rng.normal(size=(nb * p, dim)).astype(np.float32)
    a = rng.integers(0, k, nb * p).astype(np.int32)
    x3 = jax.vmap(geom.pack)(jnp.asarray(pts).reshape(nb, p, dim))
    sums, counts = wide.wide_stats(
        x3, jnp.asarray(a).reshape(nb, 1, p), n, geom=geom)
    _check_wide_stats(pts[:n], a[:n], k, np.asarray(sums),
                      np.asarray(counts))
    assert int(np.asarray(counts).sum()) == n


def test_hashed_passes_at_the_published_widths(tpu_mesh):
    """The compiled gather and scatter of SSGD over hashed rows at the
    benchmark's widths (39 slots a row into 2**20 float32 weights,
    blocks of 8192 rows; 200 000 rows of the loader's skewed table, 6
    blocks sampled) against the definition a pair at a time in float64:
    margins to float32 rounding of a sum of 40 terms; per-slot sums
    within float32 summation error of their sum of magnitudes, the slot
    that nearly every row hits included; the bias slot the residuals'
    sum; a residual of 1 everywhere counts a slot's occurrences exactly.
    Three forms at the same ids: every field by address, the loader's
    plan (21 fields by value against their dictionaries, 18 by
    address), XLA's."""
    from tpu_distalg.models import ssgd
    from tpu_distalg.ops import pallas_hashed as ph
    from tpu_distalg.parallel import get_mesh

    mesh = get_mesh(data=1, devices=jax.devices()[:1])
    cfg = ssgd.SSGDConfig(sampler="fused_gather", gather_block_rows=8192,
                          eval_test=False, mini_batch_fraction=0.25)
    X, meta = ssgd.build_hashed_table(200_000, 39, 20, mesh, cfg,
                                      data_seed=17)
    geom = ssgd.hashed_geometry(cfg, meta)
    assert geom.pass_form == "vmem" and X.shape == (25, 40, 8192)
    plan = ssgd.hashed_field_plan(cfg, meta)
    forms = [ph.field_form(0 if d is None else len(d), 8192)
             for d in meta["dictionaries"]]
    print(f"[hashed widths] fields by value {forms.count('dict')} "
          f"({plan.n_values} values), by address {forms.count('addr')}")
    assert (forms.count("dict"), forms.count("addr")) == (21, 18)
    assert (len(plan.dict_fields), len(plan.addr_fields),
            plan.n_values) == (21, 18, 13027)
    ids = jnp.array([24, 3, 11, 0, 17, 8], jnp.int32)
    key = jax.random.key(2)
    w = jax.random.normal(key, (geom.w_len,)).at[geom.n_slots + 1:].set(0)
    r = jax.random.normal(jax.random.fold_in(key, 1), (6, 8192))
    idx = np.asarray(X)[np.asarray(ids)][:, :39, :]
    w64, r64 = np.asarray(w, np.float64), np.asarray(r, np.float64)
    m_def = w64[geom.n_slots] + w64[idx].sum(axis=1)
    g_def = np.zeros((geom.n_slots,), np.float64)
    mags = np.zeros((geom.n_slots,), np.float64)
    counts = np.zeros((geom.n_slots,), np.int64)
    both = np.broadcast_to(r64[:, None, :], idx.shape)
    np.add.at(g_def, idx, both)
    np.add.at(mags, idx, np.abs(both))
    np.add.at(counts, idx, 1)
    assert counts.max() > 0.4 * r.size           # the hot slot is there
    ones = jnp.ones_like(r)
    for name, margins, sums in (
            ("vmem", ph.margins_vmem, ph.slot_sums_vmem),
            ("plan", functools.partial(ph.margins, plan=plan),
             functools.partial(ph.slot_sums, plan=plan)),
            ("xla", ph.margins_xla, ph.slot_sums_xla)):
        m = np.asarray(margins(X, w, ids, geom), np.float64)
        g = np.asarray(sums(X, r, ids, geom), np.float64)
        n1 = np.asarray(sums(X, ones, ids, geom), np.float64)
        err = np.abs(g[:geom.n_slots] - g_def)
        print(f"[hashed widths] {name}: margins max err "
              f"{np.abs(m - m_def).max():.3g}, slot sums max err over "
              f"magnitudes {(err / np.maximum(mags, 1e-30)).max():.3g}, "
              f"hot slot {counts.max()} occurrences")
        assert np.abs(m - m_def).max() < 2e-5, name
        assert (err <= (np.log2(counts + 2) + 2) * 6e-8 * mags * 8
                + 1e-30).all(), name
        assert abs(g[geom.n_slots] - r64.sum()) < 1e-2, name
        assert not g[geom.n_slots + 1:].any(), name
        np.testing.assert_array_equal(n1[:geom.n_slots], counts)


def test_indexed_passes_against_the_plain_reference(tpu_mesh):
    """The compiled steps of SSGD over indexed rows (eleven fields in
    the benchmark's order, 18.0M weights: three fields by value, six by
    address in three groups, two ranges of 5M and 4.5M slots in HBM,
    gathered a row a DMA and each summed in one accumulator of its
    range in VMEM) against the benchmark's plain
    reference (``benchmarks/reference/ssgd_indexed_ref.py``: no table,
    the rows regenerated, one flat ``w[idx]`` and ``.at[idx].add``) over
    two calls of three steps: every weight and the bias to float32
    rounding, where the reference in bfloat16 stands three orders off."""
    import os
    import sys

    from tpu_distalg.parallel import get_mesh

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import ssgd_indexed_ref as ref_mod

    cards = (24323, 594098, 13745, 3, 3, 5_000_000, 1157062, 3750862,
             2936510, 4_500_000, 21)
    c = dict(n_rows=400_000, nnz=11, n_features=sum(cards),
             gather_block_rows=8192, eta=0.1, field_cardinalities=cards,
             zipf_exponent=1.1, planted_scale=0.25, click_rate=0.0349)
    mesh = get_mesh(data=1, devices=jax.devices()[:1])
    cfg = ssgd.SSGDConfig(
        n_iterations=3, eta=0.1, lam=0.0, mini_batch_fraction=0.25,
        seed=42, eval_test=False, sampler="fused_gather",
        gather_block_rows=8192)
    fn, X, w, meta = ssgd.prepare_hashed_synthetic(
        c["n_rows"], 11, 0, mesh, cfg, data_seed=17, cardinalities=cards,
        row_format="indexed", zipf_exponent=1.1, planted_scale=0.25,
        click_rate=0.0349)
    plan = ssgd.hashed_field_plan(cfg, meta)
    assert plan.hbm_fields == (5, 9) and len(plan.addr_groups) == 3
    d = jnp.zeros((1,), jnp.float32)
    got = []
    for call in range(2):
        w, _ = fn(X, d, d, d, d, w, t0=3 * call)
        got.append(ref_mod.model_vector(w, c["n_features"]))
    ref = ref_mod.Reference(config=c, fraction=0.25, data_seed=17,
                            sample_seed=42)
    w0 = np.zeros((c["n_features"] + 1,), np.float32)
    good = ref.follow(2, 3)
    low = ref.follow(2, 3, dtype=jnp.bfloat16)
    for k in range(2):
        err = ref_mod.rel_err(got[k], good[k], w0)
        ctl = ref_mod.rel_err(low[k], good[k], w0)
        print(f"[indexed] call {k + 1}: program against reference "
              f"{err:.3g}, bfloat16 control {ctl:.3g}")
        assert err < 6e-5 < ctl


def test_field_scatter_at_the_wide_cells_two_ranges(tpu_mesh):
    """``_hashed_field_scatter_kernel`` at ``lrwide11_150m_frac01``'s two
    id fields (ranges of 24 296 581 and 21 913 244 slots, each in two
    pieces of an accumulator of 2^17 rows, 67.1 MB, in VMEM: one call of
    four phases) over 183 sampled blocks of 8192 rows of
    the loader's seeded table: each field's sums against XLA's
    scatter-add and both against float64 on the host, in norm (the
    hottest query id holds a tenth of the rows: a serial float32 sum in
    either form); then two calls of four steps of the trainer, the
    kernel in it, against the benchmark's plain reference under the
    cell's limit."""
    import os
    import sys

    from tpu_distalg.ops import pallas_hashed as ph
    from tpu_distalg.parallel import get_mesh

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import ssgd_indexed_ref as ref_mod

    cards = (24323, 594098, 13745, 3, 3, 24296581, 1157062, 3750862,
             2936510, 21913244, 21)
    n_blocks, ns = 366, 183
    c = dict(n_rows=n_blocks * 8192, nnz=11, n_features=sum(cards),
             gather_block_rows=8192, eta=0.1, field_cardinalities=cards,
             zipf_exponent=1.1, planted_scale=0.25, click_rate=0.0349)
    mesh = get_mesh(data=1, devices=jax.devices()[:1])
    cfg = ssgd.SSGDConfig(
        n_iterations=4, eta=0.1, lam=0.0, mini_batch_fraction=0.5,
        seed=42, eval_test=False, sampler="fused_gather",
        gather_block_rows=8192)
    fn, X, w, meta = ssgd.prepare_hashed_synthetic(
        c["n_rows"], 11, 0, mesh, cfg, data_seed=59, cardinalities=cards,
        row_format="indexed", zipf_exponent=1.1, planted_scale=0.25,
        click_rate=0.0349)
    geom = ssgd.hashed_geometry(cfg, meta)
    plan = ssgd.hashed_field_plan(cfg, meta)
    assert plan.hbm_fields == (5, 9)
    key = jax.random.key(59)
    ids = jnp.sort(jax.random.permutation(key, n_blocks)[:ns]).astype(
        jnp.int32)
    r = jax.random.normal(jax.random.fold_in(key, 1), (ns, 8192))
    r64 = np.asarray(r, np.float64).reshape(-1)
    assert ph.field_phases(geom, (5, 9))[0] == 1 << 17
    sums = jax.jit(lambda X, r, ids: ph.slot_sums_fields(
        X, r, ids, geom, (5, 9)))(X, r, ids)
    for f, got in zip(plan.hbm_fields, sums):
        lo, hi = geom.offsets[f], geom.offsets[f + 1]
        assert ph.field_scatter_form(hi - lo, True) == "vmem"
        xla = jax.jit(lambda X, r, ids, f=f: ph.slot_sums_hbm(
            X, r, ids, geom, (f,)))(X, r, ids)[lo:hi]
        slots = np.asarray(X[ids][:, f, :]).reshape(-1) - lo
        want = np.bincount(slots, weights=r64, minlength=hi - lo)
        scale = np.linalg.norm(want)
        errs = [float(np.linalg.norm(np.asarray(g, np.float64) - want)
                      / scale) for g in (got, xla)]
        both = float(jnp.linalg.norm(got - xla) / jnp.linalg.norm(xla))
        print(f"[field scatter] field {f}: range {hi - lo}, hottest slot "
              f"{int(np.bincount(slots).max())} of {slots.size} pairs; "
              f"off float64 in norm {errs[0]:.3g} (XLA's {errs[1]:.3g}), "
              f"off XLA's {both:.3g}")
        assert errs[0] < 1e-5 and both < 1e-5
    d = jnp.zeros((1,), jnp.float32)
    got = []
    for call in range(2):
        w, _ = fn(X, d, d, d, d, w, t0=4 * call)
        got.append(ref_mod.model_vector(w, c["n_features"]))
    ref = ref_mod.Reference(config=c, fraction=0.5, data_seed=59,
                            sample_seed=42)
    w0 = np.zeros((c["n_features"] + 1,), np.float32)
    good = ref.follow(2, 4)
    for k in range(2):
        err = ref_mod.rel_err(got[k], good[k], w0)
        print(f"[field scatter] call {k + 1}: 4 steps against the "
              f"reference {err:.3g}")
        assert err < 6e-5


def test_pairs_passes_against_the_plain_reference(tpu_mesh):
    """The compiled steps of SSGD over rows of (feature, value) pairs
    (40 000 ragged rows of 8 to 16 384 pairs, 2M weights resident in
    VMEM a pass, blocks of 2^16 pair slots) against the benchmark's
    plain reference
    (``benchmarks/reference/ssgd_pairs_ref.py``: no table, the sampled
    blocks' rows regenerated, ``w[idx] * val``, a ``segment_sum`` a row,
    ``.at[idx].add`` a block) over two calls of three steps: every
    weight and the bias to float32 rounding, where the reference in
    bfloat16 stands orders off."""
    import os
    import sys

    from tpu_distalg.models import ssgd_pairs
    from tpu_distalg.parallel import get_mesh

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import ssgd_pairs_ref as ref_mod

    mu = ssgd_pairs.length_mu_for(40_000, 500.0, length_max=1 << 14)
    c = dict(n_rows=40_000, n_features=2_000_003, pair_block_slots=1 << 16,
             pair_block_rows=256, pair_row_granule=128, pair_blocks=352,
             length_mu=mu, length_sigma=1.0, length_min=8,
             length_max=1 << 14, zipf_exponent=1.1, scatter_a=251,
             scatter_c=12345, planted_scale=0.25, positive_rate=0.6,
             eta=0.1, bias_blocks=64, heldout_blocks=64,
             heldout_offset=1 << 20)
    spec = ssgd_pairs.PairsSpec(
        n_rows=c["n_rows"], n_features=c["n_features"], length_mu=mu,
        block_slots=1 << 16, block_rows=256, n_blocks=352,
        length_max=1 << 14, scatter_c=12345)
    mesh = get_mesh(data=1, devices=jax.devices()[:1])
    cfg = ssgd.SSGDConfig(
        n_iterations=3, eta=0.1, lam=0.0, mini_batch_fraction=0.05,
        seed=42, eval_test=False, sampler="fused_gather")
    fn, X, w, meta = ssgd_pairs.prepare_synthetic(spec, mesh, cfg,
                                                  data_seed=17)
    ref = ref_mod.Reference(config=c, fraction=0.05, data_seed=17,
                            sample_seed=42)
    assert np.array_equal(ref.starts, meta["block_starts"])
    assert np.array_equal(ref.counts, meta["block_counts"])
    assert float(ref.bias) == meta["bias"]
    d = jnp.zeros((1,), jnp.float32)
    got = []
    for call in range(2):
        w, _ = fn(X, d, d, d, d, w, t0=3 * call)
        got.append(ref_mod.model_vector(w, c["n_features"]))
    w0 = np.zeros((c["n_features"] + 1,), np.float32)
    good = ref.follow(2, 3)
    low = ref.follow(2, 3, dtype=jnp.bfloat16)
    for k in range(2):
        err = ref_mod.rel_err(got[k], good[k], w0)
        ctl = ref_mod.rel_err(low[k], good[k], w0)
        print(f"[pairs] call {k + 1}: program against reference "
              f"{err:.3g}, bfloat16 control {ctl:.3g}")
        assert err < 3e-4 < ctl


# What either form's per-slot sums may stand off a float64 sum in norm at
# webspam's width over 8 blocks of the seeded draw (1.76M pairs, 144 851 of
# them in the hottest slot). Read on the chip (PR 56, call 183): the
# ``vmem`` form 2.21e-5, XLA's own 4.16e-5, 0.999 and 1.000 of the squared
# difference in the 16 hottest of 294 073 slots: float32 rounding of the
# hot slots' sums and nothing else (the whole-number pass of the same test
# is exact to the bit). 3.6 times the larger reading; a bfloat16 form
# stands a hundred times the readings off.
PAIRS_SUMS_LIMIT = 1.5e-4


def test_pairs_kernels_at_webspams_width_against_float64(tpu_mesh):
    """The two by-address kernels compiled at the benchmark cell's width
    (16 609 143 features: the 66.4 MB model vector one copy in VMEM;
    blocks of 2^18 pair slots, 2048 vectors) and at its skew (the
    loader's power law of 1.1: a piece's four pairs often share a row of
    the table, a ninth of all pairs one slot) over 8 sampled blocks, an
    empty one and one drawn twice among them, against a float64 sum
    over the blocks' own CSR arrays and against the ``xla`` form:

    * with every value and residual a small whole number each partial
      sum is one too, so float32 adds in any order are exact: both
      forms equal the float64 sums TO THE BIT. No addend is lost,
      doubled or carried to another row, whatever shares a piece;
    * with the loader's values: margins to float32 rounding; the sums
      within ``PAIRS_SUMS_LIMIT`` in norm, and what they are off by
      lies in the few hottest slots, whose addends a call adds one
      after another."""
    import dataclasses

    from tpu_distalg.models import ssgd_pairs
    from tpu_distalg.ops import pairs
    from tpu_distalg.parallel import get_mesh

    spec = ssgd_pairs.PairsSpec(
        n_rows=1500, n_features=16_609_143, length_mu=7.72585391998291,
        block_slots=1 << 18, block_rows=512, n_blocks=24, scatter_c=0)
    assert spec.zipf_exponent == 1.1
    mesh = get_mesh(data=1, devices=jax.devices()[:1])
    X, meta = ssgd_pairs.build_table(spec, mesh, data_seed=56)
    geom = ssgd_pairs.geometry(meta)
    assert geom.pass_form == "vmem" and geom.w_len == 129759 * 128
    assert meta["blocks_used"] < 24          # the last block holds no row
    forms = {"vmem": geom, "xla": dataclasses.replace(geom, on_tpu=False)}
    assert forms["xla"].pass_form == "xla"
    sel = [3, 23, 0, 11, 7, 3, 19, 1]
    ids = jnp.asarray(sel, jnp.int32)
    V, R = geom.vectors, geom.block_rows
    valid = pairs.labels(X, ids, geom)[1]

    def both(X, w, r):
        return {form: jax.jit(lambda X, w, r, g=g: (
            pairs.margins(X, w, ids, g), pairs.slot_sums(X, r, ids, g)))(
                X, w, r) for form, g in forms.items()}

    def float64(X, w, r):
        """From the sampled blocks' own rows, on the host."""
        indptr, h, v, _, block_of = pairs.csr_from_blocks(
            np.asarray(X[ids]), geom)
        n = np.diff(indptr)
        slot = np.concatenate([np.arange(c) for c in
                               np.bincount(block_of, minlength=len(sel))])
        row = np.repeat(np.arange(len(n)), n)
        r_row = np.asarray(r, np.float64)[block_of, slot]
        w64 = np.asarray(w, np.float64)
        m = np.full((len(sel), R), w64[geom.n_slots])
        m[block_of, slot] += np.bincount(
            row, weights=w64[h] * v.astype(np.float64), minlength=len(n))
        g = np.bincount(h, weights=r_row[row] * v.astype(np.float64),
                        minlength=geom.w_len)
        g[geom.n_slots] = np.asarray(r, np.float64).sum()
        return m, g, np.bincount(h, minlength=geom.w_len)

    # whole numbers: the values -3 .. 3 where a slot holds a pair, the
    # residuals -2 .. 2
    k1, k2, k3 = jax.random.split(jax.random.key(3), 3)
    held = X[:, V:2 * V] != 0
    whole = jax.random.randint(k1, held.shape, -3, 4).astype(jnp.float32)
    Xw = X.at[:, V:2 * V].set(jnp.where(
        held, jax.lax.bitcast_convert_type(whole, jnp.int32), 0))
    rw = jax.random.randint(k2, (len(sel), R), -2, 3).astype(
        jnp.float32) * valid
    ww = jax.random.randint(k3, (geom.w_len,), -4, 5).astype(jnp.float32)
    m64, g64, count = float64(Xw, ww, rw)
    assert np.abs(g64).max() < 1 << 24 and np.abs(m64).max() < 1 << 24
    hot = int(count[:geom.n_slots].max())
    for form, (m, g) in both(Xw, ww, rw).items():
        assert np.array_equal(np.asarray(m, np.float64), m64), form
        assert np.array_equal(np.asarray(g, np.float64), g64), form
    print(f"[pairs] whole numbers at webspam's width: both forms equal "
          f"the float64 sums to the bit; {int(count.sum())} pairs, the "
          f"hottest slot {hot} of them, largest sum "
          f"{np.abs(g64).max():.0f}")

    # the loader's values
    w = jax.random.normal(jax.random.key(1), (geom.w_len,), jnp.float32)
    r = jax.random.normal(jax.random.key(2), (len(sel), R),
                          jnp.float32) * valid
    m64, g64, count = float64(X, w, r)
    top = np.argsort(count)[-16:]
    read = {}
    for form, (m, g) in both(X, w, r).items():
        off = np.asarray(g, np.float64) - g64
        read[form] = (
            float(np.abs(np.asarray(m, np.float64) - m64).max()
                  / np.abs(m64).max()),
            float(np.linalg.norm(off) / np.linalg.norm(g64)),
            float((off[top] ** 2).sum() / max((off ** 2).sum(), 1e-300)))
        print(f"[pairs] {form} against float64 at webspam's width: "
              f"margins {read[form][0]:.3g} of the largest, sums "
              f"{read[form][1]:.3g} in norm, {read[form][2]:.3f} of that "
              f"(squared) in the 16 hottest of "
              f"{int(np.count_nonzero(count))} slots")
        assert abs(float(g[geom.n_slots]) - g64[geom.n_slots]) < 1e-3, form
        assert not np.asarray(g[geom.n_slots + 1:]).any(), form
    for form in forms:
        assert read[form][0] < 1e-5, form
        assert read[form][1] < PAIRS_SUMS_LIMIT, form
    # rounding of serial sums, not a fault in a cold slot: it lies where
    # the addends are
    assert read["vmem"][2] > 0.5


def test_sparse_als_half_sweep_at_rank_100(tpu_mesh):
    """A sparse ALS half-sweep compiled at the benchmark's rank and
    widths (rank 100 in 128 lanes, segments of 32 slots, the eleven
    classes and pieces of 64 at a batch of 768; 1 500 000 seeded ratings
    of 15 000 users by 9 000 items) against the plain reference's
    float32 ``jnp.linalg.solve`` owner by owner on the followed owners,
    and the solve along the lanes against NumPy in float64 on systems
    taken from the run: the six-pass Gramians and the Cholesky hold
    float32 accuracy where a bfloat16 Gramian does not."""
    import os
    import sys

    from tpu_distalg.models import als
    from tpu_distalg.ops import als_sparse as ops
    from tpu_distalg.parallel import get_mesh

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import als_sparse_ref as ref_mod

    mesh = get_mesh(data=1, devices=jax.devices()[:1])
    n, m_u, m_i, k = 1_500_000, 15_000, 9_000, 100
    gen = dict(d_min=20, user_d_max=20_000, item_d_max=40_000)
    geometry = dict(seg_slots=32, piece_segs=64, batch=768,
                    classes=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48))
    arrays, meta = als.build_ratings_table(
        n, m_u, m_i, k, mesh, data_seed=9, n_heldout=4096,
        geometry=geometry, **gen)
    geom, pu, pi = meta["geometry"], meta["user"], meta["item"]
    assert geom.width == 128 and geom.solve_n == 104
    cfg = als.ALSConfig(lam=1.4, m=m_u, n=m_i, k=k, n_iterations=1, seed=4)
    X, Theta = als.start_factors(meta, mesh, cfg.seed)
    V0 = np.asarray(als.owners_from_rows(Theta, pi, k))
    X, Theta, errs, seen = als.make_fit_fn(mesh, cfg, meta)(
        *arrays, X, Theta)
    assert np.asarray(seen).tolist() == [[n, n]]
    U = np.asarray(als.owners_from_rows(X, pu, k))
    V = np.asarray(als.owners_from_rows(Theta, pi, k))
    ref = ref_mod.Reference(
        config=dict(k=k, lam=1.4, n_users=m_u, n_items=m_i, n_ratings=n,
                    n_heldout=4096, rating_low=0.0, rating_high=100.0,
                    reference_sample=4096, reference_heavy_over=2048,
                    generator=dict(als.RATINGS_DEFAULTS, **gen)),
        data_seed=9, start_seed=4)
    np.testing.assert_array_equal(ref.start_items(), V0)
    for side, other, got, before in ((0, V0, U, np.zeros_like(U)),
                                     (1, U, V, V0)):
        own, want = ref.half(side, other)
        err = ref_mod.rel_err(got[own], want, before[own])
        _, low = ref.half(side, other, dtype=jnp.bfloat16)
        control = ref_mod.rel_err(low, want, before[own])
        print(f"[als rank 100] side {side}: {len(own)} owners, rel err "
              f"{err:.3g}, bfloat16 control {control:.3g}")
        assert err < 1e-4 < control
    # the solve alone, against float64
    rng = np.random.default_rng(0)
    G = np.zeros((geom.batch, 300, geom.width), np.float32)
    G[:, :, :k] = rng.random((geom.batch, 300, k))
    G[:, :, k] = rng.integers(0, 101, (geom.batch, 300))
    G[:, :, k + 1] = 1.0
    Ap = np.einsum("bsd,bse->bde", G.astype(np.float64),
                   G.astype(np.float64))
    rows, has, _, _ = jax.jit(
        lambda a: ops.solve_batch(a, 1.4, geom))(
        jnp.asarray(Ap, jnp.float32))
    want = np.stack([np.linalg.solve(
        Ap[i, :k, :k] + 1.4 * 300 * np.eye(k), Ap[i, :k, k])
        for i in range(geom.batch)])
    err = np.abs(np.asarray(rows)[:, :k] - want).max() / np.abs(want).max()
    print(f"[als rank 100] XLA's solve along the lanes: max err {err:.3g}")
    assert bool(np.asarray(has).all()) and err < 1e-3


def test_sparse_als_solve_kernel_at_rank_100_against_float64():
    """The Mosaic solve (``ops/pallas_als.solve_lanes``) compiled at the
    benchmark's tile (128 owners' rows of rank 100 in 104, turned along
    the lanes in VMEM; six tiles) against NumPy in float64 and against
    XLA's ``cholesky_solve_lanes`` on the same systems: Gramians of 0 to
    2000 rows of eighths with ratings 0 to 100, ``lam n_u`` on the
    diagonal, so some owners have fewer ratings than the rank and three
    have none. What this guards is the VPU's square root and division
    (an approximate reciprocal would read 1e-3 here) and the turn: the
    unknowns are bit for bit those of the form that was handed the batch
    along the lanes (``scripts/step0_als_solve.solve_from_lanes``, what
    shipped until PR 50), on Gramians that are not symmetric to the
    last bit."""
    import os
    import sys

    from tpu_distalg.ops import als_sparse as ops
    from tpu_distalg.ops import pallas_als

    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import step0_als_solve as step0

    k, batch, lam = 100, 768, 1.4
    geom = ops.SparseGeometry(k=k, batch=batch)
    assert ops.solve_plan(geom, True) == ops.SolvePlan("mosaic", 128)
    rng = np.random.default_rng(41)
    cnt = np.concatenate([[0, 0, 0], rng.integers(1, 100, 253),
                          rng.integers(100, 2000, batch - 256)])
    Ap = np.zeros((batch, geom.width, geom.width))
    for i in range(batch):
        G = np.zeros((cnt[i], geom.width))
        G[:, :k] = rng.integers(-8, 9, (cnt[i], k)) / 8
        G[:, k] = rng.integers(0, 101, cnt[i])
        G[:, k + 1] = 1.0
        Ap[i] = G.T @ G                 # exact in float32 too
    want = np.stack([np.linalg.solve(
        Ap[i, :k, :k] + (lam * cnt[i] if cnt[i] else 1.0) * np.eye(k),
        Ap[i, :k, k]) for i in range(batch)])
    owners = jnp.asarray(Ap, jnp.float32)
    skew = owners * (1 + 1e-7 * jnp.asarray(
        rng.standard_normal(Ap.shape), jnp.float32))
    x, b = jax.jit(lambda a: pallas_als.solve_lanes(a, k, lam))(skew)
    was = jax.jit(lambda a: step0.solve_from_lanes(
        ops.to_lanes(a), k, lam))(skew)
    assert np.array_equal(np.asarray(x), np.asarray(was))
    assert np.array_equal(np.asarray(b)[:k], np.asarray(skew[:, :k, k]).T)
    got = {}
    for name, plan in (("mosaic", ops.SolvePlan("mosaic", 128)),
                       ("xla", None)):
        rows, has, _, seen = jax.jit(
            lambda a, plan=plan: ops.solve_batch(a, lam, geom, plan))(owners)
        assert np.asarray(has).tolist() == (cnt > 0).tolist()
        assert int(seen) == int(cnt.sum())
        rows = np.asarray(rows)
        assert not rows[:3].any() and not rows[:, k:].any()
        got[name] = np.linalg.norm(rows[:, :k] - want) / np.linalg.norm(want)
        worst = (np.linalg.norm(rows[3:, :k] - want[3:], axis=1)
                 / np.linalg.norm(want[3:], axis=1)).max()
        print(f"[als solve rank 100] {name}: rel err {got[name]:.3g}, "
              f"worst system {worst:.3g}")
        assert worst < 1e-4
    assert got["mosaic"] < 5e-6 and got["mosaic"] <= 2 * got["xla"]


@pytest.mark.parametrize("table_rows,hot_row0", [
    (663_560, 645_120),        # the items' table, what the user half reads
    (1_032_200, 1_013_760)])   # the users'
def test_sparse_als_resident_gather_is_xlas_bitwise(table_rows, hot_row0):
    """The Mosaic gather (``ops/pallas_als.py``, on the loader's lists)
    against XLA's ``other[idx]`` and its ``where`` for the rating's and
    the validity's lanes, bit for bit, on one block at the benchmark's
    block shape (6144 segments x 32 slots = 196 608 rows of 128 lanes)
    and both tables' row counts with the heavy class and the zero rows
    resident: the cell's mix of heavy, padding and cold slots, then the
    rows at the range's two ends."""
    from tpu_distalg.ops import als_sparse as ops

    zero_row = table_rows - 8
    rng = np.random.default_rng(7)
    T = jnp.asarray(rng.standard_normal((table_rows, 128), np.float32)
                    ).at[zero_row:].set(0.0).at[:, 100:].set(0.0)
    n = 1536 * 128
    u = rng.random(n)
    idx = np.where(u < 0.568, rng.integers(hot_row0, zero_row, n),
                   np.where(u < 0.736, zero_row,
                            rng.integers(0, hot_row0, n)))
    idx[:4] = [hot_row0 - 1, hot_row0, zero_row - 1, zero_row]
    idx[-4:] = [zero_row, 0, table_rows - 1, hot_row0 - 1]
    idx = jnp.asarray(idx.astype(np.int32).reshape(1536, 128))
    val = jnp.asarray(rng.integers(0, 101, (1536, 128)).astype(np.float32))
    got = jax.jit(_sparse_als_block_mosaic, static_argnums=(3, 4))(
        T, idx, val, hot_row0, zero_row)
    want = jax.jit(_sparse_als_block_xla, static_argnums=3)(
        T, idx, val, zero_row)
    assert got.shape == (n, 128)
    assert bool(jnp.array_equal(got, want))
    assert bool(jnp.array_equal(got[:, :100],
                                ops.gather_rows(T, idx)[:, :100]))
    assert float(jnp.abs(got).sum()) > 0


def _sparse_als_block_mosaic(T, idx, val, hot_row0, zero_row):
    """One block as the product wants it, through the kernel: the
    loader's lists, the table as the gather reads it, the kernel."""
    from tpu_distalg.ops import als_sparse as ops
    from tpu_distalg.ops import pallas_als

    plan = ops.GatherPlan("mosaic", hot_row0, T.shape[0] - hot_row0)
    lists = (a[0] for a in ops.gather_lists(idx[None], val[None], plan))
    table = ops.gather_table(T, ops.SparseGeometry(k=100), zero_row, plan)
    return pallas_als.gather_rows_resident(table, idx, *lists, hot_row0,
                                           100)


def _sparse_als_block_xla(T, idx, val, zero_row):
    from tpu_distalg.ops import als_sparse as ops

    lane = jnp.arange(128)[None, :]
    ok = (idx.reshape(-1) != zero_row).astype(jnp.float32)[:, None]
    return jnp.where(lane == 100, val.reshape(-1, 1),
                     jnp.where(lane == 101, ok, ops.gather_rows(T, idx)))


def test_sparse_als_resident_gather_over_many_blocks():
    """No row of 256 blocks' 50M slots differs from XLA's: the cold
    rows' copies land over rows that pass 1 has stored, chunk after
    chunk and call after call, and a race between the two would show
    as a stale row here and as a digit in a fit."""
    table_rows, hot_row0 = 663_560, 645_120
    zero_row = table_rows - 8
    T = jax.random.normal(jax.random.PRNGKey(1), (table_rows, 128),
                          jnp.float32)
    T = T.at[zero_row:].set(0.0).at[:, 100:].set(0.0)

    def one(bad, key):
        ku, kh, kc, kv = jax.random.split(key, 4)
        u = jax.random.uniform(ku, (1536, 128))
        idx = jnp.where(
            u < 0.568, jax.random.randint(kh, u.shape, hot_row0, zero_row),
            jnp.where(u < 0.736, zero_row,
                      jax.random.randint(kc, u.shape, 0, hot_row0)))
        val = jax.random.randint(kv, u.shape, 0, 101).astype(jnp.float32)
        differ = jnp.any(
            _sparse_als_block_mosaic(T, idx, val, hot_row0, zero_row)
            != _sparse_als_block_xla(T, idx, val, zero_row), axis=1)
        return bad + jnp.sum(differ.astype(jnp.int32)), None

    bad, _ = jax.jit(lambda keys: jax.lax.scan(one, jnp.int32(0), keys))(
        jax.random.split(jax.random.PRNGKey(2), 256))
    assert int(bad) == 0


def test_a_loader_span_ends_with_at_least_the_bytes_it_states():
    """On the chip the allocator keeps stats: after
    ``als.build_ratings_table`` at the half-sweep test's shape every
    loader span carries ``hbm_in_use`` / ``hbm_peak`` / ``hbm_in_use_start``
    (one entry, one chip), ``als:prepare`` ends with at least the
    ``bytes`` it states resident (its arrays' own ``nbytes``), the
    generate phase's rise is no less than what it says it made (the
    programs it loaded lie in HBM too), and a ``memory`` sample costs
    microseconds."""
    import time

    from tpu_distalg.models import als
    from tpu_distalg.parallel import get_mesh
    from tpu_distalg.telemetry import events

    mesh = get_mesh(data=1, devices=jax.devices()[:1])
    geometry = dict(seg_slots=32, piece_segs=64, batch=768,
                    classes=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48))
    events._FINISHED.clear()
    arrays, meta = als.build_ratings_table(
        1_500_000, 15_000, 9_000, 100, mesh, data_seed=9, n_heldout=4096,
        geometry=geometry, d_min=20, user_d_max=20_000, item_d_max=40_000)
    done = {}
    for s in events.finished():
        done.setdefault(s.name, s)
    root = done["als:prepare"].fields
    held = sum(a.nbytes for a in arrays)
    assert root["bytes"] == held
    assert len(root["hbm_in_use"]) == 1
    assert root["hbm_in_use"][0] >= root["bytes"]
    assert root["hbm_peak"][0] >= root["hbm_in_use"][0]
    gen = done["als:generate"].fields
    rise = gen["hbm_in_use"][0] - gen["hbm_in_use_start"][0]
    assert rise >= 0.9 * gen["bytes"], (rise, gen)
    for name in ("als:pack", "als:heldout", "als:lists"):
        assert done[name].fields["hbm_in_use"][0] > 0, name
    assert not [s.name for s in events.finished()
                if s.name.startswith("jit:") and "hbm_in_use" in s.fields]
    t0 = time.perf_counter()
    for _ in range(1000):
        events.memory(mesh.local_devices)
    us = (time.perf_counter() - t0) * 1e3
    print(f"[memory] a sample of {len(mesh.local_devices)} device(s): "
          f"{us:.2f} us; als:prepare in use {root['hbm_in_use'][0]} B for "
          f"{held} B stated, peak {root['hbm_peak'][0]} B; als:generate "
          f"rose {rise} B for {gen['bytes']} B stated")
    assert us < 1000


@pytest.mark.parametrize("density", [1e-4, 0.02, 1.0])
def test_closure_compose_kernel_is_xlas_boolean_product(density):
    """``ops/pallas_closure.compose`` compiled, at the shipped tiles, on
    4096 vertices: bit for bit XLA's own bfloat16 product or-ed into the
    left operand, and per-tile counts that add up to its pairs."""
    from tpu_distalg.ops import pallas_closure

    n = 4096
    kp, kq = jax.random.split(jax.random.key(7))
    p = jax.random.bernoulli(kp, density, (n, n)).astype(jnp.int8)
    q = jax.random.bernoulli(kq, density, (n, n)).astype(jnp.int8)
    new, partials = pallas_closure.compose(p, q)
    want = (jnp.dot(p.astype(jnp.bfloat16), q.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32) > 0) | (p != 0)
    np.testing.assert_array_equal(np.asarray(new) != 0, np.asarray(want))
    assert partials.shape == (n // pallas_closure.TILE_M,
                              n // pallas_closure.TILE_N)
    assert int(np.asarray(partials, np.int64).sum()) \
        == int(np.asarray(want).sum())


def test_closure_forms_close_the_sources_grid(tpu_mesh):
    """The dense form through the model's own choice (the kernel on one
    chip, XLA's product on a mesh) closes the grid at side 63 to the
    closed form in 8 doubling rounds; the sparse form, the reference's
    linear join, reaches the dense form's set at side 12."""
    from tpu_distalg.models import transitive_closure as tc
    from tpu_distalg.utils import datasets

    one = tpu_mesh.shape["data"] == 1
    dense = tc.run(datasets.grid_edges(63, 9), tpu_mesh)
    assert tc.dense_geometry(4096, tpu_mesh).form == \
        ("mosaic" if one else "xla")
    assert dense.n_paths == datasets.grid_closure_pairs(63)
    assert dense.n_rounds == 8
    edges = datasets.grid_edges(12, 2)
    small = tc.run(edges, tpu_mesh, n_vertices=169)
    sparse = tc.run_sparse(edges, tpu_mesh,
                           tc.SparseClosureConfig(capacity=1 << 14),
                           n_vertices=169)
    assert sparse.n_paths == small.n_paths \
        == datasets.grid_closure_pairs(12)
    got = np.zeros((169, 169), bool)
    got[sparse.paths[:, 0], sparse.paths[:, 1]] = True
    np.testing.assert_array_equal(got, np.asarray(small.paths)[:169, :169])


def test_sparse_round_closes_a_grid_and_a_tree(tpu_mesh):
    """The pair-set form's semi-naive round compiled for the chip: the
    grid at side 50 (2 601 vertices, 1 755 675 pairs, 100 rounds, many
    derivations a pair) against the dense form's set and the closed
    form, and a tree of height 11 (783 991 pairs, no candidate ever a
    duplicate) to its fixpoint in 12 rounds."""
    from tpu_distalg.models import transitive_closure as tc
    from tpu_distalg.utils import datasets

    v = 51 * 51
    edges = datasets.grid_edges(50, 4)
    dense = tc.run(edges, tpu_mesh, n_vertices=v)
    sparse = tc.run_sparse(edges, tpu_mesh, tc.SparseClosureConfig(
        capacity=1 << 21, delta_capacity=1 << 16, join_capacity=1 << 17),
        n_vertices=v)
    assert sparse.n_paths == dense.n_paths \
        == datasets.grid_closure_pairs(50) == 1755675
    assert sparse.n_rounds == 100
    got = np.zeros((v, v), bool)
    got[sparse.paths[:, 0], sparse.paths[:, 1]] = True
    np.testing.assert_array_equal(got, np.asarray(dense.paths)[:v, :v])
    tree = datasets.tree_edges(11, 6)
    res = tc.run_sparse(tree, tpu_mesh, tc.SparseClosureConfig(
        capacity=1 << 20, delta_capacity=1 << 17, join_capacity=1 << 17),
        n_vertices=len(tree) + 1, keep_paths=False)
    assert res.n_paths == datasets.tree_closure_pairs(11) == 783991
    assert res.n_rounds == 12
