"""Communication-efficient collectives (tpu_distalg/parallel/comms.py).

The layer's contract, tested at three levels:

  * schedule level — dense is BITWISE the old ``tree_allreduce_sum``;
    bucketed/hier reduce to the same sum (float reduction order only);
    bf16/int8 land within their precision bands; all are
    seeded-replay deterministic;
  * trainer level — the layer's dense schedule inside a trainer is
    bitwise the trainer's plain-psum path (both run here), compressed
    schedules converge in the dense band and replay bitwise;
  * durability — the top-k error-feedback residual rides the scan
    carry INTO the checkpoint state: a ``run_segmented`` resume is
    bitwise-equal to a straight run, and the residual is provably
    nonzero at the boundary (a silently dropped residual would fail
    the bitwise compare).

Plus the byte accounting the bench lines rely on: int8 cuts
``bytes_wire`` >=3x and topk >=4x vs dense at the benchmark widths.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_distalg.models import bmuf, easgd, local_sgd, ma, ssgd
from tpu_distalg.models import logistic_regression as lr
from tpu_distalg.parallel import (
    comms,
    data_parallel,
    tree_allreduce_sum,
)


def _h(x) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(x)).tobytes()).hexdigest()[:16]


def _reduce_on_mesh(mesh, sched, gs, cnts, t=3):
    """Run one sync of (grad, count) through the schedule on the mesh;
    returns (summed grad, summed count, residual host array)."""
    example = (jax.ShapeDtypeStruct(gs.shape[1:], jnp.float32),
               jax.ShapeDtypeStruct((), jnp.float32))
    sync = comms.make_sync(sched, mesh, example)

    def body(g, c, res, tt):
        (gg, cc), r = sync.reduce((g[0], c[0]), res, tt)
        return gg, cc, r

    fn = data_parallel(
        body, mesh,
        in_specs=(P("data", None), P("data"), P("data", None), P()),
        out_specs=(P(), P(), P("data", None)))
    g_sh = jax.device_put(gs, NamedSharding(mesh, P("data", None)))
    c_sh = jax.device_put(cnts, NamedSharding(mesh, P("data")))
    res = jax.device_put(jnp.asarray(sync.init_state()),
                         NamedSharding(mesh, P("data", None)))
    out, cnt, res = jax.jit(fn)(g_sh, c_sh, res, jnp.int32(t))
    return np.asarray(out), float(cnt), np.asarray(res)


# ------------------------------------------------------ schedule level


def test_dense_bitwise_equals_tree_allreduce_sum(mesh4):
    """The default schedule IS the old collective: same psum per leaf,
    bit for bit."""
    rng = np.random.default_rng(0)
    gs = rng.normal(size=(4, 31)).astype(np.float32)
    cnts = np.arange(1.0, 5.0, dtype=np.float32)

    def old(g, c):
        return tree_allreduce_sum((g[0], c[0]))

    fn = data_parallel(
        old, mesh4, in_specs=(P("data", None), P("data")),
        out_specs=(P(), P()))
    g_sh = jax.device_put(gs, NamedSharding(mesh4, P("data", None)))
    c_sh = jax.device_put(cnts, NamedSharding(mesh4, P("data")))
    want_g, want_c = jax.jit(fn)(g_sh, c_sh)

    got_g, got_c, _ = _reduce_on_mesh(mesh4, "dense", gs, cnts)
    np.testing.assert_array_equal(got_g, np.asarray(want_g))
    assert got_c == float(want_c)


@pytest.mark.parametrize("sched,rtol", [
    ("bucketed", 1e-5),   # same f32 sum, ring reduction order
    ("bucketed:64", 1e-5),  # MULTI-bucket: 257 elems over 64-buckets
    ("hier", 1e-5),       # same f32 sum, two-level order
    ("hier:2", 1e-5),
    ("hier:4", 1e-5),     # g == n_shards: degenerates to the flat ring
    ("bf16", 2e-2),       # bf16 wire precision
    ("int8", 6e-2),       # 1/127 quantization against the leaf max
])
def test_schedules_reduce_to_the_sum(mesh4, sched, rtol):
    rng = np.random.default_rng(1)
    gs = rng.normal(size=(4, 257)).astype(np.float32)  # non-divisible len
    cnts = np.arange(1.0, 5.0, dtype=np.float32)
    want = gs.sum(axis=0)
    got, cnt, _ = _reduce_on_mesh(mesh4, sched, gs, cnts)
    assert cnt == 10.0  # the count leaf is NEVER compressed
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, atol=rtol * scale)


def test_schedules_replay_deterministic(mesh4):
    """Same inputs, same step id -> bitwise-identical results, twice —
    int8's stochastic rounding included (threefry(seed, t, shard))."""
    rng = np.random.default_rng(2)
    gs = rng.normal(size=(4, 64)).astype(np.float32)
    cnts = np.ones(4, np.float32)
    for sched in ("bucketed", "hier", "bf16", "int8", "topk:0.1"):
        a, _, ra = _reduce_on_mesh(mesh4, sched, gs, cnts, t=7)
        b, _, rb = _reduce_on_mesh(mesh4, sched, gs, cnts, t=7)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(ra, rb)


def test_int8_rounding_noise_varies_with_step(mesh4):
    """The stochastic-rounding key folds the step id in: different t,
    different (deterministic) noise — the seeded-replay contract, not
    a frozen rounding pattern."""
    rng = np.random.default_rng(3)
    gs = rng.normal(size=(4, 64)).astype(np.float32)
    cnts = np.ones(4, np.float32)
    a, _, _ = _reduce_on_mesh(mesh4, "int8", gs, cnts, t=1)
    b, _, _ = _reduce_on_mesh(mesh4, "int8", gs, cnts, t=2)
    assert not np.array_equal(a, b)


def test_topk_error_feedback_conserves_mass(mesh4):
    """sent + residual == gradient + previous residual, per shard: the
    EF construction loses nothing (arXiv:1312.3020 + EF-SGD)."""
    rng = np.random.default_rng(4)
    gs = rng.normal(size=(4, 40)).astype(np.float32)
    cnts = np.ones(4, np.float32)
    got, _, res = _reduce_on_mesh(mesh4, "topk:0.1", gs, cnts)
    k = max(1, round(0.1 * 40))
    # each shard kept exactly k entries; the residual holds the rest
    sent = gs - res
    assert all(int((np.abs(sent[i]) > 0).sum()) <= k for i in range(4))
    np.testing.assert_allclose(got, sent.sum(axis=0), atol=1e-5)


@pytest.mark.parametrize("sched", ["bucketed", "hier:2", "hier:4",
                                   "bf16", "int8", "topk:0.1"])
def test_schedules_output_bitwise_replicated(mesh8, sched):
    """Every shard computes the bitwise-SAME reduced value — the
    replicated-output contract psum gives for free, which the ring /
    hierarchical / sparse paths must earn with fixed-origin-order
    accumulation (g>=3 hier and topk would silently de-replicate
    under per-shard rotational order; float addition is not
    associative). Observed directly: the body re-emits its local copy
    of the 'replicated' result, one row per shard."""
    rng = np.random.default_rng(5)
    gs = rng.normal(size=(8, 67)).astype(np.float32)
    sync = comms.make_sync(sched, mesh8,
                           jax.ShapeDtypeStruct((67,), jnp.float32))

    def body(g, res, t):
        out, _ = sync.reduce(g[0], res, t)
        return out[None, :]

    fn = data_parallel(
        body, mesh8,
        in_specs=(P("data", None), P("data", None), P()),
        out_specs=P("data", None))
    g_sh = jax.device_put(gs, NamedSharding(mesh8, P("data", None)))
    res = jax.device_put(jnp.asarray(sync.init_state()),
                         NamedSharding(mesh8, P("data", None)))
    rows = np.asarray(jax.jit(fn)(g_sh, res, jnp.int32(1)))
    for i in range(1, 8):
        np.testing.assert_array_equal(
            rows[0], rows[i],
            err_msg=f"{sched}: shard {i} diverged from shard 0")


def test_comm_spec_parse_and_errors():
    assert comms.CommSpec.parse(None).schedule == "dense"
    assert comms.CommSpec.parse("topk:0.05").topk_fraction == 0.05
    assert comms.CommSpec.parse("bucketed:1024").bucket_elems == 1024
    assert comms.CommSpec.parse("hier:2").hier_groups == 2
    assert comms.CommSpec.parse("int8:9").seed == 9
    with pytest.raises(ValueError, match="unknown comm schedule"):
        comms.CommSpec.parse("zstd")
    with pytest.raises(ValueError, match="takes no argument"):
        comms.CommSpec.parse("dense:4")
    with pytest.raises(ValueError, match="topk_fraction"):
        comms.CommSpec.parse("topk:0")


def test_comm_spec_overlap_spellings():
    """Overlap is ON by default; '@seq' spells the sequential A/B, and
    'int8:seed:bucket' sets the overlap-bucket granularity."""
    assert comms.CommSpec.parse("int8").overlap is True
    assert comms.CommSpec.parse("int8@seq").overlap is False
    assert comms.CommSpec.parse("bucketed:64@seq").bucket_elems == 64
    assert comms.CommSpec.parse("bucketed:64@seq").overlap is False
    assert comms.CommSpec.parse("topk:0.05@seq").topk_fraction == 0.05
    assert comms.CommSpec.parse("int8@ov").overlap is True
    spec = comms.CommSpec.parse("int8:9:128")
    assert spec.seed == 9 and spec.bucket_elems == 128
    with pytest.raises(ValueError, match="unknown comm schedule"):
        comms.CommSpec.parse("int8seq")


# ------------------------------------------------------------- overlap


@pytest.mark.parametrize("ov,seq", [
    ("bucketed", "bucketed@seq"),
    ("bucketed:64", "bucketed:64@seq"),       # multi-bucket f32 ring
    ("int8", "int8@seq"),
    ("int8:0:64", "int8:0:64@seq"),           # multi-bucket int8 ring
    ("topk:0.1", "topk:0.1@seq"),
])
def test_overlap_bitwise_equals_sequential(mesh4, ov, seq):
    """The double-buffered pipeline is a SCHEDULING change only: per
    comm spec, overlapped and sequential runs produce bitwise-identical
    sums and residuals (the per-bucket math is the same composition in
    both orders) — 257 elems so the multi-bucket cases carry an odd
    remainder through the padding path."""
    rng = np.random.default_rng(11)
    gs = rng.normal(size=(4, 257)).astype(np.float32)
    cnts = np.ones(4, np.float32)
    a, ca, ra = _reduce_on_mesh(mesh4, ov, gs, cnts, t=5)
    b, cb, rb = _reduce_on_mesh(mesh4, seq, gs, cnts, t=5)
    np.testing.assert_array_equal(a, b)
    assert ca == cb
    np.testing.assert_array_equal(ra, rb)


def test_int8_multi_bucket_odd_remainder_sums(mesh8):
    """Native int8 ring at a deliberately awkward shape: 257 elems over
    8 shards with 64-elem buckets (5 buckets, last one mostly padding)
    still lands in the two-stage quantization band and keeps the count
    leaf exact."""
    rng = np.random.default_rng(12)
    gs = rng.normal(size=(8, 257)).astype(np.float32)
    cnts = np.arange(1.0, 9.0, dtype=np.float32)
    want = gs.sum(axis=0)
    got, cnt, _ = _reduce_on_mesh(mesh8, "int8:0:64", gs, cnts)
    assert cnt == float(cnts.sum())
    scale = float(np.abs(gs).max())
    # two seeded stochastic roundings at 1/127 granularity each, n=8:
    # per-element error bound ~ 2·n·(max/127)
    np.testing.assert_allclose(got, want, atol=2 * 8 * scale / 127)


def test_reduce_compute_thunk_rides_the_sync(mesh4):
    """`reduce(..., compute=thunk)` returns the thunk's value as aux
    and leaves the reduction bitwise-unchanged — the overlap window is
    free to hide trainer math without touching numerics."""
    rng = np.random.default_rng(13)
    gs = rng.normal(size=(4, 64)).astype(np.float32)
    for sched in ("dense", "int8", "bucketed:16", "topk:0.1"):
        sync = comms.make_sync(sched, mesh4,
                               jax.ShapeDtypeStruct((64,), jnp.float32))

        def plain(g, res, t):
            out, _ = sync.reduce(g[0], res, t)
            return out

        def with_thunk(g, res, t):
            out, _, aux = sync.reduce(g[0], res, t,
                                      compute=lambda: g[0] * 3.0)
            return out, aux[None, :]

        specs = (P("data", None), P("data", None), P())
        res = jax.device_put(jnp.asarray(sync.init_state()),
                             NamedSharding(mesh4, P("data", None)))
        g_sh = jax.device_put(gs, NamedSharding(mesh4, P("data", None)))
        f0 = data_parallel(plain, mesh4, in_specs=specs, out_specs=P())
        f1 = data_parallel(with_thunk, mesh4, in_specs=specs,
                           out_specs=(P(), P("data", None)))
        want = np.asarray(jax.jit(f0)(g_sh, res, jnp.int32(2)))
        got, aux = jax.jit(f1)(g_sh, res, jnp.int32(2))
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=sched)
        np.testing.assert_array_equal(np.asarray(aux), gs * 3.0)


def test_sparse_allreduce_public_api(mesh4):
    """The generalized sparse-vector combine (usable beyond gradients):
    per-shard (value, index) pairs — duplicates included — sum into the
    dense vector, replicated bitwise-identically on every shard."""
    length, k = 40, 3
    idx = np.array([[0, 5, 5], [1, 5, 39], [2, 0, 7], [39, 39, 3]],
                   np.int32)
    vals = np.arange(12, dtype=np.float32).reshape(4, k) + 1.0
    want = np.zeros(length, np.float32)
    for s in range(4):
        for j in range(k):
            want[idx[s, j]] += vals[s, j]

    def body(v, i):
        out = comms.sparse_allreduce(v[0], i[0], length)
        return out[None, :]

    fn = data_parallel(
        body, mesh4,
        in_specs=(P("data", None), P("data", None)),
        out_specs=P("data", None))
    rows = np.asarray(jax.jit(fn)(
        jax.device_put(vals, NamedSharding(mesh4, P("data", None))),
        jax.device_put(idx, NamedSharding(mesh4, P("data", None)))))
    for s in range(4):
        np.testing.assert_allclose(rows[s], want, atol=1e-6)
    np.testing.assert_array_equal(rows[0], rows[1])
    np.testing.assert_array_equal(rows[0], rows[3])


def test_sync_stats_wire_reductions(mesh8):
    """The acceptance floor of the bench comparison lines: at the
    benchmark gradient width, int8 moves >=3x fewer wire bytes than
    dense and topk >=4x fewer (the count leaf's dense bytes included)."""
    example = (jax.ShapeDtypeStruct((126,), jnp.float32),
               jax.ShapeDtypeStruct((), jnp.float32))
    stats = {s: comms.make_sync(s, mesh8, example).stats()
             for s in ("dense", "bf16", "int8", "topk", "hier")}
    dense = stats["dense"]["bytes_wire"]
    assert dense == stats["hier"]["bytes_wire"]  # same f32 payload
    assert dense / stats["bf16"]["bytes_wire"] >= 1.8
    assert dense / stats["int8"]["bytes_wire"] >= 3.0
    assert dense / stats["topk"]["bytes_wire"] >= 4.0
    for s in stats.values():
        assert s["bytes_logical"] == 4 * 127


def test_hier_group_inference_and_validation(mesh8, mesh4):
    # flat CPU topology, even axis -> 2 groups (both levels exercised)
    assert comms.infer_groups(mesh8) == 2
    assert comms.infer_groups(mesh4) == 2
    with pytest.raises(ValueError, match="groups do not divide"):
        comms.make_sync("hier:3", mesh4,
                        jax.ShapeDtypeStruct((8,), jnp.float32))


# ------------------------------------------------------- trainer level

# What --comm dense owes a default run: the comms layer's dense
# schedule is bitwise the plain per-leaf psum. A trainer given
# comm='dense' never enters the layer (its plain path calls
# ``tree_allreduce_sum``), so the candidate is the spelling that does:
# 'dense@ov' parses to the same dense schedule and sends the trainer
# down its comm scan (``comms.make_sync`` + ``sync.reduce``). Both run
# here, in one process, with the plain run repeated; nothing recorded
# on another machine is compared.
_DENSE_TRAINERS = {
    "ssgd": lambda comm: (ssgd, ssgd.SSGDConfig(
        n_iterations=30, comm=comm)),
    "ma": lambda comm: (ma, ma.MAConfig(n_iterations=10, comm=comm)),
    "bmuf": lambda comm: (bmuf, bmuf.BMUFConfig(
        n_iterations=10, comm=comm)),
    "easgd": lambda comm: (easgd, easgd.EASGDConfig(
        n_iterations=10, comm=comm)),
    "local_sgd": lambda comm: (local_sgd, local_sgd.LocalSGDConfig(
        n_iterations=10, resample_per_local_step=True, comm=comm)),
}


@pytest.mark.parametrize("name", sorted(_DENSE_TRAINERS))
def test_comm_dense_trajectory_bitwise_plain_psum(mesh4, cancer_data,
                                                  name):
    def run(comm):
        module, cfg = _DENSE_TRAINERS[name](comm)
        res = module.train(*cancer_data, mesh4, cfg)
        return np.asarray(res.w).tobytes(), np.asarray(res.accs).tobytes()

    plain = run("dense")
    assert run("dense@ov") == plain, "the layer's dense != the psum"
    assert run("dense") == plain, "a repeated run differs"


def test_trainer_compressed_replay_deterministic(mesh4, cancer_data):
    """Two full runs under each compressed schedule -> identical
    trajectories (weights AND acc history), per trainer family."""
    for comm in ("int8", "topk:0.05"):
        a = ssgd.train(*cancer_data, mesh4,
                       ssgd.SSGDConfig(n_iterations=25, comm=comm))
        b = ssgd.train(*cancer_data, mesh4,
                       ssgd.SSGDConfig(n_iterations=25, comm=comm))
        assert _h(a.w) == _h(b.w) and _h(a.accs) == _h(b.accs), comm
    a = ma.train(*cancer_data, mesh4,
                 ma.MAConfig(n_iterations=8, comm="int8"))
    b = ma.train(*cancer_data, mesh4,
                 ma.MAConfig(n_iterations=8, comm="int8"))
    assert _h(a.w) == _h(b.w)
    a = lr.train(*cancer_data, mesh4,
                 lr.LRConfig(n_iterations=12, comm="bf16"))
    b = lr.train(*cancer_data, mesh4,
                 lr.LRConfig(n_iterations=12, comm="bf16"))
    assert _h(a.w) == _h(b.w)


@pytest.fixture(scope="module")
def dense_converged_acc(mesh4, cancer_data):
    return ssgd.train(*cancer_data, mesh4, ssgd.SSGDConfig(
        n_iterations=1500, eval_every=150)).final_acc


@pytest.mark.parametrize("comm", ["bf16", "int8", "topk"])
def test_trainer_compressed_converges_in_band(mesh4, cancer_data,
                                              dense_converged_acc, comm):
    """CONVERGED (full 1500-iteration) SSGD: a compressed schedule ends
    within 5 points of dense (top-k's error feedback is what makes its
    1%-of-entries sync hold this). Read on jax 0.9.0's CPU, mesh4:
    dense 0.9415, bf16 0.9298, int8 0.9240, topk 0.9064, so the gaps
    are 0.012 / 0.018 / 0.035; a schedule that loses its gradient ends
    near 0.37 to 0.63. The band is no tighter because SGD on this
    unnormalized task is chaotic: the dense run's own last four
    evaluations read 0.9415, 0.8480, 0.9357, 0.9415, and an earlier
    jax build read dense 0.8129 under topk's 0.8363. Mid-trajectory
    points are NOT comparable at all."""
    acc = ssgd.train(*cancer_data, mesh4, ssgd.SSGDConfig(
        n_iterations=1500, eval_every=150, comm=comm)).final_acc
    assert acc >= dense_converged_acc - 0.05, (comm, acc,
                                               dense_converged_acc)


def test_fused_gather_comm_schedule(mesh4):
    """The flagship kernel path composes with the comm schedules
    (interpret mode): bf16 sync stays near the dense kernel run and
    replays bitwise."""
    import warnings

    from tpu_distalg.utils import datasets

    Xg, yg = datasets.synthetic_two_class(n_rows=256 * 4, n_features=8,
                                          seed=0)
    Xg = datasets.add_bias_column(Xg)
    kw = dict(n_iterations=4, sampler="fused_gather", fused_pack=4,
              gather_block_rows=32, shuffle_seed=0, eval_test=False)
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message="fused_gather:")
        dense = ssgd.train(Xg, yg, Xg[:4], yg[:4], mesh4,
                           ssgd.SSGDConfig(**kw))
        a = ssgd.train(Xg, yg, Xg[:4], yg[:4], mesh4,
                       ssgd.SSGDConfig(**kw, comm="bf16"))
        b = ssgd.train(Xg, yg, Xg[:4], yg[:4], mesh4,
                       ssgd.SSGDConfig(**kw, comm="bf16"))
    assert _h(a.w) == _h(b.w)
    np.testing.assert_allclose(np.asarray(a.w), np.asarray(dense.w),
                               atol=2e-2 * float(np.abs(
                                   np.asarray(dense.w)).max()))


def test_comm_rejected_where_no_per_step_collective(mesh4, cancer_data):
    for bad in (dict(sampler="fused_train", comm="bf16"),
                dict(feature_sharded=True, comm="topk")):
        with pytest.raises(ValueError, match="comm"):
            ssgd.train(*cancer_data, mesh4,
                       ssgd.SSGDConfig(n_iterations=2, **bad))


# ---------------------------------------------------------- durability


def test_topk_residual_nonzero_mid_run(mesh4, cancer_data):
    """The error-feedback state is real state: after a few steps the
    carried residual is nonzero (so the round-trip test below would
    fail if a resume dropped it)."""
    X_train, y_train, X_test, y_test = cancer_data
    from tpu_distalg.parallel import parallelize

    cfg = ssgd.SSGDConfig(n_iterations=7, comm="topk:0.05")
    Xs = parallelize(X_train, mesh4)
    ys = parallelize(y_train, mesh4)
    d = X_train.shape[1]
    fn = ssgd.make_train_fn(mesh4, cfg, Xs.n_padded, d=d)
    from tpu_distalg.models.ssgd import _comm_sync

    sync = _comm_sync(mesh4, cfg, d)
    res0 = jax.device_put(jnp.asarray(sync.init_state()),
                          NamedSharding(mesh4, P("data", None)))
    w0 = jnp.zeros((d,), jnp.float32)
    _, _, res = fn(Xs.data, ys.data, Xs.mask, jnp.asarray(X_test),
                   jnp.asarray(y_test), w0, res0)
    assert float(np.abs(np.asarray(res)).max()) > 0.0


def test_topk_residual_survives_segmented_checkpoint(
        mesh4, cancer_data, tmp_path):
    """checkpoint.run_segmented round-trip: segmented topk == straight
    topk BITWISE — only possible if the residual is saved and restored
    exactly (segment boundary at step 7 of 20, residual nonzero)."""
    cfg = ssgd.SSGDConfig(n_iterations=20, comm="topk:0.05")
    straight = ssgd.train(*cancer_data, mesh4, cfg)
    seg = ssgd.train(*cancer_data, mesh4, cfg,
                     checkpoint_dir=str(tmp_path / "ssgd"),
                     checkpoint_every=7)
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(seg.w))
    np.testing.assert_array_equal(np.asarray(straight.accs),
                                  np.asarray(seg.accs))


def test_local_sgd_comm_segmented_checkpoint(mesh4, cancer_data,
                                             tmp_path):
    """The round-combine family carries (w, ws, delta, residual):
    segmented == straight bitwise under topk, resumed mid-run."""
    cfg = ma.MAConfig(n_iterations=9, comm="topk:0.1")
    straight = ma.train(*cancer_data, mesh4, cfg)
    seg = ma.train(*cancer_data, mesh4, cfg,
                   checkpoint_dir=str(tmp_path / "ma"),
                   checkpoint_every=4)
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(seg.w))
    np.testing.assert_array_equal(np.asarray(straight.ws),
                                  np.asarray(seg.ws))


def test_int8_overlap_segmented_checkpoint(mesh4, cancer_data,
                                           tmp_path):
    """Resume mid-schedule under the OVERLAPPED multi-bucket native
    int8 ring (d=31 over 16-elem buckets → 2 in-flight buckets per
    sync): the pipeline drains inside every sync and the rounding keys
    fold the absolute step id, so segmented == straight BITWISE — the
    in-flight bucket state never leaks across the checkpoint boundary
    and the stochastic rounding replays exactly."""
    cfg = ssgd.SSGDConfig(n_iterations=20, comm="int8:3:16")
    straight = ssgd.train(*cancer_data, mesh4, cfg)
    seg = ssgd.train(*cancer_data, mesh4, cfg,
                     checkpoint_dir=str(tmp_path / "ssgd_int8"),
                     checkpoint_every=7)
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(seg.w))
    np.testing.assert_array_equal(np.asarray(straight.accs),
                                  np.asarray(seg.accs))


def test_topk_overlap_vs_seq_full_trainer(mesh4, cancer_data):
    """Trainer-level A/B of the overlap knob: a full topk run with the
    pipeline on equals the @seq run bit for bit (weights, accs) —
    overlap buys schedule, never numerics, through the whole EF-residual
    carry chain."""
    a = ssgd.train(*cancer_data, mesh4,
                   ssgd.SSGDConfig(n_iterations=15, comm="topk:0.05"))
    b = ssgd.train(*cancer_data, mesh4,
                   ssgd.SSGDConfig(n_iterations=15,
                                   comm="topk:0.05@seq"))
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    np.testing.assert_array_equal(np.asarray(a.accs),
                                  np.asarray(b.accs))


def test_lr_comm_segmented_checkpoint(mesh4, cancer_data, tmp_path):
    cfg = lr.LRConfig(n_iterations=10, comm="int8")
    straight = lr.train(*cancer_data, mesh4, cfg)
    seg = lr.train(*cancer_data, mesh4, cfg,
                   checkpoint_dir=str(tmp_path / "lr"),
                   checkpoint_every=4)
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(seg.w))


# ----------------------------------------------------------- telemetry


def test_comm_counters_emitted(mesh4, cancer_data, tmp_path):
    """A comm run bumps comm.bytes_wire/bytes_logical/rounds/syncs —
    and the report layer surfaces the achieved compression ratio."""
    from tpu_distalg import telemetry
    from tpu_distalg.telemetry import report as treport

    telemetry.configure(str(tmp_path))
    try:
        ssgd.train(*cancer_data, mesh4,
                   ssgd.SSGDConfig(n_iterations=5, comm="int8"))
    finally:
        telemetry.configure(False)
    summary = treport.summarize(treport.load_events(str(tmp_path)))
    counters = summary["counters"]
    assert counters["comm.syncs"] == 5
    assert counters["comm.rounds"] >= 5
    assert 0 < counters["comm.bytes_wire"] < counters[
        "comm.bytes_logical"]
    rendered = treport.render(summary)
    assert "comm:" in rendered and "compression" in rendered


def test_overlap_counters_render_efficiency_line(tmp_path):
    """comm.overlap_hidden_ms / comm.sync_ms (bumped by the bench's
    seq-vs-overlap calibration via comms.emit_overlap_counters) render
    as the tda report overlap-efficiency line: fraction of comm time
    hidden behind compute."""
    from tpu_distalg import telemetry
    from tpu_distalg.telemetry import report as treport

    telemetry.configure(str(tmp_path))
    try:
        comms.emit_overlap_counters(hidden_ms=300.4, comm_ms=100.2)
    finally:
        telemetry.configure(False)
    summary = treport.summarize(treport.load_events(str(tmp_path)))
    assert summary["counters"]["comm.overlap_hidden_ms"] == 300
    assert summary["counters"]["comm.sync_ms"] == 100
    rendered = treport.render(summary)
    assert "comm overlap: 300 ms hidden behind compute" in rendered
    assert "75% of 400 ms comm time" in rendered


# -------------------------------------------------- host wire codecs


def test_host_codec_int8_deterministic_unbiased_and_exact_decode():
    """The cluster wire's int8 stage: same (seed, path) ⇒ identical
    bytes; different path ⇒ different rounding noise; decode widens
    int8→int32 exactly before the one scale multiply; stochastic
    rounding is unbiased over repeats."""
    codec = comms.make_host_codec("int8:7")
    x = np.random.RandomState(0).randn(512).astype(np.float32)
    a1, _ = codec.encode(x, None, 1, 0, 3)
    a2, _ = codec.encode(x, None, 1, 0, 3)
    assert np.array_equal(a1["q"], a2["q"])
    assert np.array_equal(a1["scale"], a2["scale"])
    a3, _ = codec.encode(x, None, 1, 0, 4)
    assert not np.array_equal(a1["q"], a3["q"])
    assert a1["q"].dtype == np.int8
    dec = codec.decode(a1, 512)
    scale = float(a1["scale"][0])
    assert np.abs(dec - x).max() <= scale + 1e-7
    # unbiased: mean reconstruction error over many seeded paths ~ 0
    errs = []
    for p in range(64):
        a, _ = codec.encode(x, None, 1, 0, p)
        errs.append((codec.decode(a, 512) - x).mean())
    assert abs(float(np.mean(errs))) < scale / 4


def test_host_codec_topk_pairs_and_error_feedback():
    """topk keeps the k largest-|.| of (delta + residual) as (value,
    index) pairs, scatter-adds exactly on decode, and the residual
    carries everything unsent — over windows nothing is lost (EF-SGD:
    the sums telescope)."""
    codec = comms.make_host_codec("topk:0.25")
    d = 64
    rng = np.random.RandomState(1)
    res = np.zeros(d, np.float32)
    sent_total = np.zeros(d, np.float32)
    pushed_total = np.zeros(d, np.float32)
    for w in range(8):
        delta = rng.randn(d).astype(np.float32)
        pushed_total += delta
        arrays, res = codec.encode(delta, res, 1, 0, w)
        assert arrays["vals"].shape == (16,)        # 0.25 * 64
        assert arrays["idx"].dtype == np.int32
        sent_total += codec.decode(arrays, d)
    # telescoping EF invariant: sent + residual == everything pushed
    np.testing.assert_allclose(sent_total + res, pushed_total,
                               rtol=1e-4, atol=1e-4)


def test_host_codec_tree_round_trip_and_schedule_gate():
    codec = comms.make_host_codec(comms.CommSpec.parse("int8:5"))
    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.ones(5, np.float32)}
    arrays, resd = comms.encode_tree(
        codec, tree, comms.zero_residuals(tree), 2, 1, 0, 7)
    assert set(arrays) == {"w.q", "w.scale", "b.q", "b.scale"}
    out = comms.decode_tree(codec, arrays, tree)
    assert out["w"].shape == (3, 4) and out["b"].shape == (5,)
    assert np.abs(out["w"] - tree["w"]).max() < 0.1
    assert sorted(resd) == ["b", "w"]
    # device-only schedules have no host spelling — refused, named
    with pytest.raises(ValueError, match="host-wire codec"):
        comms.make_host_codec("hier")
    assert comms.make_host_codec("dense") is None


def test_host_pull_codec_is_int8_under_every_compressed_mode():
    """Review pin: pulls ride the int8 codec under BOTH compressed
    modes — topk pairs on the pull direction would silently lose the
    untransmitted (1−frac) of every center delta from the worker's
    cached view (no residual channel exists coordinator-side)."""
    assert comms.make_host_pull_codec("dense") is None
    assert isinstance(comms.make_host_pull_codec("int8:7"),
                      comms.Int8HostCodec)
    assert isinstance(comms.make_host_pull_codec("topk:0.25"),
                      comms.Int8HostCodec)
    # and the seed rides through, so both ends derive the same stream
    assert comms.make_host_pull_codec("int8:7").spec.seed == 7
