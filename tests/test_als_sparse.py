"""ALS on a ratings list (``models/als.py``'s sparse trainer,
``ops/als_sparse.py``): the trainer against the plain reference
(``benchmarks/reference/als_sparse_ref.py``: ``A_u``, ``b_u`` and
``jnp.linalg.solve`` owner by owner, its own restatement of the
generator) on seeded ratings in several geometries of the pack, with the
reference's bfloat16 control outside the same limit; a fully observed R
against the dense ``als.fit``; one shard against four; resume through
``run_segmented``; the pack's and the generator's invariants; the solve
along the lanes against NumPy; the refusals; the spans and the report."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import als
from tpu_distalg.ops import als_sparse as ops
from tpu_distalg.telemetry import events, report
from tpu_distalg.utils import datasets

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import als_sparse_ref as ref_mod  # noqa: E402

GEOM = dict(seg_slots=8, piece_segs=4, batch=8,    # a block: 64 slots
            classes=(1, 2))
LIMIT = 2e-4        # float32 against float32 reads 1e-7 to 6e-5
K, LAM = 5, 1.4


def _even(total, n):
    d = np.full(n, total // n)
    d[:total - d.sum()] += 1
    return d


def _degrees(kind):
    """The users' degrees of a geometry; the items share the total
    evenly."""
    rng = np.random.default_rng(5)
    if kind == "three_blocks":      # 150 ratings: 19 segments, 5 pieces,
        du = np.concatenate([[150], rng.integers(1, 30, 20)])  # 3 blocks
    elif kind == "minimum_block":   # sixteen owners of one segment: two
        du = rng.integers(5, 9, 16)                      # full batches
    elif kind == "empty_owner":
        du = np.concatenate([[0, 40, 0], rng.integers(1, 20, 12)])
    elif kind == "padded_last":     # 11 owners of 2 segments: a batch
        du = rng.integers(9, 17, 11)                     # of 8, one of 3
    else:                           # every class at once
        du = np.array([3, 12, 20, 30, 70, 200, 8, 16, 17, 33, 1, 0])
    return du, _even(int(du.sum()), 9)


def _ref(du, di, seed, start):
    config = dict(k=K, lam=LAM, n_users=len(du), n_items=len(di),
                  n_ratings=int(du.sum()), n_heldout=64, rating_low=0.0,
                  rating_high=100.0, reference_sample=10 ** 6,
                  reference_heavy_over=10 ** 9, generator=dict(
                      d_min=1, user_d_max=1, item_d_max=1,
                      **{k: als.RATINGS_DEFAULTS[k]
                         for k in ("mean", "scale", "noise")}))
    return ref_mod.Reference(config=config, data_seed=seed,
                             start_seed=start, degrees_of=(du, di))


def _table(mesh, du, di, seed, **kw):
    return als.build_ratings_table(
        int(du.sum()), len(du), len(di), K, mesh, data_seed=seed,
        n_heldout=64, degrees=(du, di), geometry=GEOM, **kw)


def _cfg(du, di, iterations=1, start=3):
    return als.ALSConfig(lam=LAM, m=len(du), n=len(di), k=K,
                         n_iterations=iterations, seed=start)


def _coo(arrays, meta):
    """The ratings back out of the user side's packed blocks."""
    pu, pi = meta["user"], meta["item"]
    shape = (*pu.seg_owner.shape, -1)
    idx = np.asarray(arrays[0]).reshape(shape)
    val = np.asarray(arrays[1]).reshape(shape)
    ok = idx != pi.static.zero_row
    users = np.broadcast_to(pu.seg_owner[:, :, None], idx.shape)[ok]
    return users, pi.owner_of_row[idx[ok]], val[ok]


# ---- the tie to the dense path ----------------------------------------

def test_fully_observed_r_gives_the_dense_fits_factors(mesh1):
    cfg = als.ALSConfig(lam=0.05, m=12, n=9, k=4, n_iterations=3, seed=2)
    R = als.synthesize_rank_k(cfg)
    dense = als.fit(mesh1, cfg, R)
    users, items = np.divmod(np.arange(12 * 9), 9)
    arrays, meta = als.ratings_from_coo(
        users, items, R[users, items], 12, 9, 4, mesh1, **GEOM)
    V0 = np.random.default_rng(cfg.seed + 1).random((9, 4),
                                                    dtype=np.float32)
    sparse = als.fit_ratings(mesh1, cfg, arrays, meta,
                             init=(np.zeros((12, 4), np.float32), V0))
    np.testing.assert_allclose(np.asarray(sparse.U), np.asarray(dense.U),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sparse.V), np.asarray(dense.V),
                               rtol=2e-3, atol=2e-4)
    # the dense path's RMSE is over all m x n entries: the same ones
    np.testing.assert_allclose(np.asarray(sparse.rmse_history),
                               np.asarray(dense.rmse_history), atol=2e-4)


# ---- the mesh, the segments --------------------------------------------

def test_one_shard_against_four(mesh1, mesh4):
    du, di = _degrees("mixed")
    one = als.fit_ratings(mesh1, _cfg(du, di, 2), *_table(mesh1, du, di, 9))
    arrays, meta = _table(mesh4, du, di, 9)
    assert meta["user"].static.n_shards == 4
    four = als.fit_ratings(mesh4, _cfg(du, di, 2), arrays, meta)
    for a, b in ((one.U, four.U), (one.V, four.V)):
        assert ref_mod.rel_err(b, a, np.zeros_like(a)) < 1e-4
    np.testing.assert_allclose(np.asarray(four.rmse_history),
                               np.asarray(one.rmse_history), rtol=1e-5)


def test_resume_through_run_segmented_is_bitwise(mesh1, tmp_path):
    du, di = _degrees("mixed")
    arrays, meta = _table(mesh1, du, di, 4)
    straight = als.fit_ratings(mesh1, _cfg(du, di, 4), arrays, meta)
    d = str(tmp_path / "ck")
    als.fit_ratings(mesh1, _cfg(du, di, 2), arrays, meta,
                    checkpoint_dir=d, checkpoint_every=1)
    resumed = als.fit_ratings(mesh1, _cfg(du, di, 4), arrays, meta,
                              checkpoint_dir=d, checkpoint_every=1)
    for a, b in ((resumed.U, straight.U), (resumed.V, straight.V),
                 (resumed.rmse_history, straight.rmse_history),
                 (resumed.heldout_history, straight.heldout_history)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert straight.rmse_history[-1] < straight.rmse_history[0]


def test_segments_after_the_first_run_the_first_ones_program(
        mesh1, tmp_path, monkeypatch):
    built = []
    real = als.make_fit_fn

    def recording(mesh, config, meta=None):
        built.append(real(mesh, config, meta))
        return built[-1]

    monkeypatch.setattr(als, "make_fit_fn", recording)
    du, di = _degrees("padded_last")
    arrays, meta = _table(mesh1, du, di, 4)
    als.fit_ratings(mesh1, _cfg(du, di, 3), arrays, meta,
                    checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=1)
    assert [fn._cache_size() for fn in built] == [1]


def test_a_dense_r_still_takes_the_dense_trainer(mesh1):
    cfg = als.ALSConfig(m=8, n=6, k=2, n_iterations=1)
    dense = als.make_fit_fn(mesh1, cfg)
    assert dense is not None and als.make_fit_fn(
        mesh1, cfg, {"layout": "dense"}) is not None


# ---- the pack -----------------------------------------------------------

@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("kind", ["three_blocks", "empty_owner", "mixed"])
def test_the_pack_places_every_segment_once(kind, shards):
    du, _ = _degrees(kind)
    geom = ops.SparseGeometry(k=K, **GEOM)
    plan = ops.plan_side(du, geom, shards)
    k0, n_valid = ops.segment_stubs(plan, geom)
    off = np.cumsum(du) - du
    seen = np.zeros(int(du.sum()), np.int64)
    for a, n in zip(k0.reshape(-1), n_valid.reshape(-1)):
        seen[a:a + n] += 1
    assert (seen == 1).all()                   # every rating, once
    own = plan.seg_owner.reshape(-1)
    for u in np.flatnonzero(du):               # in its owner's segments
        mine = np.flatnonzero(own == u)
        assert n_valid.reshape(-1)[mine].sum() == du[u]
        assert k0.reshape(-1)[mine][n_valid.reshape(-1)[mine] > 0].min() \
            == off[u]
    rows = plan.row_of_owner
    assert len(set(rows.tolist())) == len(du) and rows.min() >= 0
    assert (plan.owner_of_row[rows] == np.arange(len(du))).all()
    assert rows.max() < plan.static.zero_row
    assert plan.padding_share == plan.slots_held / du.sum() >= 1.0
    st = plan.static
    assert st.n_blocks * shards == plan.seg_owner.shape[0]


def test_pack_coo_holds_a_repeated_pair_twice():
    geom = ops.SparseGeometry(k=2, **GEOM)
    users = np.array([0, 0, 1, 0])
    items = np.array([2, 2, 0, 1])
    pu = ops.plan_side(np.bincount(users, minlength=2), geom)
    pi = ops.plan_side(np.bincount(items, minlength=3), geom)
    idx, val = ops.pack_coo(pu, geom, users, items, [5., 6., 7., 8.],
                            pi.row_of_owner, pi.static.zero_row)
    assert idx.shape == val.shape == (pu.static.n_blocks, 1, 64)
    ok = idx != pi.static.zero_row
    assert ok.sum() == 4 and sorted(val[ok].tolist()) == [5., 6., 7., 8.]
    assert (pi.owner_of_row[idx[ok]] == 2).sum() == 2
    assert (val[~ok] == 0).all()


# ---- the generator ------------------------------------------------------

@pytest.mark.parametrize("n_owners,total,d_max", [
    (5000, 200_000, 2000), (777, 30_001, 900), (64, 64 * 20, 50)])
def test_degrees_are_a_function_of_the_sizes_alone(n_owners, total, d_max):
    d = datasets.power_law_degrees(n_owners, total, 20, d_max, 1)
    assert d.sum() == total and d.min() >= 20 and d.max() <= d_max
    np.testing.assert_array_equal(
        d, datasets.power_law_degrees(n_owners, total, 20, d_max, 1))
    np.testing.assert_array_equal(
        d, ref_mod.degrees(n_owners, total, 20, d_max, 1))
    if total > n_owners * 20:
        assert np.median(d) < d.mean()              # a long tail
        other = datasets.power_law_degrees(n_owners, total, 20, d_max, 2)
        assert (other != d).any() and \
            sorted(other.tolist()) == sorted(d.tolist())


def test_the_seed_pairs_and_rates_but_sizes_nothing(mesh1):
    du = datasets.power_law_degrees(60, 2400, 20, 300, 1)
    di = datasets.power_law_degrees(40, 2400, 20, 400, 2)
    a, meta_a = _table(mesh1, du, di, 3)
    b, _ = _table(mesh1, du, di, 3)
    c, meta_c = _table(mesh1, du, di, 4)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert [x.shape for x in a] == [x.shape for x in c]
    assert meta_a["user"].static == meta_c["user"].static
    assert (np.asarray(a[0]) != np.asarray(c[0])).any()
    users, items, r = _coo(a, meta_a)
    np.testing.assert_array_equal(np.bincount(users, minlength=60), du)
    np.testing.assert_array_equal(np.bincount(items, minlength=40), di)
    assert r.min() >= 0 and r.max() <= 100 and (r == np.round(r)).all()
    assert 5 < r.std() < 40
    # the item side holds the same ratings
    pu, pi = meta_a["user"], meta_a["item"]
    shape = (*pi.seg_owner.shape, -1)
    idx = np.asarray(a[3]).reshape(shape)
    val = np.asarray(a[4]).reshape(shape)
    ok = idx != pu.static.zero_row
    theirs = sorted(zip(
        pu.owner_of_row[idx[ok]].tolist(),
        np.broadcast_to(pi.seg_owner[:, :, None], idx.shape)[ok].tolist(),
        val[ok].tolist()))
    assert theirs == sorted(zip(users.tolist(), items.tolist(), r.tolist()))


@pytest.mark.parametrize("n", [2, 10, 1000, 4097, 70_000])
def test_the_feistel_walk_is_a_permutation_with_its_inverse(n):
    fwd, inv = datasets.feistel_permutation(n)
    x = jnp.arange(n, dtype=jnp.uint32)
    y = np.asarray(fwd(x, 123))
    assert sorted(y.tolist()) == list(range(n))
    np.testing.assert_array_equal(np.asarray(inv(jnp.asarray(y), 123)),
                                  np.arange(n))
    if n > 10:
        assert (y != np.asarray(fwd(x, 124))).any()
        perm = ref_mod.Permutation(n)
        np.testing.assert_array_equal(
            np.asarray(perm.apply(x, jnp.uint32(123))), y)


def test_a_planted_dot_is_exact_in_any_order():
    gen = datasets.seeded_ratings(1000, 100)
    a = np.asarray(gen.planted(jnp.arange(50), 7, 0), np.float64)
    b = np.asarray(gen.planted(jnp.arange(50), 7, 1), np.float64)
    assert (a * 8 == np.round(a * 8)).all() and np.abs(a).max() <= 1
    exact = np.sum(a * b, axis=1)
    got = np.sum(a.astype(np.float32)[:, ::-1] * b.astype(np.float32)[:, ::-1],
                 axis=1, dtype=np.float32)
    np.testing.assert_array_equal(got, exact)
    low = jnp.sum(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)
                  * jnp.asarray(b, jnp.bfloat16).astype(jnp.float32), 1)
    np.testing.assert_array_equal(np.asarray(low), exact)


# ---- the solve along the lanes ------------------------------------------

@pytest.mark.parametrize("k,batch", [(5, 8), (12, 16), (100, 8)])
def test_solve_batch_against_numpy(k, batch):
    geom = ops.SparseGeometry(k=k, seg_slots=8, piece_segs=4, batch=batch,
                              classes=(1, 2))
    rng = np.random.default_rng(k)
    W = geom.width
    G = np.zeros((batch, 3 * k, W), np.float32)
    G[:, :, :k] = rng.random((batch, 3 * k, k))
    G[:, :, k] = rng.random((batch, 3 * k)) * 100
    G[:, :, k + 1] = 1.0
    G[1] = 0.0                                  # an owner with no rating
    Ap = np.einsum("bsd,bse->bde", G.astype(np.float64),
                   G.astype(np.float64))
    rows, has, sse, seen = jax.jit(
        lambda a: ops.solve_batch(a, LAM, geom))(
        jnp.asarray(Ap, jnp.float32))
    assert np.asarray(has).tolist() == [i != 1 for i in range(batch)]
    assert int(seen) == 3 * k * (batch - 1)
    want = np.stack([np.linalg.solve(
        Ap[i, :k, :k] + LAM * 3 * k * np.eye(k), Ap[i, :k, k])
        for i in range(batch) if i != 1])
    got = np.asarray(rows)[np.asarray(has)]
    assert np.abs(got[:, k:]).max() == 0
    assert np.abs(got[:, :k] - want).max() < 2e-4 * np.abs(want).max()
    err = sum(np.sum((G[i, :, :k].astype(np.float64) @ want[j]
                      - G[i, :, k]) ** 2)
              for j, i in enumerate(i for i in range(batch) if i != 1))
    assert abs(float(sse) - err) < 1e-3 * err


def test_geometry_numbers():
    g = ops.SparseGeometry(k=100)
    assert (g.width, g.solve_n, g.classes, g.block_slots,
            g.block_shape) == (128, 104, ops.CLASSES, 196608, (1536, 128))
    assert ops.SparseGeometry(k=127).width == 256
    small = ops.SparseGeometry(k=5, **GEOM)
    assert (small.classes, small.block_shape) == ((1, 2), (1, 64))
    # where none is stated: the published shape's, the batch scaled
    assert ops.geometry_for(100, 1000990, 624961) == g
    assert ops.geometry_for(12, 900, 500).batch == 192
    assert ops.geometry_for(12, 40000, 500).batch == 2688


# ---- what is refused, by name -------------------------------------------

@pytest.mark.parametrize("what,word", [
    ("sums", "add up to"), ("sizes", "for a table of"),
    ("rank", "packed for rank"), ("pieces", "piece_segs"),
    ("degrees_fit", "do not fit"), ("coo", "not this list's")])
def test_what_cannot_be_packed_or_trained_refuses_by_name(mesh1, what, word):
    du, di = _degrees("padded_last")
    with pytest.raises(ValueError, match=word):
        if what == "sums":
            als.plan_ratings(int(du.sum()), len(du), len(di), K,
                             degrees=(du, di + 1), geometry=GEOM)
        elif what == "sizes":
            als.plan_ratings(int(du.sum()), len(du) + 1, len(di), K,
                             degrees=(du, di), geometry=GEOM)
        elif what == "rank":
            meta = als.plan_ratings(int(du.sum()), len(du), len(di), K,
                                    degrees=(du, di), geometry=GEOM)
            als.make_fit_fn(mesh1, dataclasses.replace(
                _cfg(du, di), k=K + 1), meta)
        elif what == "pieces":
            ops.SparseGeometry(k=K, seg_slots=8, piece_segs=3, batch=9,
                               classes=(1, 2))
        elif what == "degrees_fit":
            datasets.power_law_degrees(10, 100, 20, 50, 1)
        else:
            geom = ops.SparseGeometry(k=K, **GEOM)
            ops.pack_coo(ops.plan_side(du, geom), geom, [0, 0], [1, 1],
                         [1., 2.], np.arange(9), 9)


@pytest.mark.parametrize("argv,word", [
    (["als", "--ratings", "100"], "--users and --items"),
    (["als", "--ratings", "100", "--users", "5", "--items", "5",
      "--data-backend", "virtual"], "held on the device")])
def test_the_cli_refuses_by_name(argv, word):
    from tpu_distalg import cli

    with pytest.raises(SystemExit, match=word):
        cli.main(["--emulate", "1", *argv])


# ---- spans, the report ----------------------------------------------------

def test_spans_and_report_say_the_layout(mesh1, tmp_path):
    du, di = _degrees("mixed")
    tel = str(tmp_path / "tel")
    events.configure(tel)
    try:
        arrays, meta = _table(mesh1, du, di, 2)
        als.fit_ratings(mesh1, _cfg(du, di, 2), arrays, meta,
                        checkpoint_dir=str(tmp_path / "ck"),
                        checkpoint_every=1)
    finally:
        events.configure(False)
    evts = report.load_events(tel)
    ends = {e["name"]: e for e in evts if e["ev"] == "span_end"}
    prep = ends["als:prepare"]
    assert (prep["layout"], prep["ratings"], prep["users"], prep["items"],
            prep["k"]) == ("ratings", int(du.sum()), 12, 9, K)
    # what the loader left on the device, by the arrays' own count (the
    # plan's count, the two factor tables included, is the CLI's line)
    assert prep["bytes"] == sum(a.nbytes for a in arrays) \
        >= meta["ratings_bytes"]
    made = {n: ends[n]["bytes"] for n in ("als:pack", "als:generate",
                                          "als:heldout")}
    assert all(v > 0 for v in made.values()) and "hbm_in_use" not in prep
    assert (prep["user_blocks"], prep["item_blocks"]) == meta["blocks"]
    assert prep["padding_share"] == round(meta["padding_share"], 4) > 1
    parents = {e["name"]: e["parent"] for e in evts
               if e["ev"] == "span_end"}
    for child in ("als:pack", "als:generate", "als:heldout"):
        assert parents[child] == prep["id"], child
    seg = ends["train:segment"]
    assert (seg["layout"], seg["als_gather_form"], seg["als_gram_form"],
            seg["als_gram_layout"], seg["als_solve_form"], seg["tag"]) == (
        "ratings", "xla", "xla", "lanes", "xla", "als")
    lines = report.render(report.summarize(evts)).splitlines()
    assert ("R layout: ratings (gather: xla, gramians: xla by lanes, "
            "solve: xla)") in lines
