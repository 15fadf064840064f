"""The scale path's Lloyd iteration on the lanes layout
(``ops/pallas_lloyd.py``, ``kmeans.build_scaled`` / ``make_fit_seg_fn``)
against a plain float32 reference, at the HiBench shape's widths
(dim 20, k 10, 5 generating clusters) and small row counts.

Blocks of 16 sublane rows (2048 points) instead of the 512 the program
picks at dim 20, so that 4 096 points are two whole blocks, 20 000 end
inside the tenth and 20 011 end on an odd lane. On the CPU the kernel
runs interpreted (the loops over centres rolled; the matmul of the
per-cluster sums is the chip's own bfloat16 ``dot_general``); the chip
compiles it (``tests_tpu``, ``benchmarks/tools/compile_check_kmeans.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import kmeans
from tpu_distalg.ops import bf16_pieces
from tpu_distalg.ops import pallas_lloyd as lloyd
from tpu_distalg.parallel import build_sharded
from tpu_distalg.telemetry import events, names, report
from tpu_distalg.utils import datasets

DIM, K, GEN = 20, 10, 5
LANES16 = lloyd.lanes_geometry(DIM, K, block_rows=16)
MAKE_ROWS, _ = datasets.gaussian_mixture_rows(k=GEN, dim=DIM, spread=8.0)


def _table(mesh, n, seed, lanes=LANES16):
    ps = build_sharded(mesh, n, MAKE_ROWS, seed=seed,
                       chunk_rows=lanes.block_points, pack=lanes.pack)
    return ps.data, jnp.int32(n)


def _rows(n, seed):
    return np.asarray(jax.jit(MAKE_ROWS)(jnp.arange(n), jnp.int32(seed)))


def _reference(pts, centers, iterations):
    """Lloyd in NumPy float32: direct squared distances, first minimum,
    an empty cluster keeps its centre. Returns (centers, last counts)."""
    c = np.asarray(centers, np.float32).copy()
    counts = np.zeros(len(c), np.int64)
    for _ in range(iterations):
        d2 = ((pts[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        a = d2.argmin(1)
        counts = np.bincount(a, minlength=len(c))
        for j in np.nonzero(counts)[0]:
            c[j] = pts[a == j].sum(0, dtype=np.float32) / np.float32(
                counts[j])
    return c, counts


def _seg(mesh, iterations, lanes=LANES16):
    return kmeans.make_fit_seg_fn(
        mesh, kmeans.KMeansConfig(k=K, n_iterations=iterations),
        iterations, lanes)


def _start(centers0):
    return jnp.asarray(centers0), jnp.float32(0.0), jnp.int32(0)


def _inertia(pts, centers):
    d2 = ((pts[:, None, :] - np.asarray(centers)[None]) ** 2).sum(-1)
    return float(d2.min(1).mean(dtype=np.float64))


@pytest.mark.parametrize("iterations", [1, 5, 20])
@pytest.mark.parametrize("n", [4096, 20000, 20011])
def test_segment_follows_the_float32_reference(mesh1, n, iterations):
    """Two tolerances, each with its reason. Both sides are float32;
    they differ in the form of the distance (|c|^2 - 2 x.c against
    (x - c)^2) and in the order of the sums.

    One iteration from the same centres: 2e-5 of the data's spread
    (8.0) where no point changes sides (summation order: measured
    4e-6), 1e-3 where at most two do (a point within ~1e-4 of a
    boundary moves a centre by its distance over the cluster's count,
    6 / 1000 here). A bfloat16 product or centre shows at 4e-3.

    A whole segment: at these row counts one such point sends the two
    trajectories apart by 1e-3 an iteration (the next iteration's
    boundary points differ), so the centres are held through what
    they are for: the inertia within 1e-3 of the reference's."""
    pts = _rows(n, 11)
    x4, valid = _table(mesh1, n, 11)
    c0 = pts[np.random.default_rng(n + iterations).choice(
        n, K, replace=False)]
    centers, _, n_run, counts = _seg(mesh1, iterations)(
        x4, valid, *_start(c0))
    assert int(n_run) == iterations
    assert np.asarray(counts).dtype == np.int32
    assert int(np.asarray(counts).sum()) == n
    before, _ = _reference(pts, c0, iterations - 1)
    want, want_counts = _reference(pts, before, 1)
    assert abs(_inertia(pts, centers) / _inertia(pts, want) - 1) < 1e-3

    got, _, _, got_counts = _seg(mesh1, 1)(x4, valid, *_start(before))
    moved = int(np.abs(np.asarray(got_counts) - want_counts).sum())
    assert moved <= 4
    err = np.abs(np.asarray(got) - want).max() / 8.0
    assert err < (2e-5 if moved == 0 else 1e-3), (err, moved)


def test_table_is_the_generators_rows_in_id_order(mesh4):
    """The packed table, unpacked, is ``make_rows(arange(n), seed)``,
    whatever the shard count; the default geometry at dim 20 packs
    65 536 points to a block with no byte of padding."""
    n = 3 * LANES16.block_points + 5
    x4, _ = _table(mesh4, n, 3)
    assert x4.shape == (4, DIM, 16, 128)
    np.testing.assert_array_equal(
        np.asarray(LANES16.unpack(x4))[:n], _rows(n, 3))
    g = lloyd.lanes_geometry(DIM, K)
    assert (g.block_rows, g.block_points) == (512, 65536)
    assert lloyd.lanes_geometry(64, 32) is None     # rows layout then


def test_shards_agree_with_one_device(mesh1, mesh4):
    """Validity follows from the id: four shards, the last one holding
    the ragged end and one holding only padding, give the counts one
    device gives and the same centres to float32 summation order."""
    n = 2 * LANES16.block_points + 77          # shards 2.04 blocks: 3, 4 empty
    c0 = _rows(n, 5)[:K]
    one = _seg(mesh1, 3)(*_table(mesh1, n, 5), *_start(c0))
    four = _seg(mesh4, 3)(*_table(mesh4, n, 5), *_start(c0))
    np.testing.assert_array_equal(np.asarray(one[3]), np.asarray(four[3]))
    np.testing.assert_allclose(np.asarray(one[0]), np.asarray(four[0]),
                               rtol=0, atol=1e-4)


def test_an_empty_cluster_keeps_its_centre(mesh1):
    n = 4096
    pts = _rows(n, 2)
    c0 = pts[:K].copy()
    c0[7] = 1e3                                  # nobody's nearest
    centers, _, _, counts = _seg(mesh1, 2)(
        *_table(mesh1, n, 2), *_start(c0))
    assert int(np.asarray(counts)[7]) == 0
    np.testing.assert_array_equal(np.asarray(centers)[7], c0[7])
    assert int(np.asarray(counts).sum()) == n


def test_a_tie_goes_to_the_first_centre(mesh1):
    """Two equal centres: every point of theirs goes to the lower
    index (the reference's strict ``<`` scan), the other stays empty."""
    n = 4096
    pts = _rows(n, 4)
    c0 = pts[:K].copy()
    c0[6] = c0[2]
    centers, _, _, counts = _seg(mesh1, 1)(
        *_table(mesh1, n, 4), *_start(c0))
    counts = np.asarray(counts)
    assert counts[2] > 0 and counts[6] == 0
    np.testing.assert_array_equal(np.asarray(centers)[6], c0[2])
    a = np.asarray(jax.jit(kmeans._mesh_fns(mesh1, LANES16)[1])(
        *_table(mesh1, n, 4), jnp.asarray(c0)))[:n]
    assert not (a == 6).any() and (a == 2).sum() == counts[2]


def test_segments_chain_to_the_straight_run_bitwise(mesh1):
    """Four segments of five, centres out to centres in, are the
    twenty-iteration segment and the straight fit, bit for bit."""
    n = 20011
    x4, valid = _table(mesh1, n, 9)
    c0 = _rows(n, 9)[100:100 + K]
    state = _start(c0)
    five = _seg(mesh1, 5)
    for _ in range(4):
        *state, counts = five(x4, valid, *state)
    whole = _seg(mesh1, 20)(x4, valid, *_start(c0))
    straight = kmeans.make_fit_fn(
        mesh1, kmeans.KMeansConfig(k=K, n_iterations=20), LANES16)(
            x4, valid, jnp.asarray(c0))
    assert int(state[2]) == int(whole[2]) == int(straight[2]) == 20
    np.testing.assert_array_equal(np.asarray(state[0]),
                                  np.asarray(whole[0]))
    np.testing.assert_array_equal(np.asarray(whole[0]),
                                  np.asarray(straight[0]))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(whole[3]))


def test_one_compile_serves_every_seed(mesh1):
    """Neither the table's generator nor the segment builds a seed in:
    two seeds lower to the same program and run one compiled segment."""
    n = 4096
    fn = _seg(mesh1, 2)
    for seed in (1, 2147483001):
        x4, valid = _table(mesh1, n, seed)
        fn(x4, valid, *_start(_rows(n, seed)[:K]))
    assert fn._cache_size() == 1

    def lowered(seed):
        def gen(s):
            ids = jnp.arange(LANES16.block_points)
            return LANES16.pack(MAKE_ROWS(ids, s))
        return jax.jit(gen).lower(jnp.int32(seed)).as_text()

    assert lowered(1) == lowered(2147483001)


def test_fit_scaled_takes_the_lanes_path_and_spans_it(mesh8, tmp_path):
    """``fit_scaled`` (what ``tda kmeans --scale-points`` calls) picks
    the layout from the geometry, draws under ``kmeans:prepare`` and
    runs its checkpoint segments through ``run_segmented``."""
    tel = str(tmp_path / "tel")
    events.configure(tel)
    try:
        res = kmeans.fit_scaled(
            mesh8, 30000, MAKE_ROWS,
            kmeans.KMeansConfig(k=K, n_iterations=6, init="farthest"),
            checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=3,
            data_seed=7)
    finally:
        events.configure(False)
    assert res.n_iterations_run == 6
    assert res.centers.shape == (K, DIM)
    a = np.asarray(res.assignments)[:30000]
    assert a.min() >= 0 and a.max() < K
    ends = [e for e in report.load_events(tel) if e["ev"] == "span_end"]
    prep = [e for e in ends if e["name"] == "kmeans:prepare"]
    assert len(prep) == 1 and prep[0]["layout"] == "lanes"
    assert prep[0]["rows"] == 30000
    assert prep[0]["bytes"] == 8 * 65536 * DIM * 4
    segs = [e for e in ends if e["name"] == "train:segment"]
    assert [(e["tag"], e["t0"], e["steps"]) for e in segs] == [
        ("kmeans_fixed", 0, 3), ("kmeans_fixed", 3, 3)]
    # where the kernel adds up the per-cluster sums at this geometry
    assert lloyd.sums_form(K, DIM) == "mxu"
    assert [e["sums_form"] for e in prep + segs] == ["mxu"] * 3
    assert "cluster sums: mxu" in report.render(
        report.summarize(report.load_events(tel))).splitlines()


@pytest.mark.parametrize("where,row,value", [
    ("padding", 1005, np.nan), ("valid", 7, np.inf), ("nowhere", -1, 0.0)])
def test_build_scaled_refuses_a_table_that_is_not_finite(
        mesh1, where, row, value):
    """The matmul of the sums multiplies every point, padding included,
    by every cluster's 0 or 1, and 0 x NaN is NaN: a NaN among the
    padding rows or an infinity in one valid point would reach every
    cluster's sum of that feature. The table is checked once, where it
    is drawn."""
    def make_rows(ids, seed=None):
        rows = MAKE_ROWS(ids, seed)
        return rows.at[:, 3].set(jnp.where(ids == row, value, rows[:, 3]))

    def build():
        return kmeans.build_scaled(mesh1, 1000, make_rows, K, data_seed=1)

    if where == "nowhere":
        x4, n_valid, lanes = build()
        assert int(n_valid) == 1000 and lanes.block_points == 65536
        assert x4.shape == (1, DIM, 512, 128)
    else:
        with pytest.raises(ValueError, match="has to be finite"):
            build()


@pytest.mark.parametrize("lanes", [None, LANES16], ids=["rows", "lanes"])
def test_lowered_segment_names_its_scopes(mesh4, lanes):
    """Every part of an iteration sits under one of the four
    ``tda.kmeans.`` scopes; the kernel carries its body's name."""
    n = 4 * LANES16.block_points
    fn = _seg(mesh4, 2, lanes)
    if lanes is None:
        data = (jax.ShapeDtypeStruct((n, DIM), jnp.float32),
                jax.ShapeDtypeStruct((n,), jnp.float32))
    else:
        data = (jax.ShapeDtypeStruct((4, DIM, 16, 128), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32))
    text = fn.lower(*data, jax.ShapeDtypeStruct((K, DIM), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.int32)).as_text(
                        debug_info=True)
    for scope in (names.KMEANS_ASSIGN, names.KMEANS_STATS,
                  names.KMEANS_SYNC, names.KMEANS_UPDATE):
        assert scope + "/" in text or scope + '"' in text, scope
    if lanes is not None:
        assert "_lloyd_kernel" in text


def test_chunked_rows_equal_the_one_shot_draw(mesh4):
    """``build_sharded(chunk_rows=)`` without ``pack`` writes plain
    rows chunk by chunk into place: the rows, the mask and the padding
    rule of the one-shot draw, with the seed as an argument."""
    n = 5000
    whole = build_sharded(mesh4, n, MAKE_ROWS, seed=3, row_multiple=512)
    chunked = build_sharded(mesh4, n, MAKE_ROWS, seed=3, chunk_rows=512)
    assert chunked.n_padded == whole.n_padded == 6144
    assert chunked.n_valid == n and chunked.padded_rows is None
    np.testing.assert_array_equal(np.asarray(chunked.data),
                                  np.asarray(whole.data))
    np.testing.assert_array_equal(np.asarray(chunked.mask),
                                  np.asarray(whole.mask))
    packed = build_sharded(mesh4, n, MAKE_ROWS, seed=3, chunk_rows=2048,
                           pack=LANES16.pack)
    assert packed.mask is None and packed.n_padded == 8192
    with pytest.raises(ValueError, match="chunk_rows"):
        build_sharded(mesh4, n, MAKE_ROWS, pack=LANES16.pack)


# ---- the kernel alone: where the per-cluster sums are added up ---------

FORMS = {(10, 20): "mxu", (3, 2): "vpu", (7, 5): "vpu", (16, 16): "mxu",
         (32, 32): "mxu", (9, 17): "mxu", (8, 16): "vpu"}
SHAPES = sorted(FORMS)
POINTS16 = 16 * 128                       # a block of 16 sublane rows


def _pass(pts, centers, n_valid, **kw):
    """``lloyd_pass`` over ``pts`` packed into blocks of 16 sublane
    rows, interpreted; the rows past ``len(pts)`` of the last block
    hold 1e30 (padding may hold any finite value)."""
    k, dim = centers.shape
    g = lloyd.lanes_geometry(dim, k, block_rows=16)
    nb = -(-len(pts) // POINTS16)
    full = np.full((nb * POINTS16, dim), 1e30, np.float32)
    full[:len(pts)] = pts
    x4 = jnp.stack([g.pack(jnp.asarray(b))
                    for b in full.reshape(nb, POINTS16, dim)])
    return lloyd.lloyd_pass(x4, jnp.asarray(centers), n_valid,
                            interpret=True, **kw)


def _float64_stats(pts, assign, k):
    """Sums, sums of magnitudes (float64) and counts by ``assign``."""
    sums = np.zeros((k, pts.shape[1]))
    mags = np.zeros_like(sums)
    np.add.at(sums, assign, pts.astype(np.float64))
    np.add.at(mags, assign, np.abs(pts).astype(np.float64))
    return sums, mags, np.bincount(assign, minlength=k)


@pytest.mark.parametrize("k,dim", SHAPES)
def test_sums_form_is_a_function_of_the_geometry(k, dim):
    """Both forms have shapes here: the sums on the MXU (k and dim odd
    and even, planes of up to seven column tiles), and on the VPU."""
    assert lloyd.sums_form(k, dim) == FORMS[k, dim]
    assert lloyd.sums_on_mxu(k, dim) == (k * dim >= lloyd.MXU_MIN_WORK)


@pytest.mark.parametrize("valid", ["none", "tail", "padded_block"])
@pytest.mark.parametrize("k,dim", SHAPES)
def test_pass_sums_and_counts_against_float64(k, dim, valid):
    """Three blocks; no valid point, a tail inside the last block, a
    last block that is padding whole (and the one before it in part).
    Counts exact and int32; every sum within float32 summation error of
    the float64 sum over the points the pass itself assigned."""
    n = 3 * POINTS16
    n_valid = {"none": 0, "tail": 2 * POINTS16 + 777,
               "padded_block": POINTS16 + 5}[valid]
    rng = np.random.default_rng(k * dim)
    pts = (rng.normal(size=(n, dim)) * 5).astype(np.float32)
    pts[n_valid:] = 1e30                 # padding: finite, and huge
    centers = pts[rng.choice(max(n_valid, k), k, replace=False)] \
        if n_valid else rng.normal(size=(k, dim)).astype(np.float32)
    partial, assign = _pass(pts, centers, n_valid, assign=True)
    sums, counts = map(np.asarray, lloyd.fold_stats(partial, k, dim))
    assign = np.asarray(assign).reshape(-1)[:n_valid]
    want, mags, want_counts = _float64_stats(pts[:n_valid], assign, k)
    assert counts.dtype == np.int32 and sums.dtype == np.float32
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == n_valid
    assert sums.shape == (k, dim)
    assert (np.abs(sums - want) <= 4e-7 * mags + 1e-30).all()


def _full_significands(rng, shape):
    """float32 values with all 24 significand bits in use, mixed signs,
    magnitudes from 2**-10 to 1e6 (bfloat16 rounds them by up to 0.4%)."""
    mant = rng.integers(1 << 23, 1 << 24, size=shape).astype(np.float64)
    mant += 1 - mant % 2                          # the last bit set
    exp = rng.integers(-33, -3, size=shape)
    return (mant * 2.0 ** exp * rng.choice([-1, 1], size=shape)
            ).astype(np.float32)


def test_pieces_are_bfloat16_and_add_back_bit_for_bit():
    x = _full_significands(np.random.default_rng(1), (8, 128))
    x[0, :2] = 0.0, 1.0
    hi, mid, lo = map(np.asarray, jax.jit(bf16_pieces.split3)(jnp.asarray(x)))
    for piece in (hi, mid, lo):
        assert not (piece.view(np.uint32) & 0xFFFF).any()
        rounded = jnp.asarray(piece).astype(jnp.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(rounded.astype(jnp.float32)), piece)
    assert (mid != 0).any() and (lo != 0).any()
    back = hi.astype(np.float64) + mid + lo       # exact in float64
    np.testing.assert_array_equal(
        back.astype(np.float32).view(np.uint32), x.view(np.uint32))


def test_mxu_sums_are_float32_sums_of_unrounded_points():
    """The exactness the configuration states: no addend is rounded.
    Points with full significands over 50 binades, zeros and a
    subnormal; each of the 200 sums within float32 summation error of
    the float64 sum (4e-7 of the sum of magnitudes: measured 8e-8), and
    the same bound refuses the sums of the points rounded to bfloat16
    once, by orders of magnitude."""
    k, dim, n = 10, 20, 2 * POINTS16
    assert lloyd.sums_form(k, dim) == "mxu"
    rng = np.random.default_rng(29)
    pts = _full_significands(rng, (n, dim))
    pts[::97, 3] = 0.0
    pts[5, 7] = 1e-40                             # subnormal
    centers = pts[rng.choice(n, k, replace=False)]
    partial, assign = _pass(pts, centers, n, assign=True)
    sums, counts = map(np.asarray, lloyd.fold_stats(partial, k, dim))
    assign = np.asarray(assign).reshape(-1)
    want, mags, want_counts = _float64_stats(pts, assign, k)
    np.testing.assert_array_equal(counts, want_counts)
    err = np.abs(sums - want) / mags
    assert err.max() < 4e-7, err.max()
    rounded = np.asarray(
        jnp.asarray(pts).astype(jnp.bfloat16).astype(jnp.float32))
    control, _, _ = _float64_stats(rounded, assign, k)
    control_err = np.abs(control - want) / mags
    assert control_err.max() > 100 * 4e-7, control_err.max()
    assert np.median(control_err) > 10 * 4e-7


@pytest.mark.parametrize("k,dim", SHAPES)
def test_assignment_is_the_score_pass_alone(k, dim):
    """The pass that also adds up the sums assigns as the pass that
    only assigns (``local_assign``), bit for bit, padding included, and
    its counts are that assignment's over the valid points; against a
    float64 argmin the two differ only where the two nearest centres
    are within float32 rounding of each other."""
    n, n_valid = 2 * POINTS16, 2 * POINTS16 - 300
    rng = np.random.default_rng(7 * k + dim)
    pts = (rng.normal(size=(n, dim)) * 5).astype(np.float32)
    centers = pts[rng.choice(n, k, replace=False)]
    centers[k - 1] = centers[0]                   # a tie: the first wins
    partial, with_stats = _pass(pts, centers, n_valid, assign=True)
    alone, = _pass(pts, centers, n_valid, stats=False, assign=True)
    np.testing.assert_array_equal(np.asarray(with_stats),
                                  np.asarray(alone))
    a = np.asarray(alone).reshape(-1)
    assert not (a == k - 1).any()
    _, counts = lloyd.fold_stats(partial, k, dim)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(a[:n_valid], minlength=k))
    d2 = ((pts[:, None, :].astype(np.float64)
           - centers[None, :k - 1].astype(np.float64)) ** 2).sum(-1)
    two = np.sort(d2, axis=1)[:, :2]
    clear = two[:, 1] - two[:, 0] > 1e-3 * (1 + two[:, 1])
    np.testing.assert_array_equal(a[clear], d2.argmin(1)[clear])
    assert clear.mean() > 0.9
