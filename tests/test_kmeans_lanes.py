"""The scale path's Lloyd iteration on the lanes layout
(``ops/pallas_lloyd.py``, ``kmeans.build_scaled`` / ``make_fit_seg_fn``)
against a plain float32 reference, at the HiBench shape's widths
(dim 20, k 10, 5 generating clusters) and small row counts.

Blocks of 16 sublane rows (2048 points) instead of the 512 the program
picks at dim 20, so that 4 096 points are two whole blocks, 20 000 end
inside the tenth and 20 011 end on an odd lane. On the CPU the kernel
runs interpreted (a block a step, loops over centres rolled); the chip
compiles it (``tests_tpu``, ``benchmarks/tools/compile_check_kmeans.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import kmeans
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
