"""The scale path's Lloyd iteration on the wide layout
(``ops/pallas_lloyd_wide.py``, ``kmeans.build_scaled`` /
``make_fit_seg_fn``) against the benchmark's plain float32 reference
(``benchmarks/reference/kmeans_wide_ref.py``: the distance by its
definition, its own table, nothing of the program imported), at a small
wide shape: k 96, dim 49 (k x dim past the lanes kernel's 1024, dim not
a multiple of 8, so a block holds 64 feature rows for 49), 10 generating
clusters, blocks of 512 points.

On the CPU the kernels run interpreted, with the bfloat16
``dot_general``s and the loop over the points they ship with; the chip
compiles them (``tests_tpu``,
``benchmarks/tools/compile_check_kmeans_wide.py``). The per-cluster sums
take one of two forms (``wide.sums_form(k, dim)``): k 96 is a one-hot
product's shape, ``SCAT`` (k 2048 at the same 49 dimensions) a
scatter-add's.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import kmeans
from tpu_distalg.ops import pallas_lloyd as lloyd
from tpu_distalg.ops import pallas_lloyd_wide as wide
from tpu_distalg.parallel import build_sharded
from tpu_distalg.telemetry import events, names, report

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import kmeans_ref, kmeans_wide_ref  # noqa: E402

DIM, K, GEN, SPREAD = 49, 96, 10, 8.0
GEOM = wide.wide_geometry(DIM, K)
SCAT = wide.wide_geometry(DIM, 2048)      # past the one-hot's reach
GEOMS = {"mxu": GEOM, "scatter": SCAT}
FORMS = {"mxu": wide.onehot_stats, "scatter": wide.scatter_stats}
P = GEOM.block_points
U = 6e-8                                  # float32's unit roundoff


def make_rows(ids, seed):
    return kmeans_ref.make_rows(ids, DIM, GEN, seed, SPREAD)


def _table(mesh, n, seed, geom=GEOM, rows=make_rows):
    ps = build_sharded(mesh, n, rows, seed=seed,
                       chunk_rows=geom.block_points, pack=geom.pack)
    return ps.data, jnp.int32(n)


def _rows(n, seed):
    return np.asarray(jax.jit(make_rows)(jnp.arange(n), jnp.int32(seed)))


def _seg(mesh, iterations, geom=GEOM):
    return kmeans.make_fit_seg_fn(
        mesh, kmeans.KMeansConfig(k=geom.k, n_iterations=iterations),
        iterations, geom)


def _start(centers0):
    return jnp.asarray(centers0), jnp.float32(0.0), jnp.int32(0)


def _reference(n, seed):
    ref = kmeans_wide_ref.Reference(
        n_rows=n, dim=DIM, k=K, clusters=GEN, spread=SPREAD,
        data_seed=seed, init_seed=seed + 1, device=jax.devices()[0],
        block_rows=1024)
    ref.build()
    return ref


# one iteration from the same centres, both sides float32; they differ in
# the form of the distance (|c|^2 - 2 x.c as six bfloat16 products
# against (x - c)^2) and in the order of the sums. Where no point changes
# sides: summation order, 2e-5 of the data's spread (measured 6e-7).
# Where a point does (its two nearest centres within float32 rounding of
# the score, 1e-3 on 6000 here): it moves two centres by its distance
# over their counts, 6 / 30 of a cluster at the smallest n: 2e-2.
SAME, MOVED = 2e-5, 2e-2


@pytest.mark.parametrize("iterations", [1, 4])
@pytest.mark.parametrize("n", [3 * P, 6000, 12043])
def test_segment_follows_the_float32_reference(mesh1, n, iterations):
    """The reference follows ``iterations`` calls from the seeded
    start; the program runs the last one from the reference's centres
    (tolerances above) and the whole segment from the start (held by
    what the centres are for: the inertia on the data within 1e-3 of
    the reference's, because one point that changes sides sends two
    trajectories apart)."""
    ref = _reference(n, 11)
    c0 = ref.init_centers()
    want, want_counts = ref.follow(iterations, 1)
    x3, valid = _table(mesh1, n, 11)
    centers, _, n_run, counts = _seg(mesh1, iterations)(
        x3, valid, *_start(c0))
    assert int(n_run) == iterations
    assert np.asarray(counts).dtype == np.int32
    assert int(np.asarray(counts).sum()) == n == int(want_counts.sum())
    X = ref.heldout(2048)
    assert abs(ref.inertia(X, np.asarray(centers))
               / ref.inertia(X, want[-1]) - 1) < 1e-3

    before = c0 if iterations == 1 else want[-2]
    got, _, _, got_counts = _seg(mesh1, 1)(x3, valid, *_start(before))
    moved = int(np.abs(np.asarray(got_counts) - want_counts).sum())
    assert moved <= 4
    err = kmeans_ref.centers_err(got, want[-1], SPREAD)
    assert err < (SAME if moved == 0 else MOVED), (err, moved)


def _fewer(pieces):
    """``split3`` with only its first ``pieces`` pieces, the rest zero."""
    real = wide.split3

    def split(x):
        got = real(x)
        return got[:pieces] + tuple(jnp.zeros_like(p) for p in got[pieces:])

    return {"split3": split}


def _with_a_zero_piece():
    """``split3`` with a fourth piece of zeros for a band to point at,
    where it splits the assign kernel's block (the one-hot sums split
    chunks of ``STATS_POINTS`` and take their three)."""
    real = wide.split3

    def split(x):
        got = real(x)
        if x.shape == (GEOM.dim_held, P):
            got += (jnp.zeros_like(got[0]),)
        return got

    return split


# what each fault puts in place of the module's names, and whether it
# moves points by the dozen (an operand of one piece, a band that meets
# the wrong band) or only leaves the tolerance of the shipped product (a
# term of 2^-16 of the product missing)
FAULTS = {
    "one_piece": (lambda: _fewer(1), True),
    "two_pieces": (lambda: _fewer(2), False),
    # the block's fifth band (x_mid under c_hi) holds zeros: the score
    # is short of a term of 2^-8 of the product
    "band_zeroed": (lambda: {
        "split3": _with_a_zero_piece(),
        "BANDS": wide.BANDS[:4] + ((0, 3),) + wide.BANDS[5:]}, True),
    # the block's first two bands change places, the centres' do not:
    # lo.lo and hi.hi where lo.hi and hi.lo were
    "bands_swapped": (lambda: {
        "BANDS": ((2, 2), (0, 0)) + wide.BANDS[2:]}, True),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_bfloat16_product_is_caught(mesh1, monkeypatch, fault):
    """The same comparison with operands of fewer pieces than carry a
    float32 (one: the MXU's default precision; two: ``HIGH``) and with a
    stack whose bands do not meet their partners (one band zero, two
    bands swapped on one side only): points
    change sides by the dozen or the centres leave the tolerance that
    holds the shipped product; so does the reference's own bfloat16
    control."""
    n = 6000
    ref = _reference(n, 11)
    c0 = ref.init_centers()
    want, want_counts = ref.follow(1, 1)
    low, _ = ref.follow(1, 1, dtype=jnp.bfloat16)
    assert kmeans_ref.centers_err(low[-1], want[-1], SPREAD) > MOVED
    patch, by_the_dozen = FAULTS[fault]
    for name, value in patch().items():
        monkeypatch.setattr(wide, name, value)
    jax.clear_caches()
    try:
        got, _, _, counts = _seg(mesh1, 1)(
            *_table(mesh1, n, 11), *_start(c0))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    moved = int(np.abs(np.asarray(counts) - want_counts).sum())
    err = kmeans_ref.centers_err(got, want[-1], SPREAD)
    if by_the_dozen:
        assert moved > 4 and err > MOVED, (err, moved)
    else:
        # closer, and still not the statement's
        assert err > SAME, (err, moved)


@pytest.mark.parametrize("k,dim,layout,sums", [
    (10, 20, "lanes", "mxu"), (32, 32, "lanes", "mxu"),
    (1024, 1, "lanes", "mxu"),
    (25, 41, "wide", "mxu"), (1025, 1, "wide", "mxu"),
    (96, 49, "wide", "mxu"), (2048, 49, "wide", "scatter"),
    (4096, 784, "wide", "scatter"), (16384, 128, "wide", "scatter"),
    (2, 5000, "rows", None)])
def test_layout_is_a_function_of_k_and_dim(k, dim, layout, sums):
    """1024 and 1025 fall on the two sides; nothing else is asked. The
    spans say how the layout's pass adds up the per-cluster sums."""
    geom = kmeans.scale_geometry(dim, k)
    assert kmeans.layout_of(geom) == layout
    assert (lloyd.lanes_geometry(dim, k) is None) == (layout != "lanes")
    assert kmeans._span_fields(k, geom).get("sums_form") == sums
    if layout == "wide":
        assert geom == wide.wide_geometry(dim, k)
        assert geom.k_padded % geom.stats_tile == 0
        assert geom.stats_tile % geom.centre_tile == 0
        assert geom.dim_held % 16 == 0 and geom.dim_mxu % 128 == 0
        assert kmeans._span_fields(k, geom) == {
            "layout": "wide", "dist_form": "mxu6",
            "dist_depth": geom.dist_depth,
            "sums_form": wide.sums_form(k, dim)}
    if (k, dim) == (4096, 784):
        # the published widths: 4 x 784 bytes a point, nothing padded;
        # one accumulator of the scatter holds every centre
        assert (geom.dim_held, geom.point_bytes, geom.block_points,
                geom.centre_tile, geom.stats_tile, geom.k_padded,
                geom.scatter_tile, geom.dist_depth) == (
                    784, 3136, 512, 512, 4096, 4096, 4096, 4736)


@pytest.mark.parametrize("dim,k,form", [
    # the shapes tests_tpu compiles
    (784, 4096, "scatter"), (96, 1024, "mxu"), (128, 1024, "scatter"),
    (96, 16384, "scatter"), (128, 16384, "scatter"), (49, 96, "mxu"),
    # either side of the crossing read on the chip (PERF.md §6, PR 31)
    (784, 308, "mxu"), (784, 309, "scatter"),
    (128, 927, "mxu"), (128, 928, "scatter"),
    (1024, 255, "mxu"), (1024, 256, "scatter")])
def test_sums_form_is_a_function_of_k_and_dim(dim, k, form):
    """A one-hot product costs k x dim a point, a scatter-add its loop
    and its transpose: k * dim_held against ``SCATTER_LOOP`` +
    ``SCATTER_LANE`` * dim_mxu, nothing else."""
    assert wide.sums_form(k, dim) == form
    geom = wide.wide_geometry(dim, k)
    assert geom.sums_form == form
    held, deep = geom.dim_held, geom.dim_mxu
    assert (form == "scatter") == (
        k * held >= wide.SCATTER_LOOP + wide.SCATTER_LANE * deep)
    # an accumulator and its dump rows stay under the budget
    assert (geom.scatter_tile + 8) * deep * 4 <= wide.ACC_BYTES
    assert geom.scatter_tile % 8 == 0


@pytest.mark.parametrize("dim,depth,tile", [
    (784, 4736, 512), (64, 384, 512), (96, 640, 512), (49, 384, 512),
    (100, 768, 512), (200, 1280, 512),
    # whole slabs already: the same depth as six products of dim_mxu
    (128, 768, 512), (1024, 6144, 512),
    # a tile of the centres' stack past ACC_BYTES: half as many centres
    (2720, 16384, 512), (2721, 16512, 256), (4096, 24576, 256)])
def test_dist_depth_is_a_function_of_dim(dim, depth, tile):
    """The one contraction is ``6 * dim_held`` rows padded to whole
    128-deep slabs once, never deeper than six products of ``dim_mxu``
    each; a tile of centres' stack stays under ``ACC_BYTES``; whatever
    k."""
    for k in (600, 4096):
        geom = wide.wide_geometry(dim, k)
        assert (geom.dist_depth, geom.centre_tile) == (depth, tile)
        assert geom.dist_form == "mxu6"
        assert kmeans._span_fields(k, geom)["dist_depth"] == depth
    assert depth == -(-6 * geom.dim_held // 128) * 128 <= 6 * geom.dim_mxu
    assert tile * depth * 2 <= wide.ACC_BYTES < 2 * tile * depth * 2 \
        or tile == wide.CENTRE_TILE
    assert wide.wide_geometry(dim, 96).centre_tile == 128


def _eqns(fn, *args):
    """Every equation under ``fn``, kernels' bodies included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def _depths(fn, *args):
    """How deep every ``dot_general`` under ``fn`` contracts."""
    return [e.invars[0].aval.shape[e.params["dimension_numbers"][0][0][0]]
            for e in _eqns(fn, *args) if e.primitive.name == "dot_general"]


@pytest.mark.parametrize("dim,k", [(784, 512), (64, 128), (96, 256),
                                   (100, 256), (128, 128), (1024, 128)])
def test_lowered_kernel_holds_one_product_as_deep_as_the_geometry_says(
        dim, k):
    geom = wide.wide_geometry(dim, k)
    x3 = jnp.zeros((1, geom.dim_held, P), jnp.float32)
    c = jnp.zeros((k, dim), jnp.float32)
    assert _depths(lambda x, c: wide.wide_assign(x, c, geom=geom),
                   x3, c) == [geom.dist_depth]


def _six_products(x3, centers):
    """The first minimum of ``|c|^2 - 2 x.c`` with the product as the sum
    of six ``dot_general``s of ``split3``'s pieces, smallest terms first
    (what the kernel's one contraction stacks), a block at a time."""
    ch, cm, cl = wide.split3(-2.0 * centers)
    c2 = jnp.sum(centers * centers, axis=1)[:, None]

    def block(xb):
        xh, xm, xl = wide.split3(xb[:centers.shape[1]])
        dot = functools.partial(jnp.matmul,
                                preferred_element_type=jnp.float32)
        s = dot(cl, xh) + dot(ch, xl)
        s = s + dot(cm, xm)
        s = s + (dot(cm, xh) + dot(ch, xm))
        return jnp.argmin((s + dot(ch, xh)) + c2, axis=0)

    return jax.jit(lambda x: jax.lax.map(block, x))(x3)


@pytest.mark.parametrize("dim,k", [(49, 128), (64, 512), (96, 256),
                                   (100, 256), (200, 128), (784, 512)])
def test_stacked_product_is_the_six_products(dim, k):
    """The kernel's one contraction against the sum of six
    ``dot_general``s and against float64, three blocks with a ragged
    last one: each sends a point where float64 does wherever the two
    nearest centres lie further apart than 1e-6 of the distance, so the
    two agree there; elsewhere on all but a few."""
    n = 3 * P - 137
    rng = np.random.default_rng(dim * k)
    geom = wide.wide_geometry(dim, k)
    pts = np.zeros((3 * P, dim), np.float32)
    pts[:n] = rng.standard_normal((n, dim))
    centers = pts[rng.choice(n, k, replace=False)] \
        + 0.5 * rng.standard_normal((k, dim)).astype(np.float32)
    x3 = jax.vmap(geom.pack)(jnp.asarray(pts).reshape(3, P, dim))
    one = np.asarray(wide.wide_assign(
        x3, jnp.asarray(centers), geom=geom, interpret=True)
        ).reshape(-1)[:n]
    six = np.asarray(_six_products(x3, jnp.asarray(centers))
                     ).reshape(-1)[:n]
    d = ((pts[:n, None].astype(np.float64)
          - centers[None].astype(np.float64)) ** 2).sum(-1)
    want = d.argmin(1)
    two = np.partition(d, 1, axis=1)[:, :2]
    clear = two[:, 1] - two[:, 0] > 1e-6 * two[:, 0]
    assert clear.mean() > 0.99
    np.testing.assert_array_equal(one[clear], want[clear])
    np.testing.assert_array_equal(six[clear], want[clear])
    assert (one != six).sum() <= 2
    assert one.min() >= 0 and one.max() < k


def test_table_is_the_generators_rows_in_id_order(mesh4):
    n = 3 * P + 5
    x3, _ = _table(mesh4, n, 3)
    assert x3.shape == (4, 64, P) and GEOM.dim_held == 64
    np.testing.assert_array_equal(
        np.asarray(GEOM.unpack(x3))[:n], _rows(n, 3))
    assert not np.asarray(x3)[:, DIM:].any()      # the held rows past dim


TILES = wide.wide_geometry(2, 600)      # two tiles of 512 centres


@pytest.mark.parametrize("first,second", [
    (3, 550), (6, 13), (511, 512), (0, 599)],
    ids=["across_tiles", "across_sublanes", "at_the_boundary", "ends"])
def test_a_tie_goes_to_the_first_centre(mesh1, first, second):
    """Two equal centres, in one tile of centres or either side of the
    boundary between two: every point of theirs goes to the lower index
    (the reference's first minimum), the other stays empty."""
    assert (TILES.centre_tile, TILES.k_padded) == (512, 1024)
    n, k = 3000, TILES.k

    def rows(ids, seed):
        return kmeans_ref.make_rows(ids, 2, 50, seed, 30.0)

    pts = np.asarray(jax.jit(rows)(jnp.arange(n), jnp.int32(4)))
    c0 = pts[:k].copy()
    c0[second] = c0[first]
    x3, valid = _table(mesh1, n, 4, TILES, rows)
    centers, _, _, counts = _seg(mesh1, 1, TILES)(x3, valid, *_start(c0))
    counts = np.asarray(counts)
    assert counts[first] > 0 and counts[second] == 0
    np.testing.assert_array_equal(np.asarray(centers)[second], c0[first])
    a = np.asarray(jax.jit(kmeans._mesh_fns(mesh1, TILES)[1])(
        x3, valid, jnp.asarray(c0)))[:n]
    want = np.asarray(kmeans_wide_ref.nearest(
        jnp.asarray(pts.T), jnp.asarray(c0)))
    np.testing.assert_array_equal(a, want)
    assert int(counts.sum()) == n


def test_an_empty_cluster_keeps_its_centre(mesh1):
    n = 4000
    c0 = _rows(n, 2)[:K].copy()
    c0[7] = 1e3                                  # nobody's nearest
    centers, _, _, counts = _seg(mesh1, 2)(
        *_table(mesh1, n, 2), *_start(c0))
    assert int(np.asarray(counts)[7]) == 0
    np.testing.assert_array_equal(np.asarray(centers)[7], c0[7])
    assert int(np.asarray(counts).sum()) == n


@pytest.mark.parametrize("form", ["mxu", "scatter"])
@pytest.mark.parametrize("n", [2 * P, 2 * P - 300, P + 1, 257])
def test_padding_and_a_ragged_last_block_are_not_counted(mesh1, n, form):
    """Validity follows from the id, in whole blocks, in a block that
    ends inside a chunk of the stats kernel and in one that holds a
    single valid point: the counts are those of the valid points'
    assignment and the sums are theirs alone, in either form of the
    sums (the scatter sends padding to a row nobody reads)."""
    pts = _rows(2 * P, 6)
    c0 = pts[:K]
    x3, _ = _table(mesh1, 2 * P, 6)              # every row finite
    assign = wide.wide_assign(x3, jnp.asarray(c0), geom=GEOM,
                              interpret=True)
    sums, counts = jax.jit(
        lambda x, a: FORMS[form](x, a, n, geom=GEOM, interpret=True))(
            x3, assign)
    a = np.asarray(assign).reshape(-1)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(a[:n], minlength=K))
    assert int(np.asarray(counts).sum()) == n
    want = np.zeros((K, DIM))
    np.add.at(want, a[:n], pts[:n].astype(np.float64))
    np.testing.assert_allclose(np.asarray(sums), want, rtol=0, atol=2e-4)


def _case(dim, k, n_blocks, seed, ids=None):
    """Normal points in the wide geometry's blocks and ids for them."""
    rng = np.random.default_rng(seed)
    geom = wide.wide_geometry(dim, k)
    x = np.zeros((n_blocks, geom.dim_held, P), np.float32)
    x[:, :dim] = rng.standard_normal((n_blocks, dim, P))
    if ids is None:
        ids = rng.integers(0, k, (n_blocks, 1, P))
    ids = np.broadcast_to(ids, (n_blocks, 1, P)).astype(np.int32)
    return geom, x, ids


def _expected(geom, x, ids, n):
    """float64 sums, exact counts, and what a straight float32 sum of a
    cluster's m points may be off by: m x 6e-8 of the sum of
    magnitudes, coordinate by coordinate."""
    rows = x[:, :geom.dim].transpose(0, 2, 1).reshape(-1, geom.dim)[:n]
    a = ids.reshape(-1)[:n]
    want = np.zeros((geom.k, geom.dim))
    mags = np.zeros((geom.k, geom.dim))
    np.add.at(want, a, rows.astype(np.float64))
    np.add.at(mags, a, np.abs(rows).astype(np.float64))
    counts = np.bincount(a, minlength=geom.k)
    return want, counts, counts[:, None] * U * mags


def _run(form, geom, x, ids, n, **kw):
    sums, counts = jax.jit(lambda x, a: FORMS[form](
        x, a, n, geom=geom, interpret=True, **kw))(
            jnp.asarray(x), jnp.asarray(ids))
    return np.asarray(sums), np.asarray(counts)


@pytest.mark.parametrize("dim,k", [(64, 4096), (49, 2048), (128, 1024),
                                   (200, 704)])
def test_scatter_sums_against_float64_and_the_onehot(dim, k):
    """The scatter form on shapes that take it, a ragged end: the sums
    within a straight float32 sum's bound of ``np.add.at`` in float64
    and of the one-hot form at the same ids, the counts equal."""
    geom, x, ids = _case(dim, k, 3, dim + k)
    assert geom.sums_form == "scatter"
    n = 3 * P - 211
    want, want_counts, tol = _expected(geom, x, ids, n)
    sums, counts = _run("scatter", geom, x, ids, n)
    hot, hot_counts = _run("mxu", geom, x, ids, n)
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(counts, hot_counts)
    assert counts.dtype == np.int32 and int(counts.sum()) == n
    assert (np.abs(sums - want) <= tol + 1e-30).all()
    assert (np.abs(sums - hot) <= 2 * tol + 1e-30).all()
    assert not sums[want_counts == 0].any()      # an empty cluster: zeros


def test_scatter_with_every_point_in_one_cluster():
    """The longest chain of loads and stores to one row: 2048 points
    into centre 5, the sum within m x 6e-8 of the sum of magnitudes,
    every other cluster empty."""
    geom, x, ids = _case(49, 2048, 4, 1, ids=5)
    n = 4 * P
    want, want_counts, tol = _expected(geom, x, ids, n)
    sums, counts = _run("scatter", geom, x, ids, n)
    assert int(counts[5]) == n and int(counts.sum()) == n
    assert (np.abs(sums - want) <= tol + 1e-30).all()
    assert not np.delete(sums, 5, axis=0).any()


def test_scatter_repeats_bit_for_bit():
    geom, x, ids = _case(49, 2048, 3, 2)
    first = _run("scatter", geom, x, ids, 3 * P - 9)
    again = _run("scatter", geom, x, ids, 3 * P - 9)
    np.testing.assert_array_equal(first[0].view(np.uint32),
                                  again[0].view(np.uint32))
    np.testing.assert_array_equal(first[1], again[1])


@pytest.mark.parametrize("tile", [1024, 696])
def test_scatter_tiles_the_centres_past_its_budget(tile):
    """Centres tiled (as past ``ACC_BYTES``), the last tile ragged: a
    point of another tile goes to the dump row with the padding, and
    the tiles together are the one-tile pass bit for bit."""
    geom, x, ids = _case(49, 2048, 2, 3)
    n = 2 * P - 100
    whole = _run("scatter", geom, x, ids, n)
    tiled = _run("scatter", geom, x, ids, n, tile=tile)
    np.testing.assert_array_equal(whole[0].view(np.uint32),
                                  tiled[0].view(np.uint32))
    np.testing.assert_array_equal(whole[1], tiled[1])
    assert int(tiled[1].sum()) == n


def test_an_empty_cluster_keeps_its_centre_under_the_scatter(mesh1):
    n = 3000
    c0 = _rows(n, 2)[:SCAT.k].copy()
    c0[7] = 1e3                                  # nobody's nearest
    centers, _, _, counts = _seg(mesh1, 2, SCAT)(
        *_table(mesh1, n, 2, SCAT), *_start(c0))
    assert int(np.asarray(counts)[7]) == 0
    np.testing.assert_array_equal(np.asarray(centers)[7], c0[7])
    assert int(np.asarray(counts).sum()) == n


@pytest.mark.parametrize("form", ["mxu", "scatter"])
def test_shards_agree_with_one_device(mesh1, mesh4, form):
    """Four shards, the last one holding the ragged end and one holding
    only padding, give the counts one device gives and the same centres
    to float32 summation order, in either form of the sums."""
    n = 2 * P + 77
    geom = GEOMS[form]
    c0 = _rows(max(n, geom.k), 5)[:geom.k]
    one = _seg(mesh1, 3, geom)(*_table(mesh1, n, 5, geom), *_start(c0))
    four = _seg(mesh4, 3, geom)(*_table(mesh4, n, 5, geom), *_start(c0))
    np.testing.assert_array_equal(np.asarray(one[3]), np.asarray(four[3]))
    np.testing.assert_allclose(np.asarray(one[0]), np.asarray(four[0]),
                               rtol=0, atol=1e-4)


@pytest.mark.parametrize("form", ["mxu", "scatter"])
def test_segments_chain_to_the_straight_run_bitwise(mesh1, form):
    """The order of the adds is fixed by the ids in either form of the
    sums: a pass repeats bit for bit."""
    n = 3011
    geom = GEOMS[form]
    k = geom.k
    x3, valid = _table(mesh1, n, 9, geom)
    c0 = _rows(n, 9)[100:100 + k]
    state = _start(c0)
    two = _seg(mesh1, 2, geom)
    for _ in range(3):
        *state, counts = two(x3, valid, *state)
    whole = _seg(mesh1, 6, geom)(x3, valid, *_start(c0))
    straight = kmeans.make_fit_fn(
        mesh1, kmeans.KMeansConfig(k=k, n_iterations=6), geom)(
            x3, valid, jnp.asarray(c0))
    assert int(state[2]) == int(whole[2]) == int(straight[2]) == 6
    np.testing.assert_array_equal(np.asarray(state[0]),
                                  np.asarray(whole[0]))
    np.testing.assert_array_equal(np.asarray(whole[0]),
                                  np.asarray(straight[0]))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(whole[3]))
    np.testing.assert_array_equal(
        np.asarray(straight[1])[:n],
        np.asarray(jax.jit(kmeans._mesh_fns(mesh1, geom)[1])(
            x3, valid, whole[0]))[:n])


def test_one_compile_serves_every_seed(mesh1):
    n = 2 * P
    fn = _seg(mesh1, 2)
    for seed in (1, 2147483001):
        fn(*_table(mesh1, n, seed), *_start(_rows(n, seed)[:K]))
    assert fn._cache_size() == 1

    def lowered(seed):
        return jax.jit(lambda s: GEOM.pack(make_rows(jnp.arange(P), s))
                       ).lower(jnp.int32(seed)).as_text()

    assert lowered(1) == lowered(2147483001)


@pytest.mark.parametrize("shards", [1, 4])
def test_lowered_segment_names_its_scopes(mesh1, mesh4, shards):
    mesh = mesh1 if shards == 1 else mesh4
    x3, valid = _table(mesh, 4 * P, 1)
    text = _seg(mesh, 2).lower(
        x3, valid, *_start(_rows(K, 1))).as_text(debug_info=True)
    for scope in (names.KMEANS_ASSIGN, names.KMEANS_STATS,
                  names.KMEANS_SYNC, names.KMEANS_UPDATE):
        assert scope + "/" in text, scope
    assert f"{names.KMEANS_ASSIGN}/jit(wide_assign)" in text
    assert f"{names.KMEANS_STATS}/jit(wide_stats)" in text


def _dots(fn, *args):
    """(operand dtypes, result dtype) of every ``dot_general`` under
    ``fn``."""
    return [(tuple(str(v.aval.dtype) for v in e.invars),
             str(e.outvars[0].aval.dtype))
            for e in _eqns(fn, *args) if e.primitive.name == "dot_general"]


def _dtypes(fn, *args):
    """Every dtype a value takes under ``fn``."""
    return {str(v.aval.dtype) for e in _eqns(fn, *args)
            for v in e.outvars if hasattr(v.aval, "dtype")}


@pytest.mark.parametrize("form", ["mxu", "scatter"])
@pytest.mark.parametrize("interpret", [True, False])
def test_the_interpreted_pass_uses_the_products_that_ship(interpret, form):
    """Interpreted or compiled, the assign kernel is one bfloat16
    contraction over the six products' bands, accumulated in float32,
    and the one-hot stats kernel three;
    no float32 ``dot_general`` stands in for them on the CPU. The
    scatter has no product at all and nothing in bfloat16: float32 adds
    of the points as they are held."""
    geom = GEOMS[form]
    assert geom.sums_form == form
    x3 = jnp.zeros((2, geom.dim_held, P), jnp.float32)
    c = jnp.zeros((geom.k, DIM), jnp.float32)
    a = jnp.zeros((2, 1, P), jnp.int32)
    bf16 = (("bfloat16", "bfloat16"), "float32")
    assert _dots(lambda x, c: wide.wide_assign(
        x, c, geom=geom, interpret=interpret), x3, c) == [bf16]

    def stats(x, a):
        return wide.wide_stats(x, a, 7, geom=geom, interpret=interpret)

    if form == "mxu":
        assert _dots(stats, x3, a) == [bf16] * 3
    else:
        assert _dots(stats, x3, a) == []
        assert _dtypes(stats, x3, a) <= {"float32", "int32", "bool"}


def test_pieces_are_bfloat16_and_add_back_bit_for_bit():
    rng = np.random.default_rng(3)
    x = (rng.standard_normal(4096) * np.exp2(
        rng.integers(-30, 30, 4096))).astype(np.float32)
    x[0] = 0.0
    hi, mid, lo = (np.asarray(p) for p in wide.split3(jnp.asarray(x)))
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    back = (lo.astype(np.float32) + mid.astype(np.float32)) \
        + hi.astype(np.float32)
    np.testing.assert_array_equal(back.view(np.uint32), x.view(np.uint32))
    # one piece, or two, is not the float32
    assert (hi.astype(np.float32) != x).mean() > 0.9
    assert ((mid.astype(np.float32) + hi.astype(np.float32)) != x
            ).mean() > 0.9


@pytest.mark.parametrize("form,init", [("mxu", "farthest"),
                                       ("scatter", "sample")])
def test_fit_scaled_takes_the_wide_path_and_spans_it(mesh4, tmp_path,
                                                     form, init):
    """``fit_scaled`` (what ``tda kmeans --scale-points`` calls) picks
    the layout from the geometry, draws under ``kmeans:prepare`` and
    says in its spans how a pass scores and how it sums at this
    geometry; ``tda report`` prints both."""
    k = GEOMS[form].k
    tel = str(tmp_path / "tel")
    events.configure(tel)
    try:
        res = kmeans.fit_scaled(
            mesh4, 5000, make_rows,
            kmeans.KMeansConfig(k=k, n_iterations=4, init=init),
            checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2,
            data_seed=7)
    finally:
        events.configure(False)
    assert res.n_iterations_run == 4 and res.centers.shape == (k, DIM)
    a = np.asarray(res.assignments)[:5000]
    assert a.min() >= 0 and a.max() < k
    ends = [e for e in report.load_events(tel) if e["ev"] == "span_end"]
    prep = [e for e in ends if e["name"] == "kmeans:prepare"]
    assert len(prep) == 1 and prep[0]["layout"] == "wide"
    assert prep[0]["bytes"] == 3 * 4 * P * 64 * 4     # 64 rows held for 49
    segs = [e for e in ends if e["name"] == "train:segment"]
    assert [(e["t0"], e["steps"]) for e in segs] == [(0, 2), (2, 2)]
    for e in prep + segs:
        assert (e["layout"], e["dist_form"], e["dist_depth"],
                e["sums_form"]) == ("wide", "mxu6", 384, form)
    lines = report.render(
        report.summarize(report.load_events(tel))).splitlines()
    assert "distances: mxu6 (depth 384)" in lines
    assert f"cluster sums: {form}" in lines


@pytest.mark.parametrize("row,value", [(1005, np.nan), (7, np.inf)],
                         ids=["padding", "valid"])
def test_build_scaled_refuses_a_wide_table_that_is_not_finite(
        mesh1, row, value):
    def rows(ids, seed):
        return jnp.where((ids == row)[:, None], value,
                         make_rows(ids, seed))

    with pytest.raises(ValueError, match="wide table has to be finite"):
        kmeans.build_scaled(mesh1, 1000, rows, K, data_seed=1)


def test_farthest_start_runs_on_the_device():
    """The greedy farthest-point start, jitted: k distinct candidates,
    each next one the farthest from those before it."""
    rng = np.random.default_rng(0)
    cand = rng.normal(size=(64, 5)).astype(np.float32)
    got = np.asarray(kmeans._farthest(jnp.asarray(cand), jnp.int32(9), 6))
    chosen = [9]
    d = ((cand - cand[9]) ** 2).sum(1)
    while len(chosen) < 6:
        chosen.append(int(d.argmax()))
        d = np.minimum(d, ((cand - cand[chosen[-1]]) ** 2).sum(1))
    np.testing.assert_array_equal(got, cand[chosen])


def test_rows_path_scores_to_float32_accuracy():
    """``ops/kmeans.assign_clusters`` (the rows path that remains): the
    product pinned to ``HIGHEST``, no ``|x|^2`` term."""
    from tpu_distalg.ops import kmeans as kops

    x, c = jnp.zeros((8, 4)), jnp.zeros((3, 4))
    eqns = jax.make_jaxpr(kops.assign_clusters)(x, c).jaxpr.eqns
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 1
    assert "HIGHEST" in str(dots[0].params["precision"])
    pts = np.random.default_rng(1).normal(size=(500, 6)).astype(np.float32)
    cen = pts[:7]
    want = ((pts[:, None].astype(np.float64) - cen[None]) ** 2).sum(
        -1).argmin(1)
    np.testing.assert_array_equal(
        np.asarray(kops.assign_clusters(jnp.asarray(pts),
                                        jnp.asarray(cen))), want)
