"""The dense closure since PR 52: the byte kernel against a plain
boolean product, the doubling round against the reference script's
linear rounds and the sparse form, the count past 2^31, the start state
scattered on the device, the checkpointed run under the new round, and
the names the round leaves in its lowered program. The kernel is
interpreted (``ops/pallas_closure.compose(..., interpret=True)``); the
model picks XLA's form on the CPU by itself, and two tests steer it onto
the kernel by shrinking the module's constants (no option exists)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import transitive_closure as tc
from tpu_distalg.ops import graph as gops
from tpu_distalg.ops import pallas_closure
from tpu_distalg.telemetry import names as tnames
from tpu_distalg.utils import datasets

TILES = (128, 128, 128)


def plain_compose(p: np.ndarray, q: np.ndarray) -> np.ndarray:
    return (p != 0) | ((p.astype(np.float32) @ q.astype(np.float32)) > 0)


def linear_closure(edges: np.ndarray, v: int):
    """The reference script's loop (``transitive_closure.py:27-40``) on a
    boolean matrix: join the paths with the edges, union, count, until
    the count stands still. Returns (paths, rounds to the last growth)."""
    adj = np.zeros((v, v), bool)
    adj[edges[:, 0], edges[:, 1]] = True
    paths, grew = adj.copy(), 0
    while True:
        new = paths | ((adj.astype(np.float32)
                        @ paths.astype(np.float32)) > 0)
        if new.sum() == paths.sum():
            return paths, grew
        paths, grew = new, grew + 1


# ------------------------------------------------------------ the kernel

@pytest.mark.parametrize("density", [1e-4, 0.25, 1.0])
@pytest.mark.parametrize("n", [256, 200, 384, 130])
def test_compose_kernel_equals_a_plain_boolean_product(n, density):
    """Bit for bit with its count, on whole tiles (256, 384) and on
    sides the wrapper pads (200, 130)."""
    rng = np.random.default_rng(n)
    p = (rng.random((n, n)) < density).astype(np.int8)
    q = (rng.random((n, n)) < density).astype(np.int8)
    new, partials = pallas_closure.compose(
        jnp.asarray(p), jnp.asarray(q), tiles=TILES, interpret=True)
    want = plain_compose(p, q)
    assert new.dtype == jnp.int8 and new.shape == (n, n)
    np.testing.assert_array_equal(np.asarray(new) != 0, want)
    assert set(np.unique(np.asarray(new))) <= {0, 1}
    side = -(-n // 128)
    assert partials.shape == (side, side)
    assert int(np.asarray(partials, np.int64).sum()) == int(want.sum())


def test_compose_kernel_tiles_of_unequal_sides():
    rng = np.random.default_rng(5)
    p = (rng.random((512, 512)) < 0.01).astype(np.int8)
    new, partials = pallas_closure.compose(
        jnp.asarray(p), jnp.asarray(p), tiles=(256, 128, 512),
        interpret=True)
    want = plain_compose(p, p)
    np.testing.assert_array_equal(np.asarray(new) != 0, want)
    assert partials.shape == (2, 4)
    got = np.asarray(partials)
    for i in range(2):
        for j in range(4):
            assert got[i, j] == want[256 * i:256 * (i + 1),
                                     128 * j:128 * (j + 1)].sum()


def test_compose_refuses_what_it_cannot_compose():
    p = jnp.zeros((128, 128), jnp.int8)
    with pytest.raises(ValueError, match="int8"):
        pallas_closure.compose(p.astype(bool), p, tiles=TILES,
                               interpret=True)
    with pytest.raises(ValueError, match="square"):
        pallas_closure.compose(p, jnp.zeros((128, 256), jnp.int8),
                               tiles=TILES, interpret=True)
    with pytest.raises(ValueError, match="nest"):
        pallas_closure.compose(p, p, tiles=(128, 96, 128), interpret=True)


@pytest.mark.parametrize("form", ["xla", "mosaic"])
def test_closure_step_forms_agree(form, monkeypatch):
    """Both forms of ``ops/graph.closure_step`` give the plain product
    and partials that add up to its count."""
    for name in ("TILE", "TILE_M", "TILE_N", "TILE_K"):
        monkeypatch.setattr(pallas_closure, name, 128)
    rng = np.random.default_rng(2)
    p = (rng.random((256, 256)) < 0.02).astype(np.int8)
    q = (rng.random((256, 256)) < 0.02).astype(np.int8)
    new, partials = gops.closure_step(jnp.asarray(p), jnp.asarray(q),
                                      form=form, interpret=True)
    np.testing.assert_array_equal(np.asarray(new) != 0,
                                  plain_compose(p, q))
    assert gops.count_of(gops.path_count(partials)) \
        == int(plain_compose(p, q).sum())
    doubled, _ = gops.closure_step(jnp.asarray(p), form=form,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(doubled) != 0,
                                  plain_compose(p, p))


def test_form_and_padding_come_from_platform_shards_and_size():
    f = pallas_closure.compose_form
    big = pallas_closure.MOSAIC_MIN_VERTICES
    assert f(63001, True, 1) == "mosaic"
    assert f(big, True, 1) == "mosaic"
    assert f(big - 1, True, 1) == "xla"      # small
    assert f(63001, False, 1) == "xla"       # no TPU
    assert f(63001, True, 4) == "xla"        # a mesh
    assert pallas_closure.padded_vertices(63001, "mosaic", 1) == 63488
    assert pallas_closure.padded_vertices(63488, "mosaic", 1) == 63488
    assert pallas_closure.padded_vertices(13, "xla", 4) == 16


# ------------------------------------------------------------- the count

@pytest.mark.parametrize("partials, total", [
    ([0], 0),
    ([65535, 1], 65536),
    ([2 ** 31 - 1] * 3, 3 * (2 ** 31 - 1)),
    # Grid250's matrix filled: 62 x 31 tiles of 1024 x 2048 ones
    ([1024 * 2048] * 1922, 63488 ** 2),
    # a row's count a partial, a V past one chip: 131 072 full rows
    ([131072] * 131072, 131072 ** 2),
])
def test_count_does_not_wrap_past_2_31(partials, total):
    words = gops.path_count(jnp.asarray(partials, jnp.int32))
    assert words.dtype == jnp.int32 and words.shape == (2,)
    assert 0 <= int(words[1]) < 2 ** 16
    assert gops.count_of(words) == total


def test_equal_totals_have_equal_words():
    a = gops.path_count(jnp.asarray([70000, 70000, 5], jnp.int32))
    b = gops.path_count(jnp.asarray([140005], jnp.int32))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- the start state

@pytest.mark.parametrize("n_blocks", [1, 4])
def test_start_state_is_the_old_host_adjacency(mesh4, n_blocks, monkeypatch):
    """One scatter, and the blocks of rows a matrix of 2^31 cells or more
    is scattered in (``start_blocks``; here forced on 40 vertices)."""
    assert tc.start_blocks(40) == 1 and tc.start_blocks(63488) == 8
    assert tc.start_blocks(46340) == 1 and tc.start_blocks(46342) == 17
    monkeypatch.setattr(tc, "start_blocks", lambda v: n_blocks)
    edges = datasets.erdos_renyi_edges(37, 2.0, seed=3)
    edges = np.concatenate([edges, edges[:5]])       # arcs given twice
    job = tc.prepare_dense(edges, mesh4)
    geom, paths, count = job.geom, job.paths, job.count
    el = gops.prepare_edges(edges)
    assert geom.v_padded == 40 and geom.form == "xla"
    adj = np.zeros((geom.v_padded, geom.v_padded), bool)
    adj[el.src, el.dst] = True
    assert paths.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(paths) != 0, adj)
    assert gops.count_of(count) == el.n_edges == int(adj.sum())
    assert paths.sharding.spec[0] == "data"
    again, count2 = job.start()
    np.testing.assert_array_equal(np.asarray(again), np.asarray(paths))
    assert gops.count_of(count2) == el.n_edges


# ------------------------------------------- the doubling round, end to end

def _graphs():
    rng = np.random.default_rng(11)
    out = [(f"grid{n}", datasets.grid_edges(n - 1), n * n, 2 * (n - 1),
            datasets.grid_closure_pairs(n - 1)) for n in (3, 8, 20)]
    out.append(("chain", np.stack([np.arange(40), np.arange(1, 41)], 1),
                41, 40, 41 * 40 // 2))
    # a seeded random digraph (arcs point up: its longest path is finite)
    a, b = rng.integers(0, 60, (2, 150))
    keep = a != b
    dag = np.stack([np.minimum(a, b)[keep], np.maximum(a, b)[keep]], 1)
    out.append(("random", dag, 60, None, None))
    return out


@pytest.mark.parametrize("name, edges, v, longest, pairs", _graphs(),
                         ids=[g[0] for g in _graphs()])
def test_doubling_closure_equals_linear_rounds_and_sparse(
        name, edges, v, longest, pairs, mesh4):
    res = tc.run(edges, mesh4, n_vertices=v)
    want, grew = linear_closure(edges, v)
    assert res.paths.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(res.paths)[:v, :v], want)
    assert res.n_paths == int(want.sum())
    if pairs is not None:
        assert res.n_paths == pairs
    # the linear form grows once for every arc of the longest path past
    # the first; the doubling form needs ceil(log2 longest) + 1 rounds
    longest = longest if longest is not None else grew + 1
    assert grew + 1 == longest
    assert res.n_rounds == math.ceil(math.log2(longest)) + 1
    sparse = tc.run_sparse(edges, mesh4,
                           tc.SparseClosureConfig(capacity=v * v),
                           n_vertices=v)
    assert sparse.n_paths == res.n_paths
    got = np.zeros((v, v), bool)
    got[sparse.paths[:, 0], sparse.paths[:, 1]] = True
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_grid_count_stands_under_label_permutations(seed, mesh1):
    side = 7
    plain = tc.run(datasets.grid_edges(side), mesh1)
    perm = tc.run(datasets.grid_edges(side, seed), mesh1)
    assert perm.n_paths == plain.n_paths \
        == datasets.grid_closure_pairs(side)
    assert perm.n_rounds == plain.n_rounds
    assert not np.array_equal(np.asarray(perm.paths),
                              np.asarray(plain.paths))


def test_grid_generator_is_the_sources_table():
    e = datasets.grid_edges(250, 9)
    assert e.shape == (125500, 2) and int(e.max()) + 1 == 63001
    assert len(np.unique(e[:, 0] * 63001 + e[:, 1])) == 125500
    assert datasets.grid_closure_pairs(250) == 1000140875
    assert datasets.grid_closure_pairs(150) == 131675775


def _steer_onto_kernel(monkeypatch):
    """The model's own choice, as on a chip: whole tiles of 128 from 128
    vertices on, the kernel interpreted."""
    for name in ("TILE", "TILE_M", "TILE_N", "TILE_K"):
        monkeypatch.setattr(pallas_closure, name, 128)
    monkeypatch.setattr(pallas_closure, "MOSAIC_MIN_VERTICES", 128)
    monkeypatch.setattr(
        pallas_closure, "compose_form",
        lambda v, on_tpu, shards: "mosaic" if shards == 1 and v >= 128
        else "xla")


def test_run_through_the_kernel_equals_xlas_form(mesh1, monkeypatch):
    edges = datasets.grid_edges(12, 4)               # 169 vertices
    xla = tc.run(edges, mesh1)
    _steer_onto_kernel(monkeypatch)
    geom = tc.dense_geometry(169, mesh1)
    assert (geom.form, geom.v_padded, geom.interpret) == ("mosaic", 256,
                                                          True)
    got = tc.run(edges, mesh1)
    assert got.n_paths == xla.n_paths == datasets.grid_closure_pairs(12)
    assert got.n_rounds == xla.n_rounds == 6
    np.testing.assert_array_equal(np.asarray(got.paths)[:169, :169],
                                  np.asarray(xla.paths)[:169, :169])
    assert not np.asarray(got.paths)[169:].any()


def test_resumed_segments_equal_the_straight_run(mesh1, tmp_path,
                                                 monkeypatch):
    """``run_segmented`` under the new round, through the kernel: cut
    after 3 rounds, resumed, bit for bit with the straight run."""
    _steer_onto_kernel(monkeypatch)
    edges = datasets.grid_edges(12, 8)
    straight = tc.run(edges, mesh1)
    d = str(tmp_path / "cl")
    cut = tc.run(edges, mesh1, tc.ClosureConfig(max_iterations=3),
                 checkpoint_dir=d, checkpoint_every=2)
    assert cut.n_rounds == 3 and cut.n_paths < straight.n_paths
    resumed = tc.run(edges, mesh1, checkpoint_dir=d, checkpoint_every=2)
    assert resumed.n_paths == straight.n_paths
    assert resumed.n_rounds == straight.n_rounds
    np.testing.assert_array_equal(np.asarray(straight.paths),
                                  np.asarray(resumed.paths))


def test_max_iterations_caps_the_rounds(mesh4):
    edges = datasets.grid_edges(7)
    res = tc.run(edges, mesh4, tc.ClosureConfig(max_iterations=2))
    assert res.n_rounds == 2
    assert res.n_paths < datasets.grid_closure_pairs(7)


# --------------------------------------------------- form, names, counters

def test_choose_form_reads_the_bytes(mesh1):
    grid = tc.choose_form(63001, 125500, mesh1, pairs_bound=1000140875,
                          budget_bytes=12 << 30)
    assert grid["closure_form"] == "dense"
    assert grid["sparse_bytes"] == 1000140875 * 32       # 32.0 GB
    assert grid["dense_bytes"] == 2 * grid["v_padded"] ** 2
    # a chain forest of 100k vertices closes to 350k pairs
    chains = tc.choose_form(100000, 87500, mesh1, pairs_bound=350000,
                            budget_bytes=12 << 30)
    assert chains["closure_form"] == "sparse"
    # nothing said of the answer: V^2 pairs, so never sparse
    assert tc.choose_form(3000, 6000, mesh1)["closure_form"] == "dense"
    with pytest.raises(ValueError, match="closure refused"):
        tc.choose_form(200000, 400000, mesh1, budget_bytes=12 << 30)


def test_round_names_both_scopes_and_donates(mesh1):
    geom = tc.dense_geometry(64, mesh1)
    fn = tc.make_round_fn(mesh1, geom)
    paths = jax.ShapeDtypeStruct((64, 64), jnp.int8)
    count = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = fn.lower(paths, paths, count).as_text(debug_info=True)
    assert tnames.CLOSURE_COMPOSE + "/" in text
    assert tnames.CLOSURE_COUNT + "/" in text
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text


def test_round_names_the_kernel(mesh1, monkeypatch):
    _steer_onto_kernel(monkeypatch)
    geom = tc.dense_geometry(200, mesh1)
    fn = tc.make_round_fn(mesh1, geom)
    matrix = jax.ShapeDtypeStruct((256, 256), jnp.int8)
    text = fn.lower(matrix, matrix, jax.ShapeDtypeStruct((2,), jnp.int32)
                    ).as_text(debug_info=True)
    assert tnames.CLOSURE_COMPOSE + "/" in text
    assert "_closure_compose_kernel" in text


def test_run_leaves_spans_and_counters(mesh1):
    from tpu_distalg.telemetry import events

    before = events.counters()
    n0 = len(events.finished())
    res = tc.run(datasets.grid_edges(5, 2), mesh1)
    after = events.counters()
    assert after.get("closure.rounds", 0) - before.get("closure.rounds",
                                                       0) == res.n_rounds
    assert after.get("closure.pairs", 0) - before.get("closure.pairs",
                                                      0) == res.n_paths
    names = [s.name for s in events.finished()[n0:]]
    assert "closure:prepare" in names and "closure:fit" in names
