"""The pair-set closure's semi-naive round (``models/transitive_closure.
make_sparse_round_fn``) on the CPU at tiny sizes: the set, the new pairs
and the count after every round against the reference script's naive
linear join restated in NumPy, on trees, on grids and random graphs
where a pair has many derivations and on graphs with cycles; the
fixpoint flag; each buffer's overflow; ``choose_form`` on a tree; the
tree's generator and closed form; the CLI's ``--tree-height``; the
order-preserving compaction the round is built on."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_distalg.models import transitive_closure as tc
from tpu_distalg.ops import graph as gops
from tpu_distalg.telemetry import names as tnames
from tpu_distalg.utils import datasets


def naive_rounds(edges, v):
    """The reference script's loop (``transitive_closure.py:27-40``):
    every path joined with the edges, united with what was known, made
    distinct, until the count stands. Returns the set after every round
    as a boolean matrix, the standing round included."""
    adj = np.zeros((v, v), bool)
    if len(edges):
        adj[edges[:, 0], edges[:, 1]] = True
    paths, out = adj.copy(), []
    while True:
        joined = (paths.astype(np.int32) @ adj.astype(np.int32)) > 0
        new = paths | joined
        out.append(new)
        if new.sum() == paths.sum():
            return out
        paths = new


def _random(seed, v, e):
    rng = np.random.default_rng(seed)
    return rng.integers(0, v, size=(e, 2))


GRAPHS = [
    ("toy_cycle", datasets.toy_graph_edges(), 3),
    ("tree3", datasets.tree_edges(3, 4), 57),
    ("tree5_plain", datasets.tree_edges(5), 342),
    ("grid6", datasets.grid_edges(6, 2), 49),
    ("chains", datasets.chain_forest_edges(24, 6), 24),
    ("random_many_paths", _random(0, 30, 70), 30),
    ("random_cycles", _random(1, 25, 40), 25),
    ("ring", np.stack([np.arange(9), (np.arange(9) + 1) % 9], 1), 9),
    ("self_loops", np.array([[0, 0], [0, 1], [1, 1], [1, 2]]), 4),
    ("arcs_given_twice", np.array([[0, 1], [1, 2], [0, 1], [1, 2]]), 3),
    ("no_arcs", np.zeros((0, 2), np.int64), 4),
]


@pytest.mark.parametrize("name, edges, v", GRAPHS,
                         ids=[g[0] for g in GRAPHS])
def test_every_round_holds_the_naive_joins_set(name, edges, v, mesh1):
    want = naive_rounds(edges, v)
    job = tc.prepare_sparse(edges, mesh1, v, tc.SparseClosureConfig(
        capacity=2 * v * v + 8, join_capacity=4 * v * v + 8))
    geom, state = job.geom, job.state
    job.state = None
    round_fn = tc.make_sparse_round_fn(mesh1, geom)
    assert round_fn is job.round_fn       # one compiled round a geometry
    held = want[0] & False
    held[np.asarray(job.src)[:int(job.n_arcs)],
         np.asarray(job.dst)[:int(job.n_arcs)]] = True
    for k, exact in enumerate(want):
        state, count, still, stats = round_fn(state, job.arcs)
        sx, sz = np.asarray(state.sx), np.asarray(state.sz)
        valid = sx < v
        got = np.zeros((v, v), bool)
        got[sx[valid], sz[valid]] = True
        np.testing.assert_array_equal(got, exact, err_msg=f"round {k + 1}")
        # each pair once, in order; the count is the set's
        keys = sx[valid].astype(np.int64) * v + sz[valid]
        assert (np.diff(keys) > 0).all()
        assert gops.count_of(count) == int(state.n) == exact.sum()
        # the new pairs are what the round added, each once, in order
        nd = int(state.nd)
        dx, dz = np.asarray(state.dx), np.asarray(state.dz)
        assert (dx[nd:] == v).all() and (dz[nd:] == v).all()
        added = np.zeros((v, v), bool)
        added[dx[:nd], dz[:nd]] = True
        np.testing.assert_array_equal(added, exact & ~held)
        assert nd == added.sum()
        joined, found, over = (int(x) for x in np.asarray(stats))
        assert found == nd and joined >= nd and over == 0
        # the fixpoint flag: the count stands on the last round only
        assert bool(still) == (k == len(want) - 1)
        held = exact
    tc.check_sparse(state, geom)


def test_a_grid_joins_more_candidates_than_it_finds_pairs(mesh1):
    """Many derivations a pair: the round's distinct is what keeps the
    set a set. On a tree every candidate is new."""
    for edges, v, repeats in ((datasets.grid_edges(6, 1), 49, True),
                              (datasets.tree_edges(4, 1), 140, False)):
        job = tc.prepare_sparse(edges, mesh1, v)
        state, job.state = job.state, None
        joined = found = 0
        for _ in range(12):
            state, _, _, stats = job.round_fn(state, job.arcs)
            joined += int(stats[0])
            found += int(stats[1])
        assert (joined > found) == repeats
        assert found + len(edges) == int(state.n)


def test_run_sparse_counts_rounds_and_keeps_the_pairs_sorted(mesh1):
    edges = datasets.tree_edges(5, 3)
    res = tc.run_sparse(edges, mesh1, n_vertices=342)
    assert res.n_paths == datasets.tree_closure_pairs(5) == 1819
    # six levels under the root: paths of 6 arcs, whole after round 5,
    # round 6 sees the count stand
    assert res.n_rounds == 6
    keys = res.paths[:, 0] * 342 + res.paths[:, 1]
    assert (np.diff(keys) > 0).all() and len(keys) == 1819
    none = tc.run_sparse(edges, mesh1, n_vertices=342, keep_paths=False)
    assert none.paths is None and none.n_paths == 1819


@pytest.mark.parametrize("config, what", [
    (dict(capacity=300), "the set"),
    (dict(capacity=4096, delta_capacity=60, join_capacity=4096),
     "a round's new pairs"),
    (dict(capacity=4096, delta_capacity=4096, join_capacity=40),
     "a round's candidates")], ids=["set", "delta", "join"])
def test_an_overflow_of_each_buffer_raises(config, what, mesh1):
    edges = datasets.grid_edges(6, 1)            # 49 vertices, 784 pairs
    with pytest.raises(ValueError, match="overflowed its buffers"):
        tc.run_sparse(edges, mesh1, tc.SparseClosureConfig(**config),
                      n_vertices=49)
    roomy = tc.run_sparse(edges, mesh1, tc.SparseClosureConfig(
        capacity=4096, delta_capacity=4096, join_capacity=4096),
        n_vertices=49)
    assert roomy.n_paths == datasets.grid_closure_pairs(6)


def test_an_overflow_stays_said_and_is_counted(mesh1):
    from tpu_distalg.telemetry import events as tevents

    edges = datasets.grid_edges(6, 1)
    job = tc.prepare_sparse(edges, mesh1, 49, tc.SparseClosureConfig(
        capacity=4096, delta_capacity=4096, join_capacity=40))
    state, job.state = job.state, None
    for _ in range(3):
        state, _, _, stats = job.round_fn(state, job.arcs)
        assert int(stats[2]) == 1 and bool(state.overflow)
    with pytest.raises(ValueError, match="join_capacity 40"):
        tc.check_sparse(state, job.geom)
    before = tevents.counters().get("closure.sparse.overflow", 0)
    with pytest.raises(ValueError):
        tc.run_sparse(edges, mesh1, tc.SparseClosureConfig(
            capacity=4096, join_capacity=40), n_vertices=49)
    assert tevents.counters()["closure.sparse.overflow"] == before + 1


def test_capacities_from_what_the_caller_says():
    g = tc.sparse_geometry(100, 99)
    assert (g.capacity, g.delta_capacity, g.join_capacity) == (
        1024, 1024, 2048)
    g = tc.sparse_geometry(13_766_856, 13_766_855, tc.SparseClosureConfig(
        capacity=1 << 28, delta_capacity=1 << 24, join_capacity=1 << 24))
    assert g.resident_bytes == 2_446_903_656
    assert g.working_bytes == tc.SPARSE_BYTES_PER_CAPACITY_SLOT * (
        (1 << 28) + (1 << 24))
    with pytest.raises(ValueError, match="capacity 10 < edge count 99"):
        tc.sparse_geometry(100, 99, tc.SparseClosureConfig(capacity=10))
    with pytest.raises(ValueError, match="past int32"):
        tc.sparse_geometry(100, 99, tc.SparseClosureConfig(
            capacity=1 << 30, join_capacity=1 << 30))
    with pytest.raises(ValueError, match="under 2\\^30"):
        tc.sparse_geometry(1 << 30, 99)
    # the arcs always fit among a round's new pairs
    assert tc.sparse_geometry(100, 99, tc.SparseClosureConfig(
        capacity=200, delta_capacity=5)).delta_capacity == 99


def test_choose_form_on_a_tree(mesh1):
    """Tree17: two byte matrices of 13 766 856^2 are 379 TB, the pair
    set is what a chip holds."""
    v, pairs = 13_766_856, datasets.tree_closure_pairs(17)
    picked = tc.choose_form(v, v - 1, mesh1, pairs_bound=pairs,
                            budget_bytes=12 << 30)
    assert picked["closure_form"] == "sparse"
    assert picked["dense_bytes"] > 3.7e14
    assert picked["sparse_bytes"] == \
        pairs * tc.SPARSE_BYTES_PER_CAPACITY_SLOT < 12 << 30
    with pytest.raises(ValueError, match="closure refused"):
        tc.choose_form(v, v - 1, mesh1, pairs_bound=pairs,
                       budget_bytes=1 << 30)
    # a small tree fits both ways, and its pairs are the smaller; a
    # grid's pairs are not (a quarter of V^2 at 32 B each)
    assert tc.choose_form(342, 341, mesh1, pairs_bound=1819,
                          budget_bytes=1 << 30)["closure_form"] == "sparse"
    assert tc.choose_form(
        2601, 5100, mesh1, pairs_bound=datasets.grid_closure_pairs(50),
        budget_bytes=1 << 30)["closure_form"] == "dense"


@pytest.mark.parametrize("height", [0, 1, 4, 9])
def test_the_tree_is_a_tree_of_its_levels_on_every_seed(height):
    sizes = datasets.tree_level_sizes(height)
    v = sum(sizes)
    assert len(sizes) == height + 2 and sizes[0] == 1
    shapes = set()
    for seed in (None, 0, 1, 2**31 + 3):
        e = datasets.tree_edges(height, seed)
        assert e.shape == (v - 1, 2)
        indeg = np.bincount(e[:, 1], minlength=v)
        assert sorted(indeg.tolist()) == [0] + [1] * (v - 1)
        out = np.bincount(e[:, 0], minlength=v)
        lo, hi = datasets.TREE_CHILDREN
        assert set(np.unique(out)) <= {0} | set(range(lo, hi + 1))
        # the depths are the levels'
        parent = np.full(v, -1)
        parent[e[:, 1]] = e[:, 0]
        depth = np.zeros(v, int)
        node = parent.copy()
        while (node >= 0).any():
            depth += node >= 0
            node = np.where(node >= 0, parent[np.maximum(node, 0)], -1)
        assert np.bincount(depth).tolist() == sizes
        assert depth.sum() == datasets.tree_closure_pairs(height)
        shapes.add(tuple(np.sort(out).tolist()) + tuple(e[:5, 0].tolist()))
    assert len(shapes) == (4 if height >= 4 else len(shapes))


def test_tree17s_levels_total_the_published_row():
    sizes = datasets.tree_level_sizes(17)
    assert len(sizes) == 19 and sum(sizes) == 13_766_856
    assert datasets.tree_closure_pairs(17) == 237_977_708
    assert datasets.tree_closure_pairs(17, 1) == 13_766_855
    for n, below in zip(sizes, sizes[1:]):
        p = datasets.tree_nonleaves(n, below)
        assert p <= n and 2 * p <= below <= 6 * p
    with pytest.raises(ValueError, match="height -1"):
        datasets.tree_level_sizes(-1)


@pytest.mark.parametrize("n, share", [(1, 0.5), (2, 0.5), (7, 0.0),
                                      (64, 0.1), (1000, 0.5), (1000, 1.0),
                                      (4097, 0.9)])
def test_compact_front_is_a_filter(n, share):
    rng = np.random.default_rng(n)
    keep = rng.random(n) < share
    a = rng.integers(0, 100, n).astype(np.int32)
    b = rng.integers(0, 100, n).astype(np.int32)
    for out_len in (None, max(n // 3, 1)):
        ca, cb = jax.jit(lambda k, a, b: gops.compact_front(
            k, (a, b), (-1, -2), out_len))(keep, a, b)
        m, length = keep.sum(), n if out_len is None else out_len
        wa = np.full(n, -1, np.int32)
        wb = np.full(n, -2, np.int32)
        wa[:m], wb[:m] = a[keep], b[keep]
        np.testing.assert_array_equal(ca, wa[:length])
        np.testing.assert_array_equal(cb, wb[:length])
    src = np.asarray(gops.compact_sources(jnp.asarray(keep)))
    np.testing.assert_array_equal(src[:keep.sum()], np.nonzero(keep)[0])
    assert (src[keep.sum():] == -1).all()


def test_compact_front_with_a_bound_on_the_distance():
    rng = np.random.default_rng(5)
    keep = np.ones(5000, bool)
    keep[rng.choice(3000, 37, replace=False)] = False
    keep[4000:] = False
    a = np.arange(5000, dtype=np.int32)
    got, = gops.compact_front(jnp.asarray(keep), (jnp.asarray(a),), (-1,),
                              3000, max_shift=37)
    np.testing.assert_array_equal(got, a[keep][:3000])


def test_the_rounds_scopes_are_in_its_lowered_text(mesh1):
    job = tc.prepare_sparse(datasets.tree_edges(2, 0), mesh1)
    text = job.round_fn.lower(job.state, job.arcs).as_text(debug_info=True)
    for scope in (tnames.CLOSURE_JOIN, tnames.CLOSURE_DISTINCT,
                  tnames.CLOSURE_COUNT):
        assert scope + "/" in text, scope
    assert text.count("stablehlo.sort") == 1


def test_the_plan_event_says_the_capacities(mesh1, tmp_path):
    import json

    from tpu_distalg.telemetry import events as tevents

    tevents.configure(str(tmp_path))
    try:
        tc.run_sparse(datasets.tree_edges(3, 0), mesh1)
    finally:
        tevents.configure(None)
    evs = [json.loads(x) for f in tmp_path.rglob("*.jsonl")
           for x in f.read_text().splitlines()]
    plan = [e for e in evs if e.get("ev") == "closure:sparse_plan"]
    assert len(plan) == 1 and plan[0]["capacity"] == 1024
    assert plan[0]["resident_bytes"] == tc.sparse_geometry(
        57, 56).resident_bytes


def test_cli_tree_height_prints_the_closed_form(capsys):
    from tpu_distalg import cli

    rc = cli.main(["--emulate", "1", "closure", "--tree-height", "4",
                   "--seed", "7"])
    out = capsys.readouterr().out
    assert rc in (0, None)
    assert "The original graph has 607 paths (5 rounds)" in out
    assert "[closure] the tree's closed form: 607 pairs (equal)" in out
    assert datasets.tree_closure_pairs(4) == 607
    # the buffers are sized from the tree; asked for the pair set by name
    rc = cli.main(["--emulate", "1", "closure", "--tree-height", "4",
                   "--seed", "8", "--sparse"])
    out = capsys.readouterr().out
    assert "607 paths (5 rounds)" in out and "(equal)" in out
