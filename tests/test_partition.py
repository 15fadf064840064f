"""Partition-rule engine (``parallel/partition.py``) — PR 11:

  * rule matching — first-match-wins regexes over named pytree leaves
    (Optax-style nesting included), scalars replicated, an unmatched
    leaf a HARD error;
  * device reshard ≡ host gather+re-put BITWISE for every registered
    table pair, and the wire-byte accounting against the closed-form
    ring model;
  * the 2-D mesh geometry grid (1×N, N×1, 2×2) as a config;
  * placement pins: every model's default-config trajectory under
    rule-table placement is bitwise the one under explicit
    ``NamedSharding`` placement, both run here;
  * the checkpoint-restore placement and serve-artifact-load seams;
  * the sparse-closure scale-story satellite (capacity auto-sizing +
    the documented refusal).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from tpu_distalg.parallel import get_mesh
from tpu_distalg.parallel import partition as pt


# ------------------------------------------------------- rule matching


def test_rule_match_first_wins_and_nested_state():
    tbl = pt.RuleTable("t", (
        (r"inner/.*/mu$", P("data", None)),
        (r"^w$", P()),
        (r".*", P("data")),
    ))
    tree = {"w": np.zeros((4, 4)),
            "inner": [{"mu": np.zeros((8, 2)), "nu": np.zeros((8,))}],
            "step": np.int32(3)}          # scalar: replicated, no rule
    specs = pt.match_partition_rules(tbl, tree)
    assert specs["w"] == P()
    assert specs["inner"][0]["mu"] == P("data", None)
    assert specs["inner"][0]["nu"] == P("data")   # catch-all
    assert specs["step"] == P()                   # scalar short-circuit


def test_scalar_and_size_one_leaves_replicate():
    tbl = pt.RuleTable("t", ((r"^x$", P("data")),))
    specs = pt.match_partition_rules(
        tbl, {"x": np.zeros(()), "y": np.zeros((1,))})
    # 'y' has NO rule ('^x$' misses) — but size-1 leaves replicate
    # before the table is consulted, so no error and P()
    assert specs == {"x": P(), "y": P()}


def test_unmatched_leaf_is_hard_error():
    tbl = pt.RuleTable("t", ((r"^known$", P("data")),))
    with pytest.raises(pt.PartitionRuleError) as ei:
        pt.match_partition_rules(tbl, {"mystery": np.zeros((4, 4))})
    assert "mystery" in str(ei.value) and "t" in str(ei.value)


def test_unknown_table_and_duplicate_register():
    with pytest.raises(pt.PartitionRuleError):
        pt.table("no_such_table")
    with pytest.raises(pt.PartitionRuleError):
        pt.register(pt.RuleTable("ssgd", ()))  # already registered


def test_specs_equal_strips_trailing_none():
    assert pt.specs_equal(P("data"), P("data", None))
    assert not pt.specs_equal(P("data"), P(None, "data"))


def test_every_model_has_a_registered_table():
    names = pt.registered()
    for want in ("lr", "ssgd", "ssgd_tp", "ssgd_feature_sharded",
                 "ma", "bmuf", "easgd", "local_sgd", "kmeans",
                 "als_train", "als_serve", "pagerank", "closure_dense",
                 "ssgd_stream"):
        assert want in names, want


# ------------------------------------------------ reshard ≡ gather+put


def _pair_tree(src_name: str):
    """A tree whose leaves both tables of a registered pair name,
    shapes divisible by every axis of the 2x2 mesh."""
    rng = np.random.default_rng(7)
    if src_name.startswith("als"):
        return {"U": rng.standard_normal((8, 4)).astype(np.float32),
                "V": rng.standard_normal((8, 4)).astype(np.float32)}
    return {"X_data": rng.standard_normal((8, 8)).astype(np.float32),
            "w": rng.standard_normal((8,)).astype(np.float32),
            "res": rng.standard_normal((4, 8)).astype(np.float32)}


def test_reshard_equals_host_gather_reput_every_registered_pair(
        mesh_2x2_4dev):
    for src, dst in pt.RESHARD_PAIRS:
        tree = _pair_tree(src)
        placed = pt.place(tree, src, mesh_2x2_4dev)
        dev = pt.reshard(placed, src, dst, mesh_2x2_4dev, emit=False)
        host = pt.host_gather_reshard(placed, dst, mesh_2x2_4dev)
        for name, _ in pt.named_leaves(tree):
            a, b = np.asarray(dev[name]), np.asarray(host[name])
            assert a.tobytes() == b.tobytes(), (src, dst, name)
            # and both equal the source values — a reshard moves
            # bytes, never changes them
            assert a.tobytes() == np.ascontiguousarray(
                tree[name]).tobytes(), (src, dst, name)
            want = pt.table(dst).spec_for(name, a.shape)
            got = dev[name].sharding.spec
            assert pt.specs_equal(got, want), (src, dst, name)


def test_ensure_passes_through_placed_leaves(mesh_2x2_4dev):
    tree = _pair_tree("als_train")
    placed = pt.place(tree, "als_train", mesh_2x2_4dev)
    again = pt.ensure(placed, "als_train", mesh_2x2_4dev)
    assert again["U"] is placed["U"] and again["V"] is placed["V"]
    # host leaves take the H2D; values land bitwise
    fresh = pt.ensure(tree, "als_train", mesh_2x2_4dev)
    assert np.asarray(fresh["U"]).tobytes() == tree["U"].tobytes()


# -------------------------------------------------- wire accounting


def test_wire_accounting_closed_form(mesh_2x2_4dev, mesh_2x4, mesh4):
    B = 8 * 4 * 4  # bytes of an (8, 4) f32 leaf
    # shard → replicated: ring all-gather, B(n-1)/n per shard
    st = pt.reshard_stats({"U": np.zeros((8, 4), np.float32)},
                          "als_train", "als_serve", mesh4)
    leaf = st["leaves"]["U"]
    assert leaf["op"] == "all_gather"
    assert leaf["bytes_wire"] == int(B * 3 / 4)
    assert leaf["bytes_host_roundtrip"] == 2 * B
    # replicated → shard: local slice, zero wire
    st = pt.reshard_stats({"V": np.zeros((8, 4), np.float32)},
                          "als_serve", "als_train", mesh_2x2_4dev)
    assert st["leaves"]["V"]["op"] == "noop"  # same spec both tables
    st = pt.reshard_stats({"U": np.zeros((8, 4), np.float32)},
                          "als_serve", "als_train", mesh_2x2_4dev)
    assert st["leaves"]["U"]["op"] == "slice"
    assert st["leaves"]["U"]["bytes_wire"] == 0
    # shard → shard at equal degree: all-to-all, (B/n)(n-1)/n
    t2 = pt.RuleTable("t2", ((r"^x$", P(None, "data")),))
    t1 = pt.RuleTable("t1", ((r"^x$", P("data", None)),))
    plan = pt._leaf_plan((8, 8), np.float32,
                         t1.spec_for("x", (8, 8)),
                         t2.spec_for("x", (8, 8)), mesh4)
    nb = 8 * 8 * 4
    assert plan["op"] == "all_to_all"
    assert plan["bytes_wire"] == int(round((nb / 4) * 3 / 4))
    # equal-degree axis flip on the 2x2 mesh is ALSO an all-to-all
    plan = pt._leaf_plan((8, 8), np.float32, P("data", None),
                         P("model", None), mesh_2x2_4dev)
    assert plan["op"] == "all_to_all"
    # degree change (data=2 -> model=4 on the 2x4 mesh): gather+slice
    # decomposition upper bound, B(n_s-1)/n_s
    plan = pt._leaf_plan((8, 8), np.float32, P("data", None),
                         P("model", None), mesh_2x4)
    assert plan["op"] == "gather_slice"
    assert plan["bytes_wire"] == int(round(nb * 1 / 2))


def test_uneven_dst_pad_reshard_slice_round_trip(mesh4):
    """ROADMAP item 5's named leftover (and what a cluster shrinking
    to a worker count that does not divide the model axis produces):
    a dst layout whose shard degree does not divide the dim goes
    pad-reshard-slice — padded to divisibility inside the compiled
    program, padding itemized in the stats, sliced back off on the
    way out, round trip bitwise."""
    tree = {"res": np.arange(10 * 3, dtype=np.float32).reshape(10, 3),
            "w": np.arange(5, dtype=np.float32)}
    st = pt.reshard_stats(tree, "lr", "lr", mesh4)
    leaf = st["leaves"]["res"]
    assert leaf["pad"] == (2, 0)
    assert leaf["padded_shape"] == (12, 3)
    assert leaf["bytes_padding"] == 2 * 3 * 4
    assert st["bytes_padding"] == 2 * 3 * 4
    # wire accounting runs on the PADDED size (what actually moves)
    assert leaf["bytes_logical"] == 12 * 3 * 4
    out = pt.reshard(tree, "lr", "lr", mesh4, emit=False)
    assert out["res"].shape == (12, 3)
    assert pt.specs_equal(out["res"].sharding.spec, P("data", None))
    assert np.array_equal(np.asarray(out["res"])[:10], tree["res"])
    assert not np.asarray(out["res"])[10:].any()   # inert zeros
    # the host A/B pads identically — bitwise
    hb = pt.host_gather_reshard(tree, "lr", mesh4)
    assert np.asarray(hb["res"]).tobytes() == \
        np.asarray(out["res"]).tobytes()
    # the slice half: reshard back out with the true shapes recorded
    repl = pt.RuleTable("repl_scratch", ((r".*", P()),))
    back = pt.reshard(out, "lr", repl, mesh4, emit=False,
                      true_shapes={"res": (10, 3)})
    assert back["res"].shape == (10, 3)
    assert np.asarray(back["res"]).tobytes() == tree["res"].tobytes()
    assert np.asarray(back["w"]).tobytes() == tree["w"].tobytes()
    bst = pt.reshard_stats(out, "lr", repl, mesh4,
                           true_shapes={"res": (10, 3)})
    assert bst["leaves"]["res"]["true_shape"] == (10, 3)
    # even layouts keep the historical fast path: no pad keys, noop
    st2 = pt.reshard_stats({"res": np.zeros((8, 3), np.float32)},
                           "lr", "lr", mesh4)
    assert "pad" not in st2["leaves"]["res"]
    assert st2["bytes_padding"] == 0
    assert st2["leaves"]["res"]["op"] == "noop"


def test_uneven_pad_amounts_and_scalars(mesh_2x4):
    assert pt.pad_amounts((10, 3), P("data", None), mesh_2x4) == \
        (0, 0)                       # data=2 divides 10
    assert pt.pad_amounts((10, 3), P("model", None), mesh_2x4) == \
        (2, 0)                       # model=4: pad to 12
    assert pt.pad_amounts((7,), P(("data", "model")), mesh_2x4) == \
        (1,)                         # joint 8-way degree
    assert pt.pad_amounts((), P(), mesh_2x4) == ()


def test_size_one_axis_spellings_are_noops(mesh4):
    """Review-caught: on a model=1 mesh, P('data','model') PLACES
    identically to P('data', None) — the plan must classify the pair
    as a no-op (zero wire), not account a phantom all-to-all."""
    st = pt.reshard_stats({"X_data": np.zeros((8, 8), np.float32),
                          "w": np.zeros((8,), np.float32)},
                         "ssgd_feature_sharded", "ssgd", mesh4)
    assert st["leaves"]["X_data"]["op"] == "noop"
    assert st["leaves"]["w"]["op"] == "noop"
    assert st["bytes_wire"] == 0 and st["n_moved"] == 0


def test_reshard_counters_and_report_line(tmp_path, mesh_2x2_4dev):
    from tpu_distalg.telemetry import events, report

    d = str(tmp_path / "tel")
    events.configure(d)
    try:
        tree = _pair_tree("als_train")
        placed = pt.place(tree, "als_train", mesh_2x2_4dev)
        pt.reshard(placed, "als_train", "als_serve", mesh_2x2_4dev)
    finally:
        events.configure(False)
    s = report.summarize(report.load_events(d))
    assert s["counters"]["reshard.syncs"] == 1
    assert s["counters"]["reshard.bytes_wire"] > 0
    text = report.render(s)
    assert "reshard:" in text and "host round-trip avoided" in text


# ------------------------------------------------ 2-D geometry grid


@pytest.mark.parametrize("shape", [(1, 4), (4, 1), (2, 2)])
def test_mesh_geometry_grid_placement(shape):
    data, model = shape
    mesh = get_mesh(data=data, model=model,
                    devices=jax.devices()[:data * model])
    tree = {"X2": np.arange(64, dtype=np.float32).reshape(8, 8),
            "w": np.arange(8, dtype=np.float32)}
    placed = pt.place(tree, "ssgd_tp", mesh)
    assert pt.specs_equal(placed["X2"].sharding.spec,
                          P("data", "model"))
    assert pt.specs_equal(placed["w"].sharding.spec, P("model"))
    for k in tree:
        assert np.asarray(placed[k]).tobytes() == tree[k].tobytes()


@pytest.mark.parametrize("shape", [(4, 1), (2, 2), (1, 4)])
def test_mesh_geometry_grid_ssgd_trains(shape, cancer_data):
    """--mesh-shape is a CONFIG: the same feature-sharded trainer runs
    at every (data, model) factorization of 4 devices."""
    from tpu_distalg.models import ssgd

    data, model = shape
    mesh = get_mesh(data=data, model=model,
                    devices=jax.devices()[:data * model])
    res = ssgd.train(*cancer_data, mesh, ssgd.SSGDConfig(
        n_iterations=5, feature_sharded=True))
    assert np.isfinite(np.asarray(res.w)).all()


def test_cli_mesh_shape_parse():
    from tpu_distalg.cli import parse_mesh_shape

    assert parse_mesh_shape("4x2") == (4, 2)
    assert parse_mesh_shape("1X8") == (1, 8)
    for bad in ("4", "0x2", "4x", "axb", "4x-2"):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)


# ------------------------------------------------- placement pins
#
# What the rule table owes a trajectory: placing by the table changes
# nothing. Each workload runs twice in this process, once as it is and
# once with ``partition.put`` / ``ensure`` / ``constrain`` replaced by
# the test's own placement: an explicit ``NamedSharding`` per leaf,
# written out below (the layouts the trainers spelled by hand before
# the table). Bitwise, and nothing recorded on another machine is
# compared. ``lr``'s default run and ``kmeans.fit`` place nothing
# through the table (``parallelize`` only): their entry is empty, what
# is compared there is a replay, and the test checks which kind each
# workload is.

_EXPLICIT = {
    "ssgd_fused_gather": {"X2": P("data", None)},
    "ssgd_tp_2x2": {"X2": P("data", "model"), "w": P("model")},
    "ssgd_feature_sharded_2x2": {"X_data": P("data", "model"),
                                 "w": P("model")},
    "ssgd_ssp": {"w": P(), "clocks": P(), "pend": P(), "basegen": P(),
                 "wl": P("data", None), "accd": P("data", None),
                 "res": P("data", None)},
    "lr": {},
    "kmeans": {},
    "als": {"R": P("data", None), "U": P("data", None), "V0": P()},
    "als_2x2": {"R": P("data", None), "U": P("data", None), "V0": P(),
                "V": P("model", None)},
    "pagerank": {"src": P("data"), "dst": P("data"), "w_e": P("data"),
                 "emask": P("data")},
}


def _trajectory(name, mesh4, mesh_2x2, data):
    from tpu_distalg.models import als, kmeans, pagerank, ssgd
    from tpu_distalg.models import logistic_regression as lr

    if name == "ssgd_fused_gather":
        r = ssgd.train(*data, mesh4, ssgd.SSGDConfig(
            n_iterations=20, sampler="fused_gather"))
    elif name == "ssgd_tp_2x2":
        r = ssgd.train(*data, mesh_2x2, ssgd.SSGDConfig(
            n_iterations=20, sampler="fused_gather",
            feature_sharded=True))
    elif name == "ssgd_feature_sharded_2x2":
        r = ssgd.train(*data, mesh_2x2, ssgd.SSGDConfig(
            n_iterations=20, feature_sharded=True))
    elif name == "ssgd_ssp":
        r = ssgd.train(*data, mesh4, ssgd.SSGDConfig(
            n_iterations=24, sync="ssp:4"))
    elif name == "lr":
        r = lr.train(*data, mesh4, lr.LRConfig(n_iterations=12))
    elif name == "kmeans":
        pts = np.asarray(
            np.random.default_rng(1).normal(size=(512, 8)), np.float32)
        return (kmeans.fit(pts, mesh4, kmeans.KMeansConfig(
            k=4, n_iterations=5)).centers,)
    elif name in ("als", "als_2x2"):
        ar = als.fit(mesh4 if name == "als" else mesh_2x2,
                     als.ALSConfig(m=100, n=500, k=10, n_iterations=3))
        return ar.U, ar.V
    else:
        edges = np.random.default_rng(0).integers(
            0, 200, size=(1200, 2), dtype=np.int64)
        return (pagerank.run(edges, mesh4, pagerank.PageRankConfig(
            n_iterations=10)).ranks,)
    return r.w, r.accs


@pytest.mark.parametrize("name", sorted(_EXPLICIT))
def test_rule_table_placement_changes_no_trajectory(
        monkeypatch, mesh4, mesh_2x2_4dev, cancer_data, name):
    from jax.sharding import NamedSharding

    def run(which=name):
        return [np.asarray(x).tobytes() for x in _trajectory(
            which, mesh4, mesh_2x2_4dev, cancer_data)]

    by_table = run()
    placed = []

    def explicit(mesh, leaf):
        placed.append(leaf)
        return NamedSharding(mesh, _EXPLICIT[name][leaf])

    monkeypatch.setattr(
        pt, "put", lambda x, leaf, tbl, mesh: jax.device_put(
            x if isinstance(x, jax.Array) else np.asarray(x),
            explicit(mesh, leaf)))
    monkeypatch.setattr(
        pt, "constrain", lambda x, leaf, tbl, mesh:
        jax.lax.with_sharding_constraint(x, explicit(mesh, leaf)))
    monkeypatch.setattr(
        pt, "ensure", lambda tree, tbl, mesh: {
            leaf: jax.device_put(x, explicit(mesh, leaf))
            for leaf, x in tree.items()})
    assert run() == by_table, \
        f"{name}: trajectory changed under rule-table placement"
    assert bool(placed) == bool(_EXPLICIT[name])
    if name == "ssgd_tp_2x2":
        # the invariance the two old pins shared: the 2x2 dp x tp
        # trajectory is the 4x1 one, bit for bit
        monkeypatch.undo()
        assert run("ssgd_fused_gather") == by_table


@pytest.fixture(scope="module")
def mesh_2x2_4dev():
    return get_mesh(data=2, model=2, devices=jax.devices()[:4])


# ------------------------------------------------- the three seams


def test_checkpoint_restore_placement_seam(tmp_path, mesh_2x2_4dev):
    """Restored host leaves placed per the table == the original
    device tree bitwise, in the TABLE's layout (one H2D direct to the
    final sharding — the restore-placement seam)."""
    from tpu_distalg.utils import checkpoint as ckpt

    tree = _pair_tree("als_train")
    placed = pt.place(tree, "als_train", mesh_2x2_4dev)
    ckpt.save(str(tmp_path), pt.gather(placed), step=3)
    payload, step = ckpt.restore(str(tmp_path))
    assert step == 3
    back = pt.place(payload, "als_train", mesh_2x2_4dev)
    for name in tree:
        assert np.asarray(back[name]).tobytes() == \
            tree[name].tobytes()
        assert pt.specs_equal(
            back[name].sharding.spec,
            pt.table("als_train").spec_for(name, tree[name].shape))


def test_serve_artifact_device_vs_host_equivalence(mesh_2x2_4dev):
    """The serve seam: ``als_model`` fed DEVICE-resident factors in
    the train layout (reshard path — no host gather) answers bitwise
    the same as when fed the host copies (place path)."""
    from tpu_distalg.serve import artifacts

    rng = np.random.default_rng(3)
    U = rng.standard_normal((8, 4)).astype(np.float32)
    V = rng.standard_normal((8, 4)).astype(np.float32)
    host_model = artifacts.als_model(U, V, mesh_2x2_4dev, k_top=3)
    dev_tree = pt.place({"U": U, "V": V}, "als_train", mesh_2x2_4dev)
    dev_model = artifacts.als_model(dev_tree["U"], dev_tree["V"],
                                    mesh_2x2_4dev, k_top=3)
    ids = [0, 3, 7]
    a = host_model.predict_batch(ids, max_batch=4)
    b = dev_model.predict_batch(ids, max_batch=4)
    for (va, ia), (vb, ib) in zip(a, b):
        assert np.asarray(va).tobytes() == np.asarray(vb).tobytes()
        assert np.asarray(ia).tobytes() == np.asarray(ib).tobytes()
    assert dev_model.meta == host_model.meta


def test_serve_artifact_reshard_emits_counters(tmp_path, mesh_2x2_4dev):
    from tpu_distalg.serve import artifacts
    from tpu_distalg.telemetry import events, report

    rng = np.random.default_rng(4)
    U = rng.standard_normal((8, 4)).astype(np.float32)
    V = rng.standard_normal((8, 4)).astype(np.float32)
    dev = pt.place({"U": U, "V": V}, "als_train", mesh_2x2_4dev)
    d = str(tmp_path / "tel")
    events.configure(d)
    try:
        artifacts.als_model(dev["U"], dev["V"], mesh_2x2_4dev, k_top=2)
    finally:
        events.configure(False)
    s = report.summarize(report.load_events(d))
    assert s["counters"].get("reshard.syncs", 0) >= 1


def test_ssp_resume_renegotiation_uses_table_placement(tmp_path,
                                                       cancer_data):
    """The renegotiation seam end-to-end: an SSP run checkpointed at 4
    shards resumes at 2, renegotiates, completes — and per-shard state
    re-enters in the rule table's layout (partition.ensure inside the
    segment runner)."""
    from tpu_distalg.models import ssgd

    mesh4 = get_mesh(data=4, devices=jax.devices()[:4])
    mesh2 = get_mesh(data=2, devices=jax.devices()[:2])
    cfg = ssgd.SSGDConfig(n_iterations=16, sync="ssp:4")
    d = str(tmp_path / "ck")
    ssgd.train(*cancer_data, mesh4, ssgd.SSGDConfig(
        n_iterations=8, sync="ssp:4"), checkpoint_dir=d,
        checkpoint_every=8)
    res = ssgd.train(*cancer_data, mesh2, cfg, checkpoint_dir=d,
                     checkpoint_every=8)
    assert np.isfinite(np.asarray(res.w)).all()


# ------------------------------------ sparse-closure scale satellite


def closure_dag_edges(V: int, deg: int, seed: int = 0):
    """A forward-random-DAG edge list (dedup'd)."""
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(V - 1), deg)
    span = V - 1 - src
    dst = src + 1 + (rng.random(len(src)) * span).astype(np.int64)
    return np.unique(np.stack([src, dst], 1), axis=0)


def closure_host_count(V: int, edges) -> int:
    """Exact closure size by reverse-topological bitset DP on the host
    — O(E·V/64) word ops: the host-side reference the sparse engine's
    count is held to."""
    adj: list[list[int]] = [[] for _ in range(V)]
    for s, dd in edges:
        adj[int(s)].append(int(dd))
    words = (V + 63) // 64
    reach = np.zeros((V, words), np.uint64)
    total = 0
    for i in range(V - 1, -1, -1):
        for j in adj[i]:
            reach[i] |= reach[j]
            reach[i, j // 64] |= np.uint64(1 << (j % 64))
        total += int(np.bitwise_count(reach[i]).sum()) \
            if hasattr(np, "bitwise_count") else sum(
                bin(int(w)).count("1") for w in reach[i])
    return total


def test_closure_auto_capacity_grows_and_matches_dense(mesh4):
    from tpu_distalg.models import transitive_closure as tc

    V = 120
    edges = closure_dag_edges(V, 5, seed=1)
    dense = tc.run(edges, mesh4, n_vertices=V)
    # a deliberately tiny start capacity forces the doubling path
    sp = tc.run_sparse_auto(edges, mesh4, n_vertices=V,
                            start_capacity=len(edges) + 4)
    dm = np.asarray(dense.paths)[:V, :V]
    assert set(zip(*np.nonzero(dm))) == set(map(tuple, sp.paths))
    assert sp.n_paths == dense.n_paths
    assert sp.n_paths == closure_host_count(V, edges)


def test_closure_auto_grows_through_checkpoints(tmp_path, mesh4):
    """Review-caught: an overflowed CHECKPOINTED attempt leaves
    old-shape (C,)-buffer checkpoints behind — the doubled retry must
    prune them (run_segmented's signature check would otherwise
    reject the regrown shapes as a foreign workload and auto-sizing
    could never complete a checkpointed run)."""
    from tpu_distalg.models import transitive_closure as tc

    V = 120
    edges = closure_dag_edges(V, 5, seed=1)
    sp = tc.run_sparse_auto(edges, mesh4, n_vertices=V,
                            start_capacity=len(edges) + 4,
                            checkpoint_dir=str(tmp_path / "ck"),
                            checkpoint_every=4)
    assert sp.n_paths == closure_host_count(V, edges)


def test_closure_auto_start_capacity_below_edges_grows(mesh4):
    """Review-caught: an explicit start_capacity below the edge count
    is a growth starting point, not run_sparse's hard 'capacity < edge
    count' error."""
    from tpu_distalg.models import transitive_closure as tc

    V = 120
    edges = closure_dag_edges(V, 5, seed=1)
    sp = tc.run_sparse_auto(edges, mesh4, n_vertices=V,
                            start_capacity=8)
    assert sp.n_paths == closure_host_count(V, edges)


def test_closure_refusal_is_documented(mesh4):
    from tpu_distalg.models import transitive_closure as tc

    edges = closure_dag_edges(200, 5, seed=0)
    with pytest.raises(ValueError) as ei:
        tc.run_sparse_auto(edges, mesh4, n_vertices=200,
                           budget_bytes=1 << 14)
    msg = str(ei.value)
    assert "refused" in msg and "budget" in msg and "dense" in msg
