"""Distributed serving plane (tpu_distalg/cluster/serve.py + router.py).

Layers, cheapest first: the pure dispatch policies (seeded tie-break
determinism, consistent-hash arc stability under a death), the
checkpoint->center adapter and plan scoping, then LIVE thread-mode
fleets: routed scoring bitwise vs the host kernel, sharded-vs-single
ALS top-k bitwise under BOTH merge strategies with exact wire-byte
accounting, live hot-swap under a concurrent burst (zero drops,
per-replica version monotonicity, compressed-delta path), router WAL
crash recovery on the same port, and the chaos harness verdict
(replica kill + rpc oserror grid -> bitwise replies + availability
band).
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from tpu_distalg.cluster import serve as cserve
from tpu_distalg.cluster.router import (ConsistentHashPolicy,
                                        LeastLoadedPolicy, Router,
                                        RouterConfig, make_policy)
from tpu_distalg.telemetry import events as tevents


# ------------------------------------------------------------- policies


def test_make_policy_mapping():
    assert isinstance(make_policy("consistent_hash"),
                      ConsistentHashPolicy)
    assert isinstance(make_policy("least_loaded"), LeastLoadedPolicy)


def test_least_loaded_min_wins_and_ties_replay():
    alive = [0, 1, 2]
    p = LeastLoadedPolicy(seed=5)
    assert p.pick(alive, {0: 3, 1: 0, 2: 2}) == 1
    # all-tied sequence: seeded RNG -> identical dispatch on replay,
    # and it actually SPREADS (not a degenerate constant choice)
    q1, q2 = LeastLoadedPolicy(seed=5), LeastLoadedPolicy(seed=5)
    seq1 = [q1.pick(alive, {0: 0, 1: 0, 2: 0}) for _ in range(48)]
    seq2 = [q2.pick(alive, {0: 0, 1: 0, 2: 0}) for _ in range(48)]
    assert seq1 == seq2
    assert len(set(seq1)) == 3


def test_consistent_hash_death_remaps_only_dead_arcs():
    p = ConsistentHashPolicy(seed=0)
    alive = [0, 1, 2]
    loads = {r: 0 for r in alive}
    keys = [f"user{i}" for i in range(256)]
    owner = {k: p.pick(alive, loads, key=k) for k in keys}
    assert set(owner.values()) == {0, 1, 2}
    # kill replica 1: every key it did NOT own keeps its owner — a
    # death remaps only the dead replica's ring arcs
    owner2 = {k: p.pick([0, 2], loads, key=k) for k in keys}
    for k in keys:
        if owner[k] != 1:
            assert owner2[k] == owner[k]
        else:
            assert owner2[k] in (0, 2)
    # keyless requests ride a seeded sequence: deterministic replay
    q1, q2 = ConsistentHashPolicy(seed=3), ConsistentHashPolicy(seed=3)
    assert [q1.pick(alive, loads) for _ in range(32)] == \
        [q2.pick(alive, loads) for _ in range(32)]


# ----------------------------------------------- adapters and plan scope


def test_center_of_state_adapter():
    w = np.ones((5,), np.float64)
    kind, center = cserve.center_of_state("ssgd", [w])
    assert kind == "lr" and center["w"].dtype == np.float32
    kind, center = cserve.center_of_state("kmeans_minibatch",
                                          [np.ones((3, 2))])
    assert kind == "kmeans" and set(center) == {"centers"}
    kind, center = cserve.center_of_state(
        "als", [np.ones((4, 2)), np.ones((6, 2))])
    assert kind == "als" and set(center) == {"U", "V"}
    with pytest.raises(ValueError, match="no serving-plane adapter"):
        cserve.center_of_state("pagerank", [w])


def test_scoped_plan_spec_keeps_only_replica_rules():
    spec = "seed=3;cluster:replica@7=kill;cluster:rpc@p0.02=oserror"
    scoped = cserve.scoped_plan_spec(spec)
    assert "cluster:replica" in scoped
    assert "cluster:rpc" not in scoped
    assert cserve.scoped_plan_spec(
        "seed=3;cluster:rpc@p0.02=oserror") is None
    assert cserve.scoped_plan_spec(None) is None


# ------------------------------------------------------- routed scoring


def _kmeans_center(seed=7, k=8, dim=16):
    rng = np.random.default_rng(seed)
    return {"centers": rng.normal(size=(k, dim)).astype(np.float32)}


def test_routed_kmeans_round_trip_bitwise():
    """The wire + micro-batch path must return exactly the bytes the
    host kernel computes — versions stamped, every request answered."""
    center = _kmeans_center()
    rng = np.random.default_rng(1)
    X = rng.normal(size=(40, 16)).astype(np.float32)
    want = cserve.HostModel("kmeans", center).score_frame(
        {"x": X})["y"]
    fleet = cserve.ServeFleet(cserve.FleetConfig(
        kind="kmeans", n_replicas=2, version=3,
        max_delay_ms=1.0), center).start()
    try:
        results, info = cserve.run_fleet_closed_loop(
            fleet, list(X), concurrency=4)
    finally:
        fleet.stop()
    assert info["failed"] == 0 and info["ok"] == len(X)
    assert info["availability"] == 1.0
    assert info["p99_ms"] >= info["p50_ms"] > 0
    got = np.asarray([v for v, _ver, _rid in results])
    assert got.dtype == want.dtype
    assert np.array_equal(got, want)
    assert all(ver == 3 for _v, ver, _r in results)
    assert {rid for _v, _ver, rid in results} <= {0, 1}


# ------------------------------------ sharded == single, both merges


def _als_center(seed=5, m_users=24, n_items=300, rank=8):
    rng = np.random.default_rng(seed)
    return {"U": rng.normal(size=(m_users, rank)).astype(np.float32),
            "V": rng.normal(size=(n_items, rank)).astype(np.float32)}


@pytest.mark.parametrize("merge", ["sparse", "dense"])
def test_sharded_topk_bitwise_vs_single_with_wire_accounting(
        merge, tmp_path):
    """A 3-shard fleet's merged top-k must be BITWISE the 1-shard
    fleet's (both merge strategies), and the candidate bytes the
    router pulled over the wire must match the closed-form expectation
    exactly — the sparse pair wire moves k_top pairs per shard where
    the dense block wire moves the whole padded shard row."""
    n_items, k_top, n_req = 300, 10, 24
    center = _als_center(n_items=n_items)
    payloads = [np.int32(i) for i in range(n_req)]
    tevents.configure(str(tmp_path / "tel"))
    try:
        outs = {}
        wire = {}
        for n_rep in (1, 3):
            before = tevents.get_sink().counters().get(
                "serve.cluster_merge_bytes_wire", 0)
            fleet = cserve.ServeFleet(cserve.FleetConfig(
                kind="als", n_replicas=n_rep, sharded=True,
                merge=merge, k_top=k_top, max_delay_ms=1.0,
                version=1), center).start()
            try:
                results, info = cserve.run_fleet_closed_loop(
                    fleet, payloads, concurrency=4)
            finally:
                fleet.stop()
            assert info["failed"] == 0 and info["ok"] == n_req
            outs[n_rep] = results
            wire[n_rep] = tevents.get_sink().counters().get(
                "serve.cluster_merge_bytes_wire", 0) - before
    finally:
        tevents.configure(False)
    for (v1, ver1, _), (v3, ver3, _) in zip(outs[1], outs[3]):
        vals1, idx1 = v1
        vals3, idx3 = v3
        assert np.array_equal(vals1, vals3)
        assert np.array_equal(idx1, idx3)
        assert idx1.dtype == np.int32 and vals1.dtype == np.float32
        assert ver1 == ver3 == 1
    # exact wire-byte accounting (no faults -> no replays): sparse
    # moves k_top (f32 val, i32 idx) pairs per request per shard;
    # dense moves the full SCORE_BLOCK-padded shard row of f32 scores
    if merge == "sparse":
        per_shard = {1: n_req * k_top * 8, 3: n_req * k_top * 8 * 3}
    else:
        span = 3 * cserve.SCORE_BLOCK
        n_pad = -(-n_items // span) * span
        per_shard = {1: n_req * n_pad * 4, 3: n_req * n_pad * 4}
    assert wire == per_shard


# ------------------------------------------------------------- hot swap


def test_hot_swap_zero_drops_monotone_versions_under_burst():
    """Publishes land while a concurrent burst is in flight: zero
    requests dropped, every reply version-stamped, and per (client
    stripe, replica) the stamps never move backward — the batch-
    boundary swap can delay a version but never un-apply one. The
    int8 comm spec must ride the compressed delta path end to end."""
    center = _kmeans_center()
    rng = np.random.default_rng(2)
    X = rng.normal(size=(160, 16)).astype(np.float32)
    fleet = cserve.ServeFleet(cserve.FleetConfig(
        kind="kmeans", n_replicas=3, version=1, comm="int8",
        max_delay_ms=1.0), center).start()
    swap_modes = []
    try:
        def publisher():
            for v in range(2, 6):
                time.sleep(0.02)
                delta = {"centers":
                         center["centers"] + np.float32(v)}
                swap_modes.append(fleet.publish(delta, v))

        pub = threading.Thread(target=publisher, daemon=True)
        pub.start()
        results, info = cserve.run_fleet_closed_loop(
            fleet, list(X), concurrency=8)
        pub.join(timeout=10.0)
        final = fleet.request(X[0])
        st = fleet.stats()
    finally:
        fleet.stop()
    assert info["failed"] == 0 and info["ok"] == len(X)
    assert info["availability"] == 1.0  # zero drops, zero sheds
    assert final[1] == 5
    assert st["version"] == 5
    # every publish reached every replica, and the version-pinned
    # compressed delta path carried them (router and replica both
    # derive the codec from the same --comm spec; no dense fallback
    # on a healthy fleet)
    assert len(swap_modes) == 4
    for pub_res in swap_modes:
        assert pub_res["swapped"] == [0, 1, 2]
        assert all(m == "delta" for m in pub_res["modes"].values())
    # stamps: subset of published versions, monotone per stripe+replica
    # (worker stripes submit sequentially; a replica's version only
    # moves forward)
    seen = [ver for _v, ver, _r in results]
    assert set(seen) <= {1, 2, 3, 4, 5}
    conc = info["concurrency"]
    for w in range(conc):
        last = {}
        for j in range(w, len(X), conc):
            _v, ver, rid = results[j]
            assert ver >= last.get(rid, 0)
            last[rid] = ver


def test_hot_swap_dense_fallback_when_codec_absent():
    """A dense --comm spec has no pull codec: publishes must take the
    dense snapshot path and still stamp replies with the new version."""
    center = _kmeans_center()
    fleet = cserve.ServeFleet(cserve.FleetConfig(
        kind="kmeans", n_replicas=2, version=1, comm="dense",
        max_delay_ms=1.0), center).start()
    try:
        pub = fleet.publish(
            {"centers": center["centers"] * np.float32(2.0)}, 2)
        out = fleet.request(np.zeros((16,), np.float32))
    finally:
        fleet.stop()
    assert pub["swapped"] == [0, 1]
    assert all(m == "dense" for m in pub["modes"].values())
    assert out[1] == 2


# ------------------------------------------------------- WAL recovery


def test_router_wal_crash_recovery_same_port(tmp_path):
    """Router crash rides the PR 13 WAL: a fresh router over the same
    wal_dir rebinds the SAME port, replays membership + publish redo
    records (version restored), and serves immediately — the replicas
    never noticed."""
    wal_dir = str(tmp_path / "router_wal")
    center = _kmeans_center()
    rng = np.random.default_rng(3)
    X = rng.normal(size=(12, 16)).astype(np.float32)
    fleet = cserve.ServeFleet(cserve.FleetConfig(
        kind="kmeans", n_replicas=2, version=1, wal_dir=wal_dir,
        max_delay_ms=1.0), center).start()
    r2 = None
    try:
        port0 = fleet.router.port
        _, info = cserve.run_fleet_closed_loop(fleet, list(X))
        assert info["failed"] == 0
        fleet.publish(
            {"centers": center["centers"] + np.float32(1.0)}, 2)
        want = fleet.request(X[0])
        fleet.router.slam()  # the crash: no stop(), no WAL goodbye
        r2 = Router(RouterConfig(wal_dir=wal_dir)).start()
        assert r2.recovered
        assert r2.port == port0
        assert r2.version == 2
        got = r2.request(X[0])
        assert np.array_equal(np.asarray(got[0]),
                              np.asarray(want[0]))
        assert got[1] == 2
    finally:
        if r2 is not None:
            r2.stop()
        fleet.stop()


# ----------------------------------------------------------- chaos grid


def test_chaos_cluster_serve_kill_and_rpc_grid(tmp_path):
    """The acceptance drill: a replica killed mid-burst PLUS a wire
    oserror storm — replies bitwise-identical to the undisturbed run,
    availability above the pinned band, and the plan really fired."""
    from tpu_distalg.faults import chaos

    res = chaos.run_chaos(
        "cluster_serve", None,
        plan="seed=3;cluster:replica@7=kill;cluster:rpc@p0.02=oserror",
        workdir=str(tmp_path))
    assert res.equal, res.verdict()
    assert any(p == "cluster:replica" and k == "kill"
               for p, _h, k in res.fired), res.fired
    assert "OK" in res.verdict()


# ------------------------------------------------- registration contract
