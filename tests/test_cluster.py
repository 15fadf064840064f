"""Multi-process elastic runtime (tpu_distalg/cluster/).

Four layers of evidence, cheapest first: transport framing (round
trip + the fuzz grid: truncated frame, oversized length, deadline
expiry, CRC corruption, unsafe dtype), the PS tier's rule-table
split/merge math, the plan-pure worker schedule compiler, and the
LIVE cluster grid — thread-mode (same protocol, same sockets, fast)
for kill/straggle/join/restart/replay determinism, and a real
subprocess run (genuine ``kill -9`` + rejoin through the CLI) as the
acceptance: reduced-quorum survival, final accuracy inside the SSP
chaos band of the undisturbed run, and the same plan replaying to
the identical merge/membership event digest.
"""

from __future__ import annotations

import json
import os
import socket
import time

import numpy as np
import pytest

from tpu_distalg import cluster as clus
from tpu_distalg import faults
from tpu_distalg.cluster import ps as psmod
from tpu_distalg.cluster import transport, wal, worker
from tpu_distalg.faults import registry as fregistry
from tpu_distalg.faults.chaos import SSP_CHAOS_ACC_BAND


# ------------------------------------------------------------ transport


def _pipe():
    a, b = socket.socketpair()
    return a, b


def test_transport_round_trip():
    a, b = _pipe()
    arrays = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
              "idx": np.array([3, 1, 2], np.int64),
              "flag": np.array([True, False])}
    transport.send_frame(a, "push", {"slot": 2, "window": 7}, arrays)
    kind, meta, out = transport.recv_frame(b, deadline=5.0)
    assert kind == "push" and meta == {"slot": 2, "window": 7}
    for k, v in arrays.items():
        assert out[k].dtype == v.dtype
        assert np.array_equal(out[k], v)
    a.close(), b.close()


def test_transport_truncated_frame_is_closed_not_garbage():
    a, b = _pipe()
    buf = transport.encode_frame("x", {"n": 1}, {"w": np.ones(8)})
    a.sendall(buf[: len(buf) - 5])
    a.close()
    with pytest.raises(transport.TransportClosed,
                       match="truncated frame"):
        transport.recv_frame(b, deadline=5.0)
    b.close()


def test_transport_oversized_length_refused_before_allocation():
    a, b = _pipe()
    buf = bytearray(transport.encode_frame("x", {}))
    # forge a multi-GB body length into the prefix
    import struct

    magic, hlen, _, crc = transport._PREFIX.unpack(
        bytes(buf[: transport._PREFIX.size]))
    buf[: transport._PREFIX.size] = transport._PREFIX.pack(
        magic, hlen, 1 << 40, crc)
    a.sendall(bytes(buf))
    with pytest.raises(transport.FrameTooLarge, match="max_frame"):
        transport.recv_frame(b, deadline=5.0)
    a.close(), b.close()


def test_transport_deadline_expiry_is_timeout():
    a, b = _pipe()
    t0 = time.monotonic()
    with pytest.raises(transport.TransportTimeout, match="deadline"):
        transport.recv_frame(b, deadline=0.2)
    assert time.monotonic() - t0 < 5.0
    # and a PARTIAL frame followed by silence times out too (the
    # partition-mid-message case)
    buf = transport.encode_frame("x", {}, {"w": np.ones(4)})
    a.sendall(buf[:6])
    with pytest.raises(transport.TransportTimeout):
        transport.recv_frame(b, deadline=0.2)
    a.close(), b.close()


def test_transport_crc_and_magic_detected():
    a, b = _pipe()
    buf = bytearray(transport.encode_frame("x", {"v": 1},
                                           {"w": np.ones(4)}))
    buf[-2] ^= 0xFF  # flip a body byte after the CRC was computed
    a.sendall(bytes(buf))
    with pytest.raises(transport.TransportError, match="CRC"):
        transport.recv_frame(b, deadline=5.0)
    a.close(), b.close()
    a, b = _pipe()
    a.sendall(b"HTTP/1.1 200 OK\r\n" + b"\x00" * 16)
    with pytest.raises(transport.TransportError, match="magic"):
        transport.recv_frame(b, deadline=5.0)
    a.close(), b.close()


def test_transport_object_dtype_refused_both_ends():
    with pytest.raises(transport.TransportError, match="pickle"):
        transport.encode_frame("x", {}, {"o": np.array([{}, []],
                                                       dtype=object)})


def test_transport_rpc_fault_seam():
    faults.configure("seed=1;cluster:rpc@0=oserror")
    try:
        a, b = _pipe()
        # an injected oserror surfaces IN the transport taxonomy (a
        # torn connection), so handler/reconnect paths ride it like
        # the real thing instead of dying on a foreign OSError
        with pytest.raises(transport.TransportClosed,
                           match="injected"):
            transport.send_frame(a, "x", {})
        # next invocation passes (hit 0 consumed)
        transport.send_frame(a, "x", {})
        assert transport.recv_frame(b, deadline=5.0)[0] == "x"
        a.close(), b.close()
    finally:
        faults.configure(False)


# -------------------------------------------------------------- PS tier


def test_ps_split_uneven_and_join_round_trip():
    center = {"w": np.arange(31, dtype=np.float32)}
    shards = psmod.split_center(center, "lr", 3)
    # w is replicated P() in the lr table -> lives whole on shard 0
    assert np.array_equal(shards[0]["w"], center["w"])
    # a row-sharded leaf splits UNEVENLY via array_split (the
    # cluster-shrink case the uneven reshard satellite covers device-
    # side)
    tree = {"res": np.arange(10 * 2, dtype=np.float32).reshape(10, 2)}
    parts = psmod.split_center(tree, "lr", 3)
    assert [p["res"].shape[0] for p in parts] == [4, 3, 3]
    assert np.array_equal(psmod.join_center(parts)["res"],
                          tree["res"])


def test_ps_merge_is_staleness_weighted_mean():
    center = {"w": np.zeros(4, np.float32)}
    srv = psmod.ParameterServer(center, table="lr", n_shards=2,
                                decay=0.5)
    d0 = {"w": np.full(4, 1.0, np.float32)}
    d1 = {"w": np.full(4, 3.0, np.float32)}
    # commit window 4: slot 0 fresh (base 4, age 0, weight 1), slot 1
    # two windows stale (base 2, age 2, weight 0.25)
    recs = srv.merge(4, [(0, 4, d0), (1, 2, d1)])
    assert [r["age"] for r in recs] == [0, 2]
    want = (1.0 * 1.0 + 0.25 * 3.0) / 1.25
    np.testing.assert_allclose(srv.snapshot()["w"],
                               np.full(4, want, np.float32),
                               rtol=1e-6)
    assert srv.version == 5
    # a commit nobody delivered to is a hard no-op
    before = srv.snapshot()["w"].copy()
    srv.merge(5, [])
    assert np.array_equal(srv.snapshot()["w"], before)


# ------------------------------------------------- schedules & registry


def test_cluster_fault_points_pair_with_their_kinds_only():
    fregistry.FaultRule("cluster:worker", "kill")
    fregistry.FaultRule("cluster:worker", "straggle", arg=40.0)
    fregistry.FaultRule("cluster:rpc", "oserror")
    fregistry.FaultRule("cluster:rpc", "hang", arg=0.01)
    with pytest.raises(ValueError, match="cluster:worker"):
        fregistry.FaultRule("cluster:worker", "oserror")
    with pytest.raises(ValueError, match="cluster:rpc"):
        fregistry.FaultRule("cluster:rpc", "kill")


def test_worker_schedule_plan_pure_and_codes():
    plan = fregistry.FaultPlan.parse(
        "seed=7;cluster:worker@10=kill;cluster:worker@22=straggle:40")
    a = worker.compile_worker_schedule(10, 3, plan=plan)
    b = worker.compile_worker_schedule(10, 3, plan=plan)
    assert np.array_equal(a, b)
    assert a[3, 1] == worker.KILL          # cell 10 = w3, slot 1
    assert a[7, 1] == 40                   # cell 22 = w7, slot 1
    assert (a != 0).sum() == 2
    # no plan / no cluster rules -> all-zero schedule
    assert not worker.compile_worker_schedule(4, 2, plan=None).any()


def test_strip_kills_keeps_straggles():
    spec = ("seed=7;cluster:worker@10=kill;"
            "cluster:worker@22=straggle:40;ckpt:write@0=oserror")
    out = fregistry.FaultPlan.parse(worker.strip_kills(spec))
    kinds = sorted((r.point, r.kind) for r in out.rules)
    assert kinds == [("ckpt:write", "oserror"),
                     ("cluster:worker", "straggle")]
    assert worker.strip_kills(None) is None


# ------------------------------------------------------------------ WAL


def test_wal_append_replay_round_trip(tmp_path):
    d = str(tmp_path / "wal")
    w = wal.WriteAheadLog(d)
    w.open_segment(0, {"version": 0, "gen": 0, "events": []})
    w.append("admit", {"slot": 0, "admit": 0, "incarnation": 1,
                       "gen": 1})
    delta = np.arange(5, dtype=np.float32)
    w.append("commit",
             {"window": 0,
              "contribs": [{"slot": 0, "base": 0, "age": 0,
                            "digest": wal.delta_digest({"w": delta})}],
              "skipped": [], "version": 1},
             {"0/w": delta})
    w.close()
    records, base = wal.WriteAheadLog.replay(d, 0)
    assert [r[0] for r in records] == ["base", "admit", "commit"]
    assert base == 0
    kind, meta, arrays = records[2]
    assert meta["window"] == 0
    assert np.array_equal(arrays["0/w"], delta)
    # the digest is a pure function of names + bytes
    assert wal.delta_digest({"w": delta}) == \
        meta["contribs"][0]["digest"]
    assert wal.delta_digest({"w": delta + 1}) != \
        meta["contribs"][0]["digest"]


@pytest.mark.parametrize("mutate", ["truncate", "flip"])
def test_wal_torn_tail_truncated_with_quarantine(tmp_path, mutate):
    """Fuzz the LAST record's bytes (torn write / bit rot): replay
    keeps the good prefix, truncates the bad tail durably, and emits
    the quarantine evidence — mirroring checkpoint restore."""
    d = str(tmp_path / "wal")
    w = wal.WriteAheadLog(d)
    w.open_segment(0, {"version": 0})
    w.append("admit", {"slot": 0, "admit": 0, "incarnation": 1,
                       "gen": 1})
    w.append("skip", {"slot": 0, "inc": 1, "window": 3})
    w.close()
    path = wal._segment_path(d, 0)
    size = os.path.getsize(path)
    if mutate == "truncate":
        with open(path, "r+b") as f:
            f.truncate(size - 7)
    else:
        with open(path, "r+b") as f:
            f.seek(size - 3)
            b = f.read(1)
            f.seek(size - 3)
            f.write(bytes([b[0] ^ 0xFF]))
    records, torn = wal.read_segment(path)
    assert [r[0] for r in records] == ["base", "admit"]
    assert torn > 0
    # durable truncation: a re-read is clean
    records2, torn2 = wal.read_segment(path)
    assert [r[0] for r in records2] == ["base", "admit"]
    assert torn2 == 0


def test_wal_rotation_keeps_segments_for_kept_checkpoints(tmp_path):
    d = str(tmp_path / "wal")
    w = wal.WriteAheadLog(d)
    w.open_segment(0, {"version": 0})
    w.rotate(3, {"version": 3}, keep_base=3)
    assert wal.segment_bases(d) == [3]
    w.rotate(6, {"version": 6}, keep_base=3)
    assert wal.segment_bases(d) == [3, 6]
    w.close()
    # replay from a center at 6 starts at segment 6; a quarantined
    # center falling back to 3 rolls forward through BOTH
    _, base6 = wal.WriteAheadLog.replay(d, 6)
    assert base6 == 6
    records3, base3 = wal.WriteAheadLog.replay(d, 3)
    assert base3 == 6
    assert [m.get("version") for k, m, _ in records3
            if k == "base"] == [3, 6]


def test_wal_injected_corruption_is_quarantined(tmp_path):
    """The cluster:wal fault seam: 'corrupt' REALLY flips the record's
    bytes on the way to disk — replay's CRC truncates it as a torn
    tail instead of resuming from garbage."""
    d = str(tmp_path / "wal")
    faults.configure("seed=5;cluster:wal@2=corrupt")
    try:
        w = wal.WriteAheadLog(d)
        w.open_segment(0, {"version": 0})        # hit 0 (base)
        w.append("admit", {"slot": 0, "admit": 0,
                           "incarnation": 1, "gen": 1})  # hit 1
        w.append("skip", {"slot": 0, "inc": 1, "window": 2})  # hit 2!
        w.close()
    finally:
        faults.configure(False)
    records, torn = wal.read_segment(wal._segment_path(d, 0))
    assert [r[0] for r in records] == ["base", "admit"]
    assert torn > 0


def test_wal_failed_append_rewinds_to_the_record_boundary(
        tmp_path, monkeypatch):
    """A transient append fault AFTER the bytes landed (a failed
    fsync) must not leave a duplicate/torn record mid-log for the
    retry to append after: the failed attempt truncates back to its
    start, so retry-then-replay sees each record exactly once."""
    d = str(tmp_path / "wal")
    w = wal.WriteAheadLog(d)
    w.open_segment(0, {"version": 0})
    real_fsync = os.fsync
    calls = {"n": 0}

    def flaky_fsync(fd):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("transient disk fault after the write")
        return real_fsync(fd)

    monkeypatch.setattr(wal.os, "fsync", flaky_fsync)
    with pytest.raises(OSError, match="transient"):
        w.append("skip", {"slot": 0, "inc": 1, "window": 2})
    # the retry lands exactly ONE durable copy
    w.append("skip", {"slot": 0, "inc": 1, "window": 2})
    w.close()
    records, torn = wal.read_segment(wal._segment_path(d, 0))
    assert torn == 0
    assert [r[0] for r in records] == ["base", "skip"]
    assert sum(1 for k, _m, _a in records if k == "skip") == 1


def test_wal_headerless_segment_is_rewritten_not_resurrected(
        tmp_path):
    """A segment whose ``base`` snapshot was torn/quarantined away
    must not silently swallow new acked records (replay would skip
    the headerless file whole): open_segment rewrites it fresh with
    the caller's current snapshot."""
    d = str(tmp_path / "wal")
    w = wal.WriteAheadLog(d)
    w.open_segment(0, {"version": 0})
    w.close()
    path = wal._segment_path(d, 0)
    with open(path, "r+b") as f:       # tear the base record itself
        f.truncate(5)
    w2 = wal.WriteAheadLog(d)
    w2.open_segment(0, {"version": 0, "gen": 0})
    w2.append("admit", {"slot": 0, "admit": 0, "incarnation": 1,
                        "gen": 1})
    w2.close()
    records, base = wal.WriteAheadLog.replay(d, 0)
    assert [r[0] for r in records] == ["base", "admit"]
    assert base == 0


def test_wal_headerless_newer_segment_does_not_shadow_older(
        tmp_path):
    """Replay picks its start among READABLE segments only: a newer
    segment reduced to a headerless husk must not shadow the older
    readable one's redo records."""
    d = str(tmp_path / "wal")
    w = wal.WriteAheadLog(d)
    w.open_segment(0, {"version": 0})
    w.append("admit", {"slot": 0, "admit": 0, "incarnation": 1,
                       "gen": 1})
    w.rotate(3, {"version": 3}, keep_base=0)
    w.close()
    with open(wal._segment_path(d, 3), "r+b") as f:
        f.truncate(4)
    records, base = wal.WriteAheadLog.replay(d, 3)
    assert base == 0
    assert [r[0] for r in records] == ["base", "admit"]


# ------------------------------------------------ live cluster (thread)

CFG = dict(n_slots=3, n_windows=8, staleness=3, heartbeat_timeout=3.0,
           checkpoint_every=3,
           train=clus.TrainTask(n_rows=1024, test_rows=512))


def _run(plan=None, policy="elastic", n_slots=3, n_windows=8,
         checkpoint_dir=None, heartbeat_timeout=None, comm="dense",
         **kw):
    over = {
        **CFG, "n_slots": n_slots, "n_windows": n_windows,
        "plan_spec": plan, "policy": policy, "comm": comm,
        "checkpoint_dir": checkpoint_dir}
    if heartbeat_timeout is not None:
        # the coordinator-kill scenarios use a GENEROUS timeout:
        # reconnect tolerance is what they test, and on a loaded CI
        # box a worker's resume racing parallel jax imports past a
        # tight timeout would readmit it (a legitimate degraded path)
        # and legitimately change the sequences under comparison
        over["heartbeat_timeout"] = heartbeat_timeout
    return clus.run_local_cluster(clus.ClusterConfig(**over),
                                  spawn="thread", timeout=180.0,
                                  **kw)


@pytest.fixture(scope="module")
def undisturbed():
    return _run()


def test_cluster_undisturbed_completes_and_converges(undisturbed):
    res = undisturbed
    assert res["version"] == 8
    # every merge carries all three slots at age 0, nothing skipped
    for w, applied, skipped in res["merge_sequence"]:
        assert applied == ((0, 0), (1, 0), (2, 0))
        assert skipped == ()
    assert res["membership_sequence"] == [
        ("join", 0, 0), ("join", 1, 0), ("join", 2, 0)]
    assert res["accuracy"] > 0.65
    # worker stats reported through the bye frames
    assert sorted(res["worker_stats"]) == [0, 1, 2]
    assert all(s["pushes"] == 8 for s in res["worker_stats"].values())


def test_cluster_kill_one_mid_window_and_rejoin(undisturbed):
    # cell 10 = (window 3, slot 1) at 3 slots
    res = _run(plan="seed=7;cluster:worker@10=kill", rejoin_after=2)
    assert res["version"] == 8 and res["respawns"] == 1
    mem = res["membership_sequence"]
    assert ("leave", 1, 3) in mem          # died owing window 3
    assert ("join", 1, 5) in mem           # pinned rejoin at 3+2
    by_window = {w: applied for w, applied, _ in
                 res["merge_sequence"]}
    # reduced quorum through the absence, full strength after rejoin
    assert by_window[3] == ((0, 0), (2, 0))
    assert by_window[4] == ((0, 0), (2, 0))
    assert by_window[5] == ((0, 0), (1, 0), (2, 0))
    # the acceptance band: chaos endpoint within the SSP band of the
    # undisturbed run
    assert abs(res["accuracy"]
               - undisturbed["accuracy"]) <= SSP_CHAOS_ACC_BAND


def test_cluster_straggle_one_skips_then_delivers_staler():
    # cell 13 = (window 4, slot 1): skip at 4, deliver at 5 aged
    res = _run(plan="seed=7;cluster:worker@13=straggle:30")
    assert res["version"] == 8
    by_window = {w: (applied, skipped) for w, applied, skipped in
                 res["merge_sequence"]}
    assert by_window[4] == (((0, 0), (2, 0)), (1,))
    applied5, _ = by_window[5]
    assert (1, 1) in applied5              # age-1 delivery
    assert res["worker_stats"][1]["skips"] == 1


def test_cluster_same_plan_replays_identical_sequences():
    plan = ("seed=7;cluster:worker@10=kill;"
            "cluster:worker@22=straggle:30")
    a = _run(plan=plan, rejoin_after=2)
    b = _run(plan=plan, rejoin_after=2)
    assert a["merge_sequence"] == b["merge_sequence"]
    assert a["membership_sequence"] == b["membership_sequence"]
    # the slot-ordered float merges make even the center bitwise
    assert np.array_equal(a["center"]["w"], b["center"]["w"])


def test_cluster_restart_policy_is_the_gang_scheduled_baseline(
        tmp_path):
    res = _run(plan="seed=7;cluster:worker@10=kill",
               policy="restart", checkpoint_dir=str(tmp_path))
    assert res["version"] == 8
    assert res["restarts"] == 1
    assert res["respawns"] == 0            # nobody rejoins: everyone respawns
    assert res["accuracy"] > 0.65


def test_cluster_join_one_late():
    """Spawn only 2 of 3 slots; the third joins mid-run, unsolicited.

    PR 14's tier-1 run recorded this as a LOAD-TIMING flake: the old
    spelling raced wall clock — spawn w2 once ``version >= 3`` and
    hope the clock hadn't moved past the deadline budget on a loaded
    box (two workers paying jax compiles could eat the whole 60 s
    before window 3, and nothing stopped the clock at 3 either). The
    deterministic spelling pins the rendezvous with an ADMISSION HOLD
    (the launcher's own replay mechanism): the commit of window 3
    cannot proceed until all 3 slots are active, so the clock STALLS
    at exactly version 3 until w2 joins — no race in either
    direction, under any load. The deadline below only bounds two
    workers training 3 windows."""
    cfg = clus.ClusterConfig(**{**CFG, "n_windows": 10})
    coord = clus.Coordinator(cfg).start()
    try:
        from tpu_distalg.cluster.local import _ThreadWorker

        coord.hold_admission(3, 3)
        w0 = _ThreadWorker("127.0.0.1", coord.port, 0)
        w1 = _ThreadWorker("127.0.0.1", coord.port, 1)
        deadline = time.monotonic() + 120
        while coord.version < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        # the hold makes this exact, not least-upper-bound: version
        # can never pass 3 without the third slot active
        assert coord.version == 3
        w2 = _ThreadWorker("127.0.0.1", coord.port, 2)
        res = coord.wait(timeout=120.0)
        for w in (w0, w1, w2):
            w.join(timeout=30)
    finally:
        coord.stop()
    assert res["version"] == 10
    joins = [e for e in res["membership_sequence"]
             if e[0] == "join"]
    late = [e for e in joins if e[1] == 2]
    assert late and late[0][2] == 3        # admitted exactly at the hold
    # it participates in every window from its admission on
    admit = late[0][2]
    for w, applied, _ in res["merge_sequence"]:
        slots = [s for s, _age in applied]
        assert (2 in slots) == (w >= admit)


def test_cluster_heartbeat_timeout_detects_partitioned_worker():
    """A worker that goes silent WITHOUT closing its sockets (the
    rpc-hang partition) is declared dead by the heartbeat scan and
    the run completes at reduced quorum."""
    cfg = clus.ClusterConfig(**{
        **CFG, "n_slots": 2, "n_windows": 6,
        "heartbeat_timeout": 1.0})
    coord = clus.Coordinator(cfg).start()
    try:
        from tpu_distalg.cluster.local import _ThreadWorker

        w0 = _ThreadWorker("127.0.0.1", coord.port, 0)
        # slot 1: joins, pushes nothing, beats nothing — just a held
        # socket (the partitioned peer)
        sock = transport.connect("127.0.0.1", coord.port)
        kind, meta, _ = transport.request(sock, "join", {"slot": 1})
        assert kind == "welcome"
        res = coord.wait(timeout=120.0)
        w0.join(timeout=30)
        sock.close()
    finally:
        coord.stop()
    assert res["version"] == 6
    assert ("leave", 1, 0) in res["membership_sequence"]


def test_cluster_straggle_on_final_window_records_the_loss():
    # cell 22 = (window 7, slot 1) at 8 windows: no later boundary
    # exists for the delta to ride — the loss is RECORDED, not silent
    res = _run(plan="seed=7;cluster:worker@22=straggle:30")
    assert res["version"] == 8
    _, skipped = {w: (a, sk) for w, a, sk in
                  res["merge_sequence"]}[7]
    assert skipped == (1,)
    assert res["worker_stats"][1]["undelivered_windows"] == 1
    assert res["worker_stats"][0]["undelivered_windows"] == 0


def test_cluster_zombie_incarnation_is_fenced():
    """A partitioned predecessor's late frames (and its connection's
    eventual EOF) must neither act on nor kill the slot's healthy
    replacement."""
    cfg = clus.ClusterConfig(**{**CFG, "n_slots": 1, "n_windows": 4,
                                "heartbeat_timeout": 30.0})
    coord = clus.Coordinator(cfg).start()
    try:
        zombie = transport.connect("127.0.0.1", coord.port)
        kind, meta, _ = transport.request(zombie, "join", {"slot": 0})
        assert kind == "welcome"
        old_inc = int(meta["incarnation"])
        # the zombie partitions: declared dead via its connection EOF
        zombie.close()
        deadline = time.monotonic() + 30
        while coord.slots[0].status == "active" and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        # replacement takes the slot with a fresh incarnation
        repl = transport.connect("127.0.0.1", coord.port)
        kind, meta2, _ = transport.request(repl, "join", {"slot": 0})
        assert kind == "welcome"
        assert int(meta2["incarnation"]) > old_inc
        # the healed zombie's frames carry the OLD token: rejected,
        # and its beats do not refresh the replacement's liveness
        late = transport.connect("127.0.0.1", coord.port)
        k, m, _ = transport.request(
            late, "skip", {"slot": 0, "inc": old_inc, "window": 0})
        assert k == "error" and "stale" in m["error"]
        before = coord.slots[0].last_beat
        transport.request(late, "beat", {"slot": 0, "inc": old_inc})
        assert coord.slots[0].last_beat == before
        # the zombie-tagged connections' EOFs never joined/bound here,
        # and the fenced death check keeps the replacement alive
        late.close()
        time.sleep(0.2)
        assert coord.slots[0].status == "active"
        repl.close()
    finally:
        coord.stop()


def test_worker_rpc_raises_on_error_reply(monkeypatch):
    """A fenced-out (or dying) coordinator answers a poll with
    ("error", ...). The pre-fix rpc adopted that frame as data — no
    version/done/restart key, so the admission gate spun on stale
    state until its deadline (a zombie training silently, the TDA112
    class). The fix surfaces it as a link failure the supervised
    path can rejoin from."""
    orig = worker._Link.request

    def poison(self, kind, meta, arrays=None, **kw):
        if kind == "poll":
            return "error", {"error": "stale slot"}, {}
        return orig(self, kind, meta, arrays, **kw)

    monkeypatch.setattr(worker._Link, "request", poison)
    # bound the PRE-fix failure mode: without the raise the gate
    # would spin until this deadline, not hang the suite for 300 s
    monkeypatch.setattr(worker, "GATE_DEADLINE_SECONDS", 5.0)
    cfg = clus.ClusterConfig(**{**CFG, "n_slots": 1, "n_windows": 4,
                                "heartbeat_timeout": 30.0})
    coord = clus.Coordinator(cfg).start()
    try:
        # admit_at=2 > version=0 routes the worker straight into the
        # admission gate, whose first round trip is rpc("poll", ...)
        with pytest.raises(transport.TransportClosed,
                           match="poll rejected: stale slot"):
            worker.run_worker("127.0.0.1", coord.port, slot=0,
                              admit_at=2)
    finally:
        coord.stop()


def test_cluster_rejects_bsp_and_bad_policy():
    with pytest.raises(ValueError, match="policy"):
        clus.ClusterConfig(policy="bsp")
    with pytest.raises(ValueError, match="n_slots"):
        clus.ClusterConfig(n_slots=0)


def test_cluster_checkpoint_resume_rejects_foreign_tag(tmp_path):
    from tpu_distalg.utils import checkpoint as ckpt

    ckpt.save(str(tmp_path),
              {"tag": ckpt.encode_tag("ssgd:bsp"),
               "center": {"w": np.zeros(3, np.float32)}}, step=4)
    with pytest.raises(ValueError, match="fresh directory"):
        clus.Coordinator(clus.ClusterConfig(
            **{**CFG, "checkpoint_dir": str(tmp_path)}))


# -------------------------------------- coordinator crash tolerance


def test_coordinator_kill_recovers_bitwise(undisturbed, tmp_path):
    """THE tentpole acceptance, thread mode: kill the coordinator
    mid-window (all pushes buffered, commit record not yet durable)
    -> launcher respawn on the same port -> WAL replay -> worker
    reconnects re-present incarnations -> the rolled-back window
    re-runs from re-pushed deltas. No membership epoch burns, and the
    completed run is BITWISE-identical to the undisturbed one."""
    res = _run(plan="seed=7;cluster:coordinator@4=kill",
               checkpoint_dir=str(tmp_path), heartbeat_timeout=15.0)
    assert res["version"] == 8
    assert res["coordinator_recoveries"] == 1
    assert len(res["recovery_ms"]) == 1 and res["recovery_ms"][0] > 0
    assert res["wal_records_replayed"] > 0
    assert res["merge_sequence"] == undisturbed["merge_sequence"]
    assert res["membership_sequence"] == \
        undisturbed["membership_sequence"]
    assert np.array_equal(res["center"]["w"],
                          undisturbed["center"]["w"])
    # workers resumed, not re-admitted: reconnects recorded, no
    # readmissions, no epochs
    assert sum(s.get("reconnects", 0)
               for s in res["worker_stats"].values()) >= 1
    assert all(s.get("readmissions", 0) == 0
               for s in res["worker_stats"].values())


def test_coordinator_kill_replay_determinism(tmp_path):
    """A recovered run vs its own re-run: the same plan (kill + a
    straggle riding along) replays to identical sequences and a
    bitwise center."""
    plan = ("seed=7;cluster:coordinator@4=kill;"
            "cluster:worker@13=straggle:30")
    a = _run(plan=plan, checkpoint_dir=str(tmp_path / "a"),
             heartbeat_timeout=15.0)
    b = _run(plan=plan, checkpoint_dir=str(tmp_path / "b"),
             heartbeat_timeout=15.0)
    assert a["coordinator_recoveries"] == 1
    assert a["merge_sequence"] == b["merge_sequence"]
    assert a["membership_sequence"] == b["membership_sequence"]
    assert np.array_equal(a["center"]["w"], b["center"]["w"])
    # and the straggle's aged delivery survived the recovery: slot 1
    # skipped window 4, delivered it staler at 5
    by_window = {w: (applied, skipped) for w, applied, skipped in
                 a["merge_sequence"]}
    assert by_window[4][1] == (1,)
    assert (1, 1) in by_window[5][0]


def test_coordinator_kill_requires_checkpoint_dir():
    with pytest.raises(ValueError, match="checkpoint_dir"):
        _run(plan="seed=7;cluster:coordinator@4=kill")


def test_recovered_coordinator_keeps_fencing_and_resumes(tmp_path):
    """Recovery reconstructs the incarnation table from the WAL's
    admit records: a stale token is still rejected AFTER recovery,
    and a matching one resumes without burning a membership epoch."""
    cfg = clus.ClusterConfig(**{
        **CFG, "n_slots": 1, "n_windows": 4,
        "checkpoint_dir": str(tmp_path), "heartbeat_timeout": 30.0})
    coord = clus.Coordinator(cfg).start()
    sock = transport.connect("127.0.0.1", coord.port)
    kind, meta, _ = transport.request(sock, "join", {"slot": 0})
    assert kind == "welcome"
    inc = int(meta["incarnation"])
    gen0 = int(meta["gen"])
    coord.stop()
    sock.close()
    # a NEW coordinator from the same directory: WAL recovery
    coord2 = clus.Coordinator(cfg).start()
    try:
        assert coord2.recovered
        assert coord2.slots[0].status == "active"
        assert coord2.slots[0].incarnation == inc
        # stale incarnation: rejected
        late = transport.connect("127.0.0.1", coord2.port)
        k, m, _ = transport.request(
            late, "skip", {"slot": 0, "inc": inc + 7, "window": 0})
        assert k == "error" and "stale" in m["error"]
        late.close()
        # matching incarnation: resumed, same gen, no join event
        re = transport.connect("127.0.0.1", coord2.port)
        k2, m2, _ = transport.request(
            re, "join", {"slot": 0, "inc": inc, "resume": True})
        assert k2 == "welcome" and m2.get("resume") is True
        assert int(m2["gen"]) == gen0
        assert int(m2["incarnation"]) == inc
        joins = [e for e in coord2.events if e[0] == "join"]
        assert len(joins) == 1          # only the original admission
        re.close()
    finally:
        coord2.stop()


def test_committed_window_repush_is_deduped_by_digest(tmp_path):
    """The idempotence token: a push for an already-committed window
    (the ack died with the coordinator) is acknowledged from the
    WAL's commit digest without double-applying; DIFFERENT bytes for
    the same window are refused."""
    cfg = clus.ClusterConfig(**{
        **CFG, "n_slots": 1, "n_windows": 4,
        "checkpoint_dir": str(tmp_path), "heartbeat_timeout": 30.0})
    coord = clus.Coordinator(cfg).start()
    try:
        sock = transport.connect("127.0.0.1", coord.port)
        kind, meta, center = transport.request(sock, "join",
                                               {"slot": 0})
        ident = {"slot": 0, "inc": int(meta["incarnation"])}
        delta = {"w": np.full_like(center["w"], 0.25)}
        k, m, arrays = transport.request(
            sock, "push", dict(ident, window=0, base=0), delta)
        assert k == "center" and int(m["version"]) == 1
        after = arrays["w"].copy()
        # re-deliver the identical bytes: deduped, center unchanged
        k2, m2, arrays2 = transport.request(
            sock, "push", dict(ident, window=0, base=0), delta)
        assert k2 == "center" and int(m2["version"]) == 1
        assert np.array_equal(arrays2["w"], after)
        # different bytes for the committed window: refused
        k3, m3, _ = transport.request(
            sock, "push", dict(ident, window=0, base=0),
            {"w": np.full_like(center["w"], 9.0)})
        assert k3 == "error" and "digest" in m3["error"]
        sock.close()
    finally:
        coord.stop()


def test_redial_races_eof_sweep_without_burning_an_epoch():
    """The reconnect-races-EOF-sweep edge, deterministically: an
    established incarnation's connection tears (closed under it); its
    re-dial + resume-join lands while the coordinator's EOF sweep
    has the slot merely SUSPECT — the resume supersedes the dead
    connection (serial bump), no leave fires, no generation burns,
    and after the grace elapses the slot is still alive."""
    cfg = clus.ClusterConfig(**{**CFG, "n_slots": 1, "n_windows": 4,
                                "heartbeat_timeout": 30.0})
    coord = clus.Coordinator(cfg).start()
    try:
        sock = transport.connect("127.0.0.1", coord.port)
        kind, meta, _ = transport.request(sock, "join", {"slot": 0})
        assert kind == "welcome"
        inc = int(meta["incarnation"])
        gen0 = int(meta["gen"])
        # the connection tears (rpc fault / slammed socket)
        sock.close()
        deadline = time.monotonic() + 10
        while coord.slots[0].suspect_at is None and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        assert coord.slots[0].suspect_at is not None
        # the re-dial races the sweep: resume inside the grace
        re = transport.connect("127.0.0.1", coord.port)
        k, m, _ = transport.request(
            re, "join", {"slot": 0, "inc": inc, "resume": True})
        assert k == "welcome" and m.get("resume") is True
        assert int(m["gen"]) == gen0          # no epoch burned
        assert coord.slots[0].suspect_at is None
        # outlive the grace: the dead predecessor's EOF stays inert
        time.sleep(cfg.reconnect_grace + 0.5)
        assert coord.slots[0].status == "active"
        assert not any(e[0] == "leave" for e in coord.events)
        re.close()
    finally:
        coord.stop()


def test_rpc_oserror_storm_retries_and_completes():
    """The oserror-storm pin (heartbeat-retry satellite): random torn
    connections on every transport seam; links and the heartbeat
    re-dial through it and the run completes. (Membership churn is
    tolerated: a join whose WELCOME is lost can only re-enter as a
    fresh admission.) Whether a probabilistic fire lands on a
    worker-visible seam is timing-dependent, so the retry-EVIDENCE
    assertion retries across seeds until a run shows it instead of
    betting one seed's draw against the box's timing."""
    retried = 0
    for seed in (3, 5, 9):
        plan = f"seed={seed};cluster:rpc@p0.05=oserror"
        faults.configure(plan)   # a LIVE seam, not a compiled schedule
        try:
            res = _run(plan=plan, n_windows=6)
        finally:
            faults.configure(False)
        assert res["version"] == 6
        retried = sum(s.get("reconnects", 0)
                      + s.get("heartbeat_retries", 0)
                      for s in res["worker_stats"].values())
        if retried:
            break
    assert retried >= 1


def test_heartbeat_link_survives_transient_beat_failures():
    """The heartbeat-retry satellite, unit level: a beat whose send
    blows up drops + re-dials inside the SAME beat and counts the
    retry — the thread-level loop never dies of an I/O error."""
    calls = {"n": 0}

    class _Boom(Exception):
        pass

    cfg = clus.ClusterConfig(**{**CFG, "n_slots": 1, "n_windows": 2})
    coord = clus.Coordinator(cfg).start()
    try:
        real_connect = transport.connect

        def flaky_connect(host, port, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise OSError("first dial torn")
            return real_connect(host, port, **kw)

        stats = {"heartbeat_retries": 0}
        hb = worker._HbLink("127.0.0.1", coord.port, flaky_connect,
                            {"slot": 0, "inc": 0}, 5.0, stats)
        hb.beat()     # dial fails once, retries in-beat, succeeds
        assert stats["heartbeat_retries"] == 1
        assert hb.sock is not None
        hb.beat()     # healthy beat: no new retries
        assert stats["heartbeat_retries"] == 1
        hb.close()
    finally:
        coord.stop()


def test_chaos_cluster_workload_bitwise(tmp_path):
    """``tda chaos --workload cluster``: undisturbed vs coordinator-
    kill runs compare bitwise on BOTH the center and the event
    digest."""
    from tpu_distalg.faults import chaos

    res = chaos.run_chaos(
        "cluster", None,
        plan="seed=7;cluster:coordinator@4=kill",
        workdir=str(tmp_path))
    assert res.equal, res.verdict()
    assert any(p == "cluster:coordinator" for p, _h, _k in res.fired)


def test_report_renders_recovery_line_and_worker_columns():
    from tpu_distalg.telemetry import report as treport

    evts = [
        {"ev": "counters", "counters": {
            "cluster.recoveries": 2,
            "cluster.wal_records_replayed": 7,
            "cluster.wal_quarantines": 1,
            "cluster.reconnects": 3,
            "cluster.heartbeat_retries": 4,
            "cluster.dedup_pushes": 1}},
        {"ev": "gauge", "name": "cluster.recovery_ms_p50",
         "value": 83.5},
    ]
    out = treport.render(treport.summarize(evts))
    assert ("coordinator: 2 recover(ies), median 83.5 ms, 7 WAL "
            "record(s) replayed") in out
    assert "1 torn-tail quarantine(s)" in out
    assert "3 worker reconnect(s)" in out
    assert "4 heartbeat retr(ies)" in out
    # the reconnect/retry counters ride the existing cluster.* per-
    # worker column table in the merged rendering
    assert "cluster.reconnects" in treport.render_multi(
        {"merged": treport.summarize(evts),
         "workers": {"worker-0": treport.summarize(evts)}})


def test_report_renders_cluster_wire_line():
    from tpu_distalg.telemetry import report as treport

    evts = [{"ev": "counters", "counters": {
        "cluster.wire_push_bytes": 2_500_000,
        "cluster.wire_center_bytes": 1_500_000,
        "cluster.delta_pulls": 24,
        "cluster.pull_dense_fallbacks": 3,
        "cluster.async_pushes": 24}}]
    out = treport.render(treport.summarize(evts))
    assert ("cluster wire: 2.50 MB pushed / 1.50 MB pulled "
            "(24 delta pull(s), 3 dense fallback(s), 24 overlapped "
            "push(es))") in out
    # small runs render KB, never a misleading "0.00 MB"
    evts_small = [{"ev": "counters", "counters": {
        "cluster.wire_push_bytes": 5_200,
        "cluster.wire_center_bytes": 3_100}}]
    assert "5.2 KB pushed / 3.1 KB pulled" in treport.render(
        treport.summarize(evts_small))


# ------------------------------------------- compressed cluster wire


def test_transport_parts_join_is_the_frame():
    """The scatter-gather satellite's framing pin: the buffer list
    send_frame hands to sendmsg concatenates to EXACTLY the
    contiguous encode_frame bytes — one framing implementation, zero
    drift, and the numpy-fallback sendall path is byte-identical by
    construction."""
    arrays = {"q": np.arange(64, dtype=np.int8),
              "scale": np.full((1,), 0.25, np.float32),
              "idx": np.array([5, 1], np.int32)}
    meta = {"slot": 1, "window": 4, "have": 3}
    parts = transport.encode_frame_parts("push", meta, arrays)
    assert len(parts) == 1 + len(arrays)   # prefix+header, then chunks
    assert b"".join(parts) == transport.encode_frame("push", meta,
                                                     arrays)
    # and the joined bytes parse back losslessly
    a, b = _pipe()
    transport.send_frame(a, "push", meta, arrays)
    kind, m, out = transport.recv_frame(b, deadline=5.0)
    assert kind == "push" and m == meta
    for k, v in arrays.items():
        assert out[k].dtype == v.dtype and np.array_equal(out[k], v)
    a.close(), b.close()


def test_transport_wire_stats_measure_real_frame_bytes():
    a, b = _pipe()
    transport.wire_stats_reset()
    arrays = {"w": np.ones(100, np.float32)}
    n = len(transport.encode_frame("push", {"x": 1}, arrays))
    transport.send_frame(a, "push", {"x": 1}, arrays)
    transport.send_frame(a, "center", {}, arrays)
    st = transport.wire_stats()
    assert st["push"] == {"frames": 1, "bytes": n}
    assert st["center"]["frames"] == 1
    transport.wire_stats_reset()
    assert transport.wire_stats() == {}
    a.close(), b.close()


def test_host_codec_ef_residual_resume_round_trip():
    """The EF-residual resume satellite, unit level: serialize the
    residual mid-stream (what a checkpointed worker state carries),
    restore it, and the continuation emits BITWISE the bytes of the
    uninterrupted stream — the residual is the ONLY cross-window
    codec state, so this is the whole resume story."""
    from tpu_distalg.parallel import comms

    rng = np.random.RandomState(3)
    deltas = [rng.randn(96).astype(np.float32) for _ in range(6)]
    for spec in ("int8:9", "topk:0.25"):
        codec = comms.make_host_codec(spec)
        template = {"w": np.zeros(96, np.float32)}

        def stream(residuals, start, stop, out):
            for w in range(start, stop):
                arrays, residuals = comms.encode_tree(
                    codec, {"w": deltas[w]}, residuals,
                    comms.PUSH_SEED_TAG, 0, w)
                out.append(arrays)
            return residuals

        # uninterrupted
        full: list = []
        stream(comms.zero_residuals(template), 0, 6, full)
        # interrupted at window 3: residual round-trips through bytes
        # (the checkpoint spelling — np.save/load of the flat vector)
        first: list = []
        res = stream(comms.zero_residuals(template), 0, 3, first)
        import io

        buf = io.BytesIO()
        np.save(buf, res["w"])
        buf.seek(0)
        resumed = {"w": np.load(buf)}
        stream(resumed, 3, 6, first)
        assert len(first) == len(full)
        for a, b in zip(first, full):
            assert sorted(a) == sorted(b)
            for k in a:
                assert np.array_equal(a[k], b[k]), (spec, k)


def test_cluster_dense_is_pinned_to_the_pre_compression_protocol(
        undisturbed):
    """--comm dense IS the pre-PR cluster: codec None (the verbatim
    f32 snapshot path, no 'have'/'mode' machinery), and the full run
    reproduces the undisturbed fixture bitwise — sequences, center,
    accuracy."""
    from tpu_distalg.parallel import comms

    assert comms.make_host_codec("dense") is None
    res = _run(comm="dense")
    assert res["merge_sequence"] == undisturbed["merge_sequence"]
    assert res["membership_sequence"] == \
        undisturbed["membership_sequence"]
    assert np.array_equal(res["center"]["w"],
                          undisturbed["center"]["w"])
    assert res["accuracy"] == undisturbed["accuracy"]


def test_cluster_rejects_deviceless_schedules():
    with pytest.raises(ValueError, match="host-wire codec"):
        clus.ClusterConfig(**{**CFG, "comm": "bucketed"})


@pytest.fixture(scope="module", params=["int8:5", "topk:0.25"])
def compressed_undisturbed(request):
    return request.param, _run(comm=request.param)


def test_compressed_wire_converges_and_compresses(
        compressed_undisturbed, undisturbed):
    """The compressed run completes, converges inside the SSP chaos
    band of dense, rides version-delta pulls (no dense fallbacks
    after the welcome), and overlaps every push."""
    comm, res = compressed_undisturbed
    assert res["version"] == 8
    assert abs(res["accuracy"]
               - undisturbed["accuracy"]) <= SSP_CHAOS_ACC_BAND
    for s in res["worker_stats"].values():
        assert s["pushes"] == 8
        assert s["delta_pulls"] == 8      # every ack rode a delta
        assert s["dense_pulls"] == 0
        assert s["async_pushes"] == 8     # the overlap was on


def test_compressed_seq_spelling_disables_the_overlap():
    res = _run(comm="int8:5@seq")
    assert res["version"] == 8
    for s in res["worker_stats"].values():
        assert s["async_pushes"] == 0
        assert s["delta_pulls"] == 8      # compression itself stays on


def test_compressed_chaos_grid_coordinator_kill_bitwise(
        compressed_undisturbed, tmp_path):
    """Grid row 1 — compression × coordinator kill -9: WAL rollback,
    recovery, worker reconnect + re-push of the identical COMPRESSED
    bytes, version-delta pulls re-served from the replay-rebuilt
    center history. Verdict: bitwise center + identical sequences vs
    the undisturbed run of the same wire schedule."""
    comm, und = compressed_undisturbed
    res = _run(plan="seed=7;cluster:coordinator@4=kill", comm=comm,
               checkpoint_dir=str(tmp_path), heartbeat_timeout=15.0)
    assert res["version"] == 8
    assert res["coordinator_recoveries"] == 1
    assert res["merge_sequence"] == und["merge_sequence"]
    assert res["membership_sequence"] == und["membership_sequence"]
    assert np.array_equal(res["center"]["w"], und["center"]["w"])
    # recovery re-served DELTAS, not fallbacks: the rebuilt history
    # covered every re-pushed window
    assert all(s["dense_pulls"] == 0
               for s in res["worker_stats"].values())


def test_compressed_chaos_grid_rpc_oserror_bitwise(
        compressed_undisturbed):
    """Grid row 2 — compression × cluster:rpc oserror (a torn
    connection mid-run): the link resumes and re-delivers the same
    frames; pulls stay version-pinned, so even the re-served acks are
    bitwise. Verdict: identical center + sequences vs undisturbed."""
    comm, und = compressed_undisturbed
    plan = "seed=11;cluster:rpc@40=oserror"
    faults.configure(plan)     # a LIVE seam, not a compiled schedule
    try:
        res = _run(plan=plan, comm=comm)
    finally:
        faults.configure(False)
    assert res["version"] == 8
    assert res["merge_sequence"] == und["merge_sequence"]
    assert res["membership_sequence"] == und["membership_sequence"]
    assert np.array_equal(res["center"]["w"], und["center"]["w"])


def test_compressed_chaos_grid_worker_kill_rejoin_replays(
        compressed_undisturbed, undisturbed):
    """Grid row 3 — compression × worker kill + pinned rejoin: the
    membership legitimately differs from undisturbed (that is the
    kill), so the verdict is REPLAY bitwiseness (same plan ⇒ same
    digest + center) plus convergence inside the chaos band; the
    rejoiner's fresh admission takes the dense-snapshot pull
    fallback by construction."""
    comm, und = compressed_undisturbed
    plan = "seed=7;cluster:worker@10=kill"
    a = _run(plan=plan, comm=comm, rejoin_after=2)
    b = _run(plan=plan, comm=comm, rejoin_after=2)
    assert a["version"] == 8 and a["respawns"] == 1
    assert a["merge_sequence"] == b["merge_sequence"]
    assert a["membership_sequence"] == b["membership_sequence"]
    assert np.array_equal(a["center"]["w"], b["center"]["w"])
    assert ("leave", 1, 3) in a["membership_sequence"]
    assert ("join", 1, 5) in a["membership_sequence"]
    assert abs(a["accuracy"]
               - undisturbed["accuracy"]) <= SSP_CHAOS_ACC_BAND


def test_version_delta_pull_falls_back_to_snapshot(tmp_path):
    """The fallback satellite, protocol level: a push whose ``have``
    predates the PS history window is answered with a DENSE
    version-pinned snapshot instead of an unservable delta — and a
    recovered coordinator whose rebuilt history lacks the requested
    base does the same rather than guessing."""
    cfg = clus.ClusterConfig(**{
        **CFG, "n_slots": 1, "n_windows": 6, "comm": "int8:5",
        "checkpoint_dir": str(tmp_path), "heartbeat_timeout": 30.0})
    coord = clus.Coordinator(cfg).start()
    try:
        sock = transport.connect("127.0.0.1", coord.port)
        kind, meta, center = transport.request(sock, "join",
                                               {"slot": 0})
        assert kind == "welcome" and meta["comm"] == "int8:5"
        ident = {"slot": 0, "inc": int(meta["incarnation"])}
        from tpu_distalg.parallel import comms

        codec = comms.make_host_codec("int8:5")
        delta = {"w": np.full_like(center["w"], 0.125)}
        arrays, _ = comms.encode_tree(codec, delta, None,
                                      comms.PUSH_SEED_TAG, 0, 0)
        # have = -1: nothing cached (no such version in history)
        k, m, arrs = transport.request(
            sock, "push", dict(ident, window=0, base=0, have=-1),
            arrays)
        assert k == "center" and m["mode"] == "dense"
        assert int(m["cv"]) == 1
        assert arrs["w"].dtype == np.float32     # a real snapshot
        # a served base inside the history rides a delta
        arrays2, _ = comms.encode_tree(codec, delta, None,
                                       comms.PUSH_SEED_TAG, 0, 1)
        k2, m2, arrs2 = transport.request(
            sock, "push", dict(ident, window=1, base=1, have=1),
            arrays2)
        assert k2 == "center" and m2["mode"] == "delta"
        assert int(m2["cv"]) == 2 and int(m2["have"]) == 1
        assert arrs2["w.q"].dtype == np.int8     # compressed wire
        sock.close()
    finally:
        coord.stop()


def test_pull_refresh_cadence_bounds_view_drift():
    """Review pin: pull-direction rounding noise has no EF channel,
    so every PULL_REFRESH_WINDOWS-th commit ships a dense
    version-pinned snapshot — the worker's cached-view random walk is
    bounded by the refresh period, and the cadence is a pure function
    of cv (replay-inert). A long compressed run really takes them."""
    from tpu_distalg.cluster.coordinator import PULL_REFRESH_WINDOWS

    windows = PULL_REFRESH_WINDOWS + 2
    res = _run(comm="int8:5", n_slots=1, n_windows=windows)
    assert res["version"] == windows
    s = res["worker_stats"][0]
    assert s["pushes"] == windows
    # exactly one scheduled refresh in the range (cv = REFRESH), the
    # rest deltas
    assert s["dense_pulls"] == 1
    assert s["delta_pulls"] == windows - 1


def test_wal_commit_records_carry_the_compressed_bytes(tmp_path):
    """The redo log logs what crossed the wire: under a codec the
    commit record's arrays are the int8/pair payloads (replayed
    bitwise through the same decode), never a re-densified copy."""
    res = _run(comm="int8:5", n_windows=4,
               checkpoint_dir=str(tmp_path), heartbeat_timeout=15.0)
    assert res["version"] == 4
    wal_dir = os.path.join(str(tmp_path), "wal")
    recs = []
    for b in wal.segment_bases(wal_dir):
        segment, _ = wal.read_segment(wal._segment_path(wal_dir, b))
        recs.extend(segment)
    commits = [r for r in recs if r[0] == "commit"]
    assert commits
    for _k, meta, arrays in commits:
        for c in meta["contribs"]:
            q = arrays[f"{c['slot']}/w.q"]
            assert q.dtype == np.int8
            assert f"{c['slot']}/w.scale" in arrays
            assert f"{c['slot']}/w" not in arrays


# --------------------------------------------- subprocess acceptance


def _cli_cluster(tmp, plan, extra=()):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", TDA_TELEMETRY_DIR="",
               TDA_FAULT_PLAN="")
    cmd = [sys.executable, "-m", "tpu_distalg.cli", "cluster",
           "--role", "local", "--spawn", "process", "--workers", "3",
           "--n-windows", "8", "--sync", "ssp:3",
           "--heartbeat-timeout", "3", "--n-rows", "1024",
           "--deadline", "280", "--fault-plan", plan, *extra]
    r = subprocess.run(cmd, env=env, cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, (r.returncode, r.stdout[-2000:],
                               r.stderr[-2000:])
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("cluster_result: ")][-1]
    return json.loads(line[len("cluster_result: "):])


def test_subprocess_kill9_rejoin_and_replay(tmp_path):
    """THE acceptance: a real 3-process cluster survives a genuine
    seeded ``kill -9`` of one worker mid-window plus a late rejoin,
    completes inside the SSP chaos band of the undisturbed run, and
    the same plan replays to an identical merge/membership digest."""
    plan = "seed=7;cluster:worker@13=kill"  # (window 4, slot 1)
    undisturbed = _cli_cluster(tmp_path, "seed=7")
    a = _cli_cluster(tmp_path, plan)
    b = _cli_cluster(tmp_path, plan)
    assert a["version"] == 8 and a["merges"] == 8
    assert a["respawns"] == 1
    assert a["event_digest"] == b["event_digest"]
    assert a["accuracy"] == b["accuracy"]
    assert abs(a["accuracy"]
               - undisturbed["accuracy"]) <= SSP_CHAOS_ACC_BAND
    assert undisturbed["respawns"] == 0


def test_subprocess_coordinator_kill9_recovery_and_replay(tmp_path):
    """THE coordinator-kill acceptance: the coordinator runs as a
    REAL subprocess and a seeded ``cluster:coordinator`` plan makes
    it genuinely ``kill -9`` itself mid-window; the launcher respawns
    it on the same port, it recovers from the durable WAL, the worker
    processes reconnect — and the completed run carries an event
    digest and accuracy IDENTICAL to the undisturbed run's, replayed
    identically by a second run of the same plan."""
    plan = "seed=7;cluster:coordinator@4=kill"
    undisturbed = _cli_cluster(tmp_path, "seed=7")
    a = _cli_cluster(tmp_path, plan, extra=(
        "--coordinator-spawn", "process",
        "--checkpoint-dir", str(tmp_path / "ck_a")))
    b = _cli_cluster(tmp_path, plan, extra=(
        "--coordinator-spawn", "process",
        "--checkpoint-dir", str(tmp_path / "ck_b")))
    assert a["version"] == 8 and a["merges"] == 8
    assert a["recoveries"] == 1 and b["recoveries"] == 1
    assert a["event_digest"] == b["event_digest"] \
        == undisturbed["event_digest"]
    assert a["accuracy"] == b["accuracy"] == undisturbed["accuracy"]
    assert undisturbed["recoveries"] == 0


@pytest.mark.slow
def test_subprocess_grid_straggle_and_rpc_partition(tmp_path):
    """The wider spawn-heavy grid: straggle-one and an rpc hang (a
    transient partition the transport deadline + heartbeat machinery
    must ride out), each replayed."""
    for plan in ("seed=7;cluster:worker@13=straggle:40",
                 "seed=7;cluster:rpc@p0.02=hang:0.2"):
        a = _cli_cluster(tmp_path, plan)
        b = _cli_cluster(tmp_path, plan)
        assert a["version"] == 8
        assert a["event_digest"] == b["event_digest"]
