"""Platform-aware autotuner (tpu_distalg/tune/): rig profiles, the
cost-model resolver, the `--tune` CLI plumbing and the TDA120
geometry lint.

The profile tier is tested with an INJECTABLE clock (the measurement
pass is seeded and sized by constants, so a pinned clock makes two
passes byte-identical); the resolver tier is tested against CRAFTED
profiles whose closed-form optimum is computed in the test, so the
chooser's arithmetic is checked, not mirrored.
"""

import copy
import json
import os

import pytest

from tpu_distalg import tune as ttune
from tpu_distalg.tune import defaults as tdefaults


class FakeClock:
    """Deterministic duration clock: every read advances a fixed
    step, so measured rates depend only on call counts (which the
    seeded, constant-sized pass makes deterministic)."""

    def __init__(self, step=1e-3):
        self.t, self.step = 0.0, step

    def __call__(self):
        self.t += self.step
        return self.t


def _crafted_profile(*, loopback_bw=300e6, loopback_rtt=50e-6,
                     memcpy=1e9, ram=1 << 34, collective=None,
                     codec_rate=1e12, backend_init_s=None,
                     created=1000.0):
    """A hand-built profile whose numbers the tests chose — the
    resolver must reproduce the closed-form optimum for them."""
    codecs = {s: {"encode_elems_s": codec_rate,
                  "decode_elems_s": codec_rate}
              for s in ("dense", "int8", "topk")}
    meas = {
        "loopback": {"bandwidth_bytes_s": loopback_bw,
                     "rtt_s": loopback_rtt},
        "memcpy_bytes_s": memcpy,
        "matmul_flops_s": 1e11,
        "codecs": codecs,
        "host_ram_bytes": ram,
        "collective": collective,
        "backend_init_s": backend_init_s,
        "quick": True,
    }
    return ttune.build_profile(meas, created_unix=created, seed=0,
                               rig="crafted-rig", backend="cpu")


# ---------------------------------------------------------------------
# profile artifact: round trip, version reject, CRC reject, newest


def test_profile_round_trip(tmp_path):
    prof = _crafted_profile()
    path = ttune.save_profile(prof, str(tmp_path))
    assert os.path.basename(path).startswith("RIGPROFILE_")
    assert ttune.load_profile(path) == prof


def test_profile_schema_version_rejected(tmp_path):
    prof = _crafted_profile()
    bad = dict(prof, schema_version=ttune.SCHEMA_VERSION + 1)
    bad["crc32"] = ttune.profile_crc(bad)   # honest CRC, wrong schema
    p = tmp_path / "RIGPROFILE_x.json"
    p.write_text(json.dumps(bad))
    with pytest.raises(ttune.ProfileError, match="schema_version"):
        ttune.load_profile(str(p))


def test_profile_crc_rejects_bit_rot(tmp_path):
    prof = _crafted_profile()
    path = ttune.save_profile(prof, str(tmp_path))
    rotted = open(path).read().replace(
        '"rig": "crafted-rig"', '"rig": "crafted-rig2"')
    open(path, "w").write(rotted)
    with pytest.raises(ttune.ProfileError, match="CRC"):
        ttune.load_profile(path)


def test_newest_profile_picks_newest_matching_rig(tmp_path):
    old = _crafted_profile(created=1000.0)
    new = _crafted_profile(created=2000.0)
    ttune.save_profile(old, str(tmp_path))
    ttune.save_profile(new, str(tmp_path))
    # a corrupt artifact in the dir is skipped, not fatal
    (tmp_path / "RIGPROFILE_junk.json").write_text("{not json")
    got, path = ttune.newest_profile(str(tmp_path), rig="crafted-rig")
    assert got == new and path.endswith(
        f"RIGPROFILE_{new['profile_id']}.json")
    miss, _ = ttune.newest_profile(str(tmp_path), rig="other-rig")
    assert miss is None


# ---------------------------------------------------------------------
# seeded profiling determinism


def test_measure_rig_pinned_clock_is_byte_identical():
    """Two passes under a pinned clock produce byte-identical
    profiles (modulo nothing: same clock, same seed, same sizes —
    the only nondeterminism the real pass has is the clock)."""
    m1 = ttune.measure_rig(seed=0, quick=True, clock=FakeClock())
    m2 = ttune.measure_rig(seed=0, quick=True, clock=FakeClock())
    p1 = ttune.build_profile(m1, created_unix=5.0, seed=0, rig="r",
                             backend="cpu")
    p2 = ttune.build_profile(m2, created_unix=5.0, seed=0, rig="r",
                             backend="cpu")
    assert json.dumps(p1, sort_keys=True) \
        == json.dumps(p2, sort_keys=True)
    assert p1["crc32"] == p2["crc32"]
    # the real-clock pass measures the same field set
    assert set(m1) == {"loopback", "memcpy_bytes_s",
                       "matmul_flops_s", "codecs", "host_ram_bytes",
                       "collective", "backend_init_s", "quick"}


# ---------------------------------------------------------------------
# resolver: closed-form optimum on crafted profiles


def test_slow_wire_fast_codec_resolves_topk():
    """On a slow host wire with fast codecs the wire term dominates:
    topk ships 8k(n-1) bytes vs dense's 4d·2(n-1)/n — the resolver
    must pick what the cost model prices cheapest, and the test
    re-derives that optimum from the same measured inputs."""
    prof = _crafted_profile(loopback_bw=1e6, loopback_rtt=1e-4,
                            codec_rate=1e12)
    wl = ttune.Workload(d=1 << 20, n_workers=4, transport="host")
    res = ttune.resolve(prof, wl)
    priced = {s: ttune.schedule_seconds(prof, wl, s)
              for s in ("dense", "int8", "topk")}
    assert min(priced, key=priced.get) == "topk"
    assert res.value("comm") == "topk"
    assert res.source("comm") == "resolved"
    assert "cheapest predicted sync" in res.choices["comm"].why
    assert res.predicted_sync_ms() == pytest.approx(
        1e3 * priced["topk"])


def test_fast_wire_slow_codec_resolves_dense():
    """Invert the rig: near-free wire, ruinous codecs — encode/decode
    time dwarfs the bytes saved, so dense must win."""
    prof = _crafted_profile(loopback_bw=1e12, loopback_rtt=1e-7,
                            codec_rate=1e5)
    wl = ttune.Workload(d=1 << 20, n_workers=4, transport="host")
    res = ttune.resolve(prof, wl)
    assert res.value("comm") == "dense"
    assert res.source("comm") == "resolved"


def test_device_transport_without_collective_stays_dense():
    """The honesty rule: no measured device interconnect means the
    'wire' is shared memory — nothing to compress, dense stands,
    and the WHY says so (resolved-for-a-reason, not defaulted)."""
    prof = _crafted_profile(collective=None)
    res = ttune.resolve(prof, ttune.Workload(
        d=1 << 20, transport="device", n_shards=4))
    assert res.value("comm") == "dense"
    assert res.source("comm") == "resolved"
    assert "no measured device interconnect" in res.choices["comm"].why


def test_each_knob_pinned_to_closed_form():
    """Every resolver knob against hand-computed optima for one
    crafted rig: bw=1e8 B/s, rtt=1e-4 s, memcpy=1e9 B/s, 16 GiB."""
    prof = _crafted_profile(loopback_bw=1e8, loopback_rtt=1e-4,
                            memcpy=1e9)
    wl = ttune.Workload(d=1 << 20, n_rows=0, n_workers=4,
                        transport="host")
    res = ttune.resolve(prof, wl)
    # bucket: 4x latency amortization -> 4*1e8*1e-4/4 B = 1e4 elems
    # -> nearest pow2 = 8192
    assert res.value("bucket_elems") == 8192
    # ps_shards: sqrt(4*2^20 / (1e8*1e-4)) = sqrt(419.4) ~ 20 -> 8
    assert res.value("ps_shards") == 8
    # ps_mode: 4 MB model x 8 shards = 32 MB << 16 GiB/16 ->
    # replicated, but RESOLVED (measured RAM says it fits)
    assert res.value("ps_mode") == "replicated"
    assert res.source("ps_mode") == "resolved"
    # block_rows: 2ms * 1e9 B/s / (4*2^20 B/row) < 1 row -> clamps
    # to the 256 floor
    assert res.value("block_rows") == 256
    # block_edges: 2ms * 1e9 / 8 B = 250k -> nearest pow2 = 2^18
    assert res.value("block_edges") == 1 << 18
    # mesh_shape: no measured collective -> default stands
    assert res.value("mesh_shape") is None
    assert res.source("mesh_shape") == "default"
    # every choice carries a nonempty WHY
    assert all(c.why for c in res.choices.values())


def test_mesh_shape_from_measured_collective():
    prof = _crafted_profile(collective={
        "bandwidth_bytes_s": 1e10, "rtt_s": 2e-5, "n_shards": 4})
    res = ttune.resolve(prof, ttune.Workload(
        d=1 << 20, transport="device", n_shards=4))
    assert res.value("mesh_shape") == "4x1"
    assert res.source("mesh_shape") == "resolved"


def test_pull_refresh_resolved_only_for_compressed_pulls():
    prof = _crafted_profile(loopback_bw=1e6, loopback_rtt=1e-4)
    wl = ttune.Workload(d=1 << 20, n_workers=4, transport="host")
    res = ttune.resolve(prof, wl)
    assert res.value("comm") != "dense"
    # refresh = ceil(4d / (0.25 * d)) = 16, inside [4, 64]
    assert res.value("pull_refresh_windows") == 16
    assert res.source("pull_refresh_windows") == "resolved"
    dense = ttune.resolve(prof, wl, explicit={"comm": "dense"})
    assert dense.source("pull_refresh_windows") == "default"


def test_explicit_flags_always_win():
    prof = _crafted_profile(loopback_bw=1e6, loopback_rtt=1e-4)
    res = ttune.resolve(
        prof, ttune.Workload(d=1 << 20, n_workers=4,
                             transport="host"),
        explicit={"comm": "int8:3:4096", "ps_shards": 5})
    assert res.value("comm") == "int8:3:4096"
    assert res.source("comm") == "explicit"
    # an explicit spec string passes through comm_string verbatim
    assert res.comm_string() == "int8:3:4096"
    assert res.value("ps_shards") == 5
    assert res.source("ps_shards") == "explicit"
    counts = res.counts()
    assert counts["explicit"] == 2
    assert counts["explicit"] + counts["resolved"] \
        + counts["defaulted"] == len(ttune.KNOBS)


def test_comm_string_folds_resolved_bucket():
    prof = _crafted_profile(loopback_bw=1e8, loopback_rtt=1e-4)
    res = ttune.resolve(
        prof, ttune.Workload(d=1 << 20, n_workers=4,
                             transport="host"),
        explicit={"comm": "int8"})
    assert res.comm_string() == "int8:0:8192"


# ---------------------------------------------------------------------
# CLI: tda tune artifact + --tune auto plumbing


def test_tda_tune_writes_rig_tagged_profile(tmp_path, capsys):
    from tpu_distalg import cli

    rc = cli.main(["tune", "--quick", "--no-backend-init",
                   "--out-dir", str(tmp_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "tune: rig=" in out
    import socket

    prof, path = ttune.newest_profile(str(tmp_path),
                                      rig=socket.gethostname())
    assert prof is not None
    assert prof["schema_version"] == ttune.SCHEMA_VERSION
    m = prof["measurements"]
    assert m["loopback"]["bandwidth_bytes_s"] > 0
    assert m["loopback"]["rtt_s"] > 0
    assert m["memcpy_bytes_s"] > 0 and m["matmul_flops_s"] > 0
    assert set(m["codecs"]) >= {"dense", "int8", "topk"}


def test_tune_auto_ssgd_e2e(tmp_path, monkeypatch, capsys):
    """--tune auto on the ssgd subcommand: resolves from the newest
    rig profile, logs per-knob WHYs, and `tda report` renders the
    tune: line from the tune.* counters (satellite 2)."""
    from tpu_distalg import cli

    pdir = tmp_path / "profiles"
    ttune.save_profile(
        ttune.build_profile(
            _crafted_profile()["measurements"], created_unix=1.0,
            seed=0, backend="cpu"),
        str(pdir))
    monkeypatch.setenv("TDA_PROFILE_DIR", str(pdir))
    tdir = tmp_path / "tel"
    rc = cli.main(["ssgd", "--n-slices", "2", "--n-iterations", "3",
                   "--tune", "auto", "--telemetry-dir", str(tdir)])
    assert rc in (0, None)
    err = capsys.readouterr().err
    assert "tune[comm]:" in err       # per-knob WHY logged
    from tpu_distalg.telemetry import events

    events.configure(False)   # close the sink: flush the counters
    rc = cli.main(["report", str(tdir)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "tune: profile" in out and "resolved" in out


def test_tune_auto_cluster_explicit_flag_wins(tmp_path, monkeypatch,
                                              capsys):
    """--tune auto on cluster local mode: a spelled --comm survives
    (explicit beats resolved), resolvable knobs land in the config,
    and the run completes."""
    from tpu_distalg import cli

    pdir = tmp_path / "profiles"
    ttune.save_profile(
        ttune.build_profile(
            _crafted_profile()["measurements"], created_unix=1.0,
            seed=0, backend="cpu"),
        str(pdir))
    monkeypatch.setenv("TDA_PROFILE_DIR", str(pdir))
    rc = cli.main(["cluster", "--role", "local", "--workers", "1",
                   "--spawn", "thread", "--n-windows", "4",
                   "--comm", "int8", "--tune", "auto",
                   "--telemetry-dir", str(tmp_path / "tel")])
    assert rc in (0, None)
    err = capsys.readouterr().err
    assert "tune[comm]: int8 (explicit)" in err


def test_tuned_cluster_run_stays_bitwise_deterministic(tmp_path):
    """Acceptance: tuning changes geometry, never determinism — the
    SAME resolved geometry replayed twice produces a bitwise-equal
    center."""
    from tpu_distalg import cluster as clus

    prof = _crafted_profile()
    task = clus.TrainTask(n_rows=512)
    res = ttune.resolve(prof, ttune.Workload(
        d=task.n_features + 1, n_rows=task.n_rows, n_workers=2,
        transport="host"))
    kw = {}
    if res.source("comm") == "resolved":
        kw["comm"] = res.comm_string()
    for knob in ("ps_shards", "ps_mode", "pull_refresh_windows"):
        if res.source(knob) == "resolved":
            kw[knob] = res.value(knob)
    cfg = clus.ClusterConfig(
        n_slots=2, n_windows=4, staleness=2, heartbeat_timeout=3.0,
        train=task, tune_profile=prof["profile_id"], **kw)
    a = clus.run_local_cluster(copy.deepcopy(cfg), spawn="thread",
                               timeout=60.0)
    b = clus.run_local_cluster(copy.deepcopy(cfg), spawn="thread",
                               timeout=60.0)
    assert a["center"]["w"].tobytes() == b["center"]["w"].tobytes()


# ---------------------------------------------------------------------
# TDA120: the geometry-literal lint


def test_tda120_flags_offtable_pins_in_scoped_trees():
    from tpu_distalg.analysis import RULES, lint_source

    src = (
        "HALF = 1 << 15\n"
        "block_rows = 1024\n"          # not a BLOCK_ROWS table value
        "bucket_elems = 2 * HALF\n"    # folds to 65536: allowed
        "def f(*, ps_shards: int = 4): ...\n"   # off-table default
        "store = RowStore(c, n_shards=5)\n"     # off-table call pin
        "ok = RowStore(c, n_shards=2)\n"        # table value: fine
        "block_edges = cfg.block_edges\n"       # config-carried: fine
    )
    vs = [v for v in lint_source(src, "tpu_distalg/models/fake.py",
                                 RULES) if v.code == "TDA120"]
    assert [v.line for v in vs] == [2, 4, 5]
    assert "tune/defaults.py" in vs[0].message
    # same source in cluster/ is also scoped; elsewhere it is not
    assert [v for v in lint_source(src, "tpu_distalg/cluster/f.py",
                                   RULES) if v.code == "TDA120"]
    assert not [v for v in lint_source(src, "tpu_distalg/utils/f.py",
                                       RULES) if v.code == "TDA120"]


def test_tda120_reasoned_pin_escape():
    from tpu_distalg.analysis import RULES, lint_source

    src = ("block_rows = 1024"
           "  # tda: ignore[TDA120] -- rig-pinned: measured on vX\n")
    assert not [v for v in lint_source(
        src, "tpu_distalg/models/fake.py", RULES)
        if v.code == "TDA120"]


def test_tda120_full_tree_baseline_is_clean():
    """First full-tree adjudication (satellite 1): models/ and
    cluster/ source their geometry from the tuner tables — the
    baseline stays empty."""
    from tpu_distalg.analysis import (RULES, iter_python_files,
                                      lint_file)

    root = os.path.dirname(os.path.dirname(os.path.abspath(
        ttune.__file__)))
    hits = []
    for path in iter_python_files([os.path.join(root, "models"),
                                   os.path.join(root, "cluster")]):
        hits += [v for v in lint_file(path, RULES)
                 if v.code == "TDA120"]
    assert not hits, [f"{v.path}:{v.line}" for v in hits]


def test_geometry_knob_table_spells_the_defaults():
    """The lint's allowed values ARE the default tables — a drift
    between GEOMETRY_KNOBS and the constants it polices would let
    folklore back in through the table itself."""
    assert tdefaults.GEOMETRY_KNOBS["bucket_elems"] \
        == (tdefaults.BUCKET_ELEMS,)
    assert tdefaults.GEOMETRY_KNOBS["ps_shards"] \
        == (tdefaults.PS_SHARDS,)
    assert set(tdefaults.BLOCK_ROWS.values()) \
        == set(tdefaults.GEOMETRY_KNOBS["block_rows"])
    assert tdefaults.PS_SHARDS in tdefaults.GEOMETRY_KNOBS["n_shards"]
    for knob, allowed in tdefaults.GEOMETRY_KNOBS.items():
        assert allowed, knob
        assert all(isinstance(v, int) for v in allowed), knob


def test_comms_stats_delegate_to_schedule_stats():
    """The resolver prices with comms.schedule_stats; CommSync.stats
    must report THE SAME accounting (one formula, two callers) —
    checked here at the module level without a mesh."""
    from tpu_distalg.parallel import comms

    for sched in ("dense", "int8", "topk", "bf16"):
        st = comms.schedule_stats(sched, n_shards=4,
                                  compressible_elems=1 << 16)
        assert st["bytes_wire"] > 0 and st["rounds"] >= 1
        assert st["bytes_logical"] == 4 * (1 << 16)
    int8 = comms.schedule_stats("int8", n_shards=4,
                                compressible_elems=1 << 16,
                                bucket_elems=1 << 14)
    dense = comms.schedule_stats("dense", n_shards=4,
                                 compressible_elems=1 << 16)
    assert dense["bytes_wire"] / int8["bytes_wire"] > 3.0
