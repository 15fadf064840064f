"""Stale-synchronous & elastic training (parallel/ssp.py +
parallel/membership.py).

The acceptance surface: `--sync bsp` routes through the untouched
pre-SSP programs (bitwise the golden trajectories); SSP runs under a
seeded straggler/membership plan replay BITWISE from the plan;
segmented == straight; the clock-vector gate bounds drift at the
staleness parameter; elastic membership renegotiates — in-process
epochs from `shard:leave` rules, and a checkpointed run resumed on a
DIFFERENT shard count (the subprocess test drives the real rc-75
leave → smaller-mesh resume → rejoin cycle); and SSP converges within
a band of BSP.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from tpu_distalg import faults
from tpu_distalg.models import bmuf, ssgd
from tpu_distalg.parallel import membership
from tpu_distalg.parallel import ssp as pssp
from tpu_distalg.telemetry import events


@pytest.fixture(autouse=True)
def _clean():
    yield
    faults.configure(False)
    events.configure(False)


STRAGGLE_PLAN = "seed=7;shard:straggle@p0.2=straggle:25"
FULL_PLAN = ("seed=7;shard:straggle@p0.2=straggle:25;"
             "shard:leave@p0.05=leave:2")


# ------------------------------------------------------------- SyncSpec

def test_syncspec_parse_spellings():
    assert pssp.SyncSpec.parse(None).mode == "bsp"
    assert pssp.SyncSpec.parse("bsp").mode == "bsp"
    s = pssp.SyncSpec.parse("ssp")
    assert s.is_ssp and s.staleness == pssp.DEFAULT_STALENESS
    s = pssp.SyncSpec.parse("ssp:8:0.7")
    assert (s.staleness, s.decay) == (8, 0.7)
    assert pssp.SyncSpec.parse(s) is s
    assert pssp.SyncSpec.parse(s.spec()) == s


def test_syncspec_rejects_bad_spellings():
    with pytest.raises(ValueError, match="sync mode"):
        pssp.SyncSpec.parse("asp")
    with pytest.raises(ValueError, match="only 'ssp' takes"):
        # almost certainly a typo of ssp:8 — silently dropping the
        # bound would train lock-step against the user's intent
        pssp.SyncSpec.parse("bsp:8")
    with pytest.raises(ValueError, match="staleness"):
        pssp.SyncSpec.parse("ssp:0")
    with pytest.raises(ValueError, match="decay"):
        pssp.SyncSpec.parse("ssp:4:1.5")
    with pytest.raises(ValueError, match="spelling"):
        pssp.SyncSpec.parse("ssp:4:0.5:9")


def test_window_grid_and_acc_expansion():
    assert pssp.window_grid(10, 4) == (3, 12)
    assert pssp.window_grid(8, 4) == (2, 8)
    accs = ssgd.window_accs_to_ticks([0.5, 0.7, 0.9], 4, 10)
    assert accs.shape == (10,)
    # tick t carries the last merge's acc; final tick the final merge's
    np.testing.assert_allclose(accs[:4], [0, 0, 0, 0.5])
    np.testing.assert_allclose(accs[4:8], [0.5] * 3 + [0.7])
    np.testing.assert_allclose(accs[8:], [0.7, 0.9])


def test_staleness_weights_decay_by_age():
    import jax.numpy as jnp

    w = pssp.staleness_weights(
        jnp.asarray([0, 2, 1, 0]),
        jnp.asarray([True, True, True, False]),
        jnp.asarray([True, True, False, True]), 0.5)
    np.testing.assert_allclose(np.asarray(w), [1.0, 0.25, 0.0, 0.0])


# ------------------------------------------- schedule/epoch compilation

def test_straggle_schedule_is_plan_pure_and_replayable():
    reg = faults.configure(STRAGGLE_PLAN)
    a = pssp.compile_straggle_schedule(16, 4)
    # plan-pure: a second compilation (a restarted run) is identical,
    # NOT a continuation of consumed probe counters
    b = pssp.compile_straggle_schedule(16, 4)
    np.testing.assert_array_equal(a, b)
    assert a.any() and (a == 0).any()
    assert (a[a > 0] == 25).all()
    # the live registry's seam counters were never consumed...
    assert reg.hits("shard:straggle") == 0
    # ...but the fires landed in its ledger for the chaos verdict
    assert any(p == "shard:straggle" for p, _, _ in reg.fired)
    faults.configure(False)
    assert not pssp.compile_straggle_schedule(16, 4).any()


def test_straggle_schedule_differs_by_seed():
    p7 = faults.FaultPlan.parse(STRAGGLE_PLAN)
    p8 = faults.FaultPlan.parse(STRAGGLE_PLAN.replace("seed=7",
                                                      "seed=8"))
    a = pssp.compile_straggle_schedule(32, 4, plan=p7)
    b = pssp.compile_straggle_schedule(32, 4, plan=p8)
    assert not np.array_equal(a, b)


def test_compile_epochs_hit_rule_and_generations():
    # boundary b, shard k is probe invocation b*n_shards + k: @3 is
    # (boundary 1, shard 1) — absent for windows 1..2, back at 3
    plan = faults.FaultPlan.parse("seed=1;shard:leave@3=leave:2")
    eps = membership.compile_epochs(6, 2, plan=plan)
    assert [(e.gen, e.start, e.end, e.active) for e in eps] == [
        (1, 0, 1, (True, True)),
        (2, 1, 3, (True, False)),
        (3, 3, 6, (True, True)),
    ]


def test_compile_epochs_never_quorumless():
    plan = faults.FaultPlan.parse("seed=1;shard:leave@*=leave:1")
    eps = membership.compile_epochs(3, 2, plan=plan)
    assert all(e.n_active >= 1 for e in eps)


def test_scheduling_kind_point_pairing_enforced():
    with pytest.raises(ValueError, match="shard:straggle"):
        faults.FaultRule("ckpt:write", "straggle")
    with pytest.raises(ValueError, match="scheduling kinds only"):
        faults.FaultRule("shard:leave", "oserror")


# --------------------------------------------------- BSP stays bitwise

def test_bsp_sync_spelling_routes_to_the_classic_path(mesh4,
                                                      cancer_data):
    cfg_default = ssgd.SSGDConfig(n_iterations=30)
    cfg_bsp = ssgd.SSGDConfig(n_iterations=30, sync="bsp")
    a = ssgd.train(*cancer_data, mesh4, cfg_default)
    b = ssgd.train(*cancer_data, mesh4, cfg_bsp)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    np.testing.assert_array_equal(np.asarray(a.accs),
                                  np.asarray(b.accs))


def test_bsp_straggler_arm_is_bitwise_plain_bsp(mesh4, cancer_data):
    """The bench's BSP A/B arm: interference entangled before the psum
    must not change a single bit of the trajectory — only the time."""
    import jax.numpy as jnp

    from tpu_distalg.parallel import parallelize

    X_train, y_train, X_test, y_test = cancer_data
    cfg = ssgd.SSGDConfig(n_iterations=24, eval_test=True)
    Xs = parallelize(X_train, mesh4)
    ys = parallelize(y_train, mesh4)
    from tpu_distalg.ops import logistic
    from tpu_distalg.utils import prng

    w0 = logistic.init_weights(prng.root_key(cfg.init_seed),
                               X_train.shape[1])
    X_te, y_te = jnp.asarray(X_test), jnp.asarray(y_test)
    plain_fn = ssgd.make_train_fn(mesh4, cfg, Xs.n_padded)
    w_a, accs_a = plain_fn(Xs.data, ys.data, Xs.mask, X_te, y_te, w0)
    rng = np.random.default_rng(0)
    extra = (rng.random((24, 4)) < 0.3).astype(np.int32) * 20
    strag_fn = ssgd.make_bsp_straggler_fn(mesh4, cfg, Xs.n_padded,
                                          extra)
    w_b, accs_b = strag_fn(Xs.data, ys.data, Xs.mask, X_te, y_te, w0)
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_b))
    np.testing.assert_array_equal(np.asarray(accs_a),
                                  np.asarray(accs_b))


# ------------------------------------------------- SSP determinism

def test_ssp_replay_is_bitwise_under_a_plan(mesh4, cancer_data):
    cfg = ssgd.SSGDConfig(n_iterations=32, sync="ssp:4")
    faults.configure(FULL_PLAN)
    a = ssgd.train(*cancer_data, mesh4, cfg)
    faults.configure(FULL_PLAN)
    b = ssgd.train(*cancer_data, mesh4, cfg)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    np.testing.assert_array_equal(np.asarray(a.accs),
                                  np.asarray(b.accs))


def test_ssp_segmented_equals_straight(mesh4, cancer_data, tmp_path):
    cfg = ssgd.SSGDConfig(n_iterations=32, sync="ssp:4")
    faults.configure(FULL_PLAN)
    straight = ssgd.train(*cancer_data, mesh4, cfg)
    faults.configure(FULL_PLAN)
    seg = ssgd.train(*cancer_data, mesh4, cfg,
                     checkpoint_dir=str(tmp_path / "ck"),
                     checkpoint_every=16)
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(seg.w))
    np.testing.assert_array_equal(np.asarray(straight.accs),
                                  np.asarray(seg.accs))


@pytest.mark.slow
def test_ssp_resume_continues_from_checkpoint(mesh4, cancer_data,
                                              tmp_path):
    d = str(tmp_path / "ck")
    ssgd.train(*cancer_data, mesh4,
               ssgd.SSGDConfig(n_iterations=24, sync="ssp:4"),
               checkpoint_dir=d, checkpoint_every=12)
    resumed = ssgd.train(*cancer_data, mesh4,
                         ssgd.SSGDConfig(n_iterations=48, sync="ssp:4"),
                         checkpoint_dir=d, checkpoint_every=12)
    straight = ssgd.train(*cancer_data, mesh4,
                          ssgd.SSGDConfig(n_iterations=48,
                                          sync="ssp:4"))
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(resumed.w))


def test_ssp_converges_within_band_of_bsp(mesh4, cancer_data):
    """Faults-free SSP must land in BSP's neighborhood (the bench pins
    the precise ratio on the converging synthetic task; this is the
    tier-1 smoke of the same property)."""
    bsp = ssgd.train(*cancer_data, mesh4,
                     ssgd.SSGDConfig(n_iterations=120))
    ssp = ssgd.train(*cancer_data, mesh4,
                     ssgd.SSGDConfig(n_iterations=120, sync="ssp:4"))

    def tail(res):
        a = np.asarray(res.accs)
        return float(np.mean(a[-30:]))

    assert abs(tail(bsp) - tail(ssp)) < 0.12


# -------------------------------------------------- gate & staleness

def test_ssp_gate_bounds_clock_drift(mesh4, cancer_data):
    """A shard busy at EVERY boundary keeps pending work and falls
    behind; once the drift reaches the bound the fast shards gate
    (masked no-op ticks) instead of running away — max clock spread
    stays at the staleness parameter."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_distalg.parallel import parallelize

    X_train, y_train, _, _ = cancer_data
    s, n_win, S = 4, 8, 4
    T = s * n_win
    cfg = ssgd.SSGDConfig(n_iterations=T, sync=f"ssp:{s}",
                          eval_test=False)
    Xs = parallelize(X_train, mesh4)
    ys = parallelize(y_train, mesh4)
    d = X_train.shape[1]
    fn = ssgd.make_ssp_train_fn(mesh4, cfg, Xs.n_padded, d,
                                active=(True,) * S, n_win_seg=n_win,
                                total_ticks=T)
    extra = np.zeros((n_win, s, S), np.int32)
    # shard 0 straggled at the boundary of windows 0..5: it keeps
    # pending work (no adopt, no deliver), drifts one step per window,
    # and finally delivers in window 6 — several ages stale
    extra[:6, -1, 0] = 5
    shard2 = NamedSharding(mesh4, P("data", None))
    z = jnp.zeros
    w0, clocks0, pend0, basegen0, wl0, accd0, res0 = \
        ssgd.ssp_init_state(mesh4, cfg, d)
    out = fn(Xs.data, ys.data, Xs.mask,
             z((1, d), jnp.float32), z((1,), jnp.float32),
             jnp.asarray(w0), jnp.asarray(clocks0),
             jnp.asarray(pend0), jnp.asarray(basegen0),
             jax.device_put(jnp.asarray(wl0), shard2),
             jax.device_put(jnp.asarray(accd0), shard2),
             jax.device_put(jnp.asarray(res0), shard2),
             jnp.asarray(extra), jnp.int32(0))
    clocks = np.asarray(out[1])
    gated = int(np.asarray(out[10]).sum())
    ages_max = np.asarray(out[8])
    assert clocks.max() - clocks.min() <= s
    assert gated > 0, "fast shards never gated despite sustained drift"
    # the boundary-busy shard delivers late: observed staleness > 0
    assert ages_max.max() >= 1


def test_ssp_empty_merge_is_a_noop_even_with_ef_residual(mesh4,
                                                         cancer_data):
    """Review-caught: a boundary where EVERY pending shard is busy has
    wsum == 0, but a stateful --comm schedule (topk) still flushes its
    error-feedback residual through the collective — applying that
    over the epsilon clamp would multiply it by 1e12. The merge must
    be a no-op: weights unchanged, residual carried to the next
    boundary, nothing lost."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_distalg.parallel import parallelize

    X_train, y_train, _, _ = cancer_data
    s, n_win, S = 4, 2, 4
    T = s * n_win
    cfg = ssgd.SSGDConfig(n_iterations=T, sync=f"ssp:{s}",
                          comm="topk:0.25", eval_test=False)
    Xs = parallelize(X_train, mesh4)
    ys = parallelize(y_train, mesh4)
    d = X_train.shape[1]
    fn = ssgd.make_ssp_train_fn(mesh4, cfg, Xs.n_padded, d,
                                active=(True,) * S, n_win_seg=n_win,
                                total_ticks=T)
    extra = np.zeros((n_win, s, S), np.int32)
    # window 0 delivers normally (populates the topk residual);
    # window 1's boundary is busy on EVERY shard -> wsum == 0
    extra[1, -1, :] = 5
    shard2 = NamedSharding(mesh4, P("data", None))
    z = jnp.zeros
    w0, clocks0, pend0, basegen0, wl0, accd0, res0 = \
        ssgd.ssp_init_state(mesh4, cfg, d)
    out = fn(Xs.data, ys.data, Xs.mask,
             z((1, d), jnp.float32), z((1,), jnp.float32),
             jnp.asarray(w0), jnp.asarray(clocks0),
             jnp.asarray(pend0), jnp.asarray(basegen0),
             jax.device_put(jnp.asarray(wl0), shard2),
             jax.device_put(jnp.asarray(accd0), shard2),
             jax.device_put(jnp.asarray(res0), shard2),
             jnp.asarray(extra), jnp.int32(0))
    w = np.asarray(out[0])
    res = np.asarray(out[6])
    assert np.isfinite(w).all() and np.abs(w).max() < 1e3, \
        f"residual flushed over the epsilon clamp: |w| up to " \
        f"{np.abs(w).max():.3g}"
    assert np.isfinite(res).all()


def test_ssp_n_iterations_zero_is_a_noop(mesh4, cancer_data):
    """BSP parity for the degenerate run: --sync ssp with
    n_iterations=0 must return an empty history, not crash."""
    res = ssgd.train(*cancer_data, mesh4,
                     ssgd.SSGDConfig(n_iterations=0, sync="ssp:4"))
    assert res.accs.shape == (0,)
    assert np.isfinite(np.asarray(res.w)).all()


def test_ssp_counters_and_membership_events(mesh4, cancer_data,
                                            tmp_path):
    events.configure(str(tmp_path))
    faults.configure(FULL_PLAN)
    ssgd.train(*cancer_data, mesh4,
               ssgd.SSGDConfig(n_iterations=32, sync="ssp:4"))
    faults.configure(False)
    events.configure(False)
    evts = []
    for name in sorted(os.listdir(tmp_path)):
        if name.startswith("events-"):
            with open(tmp_path / name) as f:
                evts += [json.loads(ln) for ln in f if ln.strip()]
    counters = {}
    for e in evts:
        if e.get("ev") == "counters":
            for k, v in (e.get("counters") or {}).items():
                counters[k] = counters.get(k, 0) + int(v)
    assert counters.get("ssp.merges", 0) == 8
    assert counters.get("ssp.straggle_ticks", 0) > 0
    assert counters.get("ssp.membership_epochs", 0) >= 1
    fired = [e for e in evts if e.get("ev") == "fault_injected"]
    assert any(e["point"] == "shard:straggle" for e in fired)


def test_report_renders_ssp_line(tmp_path):
    from tpu_distalg.telemetry import report

    events.configure(str(tmp_path))
    events.counter("ssp.merges", 12)
    events.gauge("ssp.max_staleness", 3)
    events.counter("ssp.straggle_ticks", 9)
    events.counter("ssp.gated_ticks", 2)
    events.counter("ssp.membership_epochs", 2)
    events.counter("ssp.stall_ms_avoided", 140)
    events.gauge("ssp.mean_staleness", 0.4)
    events.gauge("ssp.bound", 8)
    events.configure(False)
    txt = report.render(report.summarize(
        report.load_events(str(tmp_path))))
    assert "ssp: 12 merge(s) at bound 8" in txt
    assert "max 3" in txt and "2 membership epoch(s)" in txt
    assert "140 ms stall avoided" in txt


# --------------------------------------------------- elastic membership

def test_ssp_renegotiates_on_different_shard_count(mesh4, cancer_data,
                                                   tmp_path, capsys):
    import jax

    from tpu_distalg.parallel import get_mesh

    d = str(tmp_path / "ck")
    ssgd.train(*cancer_data, mesh4,
               ssgd.SSGDConfig(n_iterations=16, sync="ssp:4"),
               checkpoint_dir=d, checkpoint_every=8)
    mesh3 = get_mesh(data=3, devices=jax.devices()[:3])
    res = ssgd.train(*cancer_data, mesh3,
                     ssgd.SSGDConfig(n_iterations=32, sync="ssp:4"),
                     checkpoint_dir=d, checkpoint_every=8)
    assert res.accs.shape == (32,)
    assert "ring renegotiated: 4 -> 3" in capsys.readouterr().err
    # replaying the SAME leave/resume sequence is deterministic
    d2 = str(tmp_path / "ck2")
    ssgd.train(*cancer_data, mesh4,
               ssgd.SSGDConfig(n_iterations=16, sync="ssp:4"),
               checkpoint_dir=d2, checkpoint_every=8)
    res2 = ssgd.train(*cancer_data, mesh3,
                      ssgd.SSGDConfig(n_iterations=32, sync="ssp:4"),
                      checkpoint_dir=d2, checkpoint_every=8)
    np.testing.assert_array_equal(np.asarray(res.w), np.asarray(res2.w))


def test_ssp_checkpoint_rejects_a_different_bound(mesh4, cancer_data,
                                                  tmp_path):
    """Review-caught: windows are indexed in s-tick units and merge
    weights depend on decay, so a resume under a different --sync must
    REJECT (the spec is in the tag), never silently reinterpret the
    saved window progress."""
    d = str(tmp_path / "ck")
    ssgd.train(*cancer_data, mesh4,
               ssgd.SSGDConfig(n_iterations=16, sync="ssp:4"),
               checkpoint_dir=d, checkpoint_every=8)
    with pytest.raises(ValueError, match="workload"):
        ssgd.train(*cancer_data, mesh4,
                   ssgd.SSGDConfig(n_iterations=32, sync="ssp:8"),
                   checkpoint_dir=d, checkpoint_every=8)


def test_bsp_checkpoint_not_resumable_as_ssp(mesh4, cancer_data,
                                             tmp_path):
    """Workload tags keep a BSP checkpoint from silently continuing as
    an SSP run (different carry semantics)."""
    d = str(tmp_path / "ck")
    ssgd.train(*cancer_data, mesh4, ssgd.SSGDConfig(n_iterations=16),
               checkpoint_dir=d, checkpoint_every=8)
    with pytest.raises(ValueError, match="workload"):
        ssgd.train(*cancer_data, mesh4,
                   ssgd.SSGDConfig(n_iterations=32, sync="ssp:4"),
                   checkpoint_dir=d, checkpoint_every=8)


# ----------------------------------------------- local-update family

def test_local_sgd_family_ssp_replay_and_segmented(mesh4, cancer_data,
                                                   tmp_path):
    cfg = bmuf.BMUFConfig(n_iterations=24, sync="ssp:4")
    faults.configure(FULL_PLAN)
    a = bmuf.train(*cancer_data, mesh4, cfg)
    faults.configure(FULL_PLAN)
    b = bmuf.train(*cancer_data, mesh4, cfg,
                   checkpoint_dir=str(tmp_path / "ck"),
                   checkpoint_every=8)
    faults.configure(False)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    np.testing.assert_array_equal(np.asarray(a.ws), np.asarray(b.ws))
    np.testing.assert_array_equal(np.asarray(a.accs),
                                  np.asarray(b.accs))


def test_easgd_rejoiner_does_not_gate_the_mesh(mesh4, cancer_data,
                                               tmp_path):
    """Review-caught: EASGD never resyncs, so the in-program
    adopt-bump cannot refresh a rejoining replica's frozen clock — the
    on_epoch hook must bump it at the membership transition, or
    min_known collapses to the rejoiner and the gate serializes every
    other replica for the length of the absence. With no straggle
    rules in the plan, a healthy run must gate ZERO ticks."""
    from tpu_distalg.models import easgd

    events.configure(str(tmp_path))
    faults.configure("seed=3;shard:leave@1=leave:4")
    easgd.train(*cancer_data, mesh4,
                easgd.EASGDConfig(n_iterations=32, sync="ssp:4"))
    faults.configure(False)
    events.configure(False)
    counters = {}
    for name in sorted(os.listdir(tmp_path)):
        if name.startswith("events-"):
            with open(tmp_path / name) as f:
                for ln in f:
                    e = json.loads(ln) if ln.strip() else {}
                    if e.get("ev") == "counters":
                        for k, v in (e.get("counters") or {}).items():
                            counters[k] = counters.get(k, 0) + int(v)
    assert counters.get("ssp.membership_epochs", 0) >= 2  # left+back
    assert counters.get("ssp.gated_ticks", 0) == 0


@pytest.mark.slow
def test_local_sgd_ssp_converges_within_band(mesh4, cancer_data):
    from tpu_distalg.models import ma

    bsp = ma.train(*cancer_data, mesh4, ma.MAConfig(n_iterations=80))
    ssp = ma.train(*cancer_data, mesh4,
                   ma.MAConfig(n_iterations=80, sync="ssp:4"))

    def tail(res):
        a = np.asarray(res.accs)
        return float(np.mean(a[-20:]))

    assert abs(tail(bsp) - tail(ssp)) < 0.15


# --------------------------------------------------- rejection guards

def test_ssp_rejects_megakernel_samplers(mesh4, cancer_data):
    # PR 9's fused_gather rejection is LIFTED (the fused-SSP tests
    # below); the megakernel (no per-window collective inside a
    # launch) stays BSP, as does the local_sgd family's fused path
    with pytest.raises(ValueError, match="fused_train"):
        ssgd.train(*cancer_data, mesh4,
                   ssgd.SSGDConfig(n_iterations=8, sync="ssp:4",
                                   sampler="fused_train"))
    with pytest.raises(ValueError, match="bernoulli"):
        bmuf.train(*cancer_data, mesh4,
                   bmuf.BMUFConfig(n_iterations=8, sync="ssp:4",
                                   sampler="fused_gather"))


# ------------------------------------------- fused-kernel sampler SSP

def _fused_task(n=4096, test=512):
    from tpu_distalg.utils import datasets

    X, y = datasets.synthetic_two_class(n + test, 30, seed=0)
    X = datasets.add_bias_column(X)
    return X[:n], y[:n], X[n:], y[n:]


FUSED_KW = dict(sampler="fused_gather", gather_block_rows=128,
                eval_every=1)


@pytest.mark.parametrize("sampler", ["fused_gather", "bernoulli"])
def test_ssp_s1_bsp_parity(mesh1, sampler):
    """The s=1 parity pin: one shard, one-tick windows, decay 1 — the
    SSP window algebra degenerates to the BSP update. The ACCURACY
    trajectory is bitwise the BSP trainer's. The weights are not:
    SSP must MATERIALIZE the shipped delta while XLA contracts BSP's
    subtract-of-product into a single-rounding FMA, so a step may
    round once more. That rounding is of the update, so it is an ulp
    of the vector's scale, not of each coordinate: read on jax 0.9.0's
    CPU over 24 windows, both samplers differ by 5.96e-8 = 1 ulp of
    max|w| (0.83) on their worst coordinate, which is 24
    (fused_gather) and 60 (bernoulli) ulps of that small coordinate
    itself. Bound: 4 ulps of max|w|."""
    task = _fused_task()
    kw = FUSED_KW if sampler == "fused_gather" else dict(eval_every=1)
    cfg = dict(n_iterations=24, **kw)
    bsp = ssgd.train(*task, mesh1, ssgd.SSGDConfig(**cfg, sync="bsp"))
    s1 = ssgd.train(*task, mesh1,
                    ssgd.SSGDConfig(**cfg, sync="ssp:1:1.0"))
    assert np.asarray(bsp.accs).tobytes() == \
        np.asarray(s1.accs).tobytes()
    a, b = np.asarray(bsp.w), np.asarray(s1.w)
    assert np.abs(a - b).max() <= 4 * np.spacing(np.abs(a).max())


def test_ssp_fused_gather_replays_bitwise_under_straggle_plan(mesh4):
    task = _fused_task()
    faults.configure(STRAGGLE_PLAN)
    cfg = ssgd.SSGDConfig(n_iterations=48, sync="ssp:4", **FUSED_KW)
    a = ssgd.train(*task, mesh4, cfg)
    faults.configure(STRAGGLE_PLAN)
    b = ssgd.train(*task, mesh4, cfg)
    assert np.asarray(a.w).tobytes() == np.asarray(b.w).tobytes()
    assert np.asarray(a.accs).tobytes() == \
        np.asarray(b.accs).tobytes()


def test_ssp_fused_gather_converges_and_resumes_bitwise(mesh4,
                                                        tmp_path):
    task = _fused_task()
    cfg = ssgd.SSGDConfig(n_iterations=240, sync="ssp:4", **FUSED_KW)
    straight = ssgd.train(*task, mesh4, cfg)
    seg = ssgd.train(*task, mesh4, cfg,
                     checkpoint_dir=str(tmp_path),
                     checkpoint_every=80)
    assert np.asarray(straight.w).tobytes() == \
        np.asarray(seg.w).tobytes()
    bsp = ssgd.train(
        *task, mesh4,
        ssgd.SSGDConfig(n_iterations=240, **FUSED_KW))
    assert abs(straight.final_acc - bsp.final_acc) < 0.1
    # a resume under the BERNOULLI ssp tag must reject: the augmented
    # weight layout is not the XLA path's
    with pytest.raises(ValueError, match="fresh directory"):
        ssgd.train(*task, mesh4,
                   ssgd.SSGDConfig(n_iterations=240, sync="ssp:4"),
                   checkpoint_dir=str(tmp_path),
                   checkpoint_every=80)


def test_cli_sync_flag_threads_through(cancer_data):
    from tpu_distalg import cli

    rc = cli.main(["ssgd", "--n-slices", "4", "--n-iterations", "16",
                   "--sync", "ssp:4", "--quiet"])
    assert rc == 0


# -------------------------------- the subprocess leave/rejoin cycle

def test_subprocess_elastic_leave_and_rejoin(tmp_path):
    """PR 3-style acceptance: a 4-shard SSP run is PREEMPTED (SIGTERM →
    rc 75, boundary checkpoint, no restart-budget burn), resumed at 3
    shards — the ring renegotiates instead of rejecting — preempted
    again, and finally resumed at 4 shards (the shard rejoins) to
    completion."""
    import signal
    import subprocess
    import sys
    import time

    from tpu_distalg.utils import checkpoint as ckpt

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               TDA_TELEMETRY_DIR="", TDA_FAULT_PLAN="")
    d = str(tmp_path / "ck")

    def cmd(n_slices, plan=None):
        c = [sys.executable, "-m", "tpu_distalg.cli", "ssgd",
             "--n-slices", str(n_slices), "--n-iterations", "200",
             "--sync", "ssp:4", "--checkpoint-dir", d,
             "--checkpoint-every", "16", "--quiet"]
        return c + (["--fault-plan", plan] if plan else [])

    def preempt_once(n_slices):
        # wait for NEW progress past whatever an earlier leg left on
        # disk, so the signal never lands during interpreter startup
        start_step = ckpt.latest_step(d) or 0
        p = subprocess.Popen(
            cmd(n_slices, "seed=1;segment:run@*=hang:0.2"), env=env,
            cwd=repo, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        deadline = time.time() + 240
        while time.time() < deadline:
            if (ckpt.latest_step(d) or 0) >= start_step + 8:
                break
            if p.poll() is not None:
                break
            time.sleep(0.02)
        assert p.poll() is None, \
            f"run finished before SIGTERM landed: {p.communicate()}"
        p.send_signal(signal.SIGTERM)
        out, err = p.communicate(timeout=240)
        assert p.returncode == faults.PREEMPTED_RC, (p.returncode,
                                                     out, err)
        step = ckpt.latest_step(d)
        assert step is not None and 0 < step < 50  # window units
        return err

    preempt_once(4)                       # leave: the 4-shard run dies
    err = preempt_once(3)                 # resumed smaller, preempted
    assert "ring renegotiated: 4 -> 3" in err
    r = subprocess.run(cmd(4), env=env, cwd=repo, capture_output=True,
                       text=True, timeout=400)   # rejoin, complete
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    assert "ring renegotiated: 3 -> 4" in r.stderr
    payload, step = ckpt.restore(d)
    assert step == 50  # 200 ticks / 4-tick windows
