"""Online serving layer (`tpu_distalg/serve/` + `ops/pallas_topk.py`).

The contracts pinned here, per ISSUE 8's acceptance criteria:

  * the fused Pallas matmul+top-k kernel is exactly interchangeable
    with the XLA reference and with raw ``jax.lax.top_k`` — values
    descending, ties broken toward the LOWER item index (crafted-tie
    fixtures), padded geometry and fewer-than-k tails included;
  * batched replies are BITWISE-equal to unbatched predict for every
    served model (padding provably inert — partial batches run the
    same compiled program as full ones);
  * sharded-factor retrieval (model-axis item factors + sparse pair
    merge) returns the same top-k as the single-shard reference, for
    both merge schedules;
  * the micro-batcher dispatches on deadline-or-size (a lone request
    under a slow producer is never parked), sheds on a full queue with
    :class:`ServeOverloadError` instead of growing or dying, and a
    failed batch fails THAT batch's replies while the loop keeps
    serving;
  * `tda chaos --workload serve` proves bitwise-identical replies
    under ``data:gather`` dispatch faults and ``ckpt:read`` artifact
    corruption (re-read, never a demoted model).
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg import faults, serve
from tpu_distalg.faults import chaos
from tpu_distalg.ops import pallas_topk as pt
from tpu_distalg.parallel import get_mesh
from tpu_distalg.serve.batcher import (
    MicroBatcher,
    ServeClosedError,
    ServeOverloadError,
)
from tpu_distalg.serve.server import run_closed_loop
from tpu_distalg.utils import checkpoint as ckpt

K = 7


@pytest.fixture(scope="module")
def mesh_m4():
    """Model-axis mesh: 4 item-factor shards, no data parallelism."""
    return get_mesh(data=1, model=4, devices=jax.devices()[:4])


@pytest.fixture(scope="module")
def mesh_m1():
    return get_mesh(data=1, model=1, devices=jax.devices()[:1])


def _rand_qv(seed=0, b=8, d=48, n=500):
    rng = np.random.default_rng(seed)
    Q = rng.normal(size=(b, d)).astype(np.float32)
    V = rng.normal(size=(n, d)).astype(np.float32)
    return Q, V


def _fused(Q, V, off, nv, k=K, blk=128):
    return pt.fused_matmul_topk(jnp.asarray(Q), jnp.asarray(V), off, nv,
                                k=k, block_items=blk, interpret=True)


def _assert_same_topk(fv, fi, rv, ri):
    """Two programs, one rounding order each (the kernel's blocked dot,
    XLA's matmul): the indices are equal, the scores agree to a few
    float32 ulps where an order differs. Read on jax 0.9.0's CPU: 4 and
    5 ulps in the two tests that use this, 0 in the others; bound 16."""
    assert np.array_equal(fi, ri)
    fv, rv = np.asarray(fv), np.asarray(rv)
    finite = np.isfinite(rv)
    assert np.array_equal(np.isfinite(fv), finite)
    assert np.array_equal(fv[~finite], rv[~finite])
    np.testing.assert_array_max_ulp(fv[finite], rv[finite], maxulp=16)


# ------------------------------------------- fused kernel vs lax.top_k


def test_fused_topk_matches_lax_top_k():
    Q, V = _rand_qv()
    fv, fi = _fused(Q, V, 0, V.shape[0])
    rv, ri = pt.xla_matmul_topk(Q, V, 0, V.shape[0], k=K)
    lv, li = jax.lax.top_k(jnp.asarray(Q) @ jnp.asarray(V).T, K)
    assert np.array_equal(fv, rv) and np.array_equal(fi, ri)
    assert np.array_equal(rv, lv) and np.array_equal(ri, li)


def test_fused_topk_tie_break_toward_lower_index():
    """Crafted ties: the catalogue repeats every row 3x, so every score
    appears at three indices — selection must walk them ascending,
    exactly ``lax.top_k``'s order."""
    Q, V = _rand_qv(seed=1, n=40)
    Vt = np.concatenate([V[:15]] * 3, axis=0)
    fv, fi = _fused(Q, Vt, 0, Vt.shape[0], k=9)
    lv, li = jax.lax.top_k(jnp.asarray(Q) @ jnp.asarray(Vt).T, 9)
    assert np.array_equal(fv, lv)
    assert np.array_equal(fi, li)
    # the winners of one tie triple are its ascending index orbit
    row = np.asarray(fi)[0]
    vals = np.asarray(fv)[0]
    for j in range(8):
        if vals[j] == vals[j + 1]:
            assert row[j] < row[j + 1]


def test_fused_topk_offset_and_valid_mask():
    """``index_offset`` maps local rows to global ids; rows at or past
    ``n_valid`` can NEVER be selected even with the largest scores."""
    Q, V = _rand_qv(seed=2, n=200)
    V2 = V.copy()
    V2[150:] = 100.0  # poison the padded tail
    fv, fi = _fused(Q, V2, 1000, 150)
    rv, ri = pt.xla_matmul_topk(Q, V2, 1000, 150, k=K)
    _assert_same_topk(fv, fi, rv, ri)
    assert int(np.min(fi)) >= 1000
    assert int(np.max(fi)) < 1000 + 150


def test_fused_topk_fewer_than_k_valid_tail():
    Q, V = _rand_qv(seed=3, n=64)
    fv, fi = _fused(Q, V[:4], 0, 4, k=K)
    rv, ri = pt.xla_matmul_topk(Q, V[:4], 0, 4, k=K)
    _assert_same_topk(fv, fi, rv, ri)
    assert np.all(np.asarray(fv)[:, 4:] == -np.inf)
    assert np.all(np.asarray(fi)[:, 4:] == 2**31 - 1)


def test_fused_topk_odd_geometry_padding_inert():
    """B not a sublane multiple, d not a lane multiple, N not a
    block-items multiple: every internal pad must be inert."""
    Q, V = _rand_qv(seed=4, b=5, d=33, n=305)
    fv, fi = _fused(Q, V, 0, V.shape[0])
    rv, ri = pt.xla_matmul_topk(Q, V, 0, V.shape[0], k=K)
    assert np.array_equal(fv, rv) and np.array_equal(fi, ri)


def test_merge_topk_pairs_equals_global_topk():
    """Per-shard candidates through the merge == top-k over the whole
    catalogue (shard windows disjoint, ties still index-ascending)."""
    Q, V = _rand_qv(seed=5, n=400)
    S, local = 4, 100
    per = [pt.xla_matmul_topk(Q, V[s * local:(s + 1) * local],
                              s * local, local, k=K)
           for s in range(S)]
    mv, mi = pt.merge_topk_pairs(
        jnp.stack([v for v, _ in per]), jnp.stack([i for _, i in per]),
        k=K)
    rv, ri = pt.xla_matmul_topk(Q, V, 0, V.shape[0], k=K)
    assert np.array_equal(mv, rv) and np.array_equal(mi, ri)


# ------------------------- served models: batched == unbatched, padded


def _assert_batched_equals_unbatched(model, payloads, max_batch):
    batched = model.predict_batch(payloads, max_batch)
    for p, got in zip(payloads, batched):
        want = model.predict_one(p, max_batch)
        got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
        assert len(got_l) == len(want_l)
        for g, w in zip(got_l, want_l):
            assert np.array_equal(np.asarray(g), np.asarray(w))


def test_lr_batched_equals_unbatched():
    rng = np.random.default_rng(0)
    model = serve.lr_model(rng.normal(size=(31,)).astype(np.float32))
    rows = list(rng.normal(size=(5, 31)).astype(np.float32))
    _assert_batched_equals_unbatched(model, rows, max_batch=8)


def test_kmeans_batched_equals_unbatched():
    rng = np.random.default_rng(1)
    model = serve.kmeans_model(
        rng.normal(size=(6, 12)).astype(np.float32))
    pts = list(rng.normal(size=(5, 12)).astype(np.float32))
    _assert_batched_equals_unbatched(model, pts, max_batch=8)


def test_als_batched_equals_unbatched_sharded(mesh_m4):
    rng = np.random.default_rng(2)
    U = rng.normal(size=(32, 16)).astype(np.float32)
    V = rng.normal(size=(200, 16)).astype(np.float32)
    model = serve.als_model(U, V, mesh_m4, k_top=K)
    ids = [np.int32(i) for i in rng.integers(0, 32, size=5)]
    _assert_batched_equals_unbatched(model, ids, max_batch=8)


# --------------------------------------------- sharded == single-shard


@pytest.mark.parametrize("merge", ["sparse", "dense"])
def test_als_sharded_merge_equals_unsharded(merge, mesh_m4, mesh_m1):
    rng = np.random.default_rng(3)
    U = rng.normal(size=(64, 16)).astype(np.float32)
    V = rng.normal(size=(300, 16)).astype(np.float32)
    sharded = serve.als_model(U, V, mesh_m4, k_top=K, merge=merge,
                              name=f"a_{merge}")
    single = serve.als_model(U, V, mesh_m1, k_top=K, name="a_ref")
    ids = [np.int32(i) for i in rng.integers(0, 64, size=24)]
    got = sharded.predict_batch(ids, 32)
    want = single.predict_batch(ids, 32)
    for (gv, gi), (wv, wi) in zip(got, want):
        assert np.array_equal(gv, wv)
        assert np.array_equal(gi, wi)
    assert sharded.meta["n_model"] == 4
    if merge == "sparse":
        # 8k(S-1) wire bytes per request: the pair-ring accounting
        assert sharded.meta["merge_wire_bytes_per_request"] == \
            8 * K * 3


def test_als_wire_accounting_sparse_below_dense(mesh_m4):
    rng = np.random.default_rng(4)
    U = rng.normal(size=(16, 8)).astype(np.float32)
    V = rng.normal(size=(4096, 8)).astype(np.float32)
    sp = serve.als_model(U, V, mesh_m4, k_top=K, merge="sparse")
    dn = serve.als_model(U, V, mesh_m4, k_top=K, merge="dense")
    assert 0 < sp.meta["merge_wire_bytes_per_request"] \
        < dn.meta["merge_wire_bytes_per_request"]


# --------------------------------------------------------- micro-batcher


def test_deadline_dispatch_lone_request():
    """A lone request fires at the deadline — never parked waiting for
    a full batch that may not come."""
    b = MicroBatcher("t", lambda ps: [p * 2 for p in ps],
                     max_batch=64, max_delay_ms=25.0)
    try:
        t0 = time.perf_counter()
        assert b.submit(21).result(timeout=5.0) == 42
        assert time.perf_counter() - t0 < 2.0
        s = b.snapshot()
        assert (s.batches, s.replies) == (1, 1)
    finally:
        b.close()


def test_deadline_dispatch_under_slow_producer():
    """Requests arriving slower than the deadline each dispatch as
    their own partial batch — the producer's pace can't stall them."""
    b = MicroBatcher("t", lambda ps: [p for p in ps],
                     max_batch=8, max_delay_ms=10.0)
    try:
        replies = []
        for j in range(4):
            replies.append(b.submit(j))
            time.sleep(0.08)  # well past the 10 ms batch deadline
        assert [r.result(timeout=5.0) for r in replies] == [0, 1, 2, 3]
        assert b.snapshot().batches == 4  # no coalescing across waits
    finally:
        b.close()


def test_size_dispatch_coalesces_a_burst():
    b = MicroBatcher("t", lambda ps: [p for p in ps],
                     max_batch=4, max_delay_ms=2000.0)
    try:
        replies = [b.submit(j) for j in range(8)]
        assert [r.result(timeout=5.0) for r in replies] == list(range(8))
        s = b.snapshot()
        assert s.batches == 2  # two full batches, no deadline waits
        assert s.replies == 8
    finally:
        b.close()


def test_overload_sheds_and_keeps_serving():
    """A full bounded queue SHEDS (ServeOverloadError) and the server
    keeps answering once drained — degrade, not die."""
    entered, release = threading.Event(), threading.Event()

    def predict(ps):
        entered.set()
        assert release.wait(10.0)
        return [p for p in ps]

    b = MicroBatcher("t", predict, max_batch=1, max_delay_ms=1.0,
                     queue_depth=2)
    try:
        first = b.submit(0)
        assert entered.wait(5.0)  # dispatch thread is parked in predict
        queued = [b.submit(j) for j in (1, 2)]
        shed = b.submit(3)  # queue (depth 2) is full now
        assert isinstance(shed.error, ServeOverloadError)
        with pytest.raises(ServeOverloadError):
            shed.result(timeout=1.0)
        release.set()
        assert first.result(timeout=5.0) == 0
        assert [r.result(timeout=5.0) for r in queued] == [1, 2]
        assert b.submit(4).result(timeout=5.0) == 4  # still serving
        s = b.snapshot()
        assert s.shed == 1 and s.replies == 4
    finally:
        release.set()
        b.close()


def test_failed_batch_fails_replies_not_the_loop(tmp_path):
    from tpu_distalg.telemetry import events, report

    def predict(ps):
        if any(p < 0 for p in ps):
            raise ValueError("poison payload")
        return [p for p in ps]

    sink = str(tmp_path / "tele")
    events.configure(sink)
    b = MicroBatcher("t", predict, max_batch=1, max_delay_ms=1.0)
    try:
        bad = b.submit(-1)
        with pytest.raises(ValueError, match="poison"):
            bad.result(timeout=5.0)
        assert b.submit(7).result(timeout=5.0) == 7  # loop survived
        s = b.snapshot()
        assert s.failed_batches == 1 and s.failed_requests == 1
        assert s.replies == 1
    finally:
        b.close()
        events.configure(False)
    # the report-line counters agree with BatcherStats: a failed batch
    # was still a dispatched batch with dispatched requests
    c = report.summarize(report.load_events(sink))["counters"]
    assert c["serve.batches"] == s.batches == 2
    assert c["serve.requests"] == 2
    assert c["serve.failed_batches"] == 1


def test_close_fails_queued_and_rejects_new():
    b = MicroBatcher("t", lambda ps: [p for p in ps], max_batch=4,
                     max_delay_ms=1.0)
    b.close()
    reply = b.submit(1)
    assert isinstance(reply.error, ServeClosedError)
    with pytest.raises(ServeClosedError):
        reply.result(timeout=1.0)


# -------------------------------------------------- server / closed loop


def test_server_closed_loop_replies_match_unbatched(mesh_m1):
    rng = np.random.default_rng(5)
    w = rng.normal(size=(13,)).astype(np.float32)
    model = serve.lr_model(w, name="lr")
    cfg = serve.ServeConfig(max_batch=8, max_delay_ms=2.0)
    srv = serve.Server(mesh_m1, cfg)
    try:
        srv.add_model(model)
        rows = list(rng.normal(size=(40, 13)).astype(np.float32))
        results, info = run_closed_loop(srv, "lr", rows, concurrency=4)
        assert info["ok"] == len(rows) and info["failed"] == 0
        for p, got in zip(rows, results):
            assert np.array_equal(
                np.asarray(got),
                np.asarray(model.predict_one(p, cfg.max_batch)))
        s = srv.stats()
        assert s["replies"] == len(rows)
        assert s["p99_ms"] >= s["p50_ms"] >= 0
        assert s["qps"] > 0
    finally:
        srv.close()


def test_server_unknown_model_and_duplicate_rejected(mesh_m1):
    srv = serve.Server(mesh_m1)
    try:
        model = serve.lr_model(np.ones(3, np.float32), name="m")
        srv.add_model(model)
        with pytest.raises(ValueError, match="already served"):
            srv.add_model(serve.lr_model(np.ones(3, np.float32),
                                         name="m"))
        with pytest.raises(KeyError, match="no served model"):
            srv.submit("nope", np.zeros(3, np.float32))
    finally:
        srv.close()


# ------------------------------------------------------------- artifacts


def _save_tagged(tmp_path, tag: str, state, step=10):
    d = str(tmp_path / tag.replace(":", "_"))
    ckpt.save(d, {"tag": np.frombuffer(tag.encode(), dtype=np.uint8),
                  "state": [np.asarray(x) for x in state]}, step=step)
    return d


def test_load_artifact_dispatches_on_tag(tmp_path, mesh_m1):
    rng = np.random.default_rng(6)
    w = rng.normal(size=(9,)).astype(np.float32)
    lr_dir = _save_tagged(tmp_path, "lr:comm=dense", [w])
    m = serve.load_artifact(lr_dir, mesh_m1)
    assert (m.kind, m.source) == ("lr", lr_dir)
    assert np.array_equal(
        np.asarray(m.predict_one(np.zeros(9, np.float32), 4)),
        np.asarray(serve.lr_model(w).predict_one(
            np.zeros(9, np.float32), 4)))

    centers = rng.normal(size=(4, 6)).astype(np.float32)
    km = serve.load_artifact(
        _save_tagged(tmp_path, "kmeans_stream", [centers]), mesh_m1)
    assert km.kind == "kmeans" and km.meta["k"] == 4

    U = rng.normal(size=(8, 5)).astype(np.float32)
    V = rng.normal(size=(20, 5)).astype(np.float32)
    als = serve.load_artifact(
        _save_tagged(tmp_path, "als", [U, V]), mesh_m1, k_top=3)
    assert als.kind == "als"
    assert als.meta["n_items"] == 20 and als.meta["k_top"] == 3

    with pytest.raises(ValueError, match="no serving adapter"):
        serve.load_artifact(
            _save_tagged(tmp_path, "pagerank", [w]), mesh_m1)


def test_load_artifact_rejects_untagged_checkpoint(tmp_path, mesh_m1):
    d = str(tmp_path / "legacy")
    ckpt.save(d, {"w": np.ones(3, np.float32)}, step=1)
    with pytest.raises(ValueError, match="tagged format"):
        serve.load_artifact(d, mesh_m1)


def test_artifact_transient_read_corruption_rereads(tmp_path, mesh_m1):
    """A ckpt:read fault corrupts the bytes IN FLIGHT; the loader must
    re-read (the file is intact) instead of demoting the model."""
    w = np.arange(5, dtype=np.float32)
    d = _save_tagged(tmp_path, "lr", [w])
    faults.configure("seed=1;ckpt:read@0=corrupt")
    try:
        m = serve.load_artifact(d, mesh_m1)
        assert faults.active().fired == [("ckpt:read", 0, "corrupt")]
    finally:
        faults.configure(False)
    assert m.kind == "lr" and m.meta["d"] == 5


# ----------------------------------------------------------------- chaos


@pytest.mark.parametrize("plan", [
    # micro-batch dispatch faults: failed batches shed to the client's
    # retry loop, replies must still come back bitwise-identical
    "seed=8;data:gather@1=oserror;data:gather@3=oserror",
    # artifact-load corruption: transient re-read, same served model
    "seed=2;ckpt:read@0=corrupt",
], ids=["dispatch_gather", "artifact_read"])
def test_chaos_serve_degrades_and_recovers_bitwise(plan, mesh4,
                                                   tmp_path):
    res = chaos.run_chaos("serve", mesh4, plan=plan,
                          workdir=str(tmp_path))
    assert res.fired, "plan never fired — the seam is untested"
    assert res.equal, res.verdict()
    assert res.restarts_logged == 0  # degraded in-process, no crash
