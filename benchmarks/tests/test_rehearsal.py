"""Rehearsals on the CPU at sizes a test run can hold: each family end
to end through ``run.run_cell`` from test-only cells; the real cells'
refusal to run without a TPU; the control (the reference in bfloat16
in the program's place), which has to come out as not correct; and a
run with the timed path broken underneath, which has to report
``correct`` false."""

import contextlib
import io
import json
import os

import numpy as np
import pytest

import helpers
import run as bench
from harness import manifest as mf

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench"))
    path, bench_dir = helpers.copy_with_test_cells(tmp)
    return {"manifest_path": path, "bench_dir": bench_dir,
            "out_dir": os.path.join(tmp, "out"), "require_tpu": False}


def _run(cell, copy, seed=2**31 + 11, seconds=0.3):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc, res = bench.run_cell(cell, seed, seconds, False, **copy)
    return rc, res, out.getvalue()


@pytest.mark.parametrize("cell,rate,devices", [
    ("lr_tiny", "rows_per_s", 1),
    ("lr_tiny_dp4", "rows_per_s", 4),
    ("pagerank_tiny", "edges_per_s", 1)])
def test_family_rehearsal(cell, rate, devices, copy):
    rc, res, log = _run(cell, copy)
    assert rc == 0 and set(res) == KEYS
    json.dumps(res)
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == devices
    assert set(res["metrics"]) == {"setup_s", rate}
    assert res["metrics"][rate]["value"] > 0
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] is True, log
    assert "[check]" in log and "limit" in log and "[window]" in log
    if cell == "pagerank_tiny":
        assert "[pagerank] path spmv" in log


def test_same_seed_same_inputs(copy):
    a = _run("lr_tiny", copy, seed=5)[2]
    b = _run("lr_tiny", copy, seed=5)[2]
    c = _run("lr_tiny", copy, seed=6)[2]

    def first_err(log):
        return [ln for ln in log.splitlines() if "w_rel_err.call1" in ln][0]

    assert first_err(a) == first_err(b) != first_err(c)


@pytest.mark.parametrize("cell", [
    w["name"] for w in mf.load_json(
        os.path.join(helpers.ROOT, "BENCHMARK.json"))["workloads"]])
def test_real_cells_refuse_without_a_tpu(cell, capsys):
    rc = bench.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                     "--trace", "0"])
    got = capsys.readouterr()
    assert rc == bench.RC_NO_CHIP and got.out == ""
    assert "TPU" in got.err


def test_ssgd_control_is_not_correct():
    """bfloat16 weights, products and sums in the reference's place
    land outside the test cell's limit; float32 lands inside."""
    import jax
    import jax.numpy as jnp

    from reference import ssgd_ref

    cells = mf.load_json(os.path.join(helpers.TESTS, "data", "cells.json"))
    c, t = cells["configs"]["lr-tiny"], cells["traffic"]["tiny"]
    limit = cells["limits"]["lr_tiny"]["w_rel_err"]
    for seed in (3, 4, 5):
        ref = ssgd_ref.Reference(
            n_rows=c["n_rows"], n_features=c["n_features"], n_shards=1,
            block_rows=c["gather_block_rows"], pack=c["fused_pack"],
            fraction=t["mini_batch_fraction"], eta=c["eta"],
            separation=c["separation"], data_seed=seed, init_seed=seed + 1,
            sample_seed=seed + 2, devices=jax.devices()[:1])
        ref.build()
        w0 = np.asarray(ssgd_ref.init_weights(seed + 1, ref.d))
        good = ref.follow(2, 20)
        low = ref.follow(2, 20, dtype=jnp.bfloat16)
        assert ssgd_ref.rel_err(good[-1], good[-1], w0) == 0
        assert ssgd_ref.rel_err(low[-1], good[-1], w0) > limit


def test_pagerank_control_is_not_correct():
    import jax.numpy as jnp

    from harness import rmat
    from reference import pagerank_ref

    cells = mf.load_json(os.path.join(helpers.TESTS, "data", "cells.json"))
    limit = cells["limits"]["pagerank_tiny"]["rank_l1_err"]
    for seed in (3, 4, 5):
        edges = rmat.edges(11, 16, (0.57, 0.19, 0.19, 0.05), seed)
        good, _ = pagerank_ref.ranks(edges, 1 << 11, 0.15, 3)
        low, _ = pagerank_ref.ranks(edges, 1 << 11, 0.15, 3, jnp.bfloat16)
        assert abs(good.sum() - 1) < 1e-5
        assert pagerank_ref.l1_err(low, good) > limit


def test_a_step_that_returns_its_state_unchanged(copy, monkeypatch):
    """The harness's look for a chip skipped, the rest of a run driven,
    with the program's segment function replaced by one that hands its
    weights back: ``correct`` comes out false."""
    from tpu_distalg.models import ssgd

    real = ssgd.make_train_fn_fused

    def broken(mesh, config, meta):
        fn = real(mesh, config, meta)

        def unchanged(X2, y, valid, X_test, y_test, w, t0=0, acc0=0.0):
            _, accs = fn(X2, y, valid, X_test, y_test, w, t0=t0)
            return w, accs

        return unchanged

    monkeypatch.setattr(ssgd, "make_train_fn_fused", broken)
    rc, res, log = _run("lr_tiny", copy)
    assert rc == 0 and res["correct"] is False
    assert "w_rel_err.call1 = 1 " in log and "FAILED" in log


def test_a_sweep_that_returns_the_start_vector(copy, monkeypatch):
    import jax.numpy as jnp

    from tpu_distalg.models import pagerank

    def broken(mesh, config, n_vertices, plan=None, spmv=None):
        def run(*args, **kw):
            r = jnp.full((n_vertices,), 1.0 / n_vertices, jnp.float32)
            return r, jnp.ones((n_vertices,), jnp.float32)

        return run

    monkeypatch.setattr(pagerank, "make_run_fn", broken)
    rc, res, log = _run("pagerank_tiny", copy)
    assert rc == 0 and res["correct"] is False
