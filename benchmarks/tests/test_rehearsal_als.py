"""Rehearsal of the sparse ALS family on the CPU, as
``test_rehearsal_hashed.py`` rehearses the hashed rows: a tiny cell added
to a temporary copy of the benchmark (new files, new entries, nothing
edited) and run end to end through ``run.run_cell``; the control (the
reference with bfloat16 factors in its Gramians), which has to come out
as not correct; an iteration that hands its factors back, a half that
drops an owner's ratings and a solve that forgets the ridge, which have
to report ``correct`` false; a program without the sparse loader, which
is refused at once; the real cell's lists, work functions and readers."""

import contextlib
import io
import json
import os
import shutil

import numpy as np
import pytest

import helpers
import run as bench
from harness import manifest as mf

CELLS = mf.load_json(os.path.join(helpers.TESTS, "data", "cells_als.json"))
REAL = "als100_253m_sweep1"
TINY = "als_tiny"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_als"))
    bench_dir = os.path.join(tmp, "benchmarks")
    shutil.copytree(helpers.BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = mf.load_json(os.path.join(helpers.ROOT, "BENCHMARK.json"))

    def add(rel: str, obj) -> None:
        path = os.path.join(bench_dir, rel)
        assert not os.path.exists(path), \
            f"{rel}: a new cell may edit no file"
        with open(path, "w") as f:
            json.dump(obj, f)

    for name, cfg in CELLS["configs"].items():
        add(f"configs/{name}.json", cfg)
        manifest["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmarks/configs/{name}.json"})
    for kind in ("traffic", "limits"):
        for name, obj in CELLS[kind].items():
            add(f"{kind}/{name}.json", obj)
    manifest["workloads"] += CELLS["workloads"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for cell, like in CELLS["like"].items():
            if like in m.get("workloads", []):
                m["workloads"] = m["workloads"] + [cell]
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return {"manifest_path": path, "bench_dir": bench_dir,
            "out_dir": os.path.join(tmp, "out"), "require_tpu": False}


def _run(copy, seed=2**31 + 11, seconds=0.2, **kw):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc, res = bench.run_cell(TINY, seed, seconds, False, **copy, **kw)
    return rc, res, out.getvalue()


def _line(log, word):
    return [ln for ln in log.splitlines() if word in ln][0]


CHECKS = ("window_compiles",
          "factor_rel_err.x.call1", "factor_rel_err.theta.call1",
          "factor_rel_err.x.call2", "factor_rel_err.theta.call2",
          "visited_total_err", "heldout_rmse_rise")


def test_family_rehearsal_and_its_control(copy):
    rc, res, log = _run(copy, control=True)
    assert rc == 0
    json.dumps(res)
    assert set(res["metrics"]) == {"setup_s", "rows_per_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] is True, log
    assert "[als] layout ratings ratings 6000 users 120 items 90 k 6 " \
        "in 128 lanes" in log
    for name in CHECKS:
        assert f"[check] {name} = " in log, name
    assert "12000 rows a call" in log
    # the control, and a state left unchanged, stand outside the limits
    limits = CELLS["limits"][TINY]
    for ln in log.splitlines():
        if ln.startswith("[control] factor_rel_err"):
            assert float(ln.split("= ")[1]) > limits["factor_rel_err"], ln
        if ln.startswith("[control] heldout_rmse_rise"):
            assert float(ln.split("= ")[1]) > limits["heldout_rmse_rise"]
    assert log.count("[control] factor_rel_err") == 4


def test_same_seed_same_inputs(copy):
    a, b, c = (_run(copy, seed=s)[2] for s in (5, 5, 6))
    for word in ("seeds {", "factor_rel_err.theta.call2", "[als] call 2"):
        assert _line(a, word) == _line(b, word) != _line(c, word)


def test_an_iteration_that_returns_its_factors_unchanged(copy, monkeypatch):
    from tpu_distalg.models import als

    real = als.make_fit_fn

    def broken(mesh, config, meta=None):
        fn = real(mesh, config, meta)

        def unchanged(*args):
            X, Theta = args[-2] + 0, args[-1] + 0
            _, _, errs, seen = fn(*args)
            return X, Theta, errs, seen

        return unchanged

    monkeypatch.setattr(als, "make_fit_fn", broken)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] factor_rel_err.x.call1")
    assert "FAILED" in _line(log, "[check] heldout_rmse_rise")


def test_a_half_that_drops_ratings(copy, monkeypatch):
    """Dropped ratings are a different result: a half-sweep that leaves
    out the last slot of every segment is refused, by the owners'
    factors and by the count."""
    from tpu_distalg.ops import als_sparse

    real = als_sparse.block_gramians

    def fewer(other, idx_b, val_b, K, geom, zero_row):
        return real(other, idx_b.at[:, -1].set(zero_row),
                    val_b.at[:, -1].set(0.0), K, geom, zero_row)

    monkeypatch.setattr(als_sparse, "block_gramians", fewer)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] factor_rel_err.x.call1")
    assert "FAILED" in _line(log, "[check] visited_total_err")


def test_a_solve_without_the_weighted_ridge(copy, monkeypatch):
    from tpu_distalg.ops import als_sparse

    real = als_sparse.solve_batch

    def flat(Ap, lam, geom):
        return real(Ap, lam * 0.5, geom)

    monkeypatch.setattr(als_sparse, "solve_batch", flat)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] factor_rel_err.theta.call2")
    assert "ok" in _line(log, "[check] visited_total_err")


def test_a_program_without_the_loader_is_refused_at_once(copy, monkeypatch):
    from tpu_distalg.models import als

    monkeypatch.delattr(als, "build_ratings_table")
    with pytest.raises(RuntimeError, match="no loader of a ratings list"):
        _run(copy)


def test_a_table_of_another_geometry_is_refused(copy, monkeypatch):
    from tpu_distalg.models import als

    real = als.build_ratings_table

    def other(*args, **kw):
        kw["geometry"] = dict(kw["geometry"], batch=48)
        return real(*args, **kw)

    monkeypatch.setattr(als, "build_ratings_table", other)
    with pytest.raises(RuntimeError, match="not the one the configuration"):
        _run(copy)


def test_the_real_cell_reports_what_it_lists_and_the_new_metrics():
    """The seven lists the cell joined and the twelve new metrics, none
    of another family's; the work functions count needed work only; a
    reader finds nothing without a trace."""
    from families import als_sparse as fam
    from harness import bytes_als, flops_als

    real = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    names = {m["name"] for m in real.per_layer}
    new = {"data_build_s.als", "sweep_ms.als", "gather_ms_per_sweep.als",
           "gram_ms_per_sweep.als", "solve_ms_per_sweep.als",
           "als_gram_mxu_roofline", "als_gather_roofline",
           "scoped_busy_pct.als", "device_idle_pct.als", "hbm_peak_gb.als",
           "dispatch_gap_ms.als", "median_call_rows_per_s.als"}
    assert names == new | {"compile_s", "cache_misses", "trace_s",
                           "lower_s", "cache_load_s", "jit_traces"}
    assert {m["name"] for m in real.end_to_end} == {"setup_s",
                                                    "rows_per_s"}
    assert real.chips == 1 and real.config["family"] == "als_sparse"
    assert real.entry["traffic"] == "sweep1"
    manifest = mf.load_json(os.path.join(helpers.ROOT, "BENCHMARK.json"))
    assert len(manifest["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    cfg = [c for c in manifest["configs"]
           if c["name"] == "als-yahoomusic-f100"][0]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200

    sh = fam.shapes(real.config, real.traffic)
    assert flops_als.iteration_flops_needed(sh) == \
        2 * 252800275 * 2 * 100 * 100
    assert bytes_als.iteration_bytes_needed(sh) == 2 * 252800275 * 400
    with pytest.raises(ValueError, match="B a rating"):
        bytes_als.iteration_bytes_needed(dict(sh, row_bytes_needed=512))

    class Ctx:
        reduced = None
        shapes = sh
        peaks = {"hbm_bytes_per_sec": 819e9, "bf16_flops_per_sec": 197e12}
        counters = {}
        readings_s = []
        memory_peak_bytes = 0

        @staticmethod
        def span_seconds(name):
            return None

    for name in new:
        assert real.reader(name).read(Ctx()) is None, name


def test_the_real_cells_pack_from_its_files():
    """The sizes every seed gets: both sides' blocks, the slots held,
    the bytes resident, from the configuration alone (no device)."""
    from families import als_sparse as fam
    from tpu_distalg.models import als

    real = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    c = real.config
    meta = als.plan_ratings(c["n_ratings"], c["n_users"], c["n_items"],
                            c["k"], 1, **fam.loader_args(c))
    fam.check_meta(c, meta)
    for plan, n in ((meta["user"], c["n_users"]),
                    (meta["item"], c["n_items"])):
        assert plan.degrees.sum() == c["n_ratings"]
        assert plan.degrees.min() == 20 and len(plan.degrees) == n
    assert meta["blocks"] == (1575, 1516)
    assert 1.20 < meta["padding_share"] < 1.21
    resident = meta["ratings_bytes"] + meta["factor_bytes"]
    assert 5.6e9 < resident < 5.8e9           # 36% of a chip's 16 GB
    assert np.median(meta["user"].degrees) < 50 < meta["user"].degrees.mean()
