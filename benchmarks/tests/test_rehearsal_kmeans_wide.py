"""Rehearsal of the wide k-means family on the CPU, as
``test_rehearsal_kmeans.py`` rehearses the narrow one: a tiny cell added
to a temporary copy of the benchmark (new files, new entries, nothing
edited) and run end to end through ``run.run_cell``; the control (the
reference in bfloat16), which has to come out as not correct; an
iteration that hands its centres back, and a pass that drops points,
which have to report ``correct`` false; a program that lays the points
out otherwise, which is refused before any data is drawn."""

import contextlib
import io
import json
import os
import shutil

import pytest

import helpers
import run as bench
from harness import manifest as mf

CELLS = mf.load_json(os.path.join(helpers.TESTS, "data",
                                  "cells_kmeans_wide.json"))
REAL = "kmeans784_2m_k4096"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_kmeans_wide"))
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


def _run(copy, seed=2**31 + 11, seconds=0.3, **kw):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc, res = bench.run_cell("kmeans_wide_tiny", seed, seconds, False,
                                 **copy, **kw)
    return rc, res, out.getvalue()


def _line(log, word):
    return [ln for ln in log.splitlines() if word in ln][0]


def test_family_rehearsal(copy):
    rc, res, log = _run(copy)
    assert rc == 0
    json.dumps(res)
    assert set(res["metrics"]) == {"setup_s", "rows_per_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] is True, log
    assert "[kmeans] layout wide blocks (24, 64, 512)" in log
    assert "distances mxu6" in log
    for name in ("window_compiles", "centers_rel_err.call1",
                 "centers_rel_err.call2", "count_total_err",
                 "inertia_rise"):
        assert f"[check] {name} = " in log, name
    assert "[check] count_total_err = 0 " in log


def test_same_seed_same_inputs(copy):
    a, b, c = (_run(copy, seed=s)[2] for s in (5, 5, 6))
    # (the held-out line also names how far the window got: its own time)
    for word in ("[kmeans] layout", "centers_rel_err.call2"):
        assert _line(a, word) == _line(b, word) != _line(c, word)


def test_the_real_cell_reports_what_it_lists_and_the_new_share():
    """The manifest's twelve lists and the new roofline share; the share
    is 2 x rows a chip x k x dim over the two scopes' time over the
    bfloat16 peak, nothing without a trace, and cannot pass 16.7 while
    the distances take six passes."""
    from harness import flops_kmeans

    real = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    narrow = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"),
                     "kmeans20_100m_k10")
    names = [m["name"] for m in real.per_layer]
    assert "kmeans_mxu_roofline" in names
    assert "kmeans_pass_roofline" not in names
    assert set(names) - {"kmeans_mxu_roofline"} == \
        {m["name"] for m in narrow.per_layer} - {"kmeans_pass_roofline"}
    assert [m["name"] for m in real.end_to_end] == ["setup_s", "rows_per_s"]
    sh = {"n_rows": 2_025_000, "dim": 784, "k": 4096, "n_shards": 1}
    need = flops_kmeans.lloyd_iteration_flops_needed(sh)
    assert need == 2 * 2_025_000 * 4096 * 784 == 13_005_619_200_000
    assert flops_kmeans.lloyd_iteration_flops_needed(
        dict(sh, n_rows=8_100_000, n_shards=4)) == need
    ctx = bench.Context(real, 1, "/nonexistent")
    ctx.shapes = sh
    ctx.peaks = mf.peaks("TPU v5 lite")
    reader = real.reader("kmeans_mxu_roofline")
    assert reader.read(ctx) is None          # no trace: nothing, no raise
    six_passes = need * 6 / ctx.peaks["bf16_flops_per_sec"]
    assert need / six_passes / ctx.peaks["bf16_flops_per_sec"] * 100 \
        == pytest.approx(100 / 6)


def test_the_real_configuration_states_the_programs_layout():
    """The configuration's layout fields are the program's at the
    published widths, and its bytes are 4 x 784 a point with no padding
    held."""
    from families import kmeans_wide as fam

    c = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    geom, config = fam.program_parts(c.config, c.traffic)
    assert (geom.dim_held, geom.block_points, geom.k_padded) == \
        (784, 512, 4096)
    sh = fam.shapes(c.config, c.traffic)
    assert sh["resident_bytes"] == 3956 * 512 * 3136
    assert sh["steps_per_call"] == config.n_iterations == 1
    assert c.config["n_rows"] * 4 == c.config["n_rows_published"]


def test_wide_control_is_not_correct():
    """bfloat16 rows, centres and distance arithmetic in the
    reference's place put some cluster's sum off by over twice the test
    cell's limit (a point's worth is about half a spread; the limit
    allows two); float32 lands on itself."""
    import jax
    import jax.numpy as jnp

    from reference import kmeans_wide_ref

    c = CELLS["configs"]["kmeans-wide-tiny"]
    limit = CELLS["limits"]["kmeans_wide_tiny"]["centers_rel_err"]
    for seed in (3, 4):
        ref = kmeans_wide_ref.Reference(
            n_rows=c["n_rows"], dim=c["dim"], k=c["k"],
            clusters=c["generating_clusters"], spread=c["spread"],
            data_seed=seed, init_seed=seed + 1,
            device=jax.devices()[0])
        ref.build()
        good, counts = ref.follow(2, 1)
        per_call = ref.call_counts
        low, _ = ref.follow(2, 1, dtype=jnp.bfloat16)
        assert int(counts.sum()) == c["n_rows"]
        assert kmeans_wide_ref.sums_err(
            good[-1], good[-1], per_call[-1], c["spread"]) == 0
        assert kmeans_wide_ref.sums_err(
            low[-1], good[-1], per_call[-1], c["spread"]) > 2 * limit
        ref.free()


def test_sums_err_is_a_points_worth_whatever_the_cluster():
    """One point that changes sides moves a centre of a cluster of m
    by its distance over m: the largest coordinate difference swings
    with m, the difference times the count does not."""
    import numpy as np

    from reference import kmeans_ref, kmeans_wide_ref

    ref = np.zeros((3, 4))
    counts = np.array([2, 50, 1000])
    step = np.array([4.0, 0.0, 0.0, 0.0])          # the point's distance
    for j, m in enumerate(counts):
        got = ref.copy()
        got[j] += step / m
        assert kmeans_wide_ref.sums_err(got, ref, counts, 8.0) == \
            pytest.approx(0.5)
        assert kmeans_ref.centers_err(got, ref, 8.0) == \
            pytest.approx(0.5 / m)
    assert np.isnan(kmeans_wide_ref.sums_err(ref[:2], ref, counts, 8.0))
    bad = ref.copy()
    bad[1, 1] = np.inf
    assert np.isnan(kmeans_wide_ref.sums_err(bad, ref, counts, 8.0))


def test_wide_reference_is_the_plain_one_where_both_go():
    """Blocked over rows, a centre at a time, with its own table: the
    centres ``kmeans_ref.Reference`` gives, to float32 summation
    order."""
    import jax
    import numpy as np

    from reference import kmeans_ref, kmeans_wide_ref

    kw = dict(n_rows=3000, dim=49, k=96, clusters=10, spread=8.0,
              data_seed=5, init_seed=6, device=jax.devices()[0])
    plain = kmeans_ref.Reference(block_rows=512, **kw)
    wide = kmeans_wide_ref.Reference(block_rows=256, **kw)
    plain.build(), wide.build()
    (a, na), (b, nb) = plain.follow(2, 2), wide.follow(2, 2)
    assert np.array_equal(na, nb)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=5e-5)


def _broken(monkeypatch, wrap):
    from tpu_distalg.models import kmeans

    real = kmeans.make_fit_seg_fn
    monkeypatch.setattr(
        kmeans, "make_fit_seg_fn",
        lambda mesh, config, seg, lanes=None: wrap(
            real(mesh, config, seg, lanes)))


def test_an_iteration_that_returns_its_centres_unchanged(copy, monkeypatch):
    def wrap(fn):
        def unchanged(points, valid, centers, shift, n_run):
            _, shift, n_run, counts = fn(points, valid, centers, shift,
                                         n_run)
            return centers, shift, n_run, counts
        return unchanged

    _broken(monkeypatch, wrap)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "centers_rel_err.call1")
    assert "FAILED" in _line(log, "inertia_rise")
    assert "FAILED" not in _line(log, "count_total_err")


def test_a_pass_that_drops_points_fails_the_count(copy, monkeypatch):
    _broken(monkeypatch, lambda fn: lambda points, valid, *rest: fn(
        points, valid - 3, *rest))
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "[check] count_total_err = 3 " in log


@pytest.mark.parametrize("field, value", [
    ("block_points", 1024), ("point_bytes", 196), ("layout", "lanes"),
    ("dist_form", "mxu3")])
def test_a_layout_the_file_does_not_state_is_refused(field, value):
    from families import kmeans_wide as fam

    c = dict(CELLS["configs"]["kmeans-wide-tiny"], **{field: value})
    with pytest.raises(RuntimeError, match="is not the one"):
        fam.program_parts(c, CELLS["traffic"]["lloyd1x"])


def test_a_program_without_the_wide_pass_fails_at_once(monkeypatch):
    """What the cell's parent does: no geometry to ask for, so the
    adapter raises before any data is drawn."""
    from families import kmeans_wide as fam
    from tpu_distalg.models import kmeans

    monkeypatch.delattr(kmeans, "scale_geometry")
    with pytest.raises(RuntimeError, match="no wide k-means layout"):
        fam.program_parts(CELLS["configs"]["kmeans-wide-tiny"],
                          CELLS["traffic"]["lloyd1x"])
