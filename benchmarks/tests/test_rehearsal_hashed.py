"""Rehearsal of the hashed-row SSGD family on the CPU, as
``test_rehearsal_kmeans_wide.py`` rehearses the wide k-means: a tiny
cell added to a temporary copy of the benchmark (new files, new entries,
nothing edited) and run end to end through ``run.run_cell`` with both
Mosaic passes interpreted; the control (the reference with its weights,
gathered weights and per-slot sums in bfloat16), which has to come out
as not correct; a step that hands its state back, and a scatter that
drops a field, which have to report ``correct`` false; a loader that
holds less than the rows need, which is refused."""

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

CELLS = mf.load_json(os.path.join(helpers.TESTS, "data",
                                  "cells_hashed.json"))
REAL = "lrhash39_46m_frac01"
TINY = "lrhash_tiny"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_hashed"))
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
        rc, res = bench.run_cell(TINY, seed, seconds, False, **copy, **kw)
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
    assert "[ssgd] row format hashed nnz 9 hash_bits 12 passes vmem" in log
    assert "table (79, 16, 256) int32" in log
    for name in ("window_compiles", "w_rel_err.call1", "w_rel_err.call2",
                 "heldout_logloss_rise"):
        assert f"[check] {name} = " in log, name


def test_same_seed_same_inputs(copy):
    a, b, c = (_run(copy, seed=s)[2] for s in (5, 5, 6))
    for word in ("seeds {", "w_rel_err.call2"):
        assert _line(a, word) == _line(b, word) != _line(c, word)


def test_the_real_cell_reports_what_it_lists_and_the_new_metrics():
    """The eleven lists the cell joined and the three new metrics; not
    the dense kernel's share or time, not the dp4 metrics. The share is
    sampled rows x 157 B over the two scopes' time over the peak, and
    nothing without a trace."""
    from harness import bytes_hashed

    real = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    dense = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"),
                    "lr30_100m_smallbatch")
    names = {m["name"] for m in real.per_layer}
    new = {"gather_ms_per_step.lr", "scatter_ms_per_step.lr",
           "hashed_pass_roofline"}
    assert new <= names
    assert names - new == {m["name"] for m in dense.per_layer} - {
        "ssgd_kernel_roofline", "kernel_ms_per_step.lr"}
    assert {m["name"] for m in real.end_to_end} == {"setup_s",
                                                    "rows_per_s"}
    assert real.chips == 1 and real.config["family"] == "ssgd_hashed"

    from families import ssgd_hashed as fam

    sh = fam.shapes(real.config, real.traffic)
    assert (sh["n_blocks"], sh["n_sampled"], sh["rows_per_step"]) == \
        (5596, 56, 458752)
    assert sh["n_padded"] - real.config["n_rows"] == 1815
    assert bytes_hashed.hashed_step_bytes_needed(sh) == 458752 * 157

    class Ctx:
        reduced = None
        shapes = sh
        peaks = {"hbm_bytes_per_sec": 819e9}
        counters = {}

    for name in new:
        assert real.reader(name).read(Ctx()) is None


def _tiny_reference(seed):
    from reference import ssgd_hashed_ref as ref_mod

    c = CELLS["configs"]["lr-hashed-tiny"]
    t = CELLS["traffic"]["frac0.25x3"]
    return ref_mod, ref_mod.Reference(
        config=c, fraction=t["mini_batch_fraction"], data_seed=seed,
        sample_seed=seed + 2), c


def test_hashed_control_is_not_correct():
    """bfloat16 weights, gathered weights and per-slot sums in the
    reference's place land outside the test cell's limit; float32 is
    itself."""
    import jax.numpy as jnp

    limit = CELLS["limits"]["lrhash_tiny"]["w_rel_err"]
    for seed in (3, 4, 5):
        ref_mod, ref, c = _tiny_reference(seed)
        w0 = np.zeros(((1 << c["hash_bits"]) + 1,), np.float32)
        good = ref.follow(2, 3)
        low = ref.follow(2, 3, dtype=jnp.bfloat16)
        assert ref_mod.rel_err(good[-1], good[-1], w0) == 0
        assert ref_mod.rel_err(low[-1], good[-1], w0) > limit


def test_a_step_that_returns_its_state_unchanged(copy, monkeypatch):
    from tpu_distalg.models import ssgd

    real = ssgd.make_train_fn_fused

    def broken(mesh, config, meta):
        fn = real(mesh, config, meta)

        def unchanged(X, y, valid, X_test, y_test, w, t0=0, acc0=0.0):
            _, accs = fn(X, y, valid, X_test, y_test, w, t0=t0)
            return w, accs

        return unchanged

    monkeypatch.setattr(ssgd, "make_train_fn_fused", broken)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "w_rel_err.call1 = 1 " in log and "FAILED" in log
    assert "FAILED" in _line(log, "[check] heldout_logloss_rise")


def test_a_scatter_that_drops_a_field(copy, monkeypatch):
    """A sampled subset of a row's fields is a different result: the
    per-slot sums without the last field's occurrences are refused."""
    from tpu_distalg.ops import pallas_hashed

    real = pallas_hashed.slot_sums

    def fewer(X, r, ids, geom, **kw):
        return real(X.at[:, geom.nnz - 1, :].set(X[:, 0, :]), r, ids,
                    geom, **kw)

    monkeypatch.setattr(pallas_hashed, "slot_sums", fewer)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] w_rel_err.call1")


def test_a_loader_that_holds_too_little_is_refused(copy, monkeypatch):
    from tpu_distalg.models import ssgd

    real = ssgd.build_hashed_table

    def narrow(*args, **kw):
        X, meta = real(*args, **kw)
        return X.astype("int16"), meta

    monkeypatch.setattr(ssgd, "build_hashed_table", narrow)
    with pytest.raises(RuntimeError, match="B a row"):
        _run(copy)
