"""Rehearsal of the pairs SSGD family on the CPU, as
``test_rehearsal_indexed.py`` rehearses the indexed one: a tiny cell
added to a temporary copy of the benchmark (new files, new entries,
nothing edited) and run end to end through ``run.run_cell``; the count
of work (the rows a step samples in expectation for ``rows_per_s``; the
rows and pairs the window's steps drew counted by ``check``, after the
window and outside ``setup_s``, with the program's blocks held to the
configuration's packing rule there);
the control (the reference with its values, weights, gathered products
and per-slot sums in bfloat16), which has to come out as not correct; a
step that hands its state back, a scatter that drops a row's last
vector and a step divided by a block's nominal rows, which have to
report ``correct`` false; a program from before the format, which is
refused at once and by name; that the real cell came as files and
entries, what it lists and what its four new readers give without a
trace."""

import contextlib
import io
import json
import os
import shutil
import subprocess

import numpy as np
import pytest

import helpers
import run as bench
from harness import manifest as mf

CELLS = mf.load_json(os.path.join(helpers.TESTS, "data",
                                  "cells_pairs.json"))
REAL = "lrpairs3728_350k_frac01"
TINY = "lrpairs_tiny"
NEW = {"rowsum_ms_per_step.lr", "pair_padding_pct.lr",
       "median_call_pairs_per_s.lr", "pairs_pass_roofline"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_pairs"))
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
    for name, obj in CELLS["limits"].items():
        add(f"limits/{name}.json", obj)
    for name, obj in CELLS["traffic"].items():
        add(f"traffic/{name}.json", obj)
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
    assert ("[ssgd] row format pairs: 5000 weights (0.0 MB) in HBM, 600 "
            "rows of 120001 pairs (longest 1024) in") in log
    assert "table (96, 40, 128) int32" in log
    assert "blocks/shard 96 sampled/step 10" in log
    for name in ("window_compiles", "blocks_off_rule", "w_rel_err.call1",
                 "w_rel_err.call2", "heldout_logloss_rise"):
        assert f"[check] {name} = " in log, name
    # the reference's seconds are the check's, none of them set-up's
    assert "reference_blocks" not in _line(log, "[setup] spans")


def test_the_work_counted_is_the_expectation_and_the_draw_is_said(copy):
    """``rows_per_s`` counts the rows a step samples in expectation
    (every row with probability ``n_sampled / n_blocks``: exact, and
    the same for every seed); the rows that the window's steps did draw,
    by the configuration's rule and the benchmark's own draws, are
    printed beside it and differ from it by the luck of the draw."""
    from families import ssgd_pairs as fam
    from reference import ssgd_pairs_ref as ref_mod
    from reference import ssgd_ref

    seed = 2**31 + 11
    rc, res, log = _run(copy, seed=seed)
    calls = int(_line(log, "[window]").split()[1])
    per_call = float(_line(log, "[window]").split(";")[2].split()[0])
    assert per_call == 2 * 600 * 10 / 96 == 125.0
    elapsed = float(_line(log, "[window]").split()[4])
    assert abs(res["metrics"]["rows_per_s"]["value"] * elapsed
               / (calls * 125.0) - 1) < 1e-3
    c = CELLS["configs"]["lr-pairs-tiny"]
    t = CELLS["traffic"]["frac0.1x2"]
    seeds = fam.sub_seeds(seed)
    ref = ref_mod.Reference(config=c, fraction=0.1,
                            data_seed=seeds["data"], sample_seed=42)
    first = seeds["t0"] + t["check_calls"] * t["steps_per_call"]
    draws = ssgd_ref.block_draws(42, first, calls * 2, 1, 96, 10)
    rows = int(ref.counts[draws].sum())
    prs = int(ref.block_pairs[draws].sum())
    assert (f"the window's {calls * 2} steps drew {rows} rows and {prs} "
            f"pairs") in log
    assert rows != calls * 125 and abs(rows / (calls * 125) - 1) < 0.05
    got, got_prs = ref.rows_and_pairs(first, calls * 2)
    assert got.sum() == rows and got_prs.sum() == prs


class _Ctx:
    """What ``count_window`` touches of a run's context."""

    def __init__(self, shapes):
        self.shapes, self.counters = dict(shapes), {}
        self.compared, self.said = [], []

    def compare(self, name, value, limit):
        self.compared.append((name, value, limit))

    def say(self, msg):
        self.said.append(msg)


@pytest.mark.parametrize("moved", [False, True])
def test_check_counts_the_windows_pairs_and_holds_the_blocks(moved):
    """``median_call_pairs_per_s.lr`` and ``pairs_pass_roofline`` get
    the pairs the window's steps drew, counted in ``check``; blocks
    that are not the configuration's rule's are compared and fail."""
    from families import ssgd_pairs as fam

    ref_mod, ref, c = _tiny_reference(3)
    t = CELLS["traffic"]["frac0.1x2"]
    ctx = _Ctx(dict(fam.shapes(c, t), rows_per_step=62.5,
                    pairs_per_step_mean=-1.0))
    starts = ref.starts.copy()
    starts[1] += moved
    fam.count_window(ctx, ref, dict(
        t_window=9, window_steps=6, block_starts=starts,
        block_counts=ref.counts, n_pairs=ref.n_pairs))
    assert ctx.compared == [("blocks_off_rule", int(moved), 0)]
    rows, prs = ref.rows_and_pairs(9, 6)
    assert ctx.counters == {"pairs_per_call": prs.sum() / 3}
    assert ctx.shapes["pairs_per_step_mean"] == prs.sum() / 6
    assert prs.sum() / 6 != ref.n_pairs * 10 / 96
    assert f"6 steps drew {rows.sum()} rows and {prs.sum()} pairs" \
        in ctx.said[0]


def test_same_seed_same_inputs(copy):
    a, b, c = (_run(copy, seed=s)[2] for s in (5, 5, 6))
    for word in ("seeds {", "w_rel_err.call2"):
        assert _line(a, word) == _line(b, word) != _line(c, word)


def test_the_real_cell_came_as_files_and_entries():
    """Nothing the benchmark had was edited: the parent's files are
    there byte for byte and its entries are a prefix of every list."""
    try:
        names = subprocess.run(
            ["git", "diff", "--name-status", "HEAD", "--", "benchmarks",
             "BENCHMARK.json"], cwd=helpers.ROOT, capture_output=True,
            text=True, check=True).stdout.split("\n")
        old = json.loads(subprocess.run(
            ["git", "show", "HEAD:BENCHMARK.json"], cwd=helpers.ROOT,
            capture_output=True, text=True, check=True).stdout)
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git checkout to compare with")
    new = mf.load_json(os.path.join(helpers.ROOT, "BENCHMARK.json"))
    if REAL in [w["name"] for w in old["workloads"]]:
        pytest.skip("HEAD already holds the cell")
    for ln in filter(None, names):
        status, path = ln.split("\t")[0], ln.split("\t")[-1]
        assert status == "A" or path == "BENCHMARK.json", ln
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(new[key]) >= len(old[key])
        for was, now in zip(old[key], new[key]):
            lists = was.get("workloads"), now.get("workloads")
            if lists[0] is not None:
                assert lists[1][:len(lists[0])] == lists[0]
                assert set(lists[1][len(lists[0]):]) <= {REAL}
            assert {k: v for k, v in was.items() if k != "workloads"} == \
                {k: v for k, v in now.items() if k != "workloads"}
    assert [w["name"] for w in new["workloads"][len(old["workloads"]):]] \
        == [REAL]
    assert [m["name"] for m in new["per_layer"][len(old["per_layer"]):]] \
        == ["rowsum_ms_per_step.lr", "pair_padding_pct.lr",
            "median_call_pairs_per_s.lr", "pairs_pass_roofline"]


def test_the_real_cell_reports_what_it_lists_and_the_new_metrics():
    from families import ssgd_pairs as fam
    from harness import bytes_pairs

    path = os.path.join(helpers.ROOT, "BENCHMARK.json")
    real, wide = mf.Cell(path, REAL), mf.Cell(path, "lrwide11_150m_frac01")
    names = {m["name"] for m in real.per_layer}
    assert NEW <= names
    # the indexed cell's lists less the two that read fields or a
    # kernel by name; the table_hbm scope's reader reads this cell's too
    assert names - NEW == {m["name"] for m in wide.per_layer} - {
        "hashed_pass_roofline", "hashed_hbm_gather_roofline"}
    assert "hbm_fields_ms_per_step.lr" in names
    assert {m["name"] for m in real.end_to_end} == {"setup_s",
                                                    "rows_per_s"}
    assert real.chips == 1 and real.config["family"] == "ssgd_pairs"
    manifest = mf.load_json(path)
    # at least: a later PR appends cells and edits no test here
    assert len(manifest["workloads"]) >= 12
    assert [w["name"] for w in manifest["workloads"]].index(REAL) == 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"][:12]) == 2
    cfg = [c for c in manifest["configs"]
           if c["name"] == "lr-webspam-tri16m"][0]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    c = real.config
    assert (c["n_rows"], c["n_features"], c["nnz_total"]) == (
        350000, 16609143, 1304697446)
    for key in ("deployment", "guarantees", "assumed", "source"):
        assert c[key]
    assert "No later PR may weaken them" in c["guarantees"]
    sh = fam.shapes(c, real.traffic)
    assert (sh["n_blocks"], sh["n_sampled"]) == (5248, 52)
    assert sh["d_total"] == 129759 * 128 >= 16609143 + 1
    assert bytes_pairs.pairs_step_bytes_needed(
        dict(sh, pairs_per_step_mean=1e6)) == 28e6
    assert real.traffic["mini_batch_fraction"] == 0.01
    assert real.traffic["check_calls"] == 2
    assert set(real.limits) >= {"w_rel_err", "heldout_logloss_rise"}

    class Ctx:
        reduced = None
        shapes = sh
        peaks = {"hbm_bytes_per_sec": 819e9}
        counters = {}
        readings_s = []
        spans = []

    for name in NEW:
        assert real.reader(name).read(Ctx()) is None, name
    Ctx.counters = {"pairs_per_call": 13e6}
    Ctx.readings_s = [0.5, 0.25, 1.0]
    assert real.reader("median_call_pairs_per_s.lr").read(Ctx()) == 26e6


def _tiny_reference(seed):
    from reference import ssgd_pairs_ref as ref_mod

    c = CELLS["configs"]["lr-pairs-tiny"]
    t = CELLS["traffic"]["frac0.1x2"]
    return ref_mod, ref_mod.Reference(
        config=c, fraction=t["mini_batch_fraction"], data_seed=seed,
        sample_seed=seed + 2), c


def test_pairs_control_is_not_correct():
    """bfloat16 values, weights, gathered products and per-slot sums in
    the reference's place land outside the test cell's limit; float32
    is itself."""
    import jax.numpy as jnp

    limit = CELLS["limits"][TINY]["w_rel_err"]
    for seed in (3, 4, 5):
        ref_mod, ref, c = _tiny_reference(seed)
        w0 = np.zeros((c["n_features"] + 1,), np.float32)
        good = ref.follow(2, 2)
        low = ref.follow(2, 2, dtype=jnp.bfloat16)
        assert ref_mod.rel_err(good[-1], good[-1], w0) == 0
        assert ref_mod.rel_err(low[-1], good[-1], w0) > limit


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(helpers.BENCH, "reference", "ssgd_pairs_ref.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module)
    assert not [m for m in mods if m.startswith("tpu_distalg")]
    assert mods <= {"__future__", "math", "jax", "jax.numpy", "numpy",
                    "jax.scipy.special", "reference"}


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
    # each of the two limits refuses it alone, the second at the real
    # cell's own number
    cell = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    limit = cell.limits["heldout_logloss_rise"]
    assert CELLS["limits"][TINY]["heldout_logloss_rise"] == limit == 1e-3
    rise = _line(log, "[check] heldout_logloss_rise")
    assert "FAILED" in rise and f"limit {limit:.6g}" in rise
    assert float(rise.split()[3]) > 3 * limit


def test_a_scatter_that_drops_a_rows_last_vector(copy, monkeypatch):
    """A row cut to its first vectors is a different result."""
    import jax.numpy as jnp

    from tpu_distalg.ops import pairs

    real = pairs.slot_sums

    def shorter(X, r, ids, geom, **kw):
        V = geom.vectors
        vrow = X[:, geom.at_vrow:geom.at_labels].reshape(
            X.shape[0], -1)[:, :V]
        last = jnp.concatenate(
            [vrow[:, 1:] != vrow[:, :-1],
             jnp.ones((X.shape[0], 1), bool)], axis=1)
        keep = jnp.where(last[:, :, None], 0, X[:, V:2 * V])
        return real(X.at[:, V:2 * V].set(keep), r, ids, geom, **kw)

    monkeypatch.setattr(pairs, "slot_sums", shorter)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] w_rel_err.call1")


def test_a_step_divided_by_a_nominal_count(copy, monkeypatch):
    """The sum over the sampled rows divided by the rows a step holds
    on average, and not by the rows it drew, is refused."""
    import jax.numpy as jnp

    from tpu_distalg.ops import pairs

    real = pairs.labels

    def nominal(X, ids, geom):
        y, valid = real(X, ids, geom)
        # validity that adds up to the mean rows of a block
        return y, valid * 0 + jnp.where(
            valid > 0, 6.25 / jnp.maximum(valid.sum(axis=1,
                                                    keepdims=True), 1), 0)

    monkeypatch.setattr(pairs, "labels", nominal)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False


def test_a_program_from_before_the_format_refuses_by_name(copy,
                                                          monkeypatch):
    from tpu_distalg.models import ssgd

    monkeypatch.setattr(ssgd, "INDEX_ROW_FORMATS", ("hashed", "indexed"))
    with pytest.raises(RuntimeError, match="no row_format 'pairs'"):
        _run(copy)
    monkeypatch.delattr(ssgd, "INDEX_ROW_FORMATS")
    with pytest.raises(RuntimeError, match="no row_format 'pairs'"):
        _run(copy)
