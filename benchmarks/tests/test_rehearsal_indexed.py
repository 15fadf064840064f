"""Rehearsal of the indexed-row SSGD family on the CPU, as
``test_rehearsal_hashed.py`` rehearses the hashed one: a tiny cell
added to a temporary copy of the benchmark (new files, new entries,
nothing edited) and run end to end through ``run.run_cell`` with the
VMEM bound shrunk to 2^12 slots, so that every form a field can take
runs (by value, by address in groups, in HBM) with the Mosaic passes
interpreted; the control (the reference with its weights, gathered
weights and per-slot sums in bfloat16), which has to come out as not
correct; a step that hands its state back, and a scatter that drops a
field, which have to report ``correct`` false; a program from before
the format, which is refused at once and by name; what the real cell
lists and what its three new readers give without a trace."""

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
                                  "cells_indexed.json"))
REAL = "lrwide11_150m_frac01"
TINY = "lrwide_tiny"
NEW = {"hbm_fields_ms_per_step.lr", "update_ms_per_step.lr",
       "hashed_hbm_gather_roofline"}


@pytest.fixture(autouse=True)
def small_vmem(monkeypatch):
    from tpu_distalg.ops import pallas_hashed

    monkeypatch.setattr(pallas_hashed, "VMEM_BITS", 12)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_indexed"))
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
        path = os.path.join(bench_dir, "traffic", name + ".json")
        if not os.path.exists(path):
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
    assert ("[ssgd] row format indexed nnz 11 features 25477 (0.1 MB of "
            "weights) passes fields: by value [0, 2, 3, 4, 10] by address "
            "in VMEM [[1, 6], [7], [8]] in HBM [5, 9]") in log
    assert "table (79, 16, 256) int32" in log
    for name in ("window_compiles", "w_rel_err.call1", "w_rel_err.call2",
                 "heldout_logloss_rise"):
        assert f"[check] {name} = " in log, name


def test_same_seed_same_inputs(copy):
    a, b, c = (_run(copy, seed=s)[2] for s in (5, 5, 6))
    for word in ("seeds {", "w_rel_err.call2"):
        assert _line(a, word) == _line(b, word) != _line(c, word)


def test_the_real_cell_reports_what_it_lists_and_the_new_metrics():
    """Every list the hashed cell is in, and the three new readers; the
    share is sampled rows x 45 B over the two scopes' time over the
    peak; nothing without a trace."""
    from families import ssgd_indexed as fam
    from harness import bytes_hashed

    path = os.path.join(helpers.ROOT, "BENCHMARK.json")
    real, hashed = mf.Cell(path, REAL), mf.Cell(path, "lrhash39_46m_frac01")
    names = {m["name"] for m in real.per_layer}
    assert NEW <= names
    assert names - NEW == {m["name"] for m in hashed.per_layer}
    assert {m["name"] for m in real.end_to_end} == {"setup_s",
                                                    "rows_per_s"}
    assert real.chips == 1 and real.config["family"] == "ssgd_indexed"
    assert real.entry["traffic"] == "frac0.01"
    assert real.config["n_features"] == 54686452 == sum(
        real.config["field_cardinalities"])

    sh = fam.shapes(real.config, real.traffic)
    assert (sh["n_blocks"], sh["n_sampled"], sh["rows_per_step"]) == \
        (18267, 183, 1499136)
    assert sh["n_padded"] - real.config["n_rows"] == 4159
    assert sh["d_total"] == 427238 * 128 >= 54686452 + 1
    assert bytes_hashed.hashed_step_bytes_needed(sh) == 1499136 * 45
    from harness import bytes_indexed

    assert bytes_indexed.hbm_gather_bytes_needed(
        dict(sh, hbm_fields=2)) == 1499136 * 2 * 8

    class Ctx:
        reduced = None
        shapes = sh
        peaks = {"hbm_bytes_per_sec": 819e9}
        counters = {}

    for name in NEW | {"hashed_pass_roofline"}:
        assert real.reader(name).read(Ctx()) is None


def test_the_real_cell_takes_its_forms_from_sizes_alone():
    """What ``tda ssgd`` would run at the cell's eleven sizes under the
    real VMEM bound: three fields by value, six by address in three
    groups of at most 2^22 slots, the query and user ids in HBM."""
    from tpu_distalg.ops import pallas_hashed as ph

    real = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    from families import ssgd_indexed as fam
    from tpu_distalg.utils import datasets

    cards = fam.loader_args(real.config)["cardinalities"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ph, "VMEM_BITS", 22)
        geom = ph.HashedGeometry(11, 0, 8192, field_sizes=cards)
        plan = ph.field_plan(geom,
                             datasets.indexed_field_dictionaries(cards))
    assert geom.pass_form == "fields" and geom.fields_held == 16
    assert plan.dict_fields == (3, 4, 10) and plan.n_values == 27
    assert plan.hbm_fields == (5, 9)
    assert [g.fields for g in plan.addr_groups] == [(0, 1, 2, 6), (7,),
                                                    (8,)]
    assert all(g.n_slots <= 1 << 22 and g.n_slots % 1024 == 0
               for g in plan.addr_groups)


def _tiny_reference(seed):
    from reference import ssgd_indexed_ref as ref_mod

    c = CELLS["configs"]["lr-indexed-tiny"]
    t = CELLS["traffic"]["frac0.25x3"]
    return ref_mod, ref_mod.Reference(
        config=c, fraction=t["mini_batch_fraction"], data_seed=seed,
        sample_seed=seed + 2), c


def test_indexed_control_is_not_correct():
    """bfloat16 weights, gathered weights and per-slot sums in the
    reference's place land outside the test cell's limit; float32 is
    itself."""
    import jax.numpy as jnp

    limit = CELLS["limits"]["lrwide_tiny"]["w_rel_err"]
    for seed in (3, 4, 5):
        ref_mod, ref, c = _tiny_reference(seed)
        w0 = np.zeros((c["n_features"] + 1,), np.float32)
        good = ref.follow(2, 3)
        low = ref.follow(2, 3, dtype=jnp.bfloat16)
        assert ref_mod.rel_err(good[-1], good[-1], w0) == 0
        assert ref_mod.rel_err(low[-1], good[-1], w0) > limit


def test_the_reference_restates_the_programs_rows():
    """Row for row: the program's generator (feature = offset + value)
    and the reference's, which shares no code with it."""
    import jax.numpy as jnp

    from tpu_distalg.utils import datasets

    ref_mod, ref, c = _tiny_reference(9)
    ids = jnp.arange(4096) + 12345
    make = datasets.indexed_click_rows(
        tuple(c["field_cardinalities"]),
        zipf_exponent=c["zipf_exponent"],
        planted_scale=c["planted_scale"], click_rate=c["click_rate"])
    slots, y = make(ids, jnp.int32(9))
    idx, y_ref = ref.rows.make(ids, jnp.int32(9), ref.bias)
    assert np.array_equal(np.asarray(slots), np.asarray(idx))
    assert np.array_equal(np.asarray(y), np.asarray(y_ref))


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


@pytest.mark.parametrize("field", [0, 1, 5])
def test_a_scatter_that_drops_a_field(copy, monkeypatch, field):
    """A field's occurrences merged onto two of its values are a
    different result, whichever form serves the field (0 by value, 1
    by address, 5 in HBM), and are refused."""
    from tpu_distalg.ops import pallas_hashed

    real = pallas_hashed.slot_sums

    def fewer(X, r, ids, geom, **kw):
        lo = geom.offsets[field]
        return real(X.at[:, field, :].set(lo + (X[:, field, :] - lo) % 2),
                    r, ids, geom, **kw)

    monkeypatch.setattr(pallas_hashed, "slot_sums", fewer)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] w_rel_err.call1")


def test_a_program_from_before_the_format_refuses_by_name(copy,
                                                          monkeypatch):
    from tpu_distalg.models import ssgd

    monkeypatch.delattr(ssgd, "INDEX_ROW_FORMATS")
    with pytest.raises(RuntimeError, match="no row_format 'indexed'"):
        _run(copy)
