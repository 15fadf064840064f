"""Bench harness failure modes: with no chip the bench measures
nothing and says so — a non-zero exit and NO metric line (a CPU timing
or a zero under a device metric's name would read as a measurement);
a failed phase is recorded, the phases after it still run, and the
exit code is non-zero."""

import json


def test_backend_init_failure_prints_no_metric_and_fails(monkeypatch,
                                                         capsys):
    import bench
    from tpu_distalg import parallel

    calls = {"n": 0}

    def dead_mesh(*a, **k):
        calls["n"] += 1
        raise RuntimeError("UNAVAILABLE: backend down (test)")

    monkeypatch.setattr(parallel, "get_mesh", dead_mesh)
    monkeypatch.setattr(bench, "INIT_RETRY_ATTEMPTS", 3)
    monkeypatch.setattr(bench, "INIT_RETRY_SECONDS", 0)
    monkeypatch.setattr(bench, "_SUMMARY", {})

    rc = bench.main([])
    assert rc == 2
    assert calls["n"] == 3     # the supervised attempts, no fallback one
    out = capsys.readouterr()
    assert out.out.strip() == ""          # no metric line, no summary
    assert "backend init failed (attempt 3/3)" in out.err
    assert "no backend, no metrics" in out.err


def test_cpu_mesh_prints_no_metric_and_fails(monkeypatch, capsys):
    """JAX_PLATFORMS=cpu (what this suite runs under) yields a mesh,
    but the bench refuses it: no phase runs, nothing is printed."""
    import bench

    ran = []
    monkeypatch.setattr(bench, "_bench_ssgd",
                        lambda *a, **k: ran.append("ssgd"))
    monkeypatch.setattr(bench, "_SUMMARY", {})
    rc = bench.main([])
    assert rc == 2 and ran == []
    out = capsys.readouterr()
    assert out.out.strip() == ""
    assert "no TPU" in out.err


def test_failed_phase_is_recorded_and_makes_exit_nonzero(monkeypatch,
                                                         capsys):
    """A phase that raises does not sink the phases after it, and the
    run's exit code says so; a phase that does not apply to this mesh
    geometry is a recorded skip, not a failure."""
    import bench

    monkeypatch.setattr(bench, "_FAILED_PHASES", [])
    ran = []

    def boom():
        raise ValueError("kernel refused (test)")

    def skip():
        raise bench.PhaseNotApplicable("needs 4 devices (test)")

    assert bench._phase("a", boom) is None
    assert bench._phase("b", skip) is None
    assert bench._phase("c", lambda: ran.append("c") or 7) == 7
    assert ran == ["c"]
    assert bench._FAILED_PHASES == ["a"]
    err = capsys.readouterr().err
    assert "phase a FAILED" in err and "kernel refused (test)" in err
    assert "phase b not applicable" in err

    # _run's exit code follows the failed-phase list
    from tpu_distalg import parallel

    monkeypatch.setattr(parallel, "mesh_on_tpu", lambda mesh: True)
    real_phase = bench._phase
    monkeypatch.setattr(
        bench, "_phase",
        lambda name, fn, *a: real_phase(
            name, boom if name == "pagerank" else (lambda: None)))
    monkeypatch.setattr(bench, "_SUMMARY", {})
    assert bench.main([]) == 1
    assert bench._FAILED_PHASES == ["pagerank"]
    assert "1 phase(s) failed: pagerank" in capsys.readouterr().err


def test_peaks_are_looked_up_by_device_kind(monkeypatch):
    """A roofline share against another chip's peak is not a
    measurement: an unknown device_kind is an error, never a default."""
    import jax
    import pytest

    import bench

    class _Dev:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    assert bench._device_peaks()["hbm_bytes_per_sec"] == 819e9
    assert bench._hbm_fraction(819e9, 1.0, 2) == 0.5
    _Dev.device_kind = "cpu"
    with pytest.raises(KeyError, match="device_kind 'cpu'"):
        bench._hbm_fraction(1.0, 1.0, 1)


def test_all_metric_names_match_emission_sites():
    """ALL_METRIC_NAMES is the canonical metric set, but the real
    emissions live in the phase functions — tie the two together
    statically so a rename/addition in either place fails loudly.

    The AST walk that used to live here (and, re-implemented, in
    test_cluster/test_partition) is now the TDA102 collector — ONE
    implementation, run by `tda lint` on every gate and called here so
    both drift directions keep a direct unit-test spelling too."""
    import os

    import bench
    from tpu_distalg.analysis import telemetry_contract as tc

    root = os.path.dirname(os.path.abspath(bench.__file__))
    contract = tc.bench_contract(root)
    assert set(contract.canonical) == set(bench.ALL_METRIC_NAMES)
    unemitted, rogue = tc.contract_problems(contract)
    assert not unemitted, (
        f"canonical metrics with no emission site in bench.py "
        f"(renamed phase metric without updating ALL_METRIC_NAMES?): "
        f"{unemitted}")
    assert not rogue, (
        f"metric emissions missing from ALL_METRIC_NAMES: "
        f"{sorted(rogue)}")


def test_summary_preserves_recorded_metrics():
    """_emit_summary repeats every recorded metric in one line and
    never clobbers an already-recorded flagship value."""
    import bench

    saved = dict(bench._SUMMARY)
    try:
        bench._SUMMARY.clear()
        bench._emit({"metric": "ssgd_lr_steps_per_sec_per_chip",
                     "value": 123.0, "unit": "steps/s/chip",
                     "vs_baseline": 4.0})
        bench._emit({"metric": "x", "value": 1.5, "unit": "u",
                     "vs_baseline": None})
        bench._SUMMARY.setdefault(
            "ssgd_lr_steps_per_sec_per_chip",
            {"value": 0.0, "unit": "steps/s/chip", "vs_baseline": 0.0})
        assert bench._SUMMARY[
            "ssgd_lr_steps_per_sec_per_chip"]["value"] == 123.0
    finally:
        bench._SUMMARY.clear()
        bench._SUMMARY.update(saved)


def test_hard_deadline_reemits_metric_lines(capsys):
    """The r5 rc-124 regression: a timed-out run's stdout tail held no
    complete metric line, so the driver parsed null. The hard-deadline
    path now re-prints every successfully measured line and ends with
    the all-metrics summary — the tail alone reconstructs the run."""
    import bench

    saved_s, saved_l = dict(bench._SUMMARY), list(bench._LINES)
    try:
        bench._SUMMARY.clear()
        bench._LINES.clear()
        bench._emit({"metric": "ssgd_lr_steps_per_sec_per_chip",
                     "value": 321.0, "unit": "steps/s/chip",
                     "vs_baseline": 4.0, "extra_field": "kept"})
        bench._emit({"metric": "pagerank_1m_iters_per_sec",
                     "value": 9.0, "unit": "iter/s/chip",
                     "vs_baseline": None})
        capsys.readouterr()  # drop the first-emission prints
        bench._emit_deadline_summary()
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()]
    finally:
        bench._SUMMARY.clear()
        bench._SUMMARY.update(saved_s)
        bench._LINES.clear()
        bench._LINES.extend(saved_l)
    # both measured lines re-emitted IN FULL (extra fields included)
    assert lines[0]["metric"] == "ssgd_lr_steps_per_sec_per_chip"
    assert lines[0]["extra_field"] == "kept"
    assert lines[1]["metric"] == "pagerank_1m_iters_per_sec"
    # ... and the LAST line is the parseable all-metrics summary
    assert lines[-1]["all_metrics"] == {
        "ssgd_lr_steps_per_sec_per_chip": 321.0,
        "pagerank_1m_iters_per_sec": 9.0}


def test_init_retry_budget_caps_by_remaining_deadline():
    """Backend-init attempts fit the remaining hard-deadline window
    (half of it), never the old fixed-40 schedule: r5 spent 4 h
    retrying inside a 3 h window."""
    import bench

    per = bench.INIT_TIMEOUT_SECONDS + bench.INIT_RETRY_SECONDS
    assert bench._init_retry_budget(0) == 0
    assert bench._init_retry_budget(-10) == 0          # already past it
    # retries + the implicit FIRST attempt fit the half-window: at
    # 4*per remaining, half fits 2 attempts = 1 retry
    assert bench._init_retry_budget(2 * per) == 0
    assert bench._init_retry_budget(4 * per) == 1
    assert bench._init_retry_budget(8 * per) == 3
    # an effectively unlimited window still honors the ceiling
    assert bench._init_retry_budget(1e9) == \
        bench.INIT_RETRY_ATTEMPTS - 1
