"""Host-checkable halves of the chip bring-up contract: no silent CPU,
a compile cache that can be placed from outside, and a smoke script
that refuses to run without a TPU."""

import os
import subprocess
import sys

import jax
import pytest

from tpu_distalg.parallel import mesh as pmesh
from tpu_distalg.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_without_a_tpu():
    """JAX_PLATFORMS=cpu: non-zero exit before any stage, no result
    line on stdout."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "[stage]" not in proc.stdout + proc.stderr
    assert "no TPU" in proc.stderr


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    """The script alone, without the program, must fail too."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_compile_cache_env_var_wins_and_config_is_untouched(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_DIR, "/elsewhere/cache")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.configure() == "/elsewhere/cache"
    assert updates == []          # jax reads the variable itself
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    # this suite asked for the CPU: its runs stay hermetic, no cache
    assert compile_cache.configure() is None and updates == []
    # a run that did not (the chip): the fixed in-checkout path — part
    # of the cache's key, so the same on every call and never derived
    # from a temp name, a pid or the clock
    monkeypatch.setattr(pmesh, "cpu_requested", lambda: False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure() == want
    assert compile_cache.configure() == want
    assert updates == [("jax_compilation_cache_dir", want)] * 2
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_non_tpu_backend_without_emulate_is_an_error(monkeypatch, capsys):
    """The CPU is used only when asked for. With a non-TPU default
    backend and neither --emulate nor JAX_PLATFORMS naming cpu (faked:
    this suite itself runs under JAX_PLATFORMS=cpu), mesh construction
    raises and ``tda ssgd`` exits non-zero naming --emulate."""
    from tpu_distalg import cli

    assert pmesh.get_mesh(data=1) is not None   # asked for: fine
    monkeypatch.setattr(pmesh, "cpu_requested", lambda: False)
    with pytest.raises(pmesh.NoAcceleratorError, match="--emulate"):
        pmesh.get_mesh()
    # an explicit device list is an explicit ask
    assert pmesh.get_mesh(data=1, devices=jax.devices()[:1]) is not None
    # (the faked platform would also switch the in-checkout compile
    # cache on for the rest of this process)
    monkeypatch.setattr(compile_cache, "configure", lambda: None)
    rc = cli.main(["ssgd", "--n-iterations", "2", "--quiet"])
    assert rc != 0
    err = capsys.readouterr().err
    assert "no TPU" in err and "--emulate" in err


def test_mesh_on_tpu_emits_the_device_mark(tmp_path, mesh8):
    import json

    from tpu_distalg import telemetry

    sink = telemetry.configure(str(tmp_path))
    try:
        assert pmesh.mesh_on_tpu(mesh8) is False
    finally:
        telemetry.configure(False)
    with open(sink.path) as f:
        marks = [e for e in map(json.loads, f) if e["ev"] == "device"]
    assert marks and marks[-1]["platform"] == "cpu"
    assert marks[-1]["pallas"] == "interpret"
    assert marks[-1]["n_devices"] == 8
    assert marks[-1]["device_kind"] == jax.devices()[0].device_kind
