"""Telemetry & supervision subsystem (tpu_distalg/telemetry/).

Covers the round-6 tentpole: JSONL well-formedness under concurrent
emitters, the disabled-path zero-I/O guarantee, stall detection on a
frozen mark, the supervisor's retry/backoff/timeout/degrade paths
(with an injected hanging ``jax.devices`` stand-in), ``tda report``
output on recorded logs, and regression tests for two round-5 ADVICE
fixes (plan_spmv VMEM guard, streamed-cache tmp race).
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from tpu_distalg import telemetry
from tpu_distalg.telemetry import events, heartbeat, report, supervisor


@pytest.fixture()
def sink_dir(tmp_path):
    """A configured telemetry sink; always deconfigured afterwards."""
    d = str(tmp_path / "tel")
    events.configure(d)
    try:
        yield d
    finally:
        events.configure(False)


def _read_events(d):
    out = []
    for name in sorted(os.listdir(d)):
        with open(os.path.join(d, name)) as f:
            out += [json.loads(line) for line in f if line.strip()]
    return out


# ---------------------------------------------------------------- events

def test_event_schema_and_run_lifecycle(sink_dir):
    events.emit("custom", foo=1)
    events.mark("phase_x")
    with events.span("work", detail="d"):
        pass
    events.counter("widgets", 2)
    events.counter("widgets")
    events.gauge("temp", 3.5)
    events.configure(False)  # closes: flushes counters + run_end
    evts = _read_events(sink_dir)
    kinds = [e["ev"] for e in evts]
    assert kinds == ["run_start", "custom", "mark", "span_start",
                     "span_end", "gauge", "counters", "run_end"]
    for e in evts:
        for key in ("t_wall", "t_mono", "run", "pid", "host"):
            assert key in e
    assert evts[4]["seconds"] >= 0 and evts[4]["ok"] is True
    assert evts[6]["counters"] == {"widgets": 3}
    assert len({e["run"] for e in evts}) == 1


def test_span_records_error_and_reraises(sink_dir):
    with pytest.raises(RuntimeError, match="boom"):
        with events.span("explode"):
            raise RuntimeError("boom")
    events.configure(False)
    end = [e for e in _read_events(sink_dir) if e["ev"] == "span_end"]
    assert end[0]["ok"] is False
    assert "RuntimeError: boom" in end[0]["error"]


def test_span_caller_fields_never_mask_the_real_exception(sink_dir):
    """A caller-supplied 'error'/'seconds' field must not TypeError in
    span()'s finally and swallow the body's exception."""
    with pytest.raises(RuntimeError, match="real failure"):
        with events.span("p", error="caller context", seconds=-1):
            raise RuntimeError("real failure")
    events.configure(False)
    end = [e for e in _read_events(sink_dir) if e["ev"] == "span_end"]
    assert end[0]["ok"] is False
    assert "RuntimeError: real failure" in end[0]["error"]  # span wins


def test_concurrent_emitters_produce_wellformed_jsonl(sink_dir):
    """8 threads x 200 events: every line must parse and none may be
    lost or spliced (one locked write per line in EventSink)."""
    n_threads, n_each = 8, 200

    def hammer(tid):
        for i in range(n_each):
            events.emit("hammer", tid=tid, i=i)
            events.counter("hammered")

    threads = [threading.Thread(target=hammer, args=(t,), daemon=False)
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    events.configure(False)
    evts = _read_events(sink_dir)  # json.loads of EVERY line
    got = [(e["tid"], e["i"]) for e in evts if e["ev"] == "hammer"]
    assert len(got) == n_threads * n_each
    assert len(set(got)) == n_threads * n_each
    counters = [e for e in evts if e["ev"] == "counters"]
    assert counters[-1]["counters"]["hammered"] == n_threads * n_each


def test_disabled_path_does_zero_file_io(tmp_path, monkeypatch):
    """With telemetry off, emit/mark/span/counter/gauge must never
    touch a file — asserted by making every sink write, and ``open``
    itself, explode. The counter is kept all the same: the store is the
    process's, as the ring of finished spans is."""
    events.configure(False)

    def forbidden(*a, **k):
        raise AssertionError("file I/O on the disabled telemetry path")

    monkeypatch.setattr(events.EventSink, "write", forbidden)
    monkeypatch.setattr(events.EventSink, "__init__", forbidden)
    monkeypatch.setattr("builtins.open", forbidden)
    monkeypatch.setattr(os, "makedirs", forbidden)
    events.emit("nope", x=1)
    events.mark("nope")
    events.counter("nope")
    events.counter("nope", 2)
    events.gauge("nope", 1)
    with events.span("nope"):
        pass
    assert events.counters() == {"nope": 3}
    assert events.memory([]) is None
    monkeypatch.undo()
    assert list(tmp_path.iterdir()) == []


def test_mark_is_tracked_in_memory_even_when_disabled():
    events.configure(False)
    events.mark("offline_phase", emit_event=False)
    t, phase = events.last_mark()
    assert phase == "offline_phase"
    assert time.monotonic() - t < 5.0


def test_configure_env_fallback(tmp_path, monkeypatch):
    d = str(tmp_path / "envtel")
    monkeypatch.setenv(events.ENV_DIR, d)
    events.configure(None)  # None defers to the env var
    try:
        assert events.enabled()
        assert os.path.isdir(d)
    finally:
        events.configure(False)  # force-off even with the var set
        monkeypatch.delenv(events.ENV_DIR)
    assert not events.enabled()


# ------------------------------------------------------------- heartbeat

def test_heartbeat_emits_and_flags_stall_once_per_frozen_mark(sink_dir):
    clock = {"t": 0.0}
    events.mark("stuck_phase")
    t_mark, _ = events.last_mark()
    clock["t"] = t_mark
    hb = heartbeat.Heartbeat(interval=9999, stall_after=10.0,
                             now=lambda: clock["t"])
    hb.beat()                      # age 0: no stall
    clock["t"] = t_mark + 11.0
    hb.beat()                      # over deadline: stall fires
    hb.beat()                      # same frozen mark: no re-fire
    assert hb.n_stalls == 1
    events.mark("stuck_phase")     # new mark re-arms detection
    t2, _ = events.last_mark()
    clock["t"] = t2 + 11.0
    hb.beat()
    assert hb.n_stalls == 2
    events.configure(False)
    evts = _read_events(sink_dir)
    stalls = [e for e in evts if e["ev"] == "stall"]
    beats = [e for e in evts if e["ev"] == "heartbeat"]
    assert len(beats) == 4 and len(stalls) == 2
    assert stalls[0]["phase"] == "stuck_phase"
    assert stalls[0]["seconds_since_mark"] == pytest.approx(11.0)


def test_heartbeat_on_stall_callback_fires():
    events.configure(False)
    fired = []
    clock = {"t": 0.0}
    events.mark("p")
    t_mark, _ = events.last_mark()
    clock["t"] = t_mark + 99.0
    hb = heartbeat.Heartbeat(interval=9999, stall_after=1.0,
                             on_stall=lambda ph, age: fired.append(
                                 (ph, age)),
                             now=lambda: clock["t"])
    hb.beat()
    assert fired == [("p", pytest.approx(99.0))]


def test_heartbeat_thread_start_stop(sink_dir):
    hb = heartbeat.Heartbeat(interval=0.01, stall_after=None)
    hb.start()
    deadline = time.monotonic() + 5.0
    while hb.n_beats < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    hb.stop()
    hb.join(timeout=5.0)
    assert not hb.is_alive()
    assert hb.n_beats >= 3


def test_heartbeat_survives_a_failing_sink():
    """A beat that raises (disk full mid-run) must not kill liveness
    detection: safe_beat swallows, counts, and the next beat retries —
    a dead heartbeat would silently disarm the stall watchdog."""
    events.configure(False)
    fired = []
    boom = {"on": True}

    def flaky_emit(ev, **fields):
        if boom["on"]:
            raise OSError("No space left on device")

    clock = {"t": 0.0}
    events.mark("p")
    t_mark, _ = events.last_mark()
    clock["t"] = t_mark + 99.0
    hb = heartbeat.Heartbeat(interval=9999, stall_after=1.0,
                             on_stall=lambda ph, age: fired.append(ph),
                             emit_fn=flaky_emit,
                             now=lambda: clock["t"])
    hb.safe_beat()                 # raises inside, swallowed
    assert hb.n_errors == 1 and fired == []
    boom["on"] = False
    hb.safe_beat()                 # sink recovered: stall still armed
    assert fired == ["p"]


def test_start_heartbeat_skipped_when_disabled_and_no_action():
    events.configure(False)
    assert telemetry.start_heartbeat() is None


# ------------------------------------------------------------ supervisor

def test_supervisor_ok_first_try(sink_dir):
    devs = supervisor.init_backend(init_fn=lambda: ["dev0"],
                                   timeout=5.0)
    assert devs == ["dev0"]
    events.configure(False)
    inits = [e for e in _read_events(sink_dir)
             if e["ev"] == "backend_init"]
    assert [e["outcome"] for e in inits] == ["ok"]


def test_supervisor_retries_errors_with_backoff_then_succeeds(sink_dir):
    calls = {"n": 0}
    sleeps = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE (transient)")
        return "mesh"

    out = supervisor.init_backend(
        init_fn=flaky, timeout=5.0, retries=4, backoff=2.0,
        backoff_cap=60.0, jitter=0.5, sleep=sleeps.append,
        rng=lambda: 1.0, log=lambda m: None)
    assert out == "mesh" and calls["n"] == 3
    # exponential backoff x (1 + jitter): 2*1.5, 4*1.5
    assert sleeps == [pytest.approx(3.0), pytest.approx(6.0)]
    events.configure(False)
    evts = _read_events(sink_dir)
    outcomes = [e["outcome"] for e in evts if e["ev"] == "backend_init"]
    assert outcomes == ["error", "error", "ok"]
    assert len([e for e in evts if e["ev"] == "backend_retry"]) == 2


def test_supervisor_hanging_init_times_out_and_raises(sink_dir):
    """A wedged jax.devices() (round 5's 26-minute hang, in miniature):
    every attempt must hit the deadline, record a stall, and the
    exhausted supervisor must resolve with backend_unavailable.
    Retries are SINGLE-FLIGHT: the hung call is entered exactly once —
    later attempts wait on it instead of racing a second jax init."""
    hang = threading.Event()
    entries = {"n": 0}

    def hanging_devices():
        entries["n"] += 1
        hang.wait(30.0)  # far past the test deadline

    t0 = time.monotonic()
    with pytest.raises(supervisor.BackendUnavailableError,
                       match="after 3 attempts"):
        supervisor.init_backend(
            init_fn=hanging_devices, timeout=0.05, retries=2,
            backoff=0.0, sleep=lambda s: None, log=lambda m: None)
    assert time.monotonic() - t0 < 10.0  # did not wait out the hang
    assert entries["n"] == 1             # single-flight, no racing init
    hang.set()
    events.configure(False)
    evts = _read_events(sink_dir)
    inits = [e for e in evts if e["ev"] == "backend_init"]
    assert [e["outcome"] for e in inits] == ["timeout"] * 3
    assert len([e for e in evts if e["ev"] == "stall"]) == 3
    assert [e["ev"] for e in evts][-3] == "backend_unavailable"


def test_supervisor_config_errors():
    with pytest.raises(ValueError, match="retries"):
        supervisor.init_backend(retries=-1)


# ---------------------------------------------------------------- report

def test_report_summarize_and_render(sink_dir, capsys):
    with events.span("train"):
        events.mark("train")
    events.emit("restart", attempt=1, of=2, error="X")
    events.emit("quarantine", path="/x")
    events.emit("metric", metric="m1", value=12.5, unit="u",
                vs_baseline=3.0)
    hb = heartbeat.Heartbeat(interval=9999, stall_after=None)
    hb.beat()
    events.configure(False)
    s = report.summarize(report.load_events(sink_dir))
    assert s["phases"]["train"]["count"] == 1
    assert s["restarts"] == 1 and s["quarantines"] == 1
    assert s["last_heartbeat"] is not None
    assert s["metrics"]["m1"]["value"] == 12.5
    text = report.render(s)
    assert "train" in text and "restarts: 1" in text
    assert "m1: 12.5 u" in text

    # the CLI path: `tda report <dir>` (and --json for CI)
    from tpu_distalg import cli

    assert cli.main(["report", sink_dir]) == 0
    human = capsys.readouterr().out
    assert "phase durations" in human and "last heartbeat" in human
    assert cli.main(["report", sink_dir, "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["metrics"]["m1"]["unit"] == "u"


def _make_worker_dir(root, name, counters):
    events.configure(os.path.join(root, name))
    for k, v in counters.items():
        events.counter(k, v)
    events.mark(name)
    events.configure(False)  # close -> flush the counters event


def test_report_merges_multiple_dirs_with_per_worker_columns(
        tmp_path, capsys):
    """The cluster-runtime satellite: several --telemetry-dirs (or a
    parent of per-worker dirs) render ONE merged report with
    per-worker columns for the ssp.*/cluster.* counters."""
    root = str(tmp_path / "cluster")
    _make_worker_dir(root, "coordinator",
                     {"cluster.merges": 8, "cluster.joins": 3})
    _make_worker_dir(root, "worker-0",
                     {"cluster.pushes": 8, "ssp.merges": 8})
    _make_worker_dir(root, "worker-1",
                     {"cluster.pushes": 6, "cluster.skips": 2,
                      "ssp.merges": 6, "other.counter": 5})
    # a parent dir expands to its event-bearing children
    assert [os.path.basename(p)
            for p in report.expand_dirs([root])] == [
        "coordinator", "worker-0", "worker-1"]
    rc = report.report_main(root)
    assert rc == 0
    text = capsys.readouterr().out
    assert "merged over 3 telemetry dir(s)" in text
    assert "per-worker counters (ssp.*/cluster.*):" in text
    # merged totals sum across processes
    assert "cluster.pushes=14" in text
    # column table: worker-1's skips present, worker-0's blank
    row = [ln for ln in text.splitlines()
           if ln.strip().startswith("cluster.skips")][0]
    cols = row.split()
    assert cols[-1] == "2" and cols[-2] == "-"
    # non-prefixed counters stay out of the column table
    assert not any(ln.strip().startswith("other.counter")
                   for ln in text.splitlines()
                   if ln.startswith("  other"))
    # explicit multiple dirs work the same way; single dir renders the
    # classic report (no merge header)
    rc = report.report_main([os.path.join(root, "worker-0"),
                             os.path.join(root, "worker-1")])
    assert rc == 0
    assert "merged over 2" in capsys.readouterr().out
    rc = report.report_main(os.path.join(root, "worker-0"))
    assert "merged over" not in capsys.readouterr().out


def test_report_multi_json_mode(tmp_path, capsys):
    root = str(tmp_path / "c")
    _make_worker_dir(root, "worker-0", {"cluster.pushes": 1})
    _make_worker_dir(root, "worker-1", {"cluster.pushes": 2})
    report.report_main(root, as_json=True)
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {"merged", "workers"}
    assert doc["merged"]["counters"]["cluster.pushes"] == 3
    assert doc["workers"]["worker-1"]["counters"][
        "cluster.pushes"] == 2


def test_report_tolerates_torn_tail_line(tmp_path):
    d = str(tmp_path)
    p = os.path.join(d, "events-abc.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps({"ev": "mark", "t_wall": 1.0, "run": "abc",
                            "phase": "x"}) + "\n")
        f.write('{"ev": "heartbe')  # killed mid-write
    s = report.summarize(report.load_events(d))
    assert s["marks"] == 1 and s["torn_lines"] == 1


def test_report_missing_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        report.load_events(str(tmp_path / "nope"))


def test_report_last_wins_fields_come_from_newest_run_by_mtime(tmp_path):
    """Run ids are random hex, so file order must follow mtime, not
    name — a reused --telemetry-dir must report the NEWEST run's
    resolution, whatever its id sorts like."""
    d = str(tmp_path)

    def write_run(run_id, event, mtime):
        p = os.path.join(d, f"events-{run_id}.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({**event, "t_wall": mtime,
                                "run": run_id}) + "\n")
        os.utime(p, (mtime, mtime))

    # the OLDER run has the lexicographically LATER name on purpose
    write_run("zzzz", {"ev": "backend_unavailable"}, 1_000_000.0)
    write_run("aaaa", {"ev": "backend_init", "attempt": 1,
                       "outcome": "ok", "seconds": 0.1}, 2_000_000.0)
    s = report.summarize(report.load_events(d))
    assert s["backend_init"]["resolution"] == "ok"
    assert s["runs"] == ["zzzz", "aaaa"]


# --------------------------------- ADVICE regression: plan_spmv VMEM guard

def test_plan_spmv_rejects_vmem_overflow_before_sorting():
    from tpu_distalg.ops import pallas_pagerank as ppr

    # 40M vertices: the output table alone is 160 MB > budget (the
    # ranks table stays in HBM since PR 38, so 20M fit); must return
    # None FAST (before any sort), not at compile
    src = np.array([0, 1, 2, 3], dtype=np.int64)
    dst = np.array([1, 2, 3, 0], dtype=np.int64)
    w_e = np.full(4, 0.25, np.float32)
    t0 = time.monotonic()
    assert ppr.plan_spmv(src, dst, w_e, n_vertices=40_000_000) is None
    assert time.monotonic() - t0 < 5.0
    assert ppr.spmv_resident_bytes(40_000_000, ppr.SPMV_RG, 8) \
        > ppr.SPMV_VMEM_BUDGET
    # and the bound is tight the other way: the benchmark's graph
    # (Graph500 SCALE 24) fits at its tallest windows
    assert ppr.spmv_resident_bytes(1 << 24, ppr.SPMV_RGS[-1],
                                   ppr.SPMV_WS_CAP) \
        < ppr.SPMV_VMEM_BUDGET


def test_spmv_resident_bytes_formula():
    from tpu_distalg.ops import pallas_pagerank as ppr

    r8 = ((1_000_000 + 127) // 128 + 7) // 8 * 8
    want = (r8 + 80) * 128 * 4 + 2 * (128 + 5 * 8 * 8) * 128 * 4
    assert ppr.spmv_resident_bytes(1_000_000, 128, 80, 8) == want


def test_plan_spmv_small_graph_still_plans():
    from tpu_distalg.ops import pallas_pagerank as ppr

    rng = np.random.default_rng(0)
    v, e = 4096, 32768
    src = rng.integers(0, v, e)
    dst = rng.integers(0, v, e)
    plan = ppr.plan_spmv(src, dst, np.ones(e, np.float32), v)
    assert plan is not None


# ------------------------------ ADVICE regression: streamed cache publish

def _tiny_cache_kwargs():
    # smallest legal geometry: pack*block*shards must divide n_rows
    return dict(n_rows=1024, n_features=5, n_shards=2, pack=4,
                gather_block_rows=32, seed=0, n_test=64)


def test_streamed_cache_tmp_names_are_unique_and_cleaned(tmp_path):
    from tpu_distalg.utils import datasets

    path = str(tmp_path / "cache")
    X2, meta, _ = datasets.streamed_packed_cache(
        path, **_tiny_cache_kwargs())
    assert X2.shape[0] == 1024 // 4
    leftovers = [n for n in os.listdir(tmp_path) if ".tmp" in n]
    assert leftovers == []
    assert os.path.exists(path + ".meta.json")


def test_streamed_cache_bin_without_meta_is_regenerated(tmp_path):
    """meta.json is published LAST, so a crash between the renames
    leaves bin-without-meta — which must be treated as incomplete and
    regenerated to the same deterministic bytes."""
    from tpu_distalg.utils import datasets

    path = str(tmp_path / "cache")
    kw = _tiny_cache_kwargs()
    datasets.streamed_packed_cache(path, **kw)
    with open(path + ".bin", "rb") as f:
        want = f.read()
    os.remove(path + ".meta.json")     # simulate the torn publish
    X2, meta, _ = datasets.streamed_packed_cache(path, **kw)
    with open(path + ".bin", "rb") as f:
        assert f.read() == want
    assert os.path.exists(path + ".meta.json")


def test_streamed_cache_failed_generation_leaves_no_tmp_orphans(
        tmp_path, monkeypatch):
    """A generation that dies mid-write must unlink its PID/uuid tmp
    files (unique names mean nothing ever overwrites them — orphans at
    32 GB apiece would fill the disk); ancient crash debris is swept on
    the next call."""
    import time as _time

    from tpu_distalg.utils import datasets

    path = str(tmp_path / "cache")
    kw = _tiny_cache_kwargs()
    real_savez = np.savez

    def exploding_savez(*a, **k):
        raise OSError("disk full (injected)")

    monkeypatch.setattr(np, "savez", exploding_savez)
    with pytest.raises(OSError, match="injected"):
        datasets.streamed_packed_cache(path, **kw)
    monkeypatch.setattr(np, "savez", real_savez)
    assert [n for n in os.listdir(tmp_path) if ".tmp" in n] == []
    # kill -9 debris (finally never ran): aged past the gate, swept
    orphan = path + ".bin.tmp.99999.deadbeef"
    with open(orphan, "wb") as f:
        f.write(b"x" * 64)
    old = _time.time() - 7 * 3600
    os.utime(orphan, (old, old))
    datasets.streamed_packed_cache(path, **kw)
    assert not os.path.exists(orphan)


def test_streamed_cache_geometry_mismatch_still_rejected(tmp_path):
    from tpu_distalg.utils import datasets

    path = str(tmp_path / "cache")
    kw = _tiny_cache_kwargs()
    datasets.streamed_packed_cache(path, **kw)
    with pytest.raises(ValueError, match="was built with"):
        datasets.streamed_packed_cache(path, **{**kw, "seed": 1})


# --------------------------- ADVICE regression: ssgd_stream prefetch path

def test_stream_prefetch_producer_error_propagates_and_recovers(mesh4):
    from tpu_distalg.models import ssgd, ssgd_stream
    from tpu_distalg.utils import datasets as dsets

    X_train, y_train, X_test, y_test = dsets.breast_cancer_split()
    cfg = ssgd.SSGDConfig(n_iterations=4, sampler="fused_gather",
                          gather_block_rows=32, fused_pack=4,
                          eval_test=False, shuffle_seed=0)
    X2h, meta = ssgd_stream.pack_host(X_train, y_train, mesh4, cfg)
    trainer = ssgd_stream.StreamTrainer(X2h, meta, mesh4, cfg)
    import jax.numpy as jnp

    from tpu_distalg.ops import logistic
    from tpu_distalg.utils import prng

    d = X_train.shape[1]
    w0 = jnp.zeros((meta["d_total"],), jnp.float32).at[:d].set(
        logistic.init_weights(prng.root_key(cfg.init_seed), d))

    # the gather seam lives on the trainer's ShardedDataset since the
    # data-subsystem port (tpu_distalg/data/) — the producer thread is
    # pipeline.stream_staged's
    real_gather = trainer.dataset.gather
    calls = {"n": 0}

    def exploding_gather(ids_step):
        calls["n"] += 1
        if calls["n"] == 2:
            raise OSError("disk read failed (injected)")
        return real_gather(ids_step)

    trainer.dataset.gather = exploding_gather
    with pytest.raises(OSError, match="injected"):
        trainer.run(w0, 0, 4)
    # the trainer must stay usable after the producer died
    trainer.dataset.gather = real_gather
    w, _ = trainer.run(w0, 0, 4)
    assert np.all(np.isfinite(np.asarray(w)))
