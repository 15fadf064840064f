"""The record of what JAX traces, lowers, compiles and loads
(utils/compile_cache.py's listeners), the ring that keeps every
finished span in memory (telemetry/events.py), ``tda report``'s
per-function table, and the benchmark's four readers of the record
(benchmarks/layer_metrics/trace_s.py, lower_s.py, cache_load_s.py,
jit_traces.py), loaded by path as the harness loads them."""

import importlib.util
import os
import threading

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

from tpu_distalg.telemetry import events, report
from tpu_distalg.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = os.path.join(REPO, "benchmarks", "layer_metrics")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture()
def ring():
    """The listeners on, the sink off, and an empty ring."""
    events.configure(False)
    compile_cache.configure()
    events._FINISHED.clear()
    yield events
    events.configure(False)


def _fresh_fn():
    """A jitted function JAX holds no trace of (a new object a test)."""
    @jax.jit
    def seg(x):
        return jnp.tanh(x) * 2.0 + 1.0

    return seg


def _of(fun, spans=None):
    return [s for s in (events.finished() if spans is None else spans)
            if s.fields.get("fun") == fun]


# ---- the ring ----------------------------------------------------------

def test_a_span_is_kept_without_a_sink_and_opens_no_file(
        ring, tmp_path, monkeypatch):
    def forbidden(*a, **k):
        raise AssertionError("file I/O on the disabled telemetry path")

    monkeypatch.setattr(events.EventSink, "__init__", forbidden)
    monkeypatch.setattr(events.EventSink, "write", forbidden)
    monkeypatch.chdir(tmp_path)
    with events.span("outer", rows=3):
        with events.span("inner"):
            pass
    with pytest.raises(ValueError):
        with events.span("broken"):
            raise ValueError("x")
    inner, outer, broken = events.finished()
    assert (inner.name, outer.name) == ("inner", "outer")
    assert inner.parent == outer.id and outer.parent is None
    assert outer.fields == {"rows": 3}
    assert outer.t0 <= inner.t0
    assert inner.t0 + inner.seconds <= outer.t0 + outer.seconds
    assert (outer.ok, broken.ok, broken.parent) == (True, False, None)
    assert os.listdir(tmp_path) == []
    assert events.current() is None


def test_the_ring_is_bounded_and_finished_is_a_copy(ring):
    for _ in range(events.RING_SIZE + 10):
        events.end(events.begin("tick"))
    got = events.finished()
    assert len(got) == events.RING_SIZE
    got.clear()
    assert len(events.finished()) == events.RING_SIZE


def test_begin_and_end_nest_like_a_span(ring):
    with events.span("phase"):
        a = events.begin("half", k=1)
        assert events.current() is a
        a.fields["late"] = True
        done = events.end(a)
    phase = events.finished()[-1]
    assert done.parent == phase.id and done.fields == {"k": 1, "late": True}


# ---- what JAX does to a function ---------------------------------------

def test_a_first_call_is_traced_lowered_and_compiled_under_its_span(ring):
    seg = _fresh_fn()
    with events.span("train:segment") as _:
        seg(jnp.ones(7)).block_until_ready()
    spans = events.finished()
    segment = [s for s in spans if s.name == "train:segment"][0]
    mine = _of("seg", spans)
    assert [s.name for s in mine] == ["jit:trace", "jit:lower",
                                      "jit:compile"]
    assert all(s.parent == segment.id and s.ok for s in mine)
    assert all(s.seconds > 0 for s in mine)
    t = [s.t0 for s in mine]
    assert t == sorted(t) and segment.t0 <= t[0]
    assert sum(s.seconds for s in mine) <= segment.seconds
    # the jnp operations inside it are its own trace's, not spans
    assert mine[0].fields["inner"] >= 1
    assert not _of("tanh", spans)


def test_a_second_call_adds_nothing_and_a_new_shape_traces_again(ring):
    seg = _fresh_fn()
    seg(jnp.ones(7)).block_until_ready()
    n = len(events.finished())
    seg(jnp.ones(7)).block_until_ready()
    assert len(events.finished()) == n
    with events.span("train:segment"):
        seg(jnp.ones(9)).block_until_ready()
    assert [s.name for s in _of("seg")] == [
        "jit:trace", "jit:lower", "jit:compile"] * 2


def test_a_trace_in_another_thread_hangs_under_that_threads_span(ring):
    seg = _fresh_fn()

    def worker():
        with events.span("worker:phase"):
            seg(jnp.ones(5)).block_until_ready()

    with events.span("main:phase"):
        th = threading.Thread(target=worker, daemon=False)
        th.start()
        th.join(timeout=60)
        assert not th.is_alive()
    spans = events.finished()
    phase = {s.name: s.id for s in spans}
    assert {s.parent for s in _of("seg", spans)} == {phase["worker:phase"]}


def test_configure_twice_registers_once(ring):
    compile_cache.configure()
    compile_cache.configure()
    for registered, mine in (
            (jax_monitoring.get_scalar_listeners(),
             compile_cache._on_start),
            (jax_monitoring.get_event_duration_listeners(),
             compile_cache._on_duration),
            (jax_monitoring.get_event_listeners(),
             compile_cache._on_event)):
        assert registered.count(mine) == 1


@pytest.mark.parametrize("hit", [True, False])
def test_a_cache_load_lies_inside_its_compile(ring, hit):
    """JAX's own order on a compile that asks the persistent cache
    (compiler.compile_or_get_cached inside pxla's timer), sent through
    ``jax.monitoring`` as JAX sends it."""
    with events.span("train:build"):
        jax_monitoring.record_scalar(COMPILE_EVENT, 0.0,
                                     fun_name="jit(seg)")
        if hit:
            jax_monitoring.record_event(
                "/jax/compilation_cache/cache_hits")
            jax_monitoring.record_event_duration_secs(
                "/jax/compilation_cache/compile_time_saved_sec", 2.5)
            jax_monitoring.record_event_duration_secs(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        else:
            jax_monitoring.record_event(
                "/jax/compilation_cache/cache_misses")
        jax_monitoring.record_event_duration_secs(
            COMPILE_EVENT, 0.3, fun_name="jit(seg)")
    *inner, build = events.finished()
    comp = inner[-1]
    assert (comp.name, comp.parent) == ("jit:compile", build.id)
    assert comp.fields["fun"] == "seg" and comp.fields["hit"] is hit
    if hit:
        (load,) = inner[:-1]
        assert (load.name, load.parent) == ("jit:cache_load", comp.id)
        assert load.fields["fun"] == "seg"
        assert 0.25 <= load.seconds < 0.26
        assert comp.fields["saved_s"] == 2.5
    else:
        assert inner[:-1] == [] and "saved_s" not in comp.fields


@pytest.mark.parametrize("sent,fun", [
    ("seg", "seg"), ("jit(seg)", "seg"), ("pmap(step)", "step"),
    ("jit(<lambda>)", "<lambda>"), ("jit_seg", "jit_seg")])
def test_one_name_for_a_function(sent, fun):
    assert compile_cache.fun_of(sent) == fun


def test_other_events_cost_a_lookup_and_record_nothing(ring):
    jax_monitoring.record_event("/jax/some/other/event")
    jax_monitoring.record_scalar("/jax/some/other/scalar", 1.0)
    jax_monitoring.record_event_duration_secs("/jax/some/duration", 1.0)
    # an end with no start on this thread is dropped, not invented
    jax_monitoring.record_event_duration_secs(
        COMPILE_EVENT, 1.0, fun_name="jit(orphan)")
    assert events.finished() == []


# ---- the sink and tda report -------------------------------------------

def test_with_a_sink_the_records_are_span_end_lines_the_tree_nests(
        ring, tmp_path, capsys):
    d = str(tmp_path / "tel")
    events.configure(d)
    seg = _fresh_fn()
    with events.span("train:build", tag="t"):
        with events.span("train:segment"):
            seg(jnp.ones(7)).block_until_ready()
    with events.span("train:segment"):
        seg(jnp.ones(9)).block_until_ready()
    events.configure(False)
    evts = report.load_events(d)
    ends = [e for e in evts if e["ev"] == "span_end"
            and e.get("fun") == "seg"]
    kept = _of("seg")
    assert [(e["name"], e["id"], e["parent"]) for e in ends] == [
        (s.name, s.id, s.parent) for s in kept]
    assert all(abs(e["seconds"] - s.seconds) < 1e-5
               for e, s in zip(ends, kept))
    paths = [tuple(n["path"]) for n in report.span_tree(evts)]
    for leaf in ("jit:trace", "jit:lower", "jit:compile"):
        assert ("train:build", "train:segment", leaf) in paths
        assert ("train:segment", leaf) in paths
    rows = {r["fun"]: r for r in report.summarize(evts)["jit_functions"]}
    row = rows["seg"]
    assert row["traced"] == 2 and row["hits"] == row["misses"] == 0
    first, second = row["under"]
    assert first.startswith("train:segment#") and first != second
    assert row["trace_s"] > 0 and row["lower_s"] > 0
    assert row["compile_s"] > 0 and row["load_s"] == 0
    report.report_main(d)
    out = capsys.readouterr().out
    (line,) = [ln for ln in out.splitlines()
               if ln.strip().startswith("seg ")]
    assert " 2 * " in line and first in line and second in line
    assert "compiles by function" in out


def test_report_table_counts_loads_hits_and_the_small_rest():
    def end(name, sid, parent, seconds, **f):
        return {"ev": "span_end", "run": "r", "name": name, "id": sid,
                "parent": parent, "seconds": seconds, "ok": True, **f}

    evts = [
        end("jit:trace", 2, 1, 0.5, fun="seg"),
        end("jit:lower", 3, 1, 0.25, fun="seg"),
        end("jit:cache_load", 5, 4, 0.125, fun="seg"),
        end("jit:compile", 4, 1, 0.25, fun="seg", hit=True),
        end("jit:trace", 6, 1, 0.001, fun="add"),
        end("jit:compile", 7, 1, 0.002, fun="add", hit=False),
        end("kmeans:init", 1, None, 2.0),
    ]
    seg, add = report.jit_functions(evts)
    assert seg == {"fun": "seg", "traced": 1, "trace_s": 0.5,
                   "lower_s": 0.25, "compile_s": 0.25, "load_s": 0.125,
                   "hits": 1, "misses": 0, "under": ["kmeans:init#1"]}
    assert (add["hits"], add["misses"]) == (0, 1)
    text = report.render(report.summarize(evts))
    assert "1h/0m" in text and "kmeans:init#1" in text
    assert "1 more under 0.01 s each: 0.003 s" in text
    assert report.render(report.summarize(evts[-1:])).count(
        "compiles by function") == 0


# ---- the benchmark's readers -------------------------------------------

class StubCtx:
    """What a reader is handed, as far as these four look."""

    def __init__(self, warm_up_end):
        self.spans = [("import_program", 0.0, 1.0),
                      ("data_build", 1.0, 2.0),
                      ("warm_up", 2.0, warm_up_end)]


@pytest.fixture()
def readers(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))

    def load(metric):
        spec = importlib.util.spec_from_file_location(
            "bench_reader_" + metric, os.path.join(READERS, metric + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    return load


def _keep(name, sid, parent, t0, seconds, **fields):
    events._FINISHED.append(events.Finished(
        name, sid, parent, t0, seconds, True, fields))


def _a_setup():
    """Two functions in set-up (one of them loaded from the cache, one
    compiling an eager constant inside its trace), a reference's after
    ``warm_up`` ends at 10.0."""
    _keep("jit:trace", 2, 1, 2.0, 0.5, fun="seg", inner=40)
    _keep("jit:lower", 3, 1, 2.5, 0.25, fun="seg")
    _keep("jit:cache_load", 5, 4, 2.75, 0.125, fun="seg")
    _keep("jit:compile", 4, 1, 2.75, 0.25, fun="seg", hit=True)
    _keep("jit:compile", 8, 7, 3.0, 0.0625, fun="ones")
    _keep("jit:lower", 9, 7, 3.0, 0.03125, fun="ones")
    _keep("jit:trace", 7, None, 3.0, 1.0, fun="fit")
    _keep("train:segment", 1, None, 2.0, 3.0)
    _keep("jit:trace", 10, None, 10.5, 4.0, fun="reference")
    _keep("jit:lower", 11, None, 14.5, 4.0, fun="reference")
    _keep("jit:cache_load", 13, 12, 18.5, 4.0, fun="reference")
    _keep("jit:compile", 12, None, 18.5, 4.0, fun="reference", hit=True)


@pytest.mark.parametrize("metric,want", [
    ("trace_s", 1.5), ("lower_s", 0.25), ("cache_load_s", 0.125),
    ("jit_traces", 2)])
def test_a_reader_sums_set_up_and_leaves_the_reference_out(
        ring, readers, metric, want):
    _a_setup()
    assert readers(metric).read(StubCtx(10.0)) == want


@pytest.mark.parametrize("metric,want", [
    ("trace_s", 0.5), ("lower_s", 0.25), ("cache_load_s", 0.125),
    ("jit_traces", 1)])
def test_a_reader_leaves_out_what_ended_after_warm_up(
        ring, readers, metric, want):
    _a_setup()
    assert readers(metric).read(StubCtx(3.5)) == want


@pytest.mark.parametrize("metric", [
    "trace_s", "lower_s", "cache_load_s", "jit_traces"])
def test_a_reader_gives_none_where_there_is_no_record(
        ring, readers, monkeypatch, metric):
    reader = readers(metric)
    assert reader.read(StubCtx(10.0)) is None       # an empty ring
    _keep("ssgd:prepare", 1, None, 2.0, 1.0)        # no jit:* span
    assert reader.read(StubCtx(10.0)) is None
    _a_setup()
    assert reader.read(StubCtx(10.0)) is not None
    # a commit before the ring: the readers still load and give nothing
    monkeypatch.delattr(events, "finished")
    assert reader.read(StubCtx(10.0)) is None


def test_the_manifest_lists_the_four_in_every_cell():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    four = ["trace_s", "lower_s", "cache_load_s", "jit_traces"]
    # by name: a later cell's metrics are appended after them (PR 36)
    tail = [m for m in manifest["per_layer"] if m["name"] in four]
    assert [m["name"] for m in tail] == four
    for m in tail:
        assert m["workloads"] == cells and m["moves"] == "setup_s"
        assert (m["layer"], m["source"]) == ("runtime", "program_counter")
        assert os.path.isfile(os.path.join(READERS, m["name"] + ".py"))
