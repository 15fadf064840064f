"""The data layer from inside (PR 49): the one helper that asks a device
for its memory (telemetry/events.memory), the spans that are handed
devices and end with what they left on the chip, the work a loader
phase says it made, the counters kept without a sink, the span round
the first Pallas import, ``tda report``'s memory columns and ``hbm:``
line, and the benchmark's readers of all of it
(benchmarks/harness/spans.py and the thirteen files under
benchmarks/layer_metrics/ that call it), loaded by path as the harness
loads them."""

import ast
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpu_distalg.telemetry import events, heartbeat, report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "tpu_distalg")
BENCH = os.path.join(REPO, "benchmarks")
GB = 10 ** 9


class Chip:
    """A device as far as ``events.memory`` looks: its allocator's
    numbers now, and how often it was asked."""

    def __init__(self, in_use=0, peak=0, stats="tpu"):
        self.in_use, self.peak, self.stats, self.asked = in_use, peak, stats, 0

    def memory_stats(self):
        self.asked += 1
        if self.stats == "cpu":
            return None
        if self.stats == "bare":       # a backend with other keys only
            return {"num_allocs": 3}
        return {"bytes_in_use": self.in_use, "peak_bytes_in_use": self.peak,
                "bytes_limit": 16 * GB}

    def hold(self, more):
        self.in_use += more
        self.peak = max(self.peak, self.in_use)


@pytest.fixture()
def ring():
    """The sink off, an empty ring, an empty counter store."""
    events.configure(False)
    events._FINISHED.clear()
    yield events
    events.configure(False)


def _forbid_files(monkeypatch):
    def forbidden(*a, **k):
        raise AssertionError("a file was opened")

    monkeypatch.setattr(events.EventSink, "__init__", forbidden)
    monkeypatch.setattr("builtins.open", forbidden)


# ---- the memory helper -------------------------------------------------

@pytest.mark.parametrize("chips,want", [
    ([Chip(3 * GB, 5 * GB)], [(3 * GB, 5 * GB)]),
    ([Chip(1, 2), Chip(30, 40), Chip(5, 6), Chip(7, 8)],
     [(1, 2), (30, 40), (5, 6), (7, 8)]),
    ([Chip(stats="cpu")], None),
    ([Chip(1, 2), Chip(stats="cpu")], None),
    ([Chip(stats="bare")], None),
    ([], None),
], ids=["one", "four_in_order", "cpu", "one_without", "other_keys", "none"])
def test_memory_reads_the_devices_it_is_handed(monkeypatch, chips, want):
    """Per device ``(bytes_in_use, peak_bytes_in_use)``, ``None`` where a
    backend keeps no stats; it looks no device up and opens no file."""
    import jax

    def no_lookup(*a, **k):
        raise AssertionError("telemetry looked devices up")

    monkeypatch.setattr(jax, "devices", no_lookup)
    monkeypatch.setattr(jax, "local_devices", no_lookup)
    _forbid_files(monkeypatch)
    assert events.memory(chips) == want
    assert events.memory(iter(chips)) == want       # any iterable


def test_the_cpu_backend_keeps_no_stats_so_a_span_has_no_field(ring):
    import jax

    devices = jax.devices()[:1]
    assert events.memory(devices) is None
    with events.span("als:prepare", devices, rows=1):
        pass
    assert events.finished()[-1].fields == {"rows": 1}


def test_memory_stats_is_asked_for_in_one_place():
    """``telemetry/events.memory`` is the package's only caller."""
    callers = []
    for d, _, names in os.walk(PACKAGE):
        for n in names:
            if n.endswith(".py"):
                with open(os.path.join(d, n)) as f:
                    if "memory_stats(" in f.read():
                        callers.append(os.path.relpath(
                            os.path.join(d, n), PACKAGE))
    assert callers == [os.path.join("telemetry", "events.py")]


@pytest.mark.parametrize("module", [
    "events", "heartbeat", "report", "supervisor", "names", "__init__"])
def test_telemetry_imports_no_jax_when_it_is_imported(module):
    with open(os.path.join(PACKAGE, "telemetry", module + ".py")) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        names = ([a.name for a in node.names]
                 if isinstance(node, ast.Import) else
                 [node.module or ""] if isinstance(node, ast.ImportFrom)
                 else [])
        assert not [n for n in names if n.split(".")[0] == "jax"], module


# ---- a span that is handed devices -------------------------------------

def test_a_span_handed_devices_ends_with_what_it_left(ring, monkeypatch):
    _forbid_files(monkeypatch)
    a, b = Chip(1 * GB, 2 * GB), Chip(1 * GB, 1 * GB)
    with events.span("pagerank:plan", [a, b], rg=8):
        a.hold(3 * GB)
        b.hold(5 * GB)
        a.hold(-2 * GB)
    with events.span("pagerank:prepare"):
        pass
    plan, bare = events.finished()
    assert plan.fields == {
        "rg": 8, "hbm_in_use": [2 * GB, 6 * GB],
        "hbm_peak": [4 * GB, 6 * GB], "hbm_in_use_start": [GB, GB]}
    assert bare.fields == {}
    assert (a.asked, b.asked) == (2, 2)       # its two ends, no more


def test_a_failed_span_still_samples_and_a_sink_writes_the_fields(
        ring, tmp_path):
    chip = Chip(GB, GB)
    events.configure(str(tmp_path))
    with pytest.raises(ValueError):
        with events.span("als:generate", [chip], slots=4):
            chip.hold(GB)
            raise ValueError("x")
    events.configure(False)
    ends = [e for e in report.load_events(str(tmp_path))
            if e["ev"] == "span_end"]
    assert ends[0]["hbm_in_use"] == [2 * GB] and ends[0]["ok"] is False
    assert ends[0]["hbm_in_use_start"] == [GB] and ends[0]["slots"] == 4
    starts = [e for e in report.load_events(str(tmp_path))
              if e["ev"] == "span_start"]
    assert "hbm_in_use" not in starts[0]


def test_no_jit_span_and_no_step_takes_a_sample(ring):
    """The ``jit:*`` pairs open through ``begin`` with no devices: a
    function traced, lowered and compiled under a sampled span adds no
    sample of its own."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.utils import compile_cache

    compile_cache.configure()
    chip = Chip(GB, GB)

    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 - 1.0

    with events.span("train:segment", [chip], steps=5):
        for _ in range(5):
            fresh(jnp.ones(7)).block_until_ready()
    done = events.finished()
    jit = [s for s in done if s.name.startswith("jit:")]
    assert {s.name for s in jit} >= {"jit:trace", "jit:lower", "jit:compile"}
    assert all("hbm_in_use" not in s.fields for s in jit)
    assert chip.asked == 2


def test_the_train_spans_sample_the_devices_the_state_lies_on(
        ring, monkeypatch, tmp_path):
    import jax
    import jax.numpy as jnp

    from tpu_distalg.utils import checkpoint

    seen = []

    def fake(devices):
        seen.append(list(devices))
        return [(len(seen) * 10, len(seen) * 20) for _ in devices] or None

    monkeypatch.setattr(events, "memory", fake)
    state0 = jnp.zeros(3)

    def run_seg(fn, state, t):
        return fn(state), jnp.zeros(2)

    checkpoint.run_segmented(
        str(tmp_path), 2, 4, make_seg_fn=lambda seg: lambda s: s + seg,
        run_seg=run_seg, state0=state0, tag="t")
    by_name = {}
    for s in events.finished():
        by_name.setdefault(s.name, []).append(s)
    assert {len(by_name[n]) for n in
            ("train:segment", "train:checkpoint")} == {2}
    assert len(by_name["train:build"]) == 1
    for name in ("train:build", "train:segment", "train:checkpoint"):
        for s in by_name[name]:
            assert len(s.fields["hbm_in_use"]) == 1, name
            assert s.fields["hbm_peak"][0] == 2 * s.fields["hbm_in_use"][0]
    assert all(d == list(state0.devices()) for d in seen)
    assert by_name["train:checkpoint"][0].fields["bytes"] > 0


# ---- what a loader phase says it made ----------------------------------

def _fake_memory(monkeypatch):
    """Every sample reads 1 kB more in use than the one before."""
    calls = [0]

    def fake(devices):
        calls[0] += 1
        return [(calls[0] * 1000, calls[0] * 1000 + 500)
                for _ in devices] or None

    monkeypatch.setattr(events, "memory", fake)


def _als(mesh):
    from tpu_distalg.models import als

    du = np.array([40, 3, 9, 17, 5, 11, 8, 7])
    di = np.full(10, 10)
    arrays, _ = als.build_ratings_table(
        100, 8, 10, 5, mesh, data_seed=2, n_heldout=16, degrees=(du, di),
        geometry=dict(seg_slots=8, piece_segs=4, batch=8, classes=(1, 2)))
    return sum(a.nbytes for a in arrays)


def _pagerank(mesh):
    from tpu_distalg.models import pagerank

    graph = pagerank.build_rmat_graph(mesh, 8, 16, None, 3)
    plan = pagerank.prepare_device_spmv(graph, mesh)
    return plan.nbytes


def _hashed(mesh):
    from tpu_distalg.models import ssgd

    cfg = ssgd.SSGDConfig(
        n_iterations=1, sampler="fused_gather", eval_test=False,
        gather_block_rows=128, mini_batch_fraction=0.25, seed=42)
    X, _ = ssgd.build_hashed_table(1500, 6, 10, mesh, cfg, data_seed=3)
    return X.nbytes


def _kmeans(mesh):
    from tpu_distalg.models import kmeans
    from tpu_distalg.utils import datasets

    rows, _ = datasets.gaussian_mixture_rows(4, 3, seed=1)
    data, _, _ = kmeans.build_scaled(mesh, 2000, rows, 3)
    return data.nbytes


LOADERS = {
    "als": (_als, "als:prepare", {
        "als:pack": ("bytes",), "als:generate": ("slots", "bytes"),
        "als:heldout": ("pairs", "bytes")}),
    "pagerank": (_pagerank, "pagerank:prepare", {
        "pagerank:generate": ("rows", "bytes"),
        "pagerank:dedup": ("rows", "bytes"),
        "pagerank:plan": ("slots", "bytes")}),
    "hashed": (_hashed, "ssgd:prepare", {"ssgd:generate": ("rows", "bytes")}),
    "kmeans": (_kmeans, "kmeans:prepare", {}),
}


@pytest.mark.parametrize("family", sorted(LOADERS))
def test_a_loaders_spans_say_what_they_made_and_left(
        ring, mesh1, monkeypatch, family):
    """The root and every phase are handed the mesh's devices; a phase
    states its count under one spelling (``rows`` / ``slots`` /
    ``pairs``) and its arrays' own ``bytes``; the root's ``bytes`` is
    what the loader leaves resident."""
    _fake_memory(monkeypatch)
    load, root, phases = LOADERS[family]
    resident = load(mesh1)
    done = {s.name: s for s in events.finished()}
    assert done[root].fields["bytes"] >= resident > 0
    for name in (root, *phases):
        f = done[name].fields
        assert len(f["hbm_in_use"]) == len(f["hbm_peak"]) == 1, name
        assert f["hbm_in_use"][0] > f["hbm_in_use_start"][0], name
        for key in phases.get(name, ()):
            assert isinstance(f[key], int) and f[key] > 0, (name, key)
    assert not [s.name for s in events.finished()
                if s.name.startswith("jit:") and "hbm_in_use" in s.fields]


def test_the_root_says_no_more_than_its_phases_made(ring, mesh1, monkeypatch):
    """ALS's root once stated the plan's count with the two factor
    tables, which no loader makes: its ``bytes`` is now the arrays'."""
    _fake_memory(monkeypatch)
    resident = _als(mesh1)
    done = {s.name: s for s in events.finished()}
    assert done["als:prepare"].fields["bytes"] == resident
    assert done["als:generate"].fields["bytes"] > 0.5 * resident


# ---- counters without a sink -------------------------------------------

def test_counters_are_kept_with_the_sink_off(ring, monkeypatch):
    _forbid_files(monkeypatch)
    events.counter("spmv_plan_rejections")
    events.counter("spmv_slots_padded", 40)
    events.counter("spmv_slots_padded", 2)
    got = events.counters()
    assert got == {"spmv_plan_rejections": 1, "spmv_slots_padded": 42}
    got["spmv_slots_padded"] = 0                     # a copy
    assert events.counters()["spmv_slots_padded"] == 42


def test_configure_starts_a_runs_counters_again_and_a_sink_flushes_them(
        ring, tmp_path):
    events.counter("before")
    sink = events.configure(str(tmp_path / "a"))
    assert events.counters() == {} and not hasattr(sink, "bump")
    events.counter("checkpoints_saved", 2)
    assert sink.counters() == events.counters() == {"checkpoints_saved": 2}
    events.configure(str(tmp_path / "b"))            # closes the first
    events.counter("other")
    events.configure(False)
    assert events.counters() == {}
    lines = {d: [e for e in report.load_events(str(tmp_path / d))
                 if e["ev"] == "counters"] for d in "ab"}
    assert [e["counters"] for e in lines["a"]] == [{"checkpoints_saved": 2}]
    assert [e["counters"] for e in lines["b"]] == [{"other": 1}]
    assert report.summarize(report.load_events(str(tmp_path / "a")))[
        "counters"] == {"checkpoints_saved": 2}


def test_a_heartbeat_carries_the_counters_sink_or_no_sink(ring):
    sent = []
    events.counter("beats", 3)
    heartbeat.Heartbeat(
        1.0, None, emit_fn=lambda ev, **f: sent.append((ev, f))).beat()
    assert sent[0][0] == "heartbeat" and sent[0][1]["counters"] == {
        "beats": 3}


def test_the_pagerank_loaders_counters_need_no_sink(ring, mesh1):
    _pagerank(mesh1)
    got = events.counters()
    assert got["spmv_slots_padded"] > 0
    assert got["pagerank_shard_overflow"] == 0
    assert got["pagerank_shard_edges_max"] >= got["pagerank_shard_edges_mean"]


# ---- the first Pallas import -------------------------------------------

def test_every_kernel_module_takes_pallas_through_the_one_door():
    direct = []
    for d, _, names in os.walk(PACKAGE):
        for n in names:
            path = os.path.join(d, n)
            if n.endswith(".py") and n != "pallas_api.py":
                with open(path) as f:
                    tree = ast.parse(f.read())
                for node in ast.walk(tree):
                    if isinstance(node, ast.ImportFrom) and (
                            node.module or "").startswith(
                                "jax.experimental") and (
                            "pallas" in (node.module or "")
                            or any(a.name == "pallas" for a in node.names)):
                        direct.append(os.path.relpath(path, PACKAGE))
    assert direct == []


def test_the_first_pallas_import_lies_under_one_span():
    """In a process of its own: two kernel modules, one ``import:pallas``
    span, a child of the span that was open when the first needed it."""
    code = (
        "from tpu_distalg.telemetry import events\n"
        "with events.span('ssgd:prepare'):\n"
        "    import tpu_distalg.ops.pallas_hashed\n"
        "import tpu_distalg.ops.pallas_lloyd, json\n"
        "got = [(s.name, s.parent, s.seconds) for s in events.finished()\n"
        "       if not s.name.startswith('jit:')]\n"
        "root = [s.id for s in events.finished()\n"
        "        if s.name == 'ssgd:prepare'][0]\n"
        "print(json.dumps([got, root]))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    got, root = json.loads(out.stdout.strip().splitlines()[-1])
    imports = [g for g in got if g[0] == "import:pallas"]
    assert len(imports) == 1
    assert imports[0][1] == root and imports[0][2] > 0
    assert [g[0] for g in got] == ["import:pallas", "ssgd:prepare"]


# ---- tda report --------------------------------------------------------

def _log():
    """An ALS loader's spans as a sink wrote them on one chip: the
    generate phase raised the peak, the held-out draw freed what it
    used, the fit's first segment raised it again."""
    lines, ids = [], iter(range(1, 99))

    def span(name, parent, seconds, **f):
        sid = next(ids)
        lines.append({"ev": "span_start", "run": "r", "name": name,
                      "id": sid, "parent": parent})
        end = {"ev": "span_end", "run": "r", "name": name, "id": sid,
               "parent": parent, "seconds": seconds, "ok": True, **f}
        return sid, end

    def mem(start, end, peak):
        return dict(hbm_in_use=[int(end * GB)], hbm_peak=[int(peak * GB)],
                    hbm_in_use_start=[int(start * GB)])

    root, prepare = span("als:prepare", None, 20.0)
    lines.append(span("als:pack", root, 7.0, **mem(0.0, 0.5, 0.5))[1])
    gen, generate = span("als:generate", root, 10.0, **mem(0.5, 6.0, 8.0))
    lines.append(span("jit:compile", gen, 2.0, fun="side")[1])
    lines.append(generate)
    lines.append(span("als:heldout", root, 2.0, **mem(6.0, 5.0, 8.0))[1])
    prepare.update(mem(0.0, 5.0, 8.0))
    lines.append(prepare)
    return lines, span, mem


def test_report_prints_memory_beside_seconds_and_the_hbm_line():
    lines, span, mem = _log()
    s = report.summarize(lines)
    tree = {tuple(n["path"]): n for n in s["span_tree"]}
    gen = tree[("als:prepare", "als:generate")]
    assert gen["hbm_rise_bytes"] == int(5.5 * GB) and gen["hbm_set_peak"]
    assert gen["self_seconds"] == 8.0
    assert tree[("als:prepare", "als:heldout")]["hbm_rise_bytes"] == -GB
    assert [n["name"] for n in s["span_tree"] if n.get("hbm_set_peak")] \
        == ["als:generate"]
    assert "hbm_in_use_bytes" not in tree[
        ("als:prepare", "als:generate", "jit:compile")]
    assert s["hbm"] == {"peak_bytes": 8 * GB, "in_use_bytes": 5 * GB,
                        "last_span": "als:prepare", "devices": 1,
                        "set_under": ["als:prepare", "als:generate"]}
    text = report.render(s)
    assert ("als:generate: 10.0s total over 1 span(s), max 10.0s, self "
            "8.0s, hbm +5.500 GB -> 6.000 GB *peak") in text
    assert "self 2.0s, hbm -1.000 GB -> 5.000 GB\n" in text
    assert text.count("*peak") == 2            # the legend and the span
    assert ("hbm: peak 8.000 GB set under als:prepare > als:generate, "
            "5.000 GB in use at the last span's end (als:prepare)") in text


def test_the_peak_mark_follows_the_span_under_which_it_last_rose():
    lines, span, mem = _log()
    build, built = span("train:build", None, 30.0)
    lines.append(span("train:segment", build, 29.0,
                      **mem(5.9, 6.7, 11.6))[1])
    built.update(mem(5.9, 6.7, 11.6))
    lines.append(built)
    # four devices, the third the fullest
    lines.append(span("train:checkpoint", None, 1.0, hbm_in_use=[
        GB, GB, 3 * GB, GB], hbm_peak=[2 * GB] * 4,
        hbm_in_use_start=[GB] * 4)[1])
    s = report.summarize(lines)
    marked = [n["path"] for n in s["span_tree"] if n.get("hbm_set_peak")]
    assert marked == [["train:build", "train:segment"]]
    text = report.render(s)
    assert "hbm: peak 11.600 GB set under train:build > train:segment, " \
        "3.000 GB in use at the last span's end (train:checkpoint); the " \
        "fullest of 4 devices" in text


def test_a_log_without_the_fields_prints_no_memory(tmp_path):
    events.configure(str(tmp_path))
    with events.span("cli:ssgd"):
        pass
    events.configure(False)
    s = report.summarize(report.load_events(str(tmp_path)))
    assert s["hbm"] is None
    text = report.render(s)
    assert "hbm" not in text and "cli:ssgd" in text


# ---- the benchmark's readers -------------------------------------------

READERS = {   # metric -> what it reads off the ring below
    "pack_s.als": 1.5, "generate_s.als": 2.0, "heldout_s.als": 0.5,
    "lists_s.als": 0.75, "generate_s.graph": 3.0, "dedup_s.graph": 1.0,
    "generate_s.lr": 2.5, "data_unspanned_s": 2.0,
    "hbm_loader_peak_gb": 8.05, "hbm_resident_gb": 6.5,
    "padding_pct.als": 20.0, "gather_cold_pct.als": 26.36,
    "padding_pct.graph": 50.0}


class StubCtx:
    """What a reader is handed, as far as these look."""

    def __init__(self, spans=(("import_program", 0.0, 1.0),
                              ("data_build", 10.0, 30.0),
                              ("warm_up", 30.0, 40.0))):
        self.spans = list(spans)


@pytest.fixture()
def readers(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    for name in [n for n in sys.modules if n.split(".")[0] == "harness"]:
        monkeypatch.delitem(sys.modules, name)

    def load(metric):
        path = (os.path.join(BENCH, "harness", "spans.py")
                if metric == "spans" else
                os.path.join(BENCH, "layer_metrics", metric + ".py"))
        spec = importlib.util.spec_from_file_location(
            "bench_reader_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    return load


def _keep(name, sid, parent, t0, seconds, **fields):
    events._FINISHED.append(events.Finished(
        name, sid, parent, t0, seconds, True, fields))


def _mem(end, peak):
    return dict(hbm_in_use=[int(end * GB), int(end * GB) // 2],
                hbm_peak=[int(peak * GB) // 2, int(peak * GB)],
                hbm_in_use_start=[0, 0])


def _a_loader():
    """Every family's phases in one ``data_build`` of 10.0 to 30.0 (a
    ring no one run would hold: each reader looks at its own names).
    ``als:lists`` comes twice; ``als:generate`` holds a compile that
    holds a cache load; a root's own time and a gap under no span are
    left over; a span of a listed name ends after ``data_build``."""
    _keep("import:pallas", 1, None, 10.0, 1.0)
    _keep("als:pack", 3, 2, 11.0, 1.5, bytes=8)
    _keep("jit:trace", 5, 4, 12.5, 0.25, fun="side")
    _keep("jit:cache_load", 7, 6, 12.75, 0.25, fun="side")
    _keep("jit:compile", 6, 4, 12.75, 0.75, fun="side", hit=True)
    _keep("als:generate", 4, 2, 12.5, 3.0, slots=64, **_mem(6.0, 8.05))
    _keep("als:heldout", 8, 2, 15.5, 0.5, **_mem(5.0, 8.05))
    _keep("als:lists", 9, 2, 16.0, 0.5, side=0, **_mem(6.0, 8.0))
    _keep("als:lists", 10, 2, 16.5, 0.25, side=1, **_mem(6.5, 8.0))
    _keep("als:prepare", 2, None, 11.0, 6.0, padding_share=1.25,
          gather_cold_share=0.2636, **_mem(6.5, 8.05))
    _keep("jit:lower", 12, 11, 17.5, 1.0, fun="generate")
    _keep("pagerank:generate", 11, None, 17.5, 4.0, rows=9)
    _keep("pagerank:dedup", 13, None, 21.5, 1.0, rows=7)
    _keep("pagerank:plan", 15, 14, 23.0, 1.0, slots=16)
    _keep("pagerank:prepare", 14, None, 22.5, 2.0, padding_share=2.0)
    _keep("jit:compile", 18, 17, 25.0, 0.5, fun="body")
    _keep("ssgd:generate", 17, 16, 25.0, 3.0, rows=5)
    _keep("ssgd:prepare", 16, None, 24.75, 4.0, rows=5)
    # the reference's, after the window: the same names, left out
    _keep("ssgd:generate", 20, None, 50.0, 9.0, **_mem(15.0, 15.0))
    _keep("als:prepare", 21, None, 60.0, 9.0, padding_share=9.0,
          gather_cold_share=0.9, **_mem(15.5, 15.5))


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_reader_reads_its_phase_inside_data_build(ring, readers, metric):
    _a_loader()
    got = readers(metric).read(StubCtx())
    assert got == pytest.approx(READERS[metric], abs=1e-9), metric


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_reader_gives_none_where_there_is_nothing_to_read(
        ring, readers, monkeypatch, metric):
    reader = readers(metric)
    assert reader.read(StubCtx()) is None              # an empty ring
    _keep("kmeans:prepare", 1, None, 50.0, 1.0)        # after data_build
    assert reader.read(StubCtx()) is None
    _a_loader()
    assert reader.read(StubCtx()) is not None
    assert reader.read(StubCtx(spans=[("warm_up", 0.0, 99.0)])) is None
    # a commit before the ring: the readers still load and give nothing
    monkeypatch.delattr(events, "finished")
    assert reader.read(StubCtx()) is None


def test_a_parent_without_the_memory_fields_reads_seconds_and_no_memory(
        ring, readers):
    """The readers laid over a commit whose spans sample nothing."""
    _keep("als:generate", 2, 1, 11.0, 2.0, slots=4)
    _keep("als:prepare", 1, None, 11.0, 3.0, padding_share=1.25)
    ctx = StubCtx()
    assert readers("generate_s.als").read(ctx) == 2.0
    assert readers("padding_pct.als").read(ctx) == pytest.approx(20.0)
    assert readers("gather_cold_pct.als").read(ctx) is None
    assert readers("hbm_loader_peak_gb").read(ctx) is None
    assert readers("hbm_resident_gb").read(ctx) is None


def test_self_times_and_the_unspanned_rest_add_up_to_the_interval(
        ring, readers):
    _a_loader()
    spans, ctx = readers("spans"), StubCtx()
    inside, t0, t1 = spans.inside(ctx)
    assert (t0, t1) == (10.0, 30.0) and len(inside) == 18
    names = sorted({s.name for s in inside})
    own = {n: spans.self_seconds(ctx, n) for n in names}
    assert own["als:prepare"] == 0.25 and own["pagerank:prepare"] == 1.0
    assert own["jit:compile"] == 1.0 and own["jit:cache_load"] == 0.25
    assert own["ssgd:prepare"] == 1.0 and own["import:pallas"] == 1.0
    rest = spans.unspanned_seconds(ctx)
    assert sum(own.values()) + rest == pytest.approx(t1 - t0)
    assert rest == 2.0
    assert spans.self_seconds(ctx, "kmeans:prepare") is None


def test_the_manifest_lists_the_new_metrics_where_the_issue_says():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    als = ["als100_253m_sweep1"]
    graph = ["pagerank_g500_24_resident", "pagerank_g500_sharded4_job10"]
    pairs = "lrpairs3728_350k_frac01"
    lr = ["lrhash39_46m_frac01", "lrwide11_150m_frac01", pairs]
    # the five loader cells of PR 49, the dense closure's, which PR 52
    # appended to the three lists its loader's spans feed, and PR 54's
    # cell of ragged rows, appended to those and to ``generate_s.lr``,
    # and PR 60's pair-set closure, appended to the same three
    five = [lr[0], *als, *graph, lr[1], "closure_grid250_round1", pairs,
            "closure_tree17_round1"]
    want = {"pack_s.als": als, "generate_s.als": als, "heldout_s.als": als,
            "lists_s.als": als, "generate_s.graph": graph,
            "dedup_s.graph": graph, "generate_s.lr": lr,
            "data_unspanned_s": five, "hbm_loader_peak_gb": five,
            "hbm_resident_gb": five, "padding_pct.als": als,
            "gather_cold_pct.als": als, "padding_pct.graph": graph}
    assert set(want) == set(READERS)
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("pack_s.als")
    tail = manifest["per_layer"][at:at + len(want)]
    assert [m["name"] for m in tail] == list(want)     # appended, in order
    later = [n for n in names[at + len(want):]          # then PR 52's
             if n.split(".")[-1] in ("als", "graph", "lr", "kmeans")]
    assert later == ["rowsum_ms_per_step.lr", "pair_padding_pct.lr",
                     "median_call_pairs_per_s.lr"]       # and PR 54's
    for m in tail:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"] == want[m["name"]], m["name"]
        assert m["source"] == "program_span" and m["better"] == "lower"
        seconds = m["unit"] == "s"
        assert m["moves"] == ("setup_s" if seconds else "rows_per_s")
        assert m["layer"] == ("device" if m["unit"] == "GB" else "data")
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))
