"""The names the program owns in a trace (telemetry/names.py and the
spans of telemetry/events.py): device scopes in the lowered programs,
kernel names, host spans on the profiler's clock with their parents,
and the tree ``tda report`` prints from them. Every profiler session
of the tier-1 suite is in this file (one xdist worker gets it)."""

import dataclasses
import glob
import json
import os
import subprocess
import sys
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import pagerank, ssgd
from tpu_distalg.ops import graph as gops
from tpu_distalg.ops import pallas_kernels
from tpu_distalg.ops import pallas_pagerank as ppr
from tpu_distalg.telemetry import events, names, report
from tpu_distalg.utils import checkpoint as ckpt

CFG = ssgd.SSGDConfig(
    n_iterations=40, eval_test=False, sampler="fused_train",
    mega_steps=20, fused_pack=4, gather_block_rows=32, shuffle_seed=0,
)


@pytest.fixture()
def sink_dir(tmp_path):
    d = str(tmp_path / "tel")
    events.configure(d)
    try:
        yield d
    finally:
        events.configure(False)


def _spans(d, ev="span_end"):
    """The program's own spans: what JAX traced and compiled under them
    (``jit:*``, there once any test of the process has called
    ``compile_cache.configure``) is tests/test_compile_record.py's."""
    events.configure(False)
    return [e for e in report.load_events(d) if e["ev"] == ev
            and not e["name"].startswith(report.JIT_PREFIX)]


def _start_trace(trace_dir):
    """Annotations only: the Python tracer's per-call events are what
    makes a CPU session slow to stop."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _host_annotations(trace_dir):
    """``[(name, start_ns, end_ns, stats)]`` of the ``tda:*`` events a
    profiler session wrote, in start order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    got = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(events.ANNOTATION_PREFIX):
                        got.append((e.name, e.start_ns, e.end_ns,
                                    dict(e.stats)))
    return sorted(got, key=lambda g: g[1])


# ---- host spans --------------------------------------------------------

def test_checkpointed_train_phases_on_the_profilers_clock(
        mesh1, cancer_data, tmp_path, sink_dir):
    """One CPU profiler session round a tiny checkpointed run: the
    host plane holds prepare (pack and h2d inside it), then a segment
    and a checkpoint for each of the two segments, and the JSONL nests
    by ``parent`` the way the trace nests by time."""
    trace_dir = str(tmp_path / "trace")
    _start_trace(trace_dir)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")   # coarse-fraction geometry
            ssgd.train(*cancer_data, mesh1, CFG,
                       checkpoint_dir=str(tmp_path / "ck"),
                       checkpoint_every=20)
    finally:
        jax.profiler.stop_trace()
    notes = _host_annotations(trace_dir)
    assert [n for n, *_ in notes] == [
        "tda:ssgd:prepare", "tda:ssgd:pack", "tda:ssgd:h2d",
        "tda:train:build", "tda:train:segment", "tda:train:checkpoint",
        "tda:train:segment", "tda:train:checkpoint"]
    by_id = {st["id"]: (n, s, e) for n, s, e, st in notes}
    for name, start, end, st in notes:
        if st["parent"]:
            _, p_start, p_end = by_id[st["parent"]]
            assert p_start <= start and end <= p_end, name
    parent_name = {n: by_id[st["parent"]][0] if st["parent"] else None
                   for n, _, _, st in notes[:5]}
    assert parent_name == {
        "tda:ssgd:prepare": None, "tda:ssgd:pack": "tda:ssgd:prepare",
        "tda:ssgd:h2d": "tda:ssgd:prepare", "tda:train:build": None,
        "tda:train:segment": "tda:train:build"}
    assert notes[6][3]["parent"] == 0       # a steady segment stands alone

    ends = _spans(sink_dir)
    ids = {e["id"]: e["name"] for e in ends}
    nest = [(e["name"], ids.get(e["parent"])) for e in ends]
    assert nest == [
        ("ssgd:pack", "ssgd:prepare"), ("ssgd:h2d", "ssgd:prepare"),
        ("ssgd:prepare", None), ("train:segment", "train:build"),
        ("train:build", None), ("train:checkpoint", None),
        ("train:segment", None), ("train:checkpoint", None)]
    h2d = ends[1]
    assert h2d["bytes"] > 0 and h2d["rows"] >= cancer_data[0].shape[0]
    seg = [e for e in ends if e["name"] == "train:segment"]
    assert [(e["t0"], e["steps"]) for e in seg] == [(0, 20), (20, 20)]
    assert all(e["tag"] == "ssgd:fused_train" for e in seg)
    assert all(e["draw_form"] == "few" for e in seg)   # 1 of 13 blocks
    ck = [e for e in ends if e["name"] == "train:checkpoint"]
    assert [e["step"] for e in ck] == [20, 40]
    assert all(e["bytes"] > 0 for e in ck)


def test_train_span_and_report_say_how_the_draw_selected(
        mesh1, cancer_data, sink_dir):
    """An unsegmented fused run: ``ssgd:train`` carries the form the
    block draw took at this geometry, and ``tda report`` prints it."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")       # coarse-fraction geometry
        ssgd.train(*cancer_data, mesh1, dataclasses.replace(
            CFG, mega_steps=2, n_iterations=2))
    (train,) = [e for e in _spans(sink_dir) if e["name"] == "ssgd:train"]
    assert train["sampler"] == "fused_train"
    assert train["draw_form"] == "few"        # 1 of 13 blocks
    evts = report.load_events(sink_dir)
    assert "block draw: few" in report.render(
        report.summarize(evts)).splitlines()
    # a log of two runs that drew differently names both
    evts.append({"ev": "span_start", "name": "train:segment",
                 "draw_form": "sort"})
    assert "block draw: few, sort" in report.render(
        report.summarize(evts)).splitlines()


def test_span_annotates_with_the_sink_off(tmp_path, monkeypatch):
    """No sink, a profiler session: the span is in the trace with its
    id and parent, and no event file is written."""
    events.configure(False)

    def forbidden(*a, **k):
        raise AssertionError("file I/O on the disabled telemetry path")

    monkeypatch.setattr(events.EventSink, "__init__", forbidden)
    trace_dir = str(tmp_path / "trace")
    _start_trace(trace_dir)
    try:
        with events.span("outer", rows=3):
            with events.span("inner"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (outer, inner) = _host_annotations(trace_dir)
    assert (outer[0], inner[0]) == ("tda:outer", "tda:inner")
    assert inner[3]["parent"] == outer[3]["id"] and outer[3]["parent"] == 0
    assert outer[1] <= inner[1] and inner[2] <= outer[2]


def test_span_with_sink_off_and_no_jax_imports_nothing(tmp_path):
    """``events.py`` is stdlib-only (loaded here by path: the parent
    package's ``__init__`` imports the jax-backed layers): a span in a
    process that never imported jax leaves jax out and touches no
    file; the ring keeps both spans, and the marks land at both edges
    as they do with a sink."""
    code = (
        "import builtins, importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('ev', "
        f"{events.__file__!r})\n"
        "events = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(events)\n"
        "def no_open(*a, **k):\n"
        "    raise AssertionError('file I/O: %r' % (a,))\n"
        "builtins.open = no_open\n"
        "with events.span('outer', rows=1):\n"
        "    with events.span('inner'):\n"
        "        pass\n"
        "assert events.last_mark()[1] == 'outer'\n"
        "inner, outer = events.finished()\n"
        "assert (inner.name, inner.parent) == ('inner', outer.id)\n"
        "assert outer.fields == {'rows': 1} and outer.parent is None\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'numpy', 'tpu_distalg')]\n"
        "assert not bad, bad\n"
        "print('clean')\n")
    env = {k: v for k, v in os.environ.items() if k != events.ENV_DIR}
    got = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=60,
        check=False)
    assert got.returncode == 0, got.stderr
    assert got.stdout.strip() == "clean"
    assert os.listdir(tmp_path) == []


def test_span_parents_are_per_thread(sink_dir):
    """A span's parent is the span open on ITS thread: a worker's
    spans do not hang under whatever the main thread has open."""
    def worker():
        with events.span("worker:outer"):
            with events.span("worker:inner"):
                pass

    with events.span("main:outer"):
        th = threading.Thread(target=worker, daemon=False)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()
        with events.span("main:inner"):
            pass
    ends = {e["name"]: e for e in _spans(sink_dir)}
    assert ends["worker:outer"]["parent"] is None
    assert ends["worker:inner"]["parent"] == ends["worker:outer"]["id"]
    assert ends["main:inner"]["parent"] == ends["main:outer"]["id"]
    assert len({e["id"] for e in ends.values()}) == 4


def test_run_segmented_spans_and_heartbeat_mark(tmp_path, sink_dir):
    """The segment loop alone, with a stub segment: the span replaces
    the per-segment mark (the heartbeat reads ``train:segment`` while
    a segment runs), the first segment of a new length sits under
    ``train:build``, and a checkpoint span follows every segment."""
    seen = []

    def run_seg(fn, state, t0):
        seen.append(events.last_mark()[1])
        return (state[0] + 1.0,), np.zeros((fn,), np.float32)

    (w,), accs, start = ckpt.run_segmented(
        str(tmp_path / "ck"), 4, 10, make_seg_fn=lambda seg: seg,
        run_seg=run_seg, state0=(np.float32(0),), tag="stub")
    assert (float(w), len(accs), start) == (3.0, 10, 0)
    assert seen == ["train:segment"] * 3
    assert events.last_mark()[1] == "train:checkpoint"
    ends = _spans(sink_dir)
    ids = {e["id"]: e["name"] for e in ends}
    assert [(e["name"], ids.get(e["parent"])) for e in ends] == [
        ("train:segment", "train:build"), ("train:build", None),
        ("train:checkpoint", None),
        ("train:segment", None), ("train:checkpoint", None),
        # the last segment is shorter: a new length, built again
        ("train:segment", "train:build"), ("train:build", None),
        ("train:checkpoint", None)]
    build = [e for e in ends if e["name"] == "train:build"]
    assert [(e["tag"], e["seg"]) for e in build] == [("stub", 4),
                                                     ("stub", 2)]


def test_pagerank_prepare_is_the_plans_parent(mesh8, sink_dir):
    rng = np.random.default_rng(5)
    edges = np.stack([rng.integers(0, 4096, size=65536),
                      rng.integers(0, 4096, size=65536)], axis=1)
    el = gops.prepare_edges(edges.astype(np.int64), 4096)
    assert pagerank.prepare_device_spmv(el, mesh8) is not None
    ends = _spans(sink_dir)
    ids = {e["id"]: e["name"] for e in ends}
    ends = [e for e in ends if not e["name"].startswith("jit:")]
    assert [(e["name"], ids.get(e["parent"])) for e in ends] == [
        ("pagerank:plan", "pagerank:prepare"),
        ("pagerank:prepare", None)]
    assert ends[1]["distinct"] == int(el.n_edges)
    assert ends[1]["ranks_form"] == "resident"
    assert ends[0]["span"] <= ends[0]["ws"] == ends[1]["ws"]


# ---- tda report --------------------------------------------------------

def test_report_prints_the_tree_with_self_times(capsys):
    """Self time is duration minus what the child spans cover; the
    same name under two parents is two nodes; a log without ids (older
    than them) still reports, flat."""
    def ev(kind, name, sid, parent, seconds=None, run="r1"):
        e = {"ev": kind, "name": name, "run": run, "t_wall": 0.0}
        if sid is not None:
            e.update(id=sid, parent=parent)
        if seconds is not None:
            e.update(seconds=seconds, ok=True)
        return e

    evts = [
        ev("span_start", "cli:ssgd", 1, None),
        ev("span_start", "ssgd:prepare", 2, 1),
        ev("span_end", "ssgd:pack", 3, 2, 2.0),
        ev("span_end", "ssgd:h2d", 4, 2, 1.0),
        ev("span_end", "ssgd:prepare", 2, 1, 3.5),
        ev("span_end", "train:segment", 6, 5, 4.0),
        ev("span_end", "train:build", 5, 1, 4.25),
        ev("span_end", "train:segment", 7, 1, 1.0),
        ev("span_end", "train:segment", 8, 1, 1.0),
        ev("span_end", "cli:ssgd", 1, None, 10.0),
        ev("span_end", "old:span", None, None, 0.5, run="r0"),
    ]
    s = report.summarize(evts)
    tree = {tuple(n["path"]): n for n in s["span_tree"]}
    assert [tuple(n["path"]) for n in s["span_tree"]] == [
        ("cli:ssgd",), ("cli:ssgd", "ssgd:prepare"),
        ("cli:ssgd", "ssgd:prepare", "ssgd:pack"),
        ("cli:ssgd", "ssgd:prepare", "ssgd:h2d"),
        ("cli:ssgd", "train:build"),
        ("cli:ssgd", "train:build", "train:segment"),
        ("cli:ssgd", "train:segment"), ("old:span",)]
    assert tree[("cli:ssgd",)]["self_seconds"] == pytest.approx(
        10.0 - 3.5 - 4.25 - 2.0)
    assert tree[("cli:ssgd", "ssgd:prepare")]["self_seconds"] == \
        pytest.approx(0.5)
    assert tree[("cli:ssgd", "train:build")]["self_seconds"] == \
        pytest.approx(0.25)
    steady = tree[("cli:ssgd", "train:segment")]
    assert (steady["count"], steady["total_seconds"]) == (2, 2.0)
    assert s["phases"]["train:segment"]["count"] == 3     # flat, as before
    text = report.render(s)
    assert "  cli:ssgd: 10.0s total over 1 span(s)" in text
    assert "      ssgd:pack: 2.0s total" in text
    assert "self 0.25s" in text
    json.dumps(s)


def test_report_tree_from_a_recorded_run(sink_dir, capsys):
    from tpu_distalg import cli

    with events.span("outer"):
        with events.span("inner"):
            pass
        with events.span("inner"):
            pass
    events.configure(False)
    assert cli.main(["report", sink_dir]) == 0
    lines = capsys.readouterr().out.splitlines()
    at = lines.index(
        "phase durations (self = duration minus child spans):")
    assert lines[at + 1].startswith("  outer: ")
    assert lines[at + 2].startswith("    inner: ")
    assert "over 2 span(s)" in lines[at + 2]


# ---- device scopes -----------------------------------------------------

def _trainer(sampler, shards, **kw):
    """(jitted trainer, abstract arguments) at a tiny geometry."""
    from tpu_distalg.parallel import get_mesh

    mesh = get_mesh(data=shards, model=1, devices=jax.devices()[:shards])
    config = dataclasses.replace(
        CFG, sampler=sampler, n_iterations=4, mega_steps=2,
        gather_block_rows=128, fused_pack=16, mini_batch_fraction=0.25,
        **kw)
    d_t, y_col, v_col = pallas_kernels.packed_dims(31, 16)
    n = 128 * 8 * shards
    meta = dict(pack=16, d_total=d_t, y_col=y_col, v_col=v_col,
                n_padded=n)
    fn = ssgd.make_train_fn_fused(mesh, config, meta)
    one = jax.ShapeDtypeStruct((1,), jnp.float32)
    args = [jax.ShapeDtypeStruct((n // 16, 16 * d_t), jnp.bfloat16), one,
            one, jax.ShapeDtypeStruct((8, d_t), jnp.float32),
            jax.ShapeDtypeStruct((8,), jnp.float32),
            jax.ShapeDtypeStruct((d_t,), jnp.float32)]
    if config.comm != "dense":      # the error-feedback residual rides
        args.append(jax.ShapeDtypeStruct((shards, d_t), jnp.float32))
    return fn, args


# what only the block draw runs: the selection behind its batching rule
# and, in the 'sort' form, the sort (threefry words are also drawn by
# the int8 schedule's rounding, under the sync scope)
_DRAW_PRIMITIVES = ("custom_vmap_call", "sort")


def _scopes_of(fn, *args, primitives=_DRAW_PRIMITIVES):
    """(primitive, full name stack) of every equation of ``fn``'s
    jaxpr whose primitive is one of ``primitives``, at any depth, and
    of everything under a ``custom_vmap_call``. A nested jaxpr's name
    stacks are relative to the equation that holds it."""
    found = []

    def walk(jaxpr, prefix, inside):
        for eqn in jaxpr.eqns:
            stack = f"{prefix}/{eqn.source_info.name_stack}"
            name = eqn.primitive.name
            if inside or name in primitives:
                found.append((name, stack))
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    walk(inner, stack,
                         inside or name == "custom_vmap_call")

    walk(jax.make_jaxpr(fn)(*args).jaxpr, "", False)
    return found


@pytest.mark.parametrize("sampler,shards,extra,scopes", [
    ("fused_train", 1, dict(eval_test=True, eval_every=2),
     (names.SSGD_DRAW, names.SSGD_KERNEL, names.SSGD_UPDATE)),
    ("fused_gather", 4, {},
     (names.SSGD_DRAW, names.SSGD_KERNEL, names.SSGD_UPDATE,
      names.SSGD_SYNC)),
    ("fused_gather", 4, dict(comm="int8"),
     (names.SSGD_DRAW, names.SSGD_KERNEL, names.SSGD_UPDATE,
      names.SSGD_SYNC)),
])
def test_lowered_trainers_name_their_scopes(sampler, shards, extra,
                                            scopes):
    fn, args = _trainer(sampler, shards, **extra)
    text = fn.lower(*args).as_text(debug_info=True)
    for scope in scopes:
        assert scope + "/" in text, scope
    if shards == 1:
        assert names.SSGD_SYNC not in text    # one shard: nothing to sync
    # the draw's words, its selection and all the selection runs sit
    # under the draw's scope, and nothing of the draw sits outside it
    drawn = _scopes_of(fn, *args)
    for primitive in ("custom_vmap_call", "reduce"):
        assert primitive in {p for p, _ in drawn}, primitive
    for primitive, stack in drawn:
        assert names.SSGD_DRAW in stack, (primitive, stack)
    words = _scopes_of(fn, *args, primitives=("random_bits",))
    assert any(names.SSGD_DRAW in stack for _, stack in words)
    assert all("tda.ssgd." in stack for _, stack in words)
    assert any(names.SSGD_DRAW in ln and "custom_vmap_call" in ln
               for ln in text.splitlines())
    wrapper = ("fused_train_gathered" if sampler == "fused_train"
               else "fused_grad_sum_gathered")
    assert f"{names.SSGD_KERNEL}/jit({wrapper})" in text


@pytest.mark.parametrize("n_sampled,selects_by", [
    (4, "reduce"), (600, "sort")], ids=["few", "sort"])
def test_local_sgd_draws_under_the_same_scope(n_sampled, selects_by):
    from tpu_distalg.ops import sampling

    def draw(k):
        return sampling.sample_block_ids(k, 2, 1024, n_sampled)

    text = jax.jit(draw).lower(jax.random.key(0)).as_text(
        debug_info=True)
    assert names.SSGD_DRAW + "/" in text
    drawn = _scopes_of(draw, jax.random.key(0),
                       primitives=_DRAW_PRIMITIVES + ("random_bits",))
    assert {selects_by, "random_bits"} <= {p for p, _ in drawn}
    for primitive, stack in drawn:
        assert names.SSGD_DRAW in stack, (primitive, stack)


def _pallas_call_names(fn, *args):
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
                continue
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("kernel", [
    "_grad_kernel_gathered", "_train_kernel_gathered", "_spmv_kernel"])
def test_pallas_calls_carry_the_kernel_bodys_name(kernel):
    """The Mosaic call is named after the kernel body, not after the
    jitted wrapper round it: the name a trace reader can keep."""
    d_t, y_col, v_col = pallas_kernels.packed_dims(31, 16)
    X2 = jnp.zeros((64, 16 * d_t), jnp.bfloat16)
    kw = dict(pack=16, d_total=d_t, y_col=y_col, v_col=v_col,
              gather_block_rows=128, interpret=True)
    if kernel == "_grad_kernel_gathered":
        got = _pallas_call_names(
            lambda X, w, i: pallas_kernels.fused_grad_sum_gathered(
                X, w, i, **kw),
            X2, jnp.zeros((d_t,)), jnp.zeros((2,), jnp.int32))
    elif kernel == "_train_kernel_gathered":
        got = _pallas_call_names(
            lambda X, w, i: pallas_kernels.fused_train_gathered(
                X, w, i, eta=0.1, **kw),
            X2, jnp.zeros((16 * d_t, 1)), jnp.zeros((2, 2), jnp.int32))
    else:
        rng = np.random.default_rng(3)
        src, dst = (rng.integers(0, 4096, size=65536) for _ in "sd")
        plan = ppr.plan_spmv(src, dst, np.ones(65536, np.float32), 4096)
        got = _pallas_call_names(
            lambda ranks: ppr.spmv_table(
                plan.gbase, plan.sbase, ranks, plan.src_lane,
                plan.src_row, plan.dst_row, plan.dst_lane, plan.w_e,
                rg=plan.rg, ws=plan.ws, r8=plan.r8, blk=plan.blk,
                interpret=True),
            jnp.zeros((plan.geom.n_groups * plan.rg, 128)))
    assert got == [kernel]
    assert getattr(
        ppr if kernel == "_spmv_kernel" else pallas_kernels, kernel)


def test_pagerank_sweep_names_its_scope(mesh8):
    rng = np.random.default_rng(5)
    edges = np.stack([rng.integers(0, 4096, size=65536),
                      rng.integers(0, 4096, size=65536)], axis=1)
    el = gops.prepare_edges(edges.astype(np.int64), 4096)
    de = pagerank.prepare_device_edges(el, mesh8, build_plan=False)
    spmv = pagerank.prepare_device_spmv(el, mesh8)
    fn = pagerank.make_run_fn(
        mesh8, pagerank.PageRankConfig(n_iterations=2, mode="standard",
                                       scatter="spmv"),
        de.n_vertices, None, spmv)
    text = fn.lower(de.src, de.dst, de.w_e, de.emask, de.has_out,
                    de.n_ref).as_text(debug_info=True)
    assert f"{names.PAGERANK_SPMV}/jit(spmv_table)" in text
    assert f"{names.PAGERANK_UPDATE}/" in text


@pytest.mark.parametrize("shards", [1, 4])
def test_sparse_als_names_its_scopes(shards, mesh1, mesh4):
    """The five parts of a sparse ALS half-sweep (ops/als_sparse.py);
    the benchmark's ``*_ms_per_sweep.als`` and ``als_*_roofline`` read
    the first three. XLA forms all: no ``pallas_call``."""
    from tpu_distalg.models import als

    mesh = mesh1 if shards == 1 else mesh4
    du = np.array([3, 12, 20, 30, 70, 200, 8, 16, 17, 33, 1, 0])
    di = np.full(10, 41)
    meta = als.plan_ratings(410, 12, 10, 5, shards, n_heldout=8,
                            degrees=(du, di), geometry=dict(
                                seg_slots=8, piece_segs=4, batch=8,
                                classes=(1, 2)))
    arrays, _ = als.build_ratings_table(
        410, 12, 10, 5, mesh, n_heldout=8, degrees=(du, di),
        geometry=dict(seg_slots=8, piece_segs=4, batch=8,
                                classes=(1, 2)))
    cfg = als.ALSConfig(lam=1.4, m=12, n=10, k=5, n_iterations=1)
    X, Theta = als.start_factors(meta, mesh, 0)
    text = als.make_fit_fn(mesh, cfg, meta).lower(
        *arrays, X, Theta).as_text(debug_info=True)
    for scope in (names.ALS_GATHER, names.ALS_GRAM, names.ALS_SOLVE,
                  names.ALS_SYNC, names.ALS_UPDATE):
        assert scope + "/" in text, scope
    assert "pallas_call" not in text and "tpu_custom_call" not in text
    # and what ``train:segment`` carries of it: XLA's solve turns the
    # owner-major batch along the lanes itself
    seg = als.segment_fields(meta)
    assert (seg["als_gram_form"], seg["als_gram_layout"],
            seg["als_solve_form"]) == ("xla", "lanes", "xla")
    assert {names.ALS_GATHER, names.ALS_GRAM, names.ALS_SOLVE,
            names.ALS_SYNC, names.ALS_UPDATE} == {
        v for k, v in vars(names).items() if k.startswith("ALS_")}
