"""``harness/scopes.py`` with no chip and no program: the protobuf wire
reader against a message built here, scope extraction, self time
inside a ``while``, exposed time against an overlapping op, nothing
from a trace without scopes, and the four readers on a recorded trace
of ``lr30_400m_dp4`` (``data/scope_sample.json``,
``tools/dump_scope_sample.py``)."""

import json
import os
import time
import types

import pytest

import helpers
from harness import manifest as mf
from harness import scopes, trace

DATA = os.path.join(helpers.TESTS, "data")
DRAW, KERNEL, SYNC, UPDATE = (
    "tda.ssgd.draw", "tda.ssgd.kernel", "tda.ssgd.sync", "tda.ssgd.update")


# ---- the wire reader ---------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*pairs) -> bytes:
    """``(field, bytes | int)`` pairs as one protobuf message."""
    out = b""
    for number, value in pairs:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _hlo(instructions) -> bytes:
    comp = _msg((1, b"main"), *[
        (2, _msg((1, name.encode()), (2, b"fusion"),
                 (7, _msg((1, b"op_type"), (2, op.encode())))))
        for name, op in instructions])
    return _msg((1, _msg((1, b"jit_train"), (3, comp))))


def _xspace(hlos) -> bytes:
    entries = [(4, _msg((1, k), (2, _msg(
        (1, k), (2, b"jit_train"),
        (5, _msg((1, 7), (6, hlo))))))) for k, hlo in enumerate(hlos, 1)]
    return (_msg((1, _msg((1, 1), (2, b"/device:TPU:0"))))
            + _msg((1, _msg((1, 2), (2, b"/host:metadata"), *entries))))


def test_wire_reader_finds_op_names_in_the_metadata_plane():
    long_path = "jit(train)/while/body/" + DRAW + "/vmap(" + DRAW + ")/" \
        + "x" * 300 + "/sort"
    blob = _xspace([_hlo([
        ("sort.3", long_path),
        ("_train_kernel_gathered.3",
         f"jit(train)/while/body/{KERNEL}/jit(fused_train_gathered)/"
         "_train_kernel_gathered"),
        ("while.1", "jit(train)/while"), ("copy.2", "")])])
    names = scopes.hlo_op_names(blob)
    assert names["sort.3"] == long_path           # a two-byte length
    assert set(names) == {"sort.3", "_train_kernel_gathered.3", "while.1",
                          "copy.2"}
    assert [scopes.scope_of(names[k]) for k in sorted(names)] == [
        KERNEL, "", DRAW, ""]
    assert list(scopes.fields(_msg((3, 300), (9, b"ab")))) == [
        (3, 300), (9, b"ab")]


def test_programs_that_disagree_on_an_instruction_lose_its_scope():
    a = _hlo([("fusion.1", f"jit(a)/{DRAW}/add"), ("sort", f"x/{DRAW}/s")])
    b = _hlo([("fusion.1", "jit(b)/mul"), ("sort", f"y/{DRAW}/s")])
    names = scopes.hlo_op_names(_xspace([a, b]))
    assert scopes.scope_of(names["fusion.1"]) == ""
    assert scopes.scope_of(names["sort"]) == DRAW


# ---- scope extraction --------------------------------------------------

def test_scope_is_the_first_tda_component():
    assert scopes.scope_of(
        f"jit(train)/{DRAW}/vmap({DRAW})/jit(argsort)/sort") == DRAW
    assert scopes.scope_of(
        f"jit(train)/while/body/shard_map/{SYNC}/psum") == SYNC
    assert scopes.scope_of(f"{UPDATE}/sub") == UPDATE
    assert scopes.scope_of("jit(train)/while/body/add") == ""
    assert scopes.scope_of("jit(metadata.ssgd)/add") == ""
    assert scopes.scope_of("") == ""


def test_an_events_instruction_is_the_name_before_the_equals_sign():
    assert scopes.instruction_of(
        "%sort = (u32[2500,1,12208]{2,1,0:T(1,128)}, s32[2500]) sort(u32[] "
        "%add_xor_fusion, s32[] %iota.8), dimensions={2}") == "sort"
    assert scopes.instruction_of(
        "%_train_kernel_gathered.3 = f32[640,1]{1,0} custom-call(s32[] "
        "%dynamic-slice_bitcast_fusion.2)") == "_train_kernel_gathered.3"
    assert scopes.instruction_of(
        "all-reduce.4 = f32[40]{0} all-reduce(f32[40] %x)") == "all-reduce.4"
    assert scopes.instruction_of("no equals sign") == "no equals sign"


def test_attach_joins_events_to_scopes_and_refuses_a_trace_without():
    devices = {0: [("%sort.3 = u32[4] sort(u32[4] %a)", 10.0, 5.0),
                   ("%while.1 = (s32[]) while((s32[]) %t)", 0.0, 50.0),
                   ("%mystery = f32[] add(f32[] %a, f32[] %b)", 20.0, 1.0)]}
    names = {"sort.3": f"jit(train)/{DRAW}/sort",
             "while.1": "jit(train)/while"}
    assert scopes.attach(devices, names) == {0: [
        (DRAW, 10.0, 5.0), ("", 0.0, 50.0), ("", 20.0, 1.0)]}
    # the parent commit: ops and op_names, and no scope in any of them
    assert scopes.attach(devices, {"sort.3": "jit(train)/sort",
                                   "while.1": "jit(train)/while"}) is None
    assert scopes.attach(devices, {}) is None


# ---- reductions --------------------------------------------------------

def _toy():
    # one chip: a while (under no scope) wrapping a kernel and a draw,
    # then a psum that a late update op overlaps for 20 of its 60 ns
    return [("", 100.0, 400.0), (KERNEL, 110.0, 100.0),
            (DRAW, 300.0, 150.0), (SYNC, 600.0, 60.0),
            (UPDATE, 640.0, 50.0)]


def test_self_time_inside_a_while():
    by = trace.self_seconds(_toy())
    assert by[KERNEL] == pytest.approx(100e-9)
    assert by[DRAW] == pytest.approx(150e-9)
    assert by[""] == pytest.approx(150e-9)      # the while's own share
    busy = sum(e - s for s, e in trace.union(
        (s, s + d) for _, s, d in _toy()))
    assert sum(by.values()) == pytest.approx(busy / 1e9)


def test_leaves_leave_the_container_out():
    assert [n for n, _, _ in scopes.leaves(_toy())] == [
        KERNEL, DRAW, SYNC, UPDATE]


def test_exposed_time_against_an_overlapping_op():
    assert scopes.exposed_seconds(_toy(), SYNC) == pytest.approx(40e-9)
    # the while spans its body and hides nothing of it
    assert scopes.exposed_seconds(_toy(), KERNEL) == pytest.approx(100e-9)
    # fully hidden, and absent
    hidden = [(SYNC, 10.0, 10.0), (KERNEL, 0.0, 30.0)]
    assert scopes.leaves(hidden) == [(SYNC, 10.0, 10.0)]
    both = [(SYNC, 10.0, 10.0), (KERNEL, 5.0, 10.0), (KERNEL, 15.0, 10.0)]
    assert scopes.exposed_seconds(both, SYNC) == 0.0
    assert scopes.exposed_seconds(_toy(), "tda.ssgd.nothing") == 0.0


def test_exposed_time_of_a_whole_window_is_one_pass():
    """A dp4 window is thousands of steps, each with a psum and the
    ops round it: a reduction that holds every psum against every
    other interval (4000 x 8000 a chip) outlasts the run's time limit
    on four chips. Against the interval-by-interval form on a short
    scan, then the window's size inside a second."""
    def scan(steps):
        events, t = [("", 0.0, steps * 1500.0)], 0.0
        for k in range(steps):
            events += [(DRAW, t, 60.0), (KERNEL, t + 61.0, 1400.0),
                       (SYNC, t + 1462.0, 5.0)]
            if k % 3 == 0:                  # a copy beside the psum
                events.append(("", t + 1464.0, 6.0))
            events += [(UPDATE, t + 1471.0 + 2 * j, 1.0) for j in range(8)]
            t += 1490.0
        return events

    def by_interval(events):
        ops = scopes.leaves(events)
        rest = trace.union((s, s + d) for n, s, d in ops if n != SYNC)
        return sum((hi - lo) - sum(e - s for s, e in trace.clip(rest, lo, hi))
                   for lo, hi in trace.union(
                       (s, s + d) for n, s, d in ops if n == SYNC)) / 1e9

    short = scan(60)
    assert scopes.exposed_seconds(short, SYNC) == pytest.approx(
        by_interval(short))
    assert scopes.exposed_seconds(short, SYNC) == pytest.approx(
        (60 * 5.0 - 20 * 3.0) / 1e9)
    t0 = time.perf_counter()
    got = scopes.exposed_seconds(scan(4000), SYNC)
    assert time.perf_counter() - t0 < 5.0
    assert got == pytest.approx((4000 * 5.0 - 1334 * 3.0) / 1e9)


def _ctx(scoped, window, steps):
    reduced = None if window is None else {"window": window}
    ctx = types.SimpleNamespace(
        reduced=reduced, counters={"window_calls": 1,
                                   "steps_per_call": steps},
        out_dir="/nonexistent", cell=types.SimpleNamespace(name="none"))
    ctx._scoped = scoped
    return ctx


def _reader(name):
    return mf.load_module(os.path.join(
        helpers.BENCH, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_"))


NEW = ("draw_ms_per_step.lr", "kernel_ms_per_step.lr",
       "sync_exposed_ms_per_step.lr", "scoped_busy_pct.lr")


def test_readers_on_the_toy_trace():
    ctx = _ctx({0: _toy(), 1: _toy()}, (0.0, 1000.0), steps=2)
    got = {m: _reader(m).read(ctx) for m in NEW}
    assert got["draw_ms_per_step.lr"] == pytest.approx(150e-6 / 2)
    assert got["kernel_ms_per_step.lr"] == pytest.approx(100e-6 / 2)
    assert got["sync_exposed_ms_per_step.lr"] == pytest.approx(40e-6 / 2)
    assert got["scoped_busy_pct.lr"] == pytest.approx(
        (100 + 150 + 40 + 50) / (100 + 150 + 40 + 50 + 150) * 100)


@pytest.mark.parametrize("metric", NEW)
def test_readers_report_nothing_without_scopes(metric, tmp_path):
    """An untraced run, a trace whose programs name no scope (the parent
    commit), and a traced run whose file is looked for and not there."""
    read = _reader(metric).read
    assert read(_ctx(None, None, 1)) is None
    assert read(_ctx(None, (0.0, 1.0), 1)) is None
    ctx = _ctx(None, (0.0, 1.0), 1)
    del ctx._scoped
    ctx.out_dir = str(tmp_path)
    with pytest.raises(FileNotFoundError):
        read(ctx)


def test_a_traced_run_joins_its_window_to_the_files_hlo(tmp_path):
    """``of(ctx)`` as a reader meets it: the events the reduction kept,
    the ``.xplane.pb`` under ``out_dir/trace/<cell>``, read once."""
    d = tmp_path / "trace" / "cellname" / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace([_hlo([
        ("sort.3", f"jit(train)/{DRAW}/sort"),
        ("while.1", "jit(train)/while")])]))
    ctx = _ctx(None, (0.0, 100.0), steps=5)
    del ctx._scoped
    ctx.out_dir, ctx.cell.name = str(tmp_path), "cellname"
    ctx.reduced["per_device"] = {0: {"events": [
        ("%while.1 = (s32[]) while((s32[]) %t)", 0.0, 50.0),
        ("%sort.3 = u32[4] sort(u32[4] %a)", 10.0, 20.0)]}}
    assert _reader("draw_ms_per_step.lr").read(ctx) == pytest.approx(
        20e-6 / 5)
    assert _reader("scoped_busy_pct.lr").read(ctx) == pytest.approx(40.0)
    (d / "host.xplane.pb").unlink()          # kept on ctx: not read again
    assert _reader("kernel_ms_per_step.lr").read(ctx) == 0.0


# ---- the recorded chip trace -------------------------------------------

@pytest.fixture(scope="module")
def sample():
    with open(os.path.join(DATA, "scope_sample.json")) as f:
        raw = json.load(f)
    raw["devices"] = {int(k): [tuple(e) for e in v]
                      for k, v in raw["devices"].items()}
    raw["host"] = [tuple(e) for e in raw["host"]]
    return raw


def test_recorded_trace_every_op_is_joined(sample):
    """Every event of the sample names an instruction the HLO modules
    of its trace hold; all four scopes of a step are there on all four
    chips, and the Mosaic call sits under the kernel's."""
    names = sample["op_names"]
    for events in sample["devices"].values():
        assert all(scopes.instruction_of(n) in names for n, _, _ in events)
    scoped = scopes.attach(sample["devices"], names)
    assert sorted(scoped) == [0, 1, 2, 3]
    for events in scoped.values():
        assert {n for n, _, _ in events} >= {DRAW, KERNEL, SYNC, UPDATE}
    call = [c for c in (scopes.instruction_of(n)
                        for n, _, _ in sample["devices"][0])
            if c.startswith("_grad_kernel_gathered")]
    assert call and all(scopes.scope_of(names[c]) == KERNEL for c in call)
    reduce_ops = [scopes.instruction_of(n)
                  for n, _, _ in sample["devices"][0] if "all-reduce" in n]
    assert reduce_ops and all(scopes.scope_of(names[r]) == SYNC
                              for r in reduce_ops)


def test_recorded_trace_split_of_a_step(sample):
    """The scopes and the unscoped rest sum to the busy time (the
    sample is the head of one call: all its draws, then eight steps
    inside the ``while``); the scan is serial, so the sync scope's
    time is all exposed."""
    scoped = scopes.attach(sample["devices"], sample["op_names"])
    evs = [ev for d in sample["devices"].values() for ev in d]
    window = (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))
    for dev, events in scoped.items():
        by = trace.self_seconds(events)
        busy = sum(e - s for s, e in trace.busy_intervals(
            sample["devices"][dev], window)) / 1e9
        assert sum(by.values()) == pytest.approx(busy, rel=1e-6)
        assert by[DRAW] > 0.5 * busy and by[KERNEL] > 0.3 * busy
        assert by.get("", 0.0) < 0.01 * busy
        assert 0 < scopes.exposed_seconds(events, SYNC) == \
            pytest.approx(by[SYNC])
    ctx = _ctx(scoped, window, steps=8)
    got = {m: _reader(m).read(ctx) for m in NEW}
    assert got["scoped_busy_pct.lr"] > 99.9
    assert got["kernel_ms_per_step.lr"] == pytest.approx(1.46, rel=0.01)
    assert got["sync_exposed_ms_per_step.lr"] == pytest.approx(
        0.0041, rel=0.05)
    assert got["draw_ms_per_step.lr"] > 0


def test_recorded_trace_on_the_old_reduction(sample):
    """The same sample through ``harness/trace.py`` as it was: the
    kernel's new name still matches the roofline reader's pattern."""
    r = trace.reduce({"devices": sample["devices"],
                      "host": [h for h in sample["host"]
                               if h[0].startswith(trace.HOST_PREFIX)],
                      "lines": {}})
    pattern = _reader("ssgd_kernel_roofline").PATTERN
    seconds, n = trace.kernel_seconds(r, pattern)
    assert n > 0 and 0 < seconds <= r["busy_s"]
    assert trace.kernel_seconds(r, r"all-reduce")[1] > 0


# ---- idle gaps by host phase (tools/attribute_gaps.py) -----------------

def test_idle_gaps_go_to_the_innermost_span():
    tool = mf.load_module(os.path.join(
        helpers.BENCH, "tools", "attribute_gaps.py"), "attribute_gaps")
    host = [("tda:cli:ssgd", 0.0, 1000.0), ("tda:ssgd:prepare", 100.0, 300.0),
            ("tda:ssgd:pack", 100.0, 100.0), ("tda:ssgd:h2d", 250.0, 150.0)]
    by, pieces = tool.attribute([(0.0, 500.0), (600.0, 650.0),
                                 (1000.0, 1100.0)], host)
    assert by == {"tda:cli:ssgd": 250.0, "tda:ssgd:pack": 100.0,
                  "tda:ssgd:prepare": 50.0, "tda:ssgd:h2d": 150.0,
                  "tda:unattributed": 100.0}
    assert sum(b - a for _, a, b in pieces) == 650.0
    assert tool.innermost(host, 300.0) == "tda:ssgd:h2d"
