"""`tda lint` — the TDA0xx rule engine (tpu_distalg/analysis/).

One positive + one negative fixture per shipped rule, the suppression
grammar (reason REQUIRED), the baseline round-trip (add → baselined →
removed → stale error), --fix's mechanically-safe subset, and the
tier-1 assertion that the COMMITTED tree lints clean — the property
every other test here protects transitively.

Fixture sources are plain strings: the engine scans comments with
tokenize, so the violation-shaped text inside them never contaminates
THIS file's own lint run (itself one of the fixtures, in effect).
"""

from __future__ import annotations

import json
import os
import pathlib
import textwrap

import pytest

from tpu_distalg import analysis
from tpu_distalg.analysis import baseline as blmod
from tpu_distalg.analysis import cli as lint_cli
from tpu_distalg.analysis import engine, fixes

REPO = pathlib.Path(__file__).resolve().parent.parent

LIB = "tpu_distalg/somemod.py"      # library-code path for fixtures
TOOL = "scripts/some_tool.py"       # non-library path


def lint(src, path=LIB, **kw):
    return engine.lint_source(textwrap.dedent(src), path,
                              analysis.RULES, **kw)


def codes(violations):
    return sorted(v.code for v in violations)


# ---------------------------------------------------------------- TDA001


def test_tda001_wall_clock_flagged_in_library_code():
    src = """
    import time

    def stamp():
        return time.time()
    """
    assert codes(lint(src)) == ["TDA001"]


def test_tda001_unseeded_rngs_flagged():
    src = """
    import random

    import numpy as np

    def draw():
        a = random.randint(0, 7)
        b = np.random.rand(3)
        return a, b
    """
    assert codes(lint(src)) == ["TDA001", "TDA001"]


def test_tda001_negative_seeded_and_monotonic():
    src = """
    import random
    import time

    import numpy as np

    def draw(seed):
        t0 = time.monotonic()
        rng = np.random.default_rng(seed)
        r = random.Random(seed)
        return rng.random(3), r.random(), time.perf_counter() - t0
    """
    assert lint(src) == []


def test_tda001_scope_excludes_tests_and_telemetry():
    src = """
    import time

    def stamp():
        return time.time()
    """
    assert lint(src, path="tests/test_x.py") == []
    assert lint(src, path="tpu_distalg/telemetry/x.py") == []


# ---------------------------------------------------------------- TDA002


def test_tda002_set_and_listdir_iteration_flagged():
    src = """
    import os

    def emit_all(xs, d, sink):
        for x in set(xs):
            sink(x)
        for name in os.listdir(d):
            sink(name)
    """
    assert codes(lint(src)) == ["TDA002", "TDA002"]


def test_tda002_negative_sorted_and_dict():
    src = """
    import os

    def emit_all(xs, d, table, sink):
        for x in sorted(set(xs)):
            sink(x)
        for name in sorted(os.listdir(d)):
            sink(name)
        for k, v in table.items():
            sink(k, v)
    """
    assert lint(src) == []


# ---------------------------------------------------------------- TDA010


def test_tda010_print_and_telemetry_in_jit_flagged():
    src = """
    import jax

    from tpu_distalg.telemetry import events as tevents

    @jax.jit
    def step(w, g):
        print("stepping")
        tevents.counter("steps")
        return w - 0.1 * g
    """
    assert codes(lint(src)) == ["TDA010", "TDA010"]


def test_tda010_nonlocal_mutation_flagged():
    src = """
    import functools

    import jax

    state = {}

    @functools.partial(jax.jit, static_argnums=0)
    def step(k, w):
        state["last"] = k
        return w
    """
    assert codes(lint(src)) == ["TDA010"]


def test_tda010_negative_pure_and_undecorated():
    src = """
    import jax

    @jax.jit
    def step(w, g):
        acc = {}
        acc["w"] = w - g     # local object: fine
        return acc["w"]

    def host_side(w):
        print(w)             # not traced: fine
        return w
    """
    assert lint(src) == []


# ---------------------------------------------------------------- TDA011


def test_tda011_sync_in_step_named_loop_flagged():
    src = """
    import numpy as np

    def run(fn, w, n_steps):
        accs = []
        for t in range(n_steps):
            w = fn(w, t)
            accs.append(float(np.asarray(w)[0]))
        return w, accs
    """
    assert codes(lint(src)) == ["TDA011", "TDA011"]


def test_tda011_hot_loop_marker_applies_to_while():
    src = """
    def drain(q, fn, w):
        # tda: hot-loop
        while q:
            w = fn(w, q.pop())
            w.block_until_ready()
        return w
    """
    assert codes(lint(src)) == ["TDA011"]


def test_tda011_negative_boundary_sync_and_tests():
    boundary = """
    import numpy as np

    def run(fn, w, n_steps):
        for t in range(n_steps):
            w = fn(w, t)
        return float(np.asarray(w)[0])   # phase boundary: fine
    """
    assert lint(boundary) == []
    hot = """
    import numpy as np

    def run(fn, w, n_steps):
        for t in range(n_steps):
            w = float(np.asarray(fn(w, t)))
        return w
    """
    assert lint(hot, path="tests/test_y.py") == []  # tests may sync


# ---------------------------------------------------------------- TDA020


def test_tda020_unlocked_thread_write_flagged():
    src = """
    import threading

    shared = {}

    def work(n):
        shared["result"] = n * 2

    th = threading.Thread(target=work, args=(3,), daemon=True)
    """
    assert codes(lint(src)) == ["TDA020"]


def test_tda020_thread_subclass_run_flagged_and_locked_ok():
    src = """
    import threading

    class Worker(threading.Thread):
        def run(self):
            self.n_beats = self.n_beats + 1          # unlocked
            with self._lock:
                self.counters["x"] = 1               # locked: fine
    """
    assert codes(lint(src)) == ["TDA020"]


def test_tda020_event_box_pattern_still_flags():
    # the supervisor's single-flight box: SAFE (the Event orders the
    # write before the reader) but statically indistinguishable from a
    # race — the repo carries a reasoned ignore at the real site; this
    # fixture pins the rule's behavior on the pattern
    src = """
    import threading

    def supervised(fn):
        box = {}
        done = threading.Event()

        def work():
            box["value"] = fn()
            done.set()

        th = threading.Thread(target=work, daemon=True)
        th.start()
        done.wait()
        return box["value"]
    """
    assert codes(lint(src)) == ["TDA020"]


def test_tda020_negative_local_object_writes():
    src = """
    import threading

    def work(q):
        out = {}
        out["x"] = 1      # local: fine
        q.put(out)        # queue handoff: fine (a call, not a write)

    th = threading.Thread(target=work, args=(None,), daemon=True)
    """
    assert lint(src) == []


# ---------------------------------------------------------------- TDA021


def test_tda021_bare_thread_flagged_everywhere():
    src = """
    import threading

    def go(fn):
        th = threading.Thread(target=fn)
        th.start()
    """
    assert codes(lint(src, path="tests/test_z.py")) == ["TDA021"]


def test_tda021_negative_explicit_daemon():
    src = """
    import threading

    def go(fn):
        a = threading.Thread(target=fn, daemon=True)
        b = threading.Thread(target=fn, daemon=False)
        return a, b
    """
    assert lint(src) == []


# ---------------------------------------------------------------- TDA030


def test_tda030_raw_write_and_rename_flagged():
    src = """
    import os

    def publish(path, blob):
        with open(path + ".tmp", "wb") as f:
            f.write(blob)
        os.replace(path + ".tmp", path)
    """
    assert codes(lint(src)) == ["TDA030", "TDA030"]


def test_tda030_negative_inject_seam_covers_function():
    src = """
    import os

    from tpu_distalg import faults

    def publish(path, blob):
        body = faults.inject("ckpt:write", payload=blob)
        with open(path + ".tmp", "wb") as f:
            f.write(body)
        os.replace(path + ".tmp", path)
    """
    assert lint(src) == []


def test_tda030_scope_library_only_and_reads_ok():
    src = """
    import os

    def publish(path, blob):
        with open(path, "wb") as f:
            f.write(blob)
    """
    assert lint(src, path=TOOL) == []
    reads = """
    def load(path):
        with open(path, "rb") as f:
            return f.read()
    """
    assert lint(reads) == []


def test_tda030_callback_writer_needs_reasoned_ignore():
    # the datasets.py aux-writer false positive, reproduced: a write
    # routed through build_cache's seam VIA CALLBACK still flags
    # (single-file analysis cannot see the edge) and the documented
    # treatment is a reasoned suppression
    flagged = """
    def write_test(tmp_path, blob):
        with open(tmp_path, "wb") as f:
            f.write(blob)
    """
    assert codes(lint(flagged)) == ["TDA030"]
    suppressed = """
    def write_test(tmp_path, blob):
        # tda: ignore[TDA030] -- aux writer runs inside build_cache's
        # cache:write seam; the callback edge is invisible per-file
        with open(tmp_path, "wb") as f:
            f.write(blob)
    """
    assert lint(suppressed) == []


# ---------------------------------------------------------------- TDA040


def test_tda040_off_tile_lane_and_sublane_flagged():
    src = """
    from jax.experimental import pallas as pl

    def build(body, ix):
        return pl.pallas_call(
            body,
            in_specs=[pl.BlockSpec((8, 130), ix),
                      pl.BlockSpec((12, 128), ix)],
        )
    """
    assert codes(lint(src)) == ["TDA040", "TDA040"]


def test_tda040_negative_tiled_degenerate_and_smem():
    src = """
    from jax.experimental import pallas as pl
    from jax.experimental import pallas_tpu as pltpu

    BLOCK = 256

    def build(body, ix, b):
        return pl.pallas_call(
            body,
            in_specs=[pl.BlockSpec((8, 128), ix),
                      pl.BlockSpec((16, BLOCK), ix),
                      pl.BlockSpec((1, 256), ix),
                      pl.BlockSpec((b, 1), ix),
                      pl.BlockSpec((1, 1), ix,
                                   memory_space=pltpu.SMEM)],
        )
    """
    assert lint(src) == []


# ---------------------------------------------------------------- TDA041


def test_tda041_static_footprint_over_budget_flagged():
    src = """
    from jax.experimental import pallas as pl

    ROWS = 8192
    COLS = 4096

    def build(body, ix):
        return pl.pallas_call(
            body,
            in_specs=[pl.BlockSpec((ROWS, COLS), ix)],
            out_specs=pl.BlockSpec((ROWS, COLS), ix),
        )
    """
    # 2 x 8192 x 4096 x 4B = 256 MB > 128 MB budget
    vs = lint(src)
    assert codes(vs) == ["TDA041"]
    assert "256 MB" in vs[0].message


def test_tda041_negative_small_or_parameterized():
    src = """
    from jax.experimental import pallas as pl

    def build(body, ix, bq):
        return pl.pallas_call(
            body,
            in_specs=[pl.BlockSpec((256, 128), ix),
                      pl.BlockSpec((bq, 65536), ix)],
            out_specs=pl.BlockSpec((256, 128), ix),
        )
    """
    assert lint(src) == []  # parameterized spec: not statically sized


# ---------------------------------------------------------------- TDA050


MODEL = "tpu_distalg/models/somemodel.py"


def test_tda050_raw_collective_in_models_flagged():
    src = """
    from jax import lax

    def local_grad(g, cnt):
        g = lax.psum(g, "data")
        cnt = lax.pmean(cnt, "data")
        return g, cnt
    """
    assert codes(lint(src, path=MODEL)) == ["TDA050", "TDA050"]
    fq = """
    import jax

    def local_grad(g):
        return jax.lax.psum_scatter(g, "data")
    """
    assert codes(lint(fq, path=MODEL)) == ["TDA050"]


def test_tda050_negative_comms_wrappers_and_scope():
    blessed = """
    from tpu_distalg.parallel import comms, tree_allreduce_sum

    def local_grad(g, cnt, res, t, sync):
        z = comms.psum(g, "model")
        out, res = sync.reduce((g, cnt), res, t)
        return tree_allreduce_sum((z, cnt)), out, res
    """
    assert lint(blessed, path=MODEL) == []
    # the comms layer itself (and any non-models/ code) owns its raw
    # collectives — scope is tpu_distalg/models/ only
    raw = """
    from jax import lax

    def reduce_flat(v):
        return lax.psum(v, "data")
    """
    assert lint(raw, path="tpu_distalg/parallel/comms.py") == []
    assert lint(raw, path=LIB) == []


# ---------------------------------------------------------------- TDA051


PARALLEL = "tpu_distalg/parallel/somecomms.py"


def test_tda051_int32_psum_on_quantized_buffer_flagged():
    """The exact PR 5 regression: the quantized (clip∘floor) buffer
    widened to int32 AS IT ENTERS the psum — 4 bytes/elem on the wire
    while the accounting claims 1."""
    src = """
    import jax.numpy as jnp
    from jax import lax

    def int8_sync(x, scale, u, axis):
        q = jnp.clip(jnp.floor(x / scale + u), -127, 127)
        s = lax.psum(q.astype(jnp.int32), axis)
        return s.astype(jnp.float32) * scale
    """
    vs = lint(src, path=PARALLEL)
    assert codes(vs) == ["TDA051"]
    assert "int32" in vs[0].message


def test_tda051_widened_int8_buffer_into_any_collective_flagged():
    """Taint follows the buffer through renames/reshapes; every
    collective in the wire-op set is policed (here: all_to_all, the
    native ring's scatter phase)."""
    src = """
    import jax.numpy as jnp
    from jax import lax

    def scatter(x, scale, u, axis, n):
        q = jnp.clip(jnp.floor(x / scale + u), -127, 127) \
            .astype(jnp.int8)
        q2 = q.reshape(n, -1)
        return lax.all_to_all(q2.astype(jnp.float32), axis,
                              split_axis=0, concat_axis=0)
    """
    assert codes(lint(src, path=PARALLEL)) == ["TDA051"]


def test_tda051_nested_closure_flagged_exactly_once():
    """A violation inside a nested def (the native ring's `exchange`
    shape) is reported ONCE — the rule walks outermost functions and
    recurses itself, so re-visiting the closure as its own root would
    double-report and desync a --baseline file."""
    src = """
    import jax.numpy as jnp
    from jax import lax

    def outer(x, scale, u, axis):
        def inner():
            q = jnp.clip(jnp.floor(x / scale + u), -127, 127)
            return lax.psum(q.astype(jnp.int32), axis)
        return inner()
    """
    assert codes(lint(src, path=PARALLEL)) == ["TDA051"]


def test_tda051_tuple_unpack_and_keyword_arg_flagged():
    """Taint survives tuple-unpacking assignment, and collectives
    called with the buffer as a KEYWORD argument are still policed —
    the sibling unpacked name stays clean (element-wise pairing, no
    over-taint)."""
    src = """
    import jax.numpy as jnp
    from jax import lax

    def sync(x, scale, u, axis):
        q, s = jnp.clip(jnp.floor(x / scale + u), -127, 127), scale
        wide = lax.psum(x=q.astype(jnp.int32), axis_name=axis)
        fine = lax.psum(s.astype(jnp.float32), axis)
        return wide, fine
    """
    assert codes(lint(src, path=PARALLEL)) == ["TDA051"]


def test_tda051_negative_native_ring_and_scope():
    """The native pattern is clean: int8 rides the collectives, the
    int32 widening happens on the RECEIVED buffer (after the wire).
    bf16 casts of unquantized data, and code outside parallel/, are
    out of scope."""
    native = """
    import jax.numpy as jnp
    from jax import lax

    def int8_sync(x, scale, u, axis, n):
        q = jnp.clip(jnp.floor(x / scale + u), -127, 127) \
            .astype(jnp.int8)
        recv = lax.all_to_all(q.reshape(n, -1), axis,
                              split_axis=0, concat_axis=0)
        s = jnp.sum(recv.astype(jnp.int32), axis=0)
        return s.astype(jnp.float32) * (scale * n)
    """
    assert lint(native, path=PARALLEL) == []
    bf16 = """
    import jax.numpy as jnp
    from jax import lax

    def bf16_sync(x, axis):
        return lax.psum(x.astype(jnp.bfloat16), axis).astype(x.dtype)
    """
    assert lint(bf16, path=PARALLEL) == []
    widened = """
    import jax.numpy as jnp
    from jax import lax

    def int8_sync(x, scale, u, axis):
        q = jnp.clip(jnp.floor(x / scale + u), -127, 127)
        return lax.psum(q.astype(jnp.int32), axis)
    """
    assert lint(widened, path=LIB) == []  # parallel/ + cluster/ only


CLUSTER = "tpu_distalg/cluster/somewire.py"


def test_tda051_cluster_widening_onto_transport_flagged():
    """The cluster-wire twin of the int32-psum regression: a host-
    quantized buffer widened as it enters the framed TCP transport —
    the wire moves 4 bytes/elem while cluster_wire_reduction_vs_dense
    claims 1. Both transport spellings (send_frame under any root,
    raw socket sendall) are policed."""
    src = """
    import numpy as np
    from tpu_distalg.cluster import transport

    def push(sock, x, scale, u):
        q = np.clip(np.floor(x / scale + u), -127, 127) \
            .astype(np.int8)
        transport.send_frame(sock, "push", {"w": 0},
                             {"q": q.astype(np.float32)})
    """
    vs = lint(src, path=CLUSTER)
    assert codes(vs) == ["TDA051"]
    assert "float32" in vs[0].message
    raw_sock = """
    import numpy as np

    def push(sock, x, scale, u):
        q = np.clip(np.floor(x / scale + u), -127, 127)
        sock.sendall(q.astype(np.int32).tobytes())
    """
    # (TDA090 also legitimately flags the raw-socket spelling — the
    # widening rule must fire REGARDLESS of which send idiom hid it)
    assert "TDA051" in codes(lint(raw_sock, path=CLUSTER))


def test_tda051_cluster_native_and_scope_negative():
    """The native host-codec pattern is clean: int8 rides the frame,
    the exact int32 widening happens on the RECEIVED buffer (the PS
    decode, after the wire); and the same widening-into-send_frame
    outside tpu_distalg/cluster/ is out of scope."""
    native = """
    import numpy as np
    from tpu_distalg.cluster import transport

    def push(sock, x, scale, u):
        q = np.clip(np.floor(x / scale + u), -127, 127) \
            .astype(np.int8)
        transport.send_frame(sock, "push", {"w": 0},
                             {"q": q, "scale": scale})

    def decode(arrays, scale):
        q = arrays["q"]
        return q.astype(np.int32).astype(np.float32) * scale
    """
    assert lint(native, path=CLUSTER) == []
    outside = """
    import numpy as np
    from tpu_distalg.cluster import transport

    def push(sock, x, scale, u):
        q = np.clip(np.floor(x / scale + u), -127, 127)
        transport.send_frame(sock, "push", {"w": 0},
                             {"q": q.astype(np.float32)})
    """
    assert lint(outside, path=LIB) == []


def test_tda051_real_tree_and_baseline_stay_clean():
    """The shipped parallel/ + cluster/ trees carry no TDA051
    violations and none are baselined away — the rule extension must
    not land with suppressed debt."""
    import json
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "tpu_distalg.analysis.cli",
         "--select", "TDA051", "--format", "json",
         os.path.join(root, "tpu_distalg", "parallel"),
         os.path.join(root, "tpu_distalg", "cluster")],
        capture_output=True, text=True, cwd=root, timeout=120)
    out = json.loads(r.stdout) if r.stdout.strip() else []
    assert r.returncode == 0, (r.returncode, r.stdout[-2000:],
                               r.stderr[-500:])
    assert out == [] or all(
        v.get("code") != "TDA051" for v in out), out
    with open(os.path.join(root, "lint_baseline.json")) as f:
        baseline = json.load(f)
    assert not [e for e in (baseline if isinstance(baseline, list)
                            else baseline.get("violations", []))
                if "TDA051" in json.dumps(e)]


# ---------------------------------------------------------------- TDA060

SERVE = "tpu_distalg/serve/somemod.py"


def test_tda060_unbounded_queue_flagged():
    src = """
    import queue

    def make():
        return queue.Queue()
    """
    assert codes(lint(src, path=SERVE)) == ["TDA060"]
    spelled = """
    import queue

    a = queue.Queue(0)
    b = queue.LifoQueue(maxsize=0)
    c = queue.Queue(-1)
    """
    # maxsize <= 0 is documented-infinite: 0, -1 and the omitted arg
    # are all the same grow-until-OOM shape
    assert codes(lint(spelled, path=SERVE)) == ["TDA060"] * 3


def test_tda060_blocking_get_without_timeout_flagged():
    src = """
    def loop(q):
        while True:
            handle(q.get())
    """
    assert codes(lint(src, path=SERVE)) == ["TDA060"]
    explicit_block = """
    def drain(q):
        return q.get(True)
    """
    assert codes(lint(explicit_block, path=SERVE)) == ["TDA060"]
    # a truthy numeric block arg is the same block-forever shape, and
    # timeout=None is the SPELLED-OUT block-forever
    numeric_and_none = """
    def drain(q):
        return q.get(1), q.get(timeout=None), q.get(True, None)
    """
    assert codes(lint(numeric_and_none, path=SERVE)) == ["TDA060"] * 3


def test_tda060_negative_bounded_timeout_and_scope():
    clean = """
    import queue

    def loop(depth):
        q = queue.Queue(maxsize=depth)
        try:
            item = q.get(timeout=0.05)
        except queue.Empty:
            item = q.get_nowait()
        return item, q.get(block=False), q.get(0)
    """
    assert lint(clean, path=SERVE) == []
    # dict.get — non-numeric key — is not a queue wait; a real
    # positional timeout is bounded; a numeric dict key with a
    # non-None default stays exempt through the timeout check
    dget = """
    def lookup(d, q, key):
        return (d.get(key, None), d.get(key), q.get(True, 0.05),
                d.get(3, "fallback"))
    """
    assert lint(dget, path=SERVE) == []
    # the rule is scoped to tpu_distalg/serve/ — elsewhere other
    # disciplines own queue behavior (e.g. the Prefetcher guard)
    outside = """
    import queue

    q = queue.Queue()
    item = q.get()
    """
    assert lint(outside, path=LIB) == []


# ---------------------------------------------------------------- TDA070

PAR = "tpu_distalg/parallel/somemod.py"


def test_tda070_unseeded_schedule_rng_flagged():
    src = """
    import numpy as np

    def make(n_ticks, n_shards):
        straggle_schedule = np.random.default_rng().integers(
            0, 2, (n_ticks, n_shards))
        return straggle_schedule
    """
    # TDA001 (unseeded RNG in library code) fires too — TDA070 adds
    # the schedule-specific diagnosis
    assert "TDA070" in codes(lint(src, path=PAR))
    module_draw = """
    import numpy as np

    membership_plan = np.random.rand(8, 4)
    """
    assert "TDA070" in codes(lint(module_draw, path=PAR))


def test_tda070_clock_wait_without_deadline_flagged():
    src = """
    def wait_for(clocks, target):
        while clocks.min() < target:
            pass
    """
    assert codes(lint(src, path=PAR)) == ["TDA070"]


def test_tda070_negative_seeded_bounded_and_scoped():
    clean = """
    import numpy as np

    def make(n_ticks, n_shards, seed):
        rng = np.random.default_rng(seed)
        straggle_schedule = rng.integers(0, 2, (n_ticks, n_shards))
        return straggle_schedule

    def wait_for(clocks, target, deadline_s, now):
        while clocks.min() < target and now() < deadline_s:
            pass

    def plain_loop(items):
        while items:
            items.pop()
    """
    assert lint(clean, path=PAR) == []
    # non-schedule names and non-parallel paths are out of scope
    outside = """
    import numpy as np

    def wait_for(clocks, target):
        while clocks.min() < target:
            pass
    """
    assert lint(outside, path=LIB) == []
    unrelated_name = """
    import numpy as np

    def noise(n, seed):
        jitter = np.random.default_rng(seed).random(n)
        return jitter
    """
    assert lint(unrelated_name, path=PAR) == []


# ------------------------------------------------- suppressions / TDA000


def test_suppression_with_reason_suppresses_trailing_and_own_line():
    trailing = """
    import time

    def stamp():
        return time.time()  # tda: ignore[TDA001] -- wall-clock domain
    """
    assert lint(trailing) == []
    own_line = """
    import time

    def stamp():
        # tda: ignore[TDA001] -- compared against file mtimes
        return time.time()
    """
    assert lint(own_line) == []


def test_suppression_without_reason_is_tda000_and_inert():
    src = """
    import time

    def stamp():
        return time.time()  # tda: ignore[TDA001]
    """
    assert codes(lint(src)) == ["TDA000", "TDA001"]


def test_suppression_wrong_code_does_not_suppress():
    src = """
    import time

    def stamp():
        return time.time()  # tda: ignore[TDA021] -- wrong rule
    """
    assert codes(lint(src)) == ["TDA001"]


def test_suppression_unknown_code_reported():
    src = """
    def f():
        return 1  # tda: ignore[TDAXYZ] -- not a code
    """
    vs = lint(src)
    assert codes(vs) == ["TDA000"]
    assert "unknown code" in vs[0].message


def test_suppression_text_inside_string_is_inert():
    src = '''
    import time

    FIXTURE = "# tda: ignore[TDA001] -- this is DATA, not a comment"

    def stamp():
        return time.time()
    '''
    assert codes(lint(src)) == ["TDA001"]


def test_select_and_ignore_filter_rules():
    src = """
    import threading
    import time

    def go(fn):
        th = threading.Thread(target=fn)
        return th, time.time()
    """
    assert codes(lint(src)) == ["TDA001", "TDA021"]
    assert codes(lint(src, select=("TDA021",))) == ["TDA021"]
    assert codes(lint(src, ignore=("TDA021",))) == ["TDA001"]
    with pytest.raises(ValueError, match="unknown rule code"):
        lint(src, select=("TDA999",))


def test_syntax_error_is_tda000():
    vs = lint("def broken(:\n    pass\n")
    assert codes(vs) == ["TDA000"]
    assert "does not parse" in vs[0].message


# ------------------------------------------------------------- baseline


VIOLATING = """\
import time


def stamp():
    return time.time()
"""

CLEAN = """\
import time


def stamp():
    return time.monotonic()
"""


def test_baseline_round_trip(tmp_path):
    mod = tmp_path / "tpu_distalg" / "mod.py"
    mod.parent.mkdir()
    mod.write_text(VIOLATING)
    bl = tmp_path / "lint_baseline.json"

    vs = engine.lint_file(str(mod), analysis.RULES)
    assert codes(vs) == ["TDA001"]

    # 1. baselined: the same violation stops counting
    blmod.save(str(bl), vs)
    doc = blmod.load(str(bl))
    new, baselined, stale = blmod.apply(
        doc, engine.lint_file(str(mod), analysis.RULES))
    assert (new, len(baselined), stale) == ([], 1, [])

    # 2. line drift does not invalidate the fingerprint
    mod.write_text("# a new leading comment\n" + VIOLATING)
    new, baselined, stale = blmod.apply(
        doc, engine.lint_file(str(mod), analysis.RULES))
    assert (new, len(baselined), stale) == ([], 1, [])

    # 3. a SECOND identical violation is NOT covered by count=1
    mod.write_text(VIOLATING + "\n\ndef stamp2():\n"
                   "    return time.time()\n")
    new, _, _ = blmod.apply(
        doc, engine.lint_file(str(mod), analysis.RULES))
    assert codes(new) == ["TDA001"]

    # 4. violation fixed -> the baseline entry is STALE, an error
    mod.write_text(CLEAN)
    new, baselined, stale = blmod.apply(
        doc, engine.lint_file(str(mod), analysis.RULES))
    assert (new, baselined) == ([], [])
    assert len(stale) == 1 and stale[0]["code"] == "TDA001"


def test_baseline_round_trip_through_cli(tmp_path, monkeypatch, capsys):
    from tpu_distalg import cli

    monkeypatch.delenv("TDA_TELEMETRY_DIR", raising=False)
    mod = tmp_path / "tpu_distalg" / "mod.py"
    mod.parent.mkdir()
    mod.write_text(VIOLATING)
    bl = tmp_path / "bl.json"

    assert cli.main(["lint", str(mod), "--no-ruff"]) == 1
    assert cli.main(["lint", str(mod), "--no-ruff",
                     "--baseline", str(bl), "--update-baseline"]) == 0
    assert cli.main(["lint", str(mod), "--no-ruff",
                     "--baseline", str(bl)]) == 0
    mod.write_text(CLEAN)  # fixed -> stale entry -> exit 1
    assert cli.main(["lint", str(mod), "--no-ruff",
                     "--baseline", str(bl)]) == 1
    out = capsys.readouterr().out
    assert "stale baseline entry" in out


def test_cli_json_format(tmp_path, monkeypatch, capsys):
    from tpu_distalg import cli

    monkeypatch.delenv("TDA_TELEMETRY_DIR", raising=False)
    mod = tmp_path / "tpu_distalg" / "mod.py"
    mod.parent.mkdir()
    mod.write_text(VIOLATING)
    assert cli.main(["lint", str(mod), "--no-ruff",
                     "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["files"] == 1
    assert [v["code"] for v in doc["violations"]] == ["TDA001"]
    assert doc["violations"][0]["fingerprint"]


def test_lint_run_emits_telemetry_span(tmp_path, monkeypatch):
    from tpu_distalg import cli
    from tpu_distalg.telemetry import events as tevents

    monkeypatch.delenv("TDA_TELEMETRY_DIR", raising=False)
    mod = tmp_path / "tpu_distalg" / "mod.py"
    mod.parent.mkdir()
    mod.write_text(VIOLATING)
    tdir = tmp_path / "tel"
    assert cli.main(["lint", str(mod), "--no-ruff",
                     "--telemetry-dir", str(tdir)]) == 1
    tevents.configure(False)  # close the sink so the log is flushed
    events = []
    for p in tdir.glob("events-*.jsonl"):
        with open(p) as f:
            events.extend(json.loads(line) for line in f if line)
    names = {e.get("name") for e in events if e["ev"] == "span_end"}
    assert "lint" in names
    counters = [e for e in events if e["ev"] == "counters"]
    assert counters and counters[0]["counters"]["lint.TDA001"] == 1


# ------------------------------------------------------------------ fix


def test_fix_inserts_daemon_false():
    src = ("import threading\n\n"
           "def go(fn):\n"
           "    return threading.Thread(target=fn)\n")
    vs = engine.lint_source(src, LIB, analysis.RULES)
    fixed, n = fixes.fix_source(src, vs)
    assert n == 1
    assert "threading.Thread(target=fn, daemon=False)" in fixed
    assert engine.lint_source(fixed, LIB, analysis.RULES) == []


def test_fix_scaffolds_reasonless_suppression():
    src = ("import time\n\n\n"
           "def stamp():\n"
           "    return time.time()  # tda: ignore[TDA001]\n")
    vs = engine.lint_source(src, LIB, analysis.RULES)
    assert "TDA000" in codes(vs)
    fixed, n = fixes.fix_source(src, vs)
    assert n == 1
    assert fixes.TODO_REASON in fixed
    # the scaffolded reason makes the suppression effective (and
    # grep-able for review)
    assert engine.lint_source(fixed, LIB, analysis.RULES) == []


def test_fix_via_cli_rewrites_file(tmp_path, monkeypatch):
    from tpu_distalg import cli

    monkeypatch.delenv("TDA_TELEMETRY_DIR", raising=False)
    mod = tmp_path / "tests" / "test_mod.py"
    mod.parent.mkdir()
    mod.write_text("import threading\n\n"
                   "def go(fn):\n"
                   "    return threading.Thread(target=fn)\n")
    assert cli.main(["lint", str(mod), "--no-ruff", "--fix"]) == 0
    assert "daemon=False" in mod.read_text()


def test_fix_multiline_thread_call_with_trailing_comma():
    # regression: inserting ", daemon=False" after an existing trailing
    # comma produced a double comma — invalid Python from a tool
    # advertised as mechanically safe
    src = ("import threading\n\n"
           "t = threading.Thread(\n"
           "    target=print,\n"
           ")\n")
    vs = engine.lint_source(src, LIB, analysis.RULES)
    assert codes(vs) == ["TDA021"]
    fixed, n = fixes.fix_source(src, vs)
    assert n == 1
    import ast as _ast

    _ast.parse(fixed)  # must stay valid Python
    assert "daemon=False" in fixed
    assert engine.lint_source(fixed, LIB, analysis.RULES) == []


def test_fix_empty_arg_thread_call():
    src = "import threading\n\nt = threading.Thread()\n"
    vs = engine.lint_source(src, LIB, analysis.RULES)
    fixed, _ = fixes.fix_source(src, vs)
    assert "threading.Thread(daemon=False)" in fixed


def test_violation_paths_are_normalized():
    # regression: './tpu_distalg/x.py' and 'tpu_distalg/x.py' must
    # yield the SAME fingerprint or every baseline entry goes stale on
    # an equivalently-spelled invocation
    src = "import time\n\n\ndef f():\n    return time.time()\n"
    plain = engine.lint_source(src, "tpu_distalg/x.py", analysis.RULES)
    dotted = engine.lint_source(src, "./tpu_distalg/x.py",
                                analysis.RULES)
    absolute = engine.lint_source(
        src, os.path.join(os.getcwd(), "tpu_distalg", "x.py"),
        analysis.RULES)
    assert plain[0].path == dotted[0].path == absolute[0].path
    assert (plain[0].fingerprint == dotted[0].fingerprint
            == absolute[0].fingerprint)


def test_suppression_on_last_line_of_multiline_statement():
    # regression: the violation anchors at the statement's FIRST line;
    # a trailing comment on its last line must still suppress
    src = ("import time\n\n\n"
           "def f():\n"
           "    return time.time(\n"
           "    )  # tda: ignore[TDA001] -- wall-clock domain here\n")
    assert lint(src) == []


def test_tda002_bare_listdir_classified_as_filesystem():
    src = """
    from os import listdir

    def walk(d, sink):
        for name in listdir(d):
            sink(name)
    """
    vs = lint(src)
    assert codes(vs) == ["TDA002"]
    assert "filesystem-enumeration" in vs[0].message


# ------------------------------------------------------------- the tree


def test_committed_tree_lints_clean():
    """TIER-1 gate: the committed repo carries zero un-baselined
    violations — per-file TDA0xx AND the project-graph TDA1xx pass —
    the invariant every rule exists to hold."""
    from tpu_distalg import cli

    paths = [str(REPO / "tpu_distalg"), str(REPO / "tests"),
             str(REPO / "scripts")]
    rc = cli.main(["lint", *paths, "--no-ruff",
                   "--baseline", str(REPO / "lint_baseline.json")])
    assert rc == 0


def test_committed_baseline_carries_no_grandfathered_debt():
    """The shipped baseline is EMPTY: determinism/seam findings were
    fixed or reason-suppressed at the source, not grandfathered (the
    baseline mechanism exists for future debt, not current debt)."""
    doc = blmod.load(str(REPO / "lint_baseline.json"))
    assert doc["entries"] == []


def test_every_shipped_rule_has_code_and_invariant():
    assert [r.code for r in analysis.RULES] == sorted(
        {r.code for r in analysis.RULES})
    for rule in analysis.RULES:
        assert engine.CODE_RE.match(rule.code)
        assert rule.invariant and rule.name


# ---------------------------------------------------------------- TDA080

SRV = "tpu_distalg/serve/someserve.py"


def test_tda080_raw_namedsharding_ctor_flagged():
    src = """
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(mesh, x):
        s = NamedSharding(mesh, P("data", None))
        return s
    """
    assert codes(lint(src, path=MODEL)) == ["TDA080"]
    assert codes(lint(src, path=SRV)) == ["TDA080"]
    # only models/ and serve/ are in scope — parallel/ IS the engine
    assert "TDA080" not in codes(
        lint(src, path="tpu_distalg/parallel/somemod.py"))


def test_tda080_device_put_with_layout_flagged():
    src = """
    import jax

    def place(x, rows):
        return jax.device_put(x, rows)
    """
    assert codes(lint(src, path=MODEL)) == ["TDA080"]
    kw = """
    import jax

    def place(x, rows):
        return jax.device_put(x, device=rows)
    """
    assert codes(lint(kw, path=MODEL)) == ["TDA080"]
    ctor = """
    import jax

    def place(x, mesh):
        return jax.device_put(x, data_sharding(mesh, 2))
    """
    assert codes(lint(ctor, path=MODEL)) == ["TDA080"]


def test_tda080_spec_into_constraint_flagged():
    src = """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def body(x, mesh):
        return lax.with_sharding_constraint(x, spec_of(mesh))
    """
    assert codes(lint(src, path=MODEL)) == ["TDA080"]
    # with_sharding_constraint's real keyword is `shardings`
    kw = """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def body(x):
        return lax.with_sharding_constraint(x, shardings=P("data"))
    """
    assert codes(lint(kw, path=MODEL)) == ["TDA080"]


def test_tda080_negative_engine_and_program_specs():
    clean = """
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from tpu_distalg.parallel import partition

    def place(x, mesh, rows):
        a = partition.put(x, "w", "ssgd", mesh)
        b = jax.device_put(
            x, partition.leaf_sharding("ssgd", "X2", mesh))
        c = jax.device_put(x)          # bare staging: no layout
        d = lax.with_sharding_constraint(x, rows)  # engine-bound name
        f = jax.shard_map(lambda v: v, mesh=mesh,
                          in_specs=(P("data"),), out_specs=P())
        return a, b, c, d, f
    """
    assert lint(clean, path=MODEL) == []
    assert lint(clean, path=SRV) == []


# ---------------------------------------------------------------- TDA090

CLUS = "tpu_distalg/cluster/somemod.py"


def test_tda090_bare_recv_and_accept_flagged():
    src = """
    def serve(listener):
        conn, _ = listener.accept()
        return conn.recv(4096)
    """
    assert codes(lint(src, path=CLUS)) == ["TDA090", "TDA090"]
    # scope: only tpu_distalg/cluster/
    assert "TDA090" not in codes(lint(src, path=LIB))


def test_tda090_settimeout_arms_the_scope():
    src = """
    def serve(listener, sock, remaining):
        listener.settimeout(remaining)
        conn, _ = listener.accept()
        chunk = sock.recv(4096)
        return conn, chunk
    """
    assert lint(src, path=CLUS) == []


def test_tda090_settimeout_none_is_spelled_out_block_forever():
    src = """
    def serve(sock):
        sock.settimeout(None)
        return sock.recv(4)
    """
    got = codes(lint(src, path=CLUS))
    assert got == ["TDA090", "TDA090"]  # the None AND the bare recv


def test_tda090_unframed_sendall_flagged_framed_ok():
    bad = """
    def reply(sock, payload):
        sock.sendall(b"raw bytes")
        sock.sendall(payload)
    """
    assert codes(lint(bad, path=CLUS)) == ["TDA090", "TDA090"]
    good = """
    from tpu_distalg.cluster.transport import encode_frame

    def reply(sock, kind, meta):
        buf = encode_frame(kind, meta)
        sock.sendall(buf)
        sock.sendall(encode_frame("ack", {}))
    """
    assert lint(good, path=CLUS) == []


def test_tda090_nested_scope_needs_its_own_deadline():
    src = """
    def outer(sock, remaining):
        sock.settimeout(remaining)

        def inner(other):
            return other.recv(4)   # the outer deadline does not
        return inner               #   cover this socket
    """
    assert codes(lint(src, path=CLUS)) == ["TDA090"]


# ---------------------------------------------------------------- TDA091


def test_tda091_raw_write_without_fsync_flagged():
    bad = """
    def publish(path, buf):
        with open(path, "wb") as f:
            f.write(buf)
    """
    got = codes(lint(bad, path=CLUS))
    assert "TDA091" in got
    # scope: only tpu_distalg/cluster/ (TDA030 polices the rest)
    assert "TDA091" not in codes(lint(bad, path=LIB))
    # append mode is durable bytes too — the WAL's own mode
    bad_append = """
    def log_record(path, buf):
        with open(path, "ab") as f:
            f.write(buf)
    """
    assert "TDA091" in codes(lint(bad_append, path=CLUS))
    good = """
    import os

    def publish(path, buf):
        with open(path, "ab") as f:
            f.write(buf)
            f.flush()
            os.fsync(f.fileno())
    """
    assert lint(good, path=CLUS) == []


def test_tda091_rename_without_fsync_flagged():
    bad = """
    import os

    def swap(a, b):
        os.replace(a, b)
    """
    assert "TDA091" in codes(lint(bad, path=CLUS))
    good = """
    import os

    def swap(d, a, b):
        os.replace(a, b)
        fd = os.open(d, os.O_RDONLY)
        os.fsync(fd)
        os.close(fd)
    """
    # (TDA030's seam-coverage concern may still apply; TDA091's
    # durability-discipline one is satisfied by the fsync)
    assert "TDA091" not in codes(lint(good, path=CLUS))


def test_tda091_wal_append_must_fsync_before_send():
    bad = """
    import os
    from tpu_distalg.cluster.transport import send_frame

    def commit(f, sock, rec):
        f.write(rec)
        send_frame(sock, "ack", {})
    """
    assert codes(lint(bad, path=CLUS)) == ["TDA091"]
    # flush alone is NOT durability — the fsync is the contract
    flush_only = """
    import os
    from tpu_distalg.cluster.transport import send_frame

    def commit(f, sock, rec):
        f.write(rec)
        f.flush()
        send_frame(sock, "ack", {})
    """
    assert codes(lint(flush_only, path=CLUS)) == ["TDA091"]
    good = """
    import os
    from tpu_distalg.cluster.transport import send_frame

    def commit(f, sock, rec):
        f.write(rec)
        f.flush()
        os.fsync(f.fileno())
        send_frame(sock, "ack", {})
    """
    assert lint(good, path=CLUS) == []
    # a send BEFORE the write is not gated on it
    reply_first = """
    import os

    def reply_then_log(f, sock, buf, rec):
        sock.sendall(buf)
        f.write(rec)
        f.flush()
        os.fsync(f.fileno())
    """
    assert "TDA091" not in codes(lint(reply_first, path=CLUS))
    # the pairing judges the FIRST later send: an unfsynced nearer
    # ack must not hide behind a safe farther one (AST-walk order is
    # arbitrary — the rule sorts by source line)
    near_ack_unsafe = """
    import os
    from tpu_distalg.cluster.transport import send_frame

    def commit(f, sock, rec):
        f.write(rec)
        send_frame(sock, "ack1", {})
        f.flush()
        os.fsync(f.fileno())
        send_frame(sock, "ack2", {})
    """
    assert "TDA091" in codes(lint(near_ack_unsafe, path=CLUS))


# ------------------------------------------- TDA1xx: the project graph

from tpu_distalg.analysis import project as projmod  # noqa: E402


def plint(tmp_path, monkeypatch, files, select=None, ignore=None,
          changed_only=None, cache_dir=None):
    """Write a mini-project under tmp_path (cwd-relative, so module
    names resolve like the real tree's) and lint it whole."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    monkeypatch.chdir(tmp_path)
    return projmod.lint_tree(
        sorted(files), analysis.RULES, analysis.PROJECT_RULES,
        select=select, ignore=ignore, changed_only=changed_only,
        cache_dir=cache_dir)


TRAINER = """
import dataclasses


@dataclasses.dataclass
class TrainCarry:
    w: list
    acc: float
    res: list     # the EF residual of the topk schedule


def step(carry):
    carry.w = [x - 1 for x in carry.w]
    carry.acc = 0.5
    carry.res = [x * 2 for x in carry.res]
    return carry
"""

#: the PR 5 pre-fix spelling, reconstructed: carry grew `res`, the
#: payload builder (another module) kept serializing the old shape
CKPT_DROPS_RES = """
from miniproj.trainer import TrainCarry


def payload(c: TrainCarry) -> dict:
    return {"w": c.w, "acc": c.acc}
"""

CKPT_CARRIES_RES = """
from miniproj.trainer import TrainCarry


def payload(c: TrainCarry) -> dict:
    return {"w": c.w, "acc": c.acc, "res": c.res}
"""


def test_tda100_dropped_carry_field_flagged(tmp_path, monkeypatch):
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/trainer.py": TRAINER,
                 "miniproj/ckpt.py": CKPT_DROPS_RES},
                select=("TDA100",))
    assert [v.code for v in res.violations] == ["TDA100"]
    v = res.violations[0]
    assert v.path == "miniproj/ckpt.py"
    assert "'res'" in v.message and "TrainCarry" in v.message


def test_tda100_complete_payload_clean(tmp_path, monkeypatch):
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/trainer.py": TRAINER,
                 "miniproj/ckpt.py": CKPT_CARRIES_RES},
                select=("TDA100",))
    assert res.violations == []


def test_tda100_resolves_reexport_alias(tmp_path, monkeypatch):
    """The dataclass reaches the payload builder through a re-export
    chain with a rename — the graph still resolves it."""
    res = plint(tmp_path, monkeypatch, {
        "miniproj/__init__.py": "",
        "miniproj/trainer.py": TRAINER,
        "miniproj/api.py":
            "from miniproj.trainer import TrainCarry as TC\n",
        "miniproj/ckpt.py": """
            from miniproj.api import TC


            def payload(c: TC) -> dict:
                return {"w": c.w, "acc": c.acc}
            """,
    }, select=("TDA100",))
    assert [v.code for v in res.violations] == ["TDA100"]


CONFIG = """
import dataclasses


@dataclasses.dataclass
class JobConfig:
    beat_interval: float = 0.5
    n_windows: int = 8
    staleness: int = 4
"""

MINICLI = """
import argparse

from miniproj.config import JobConfig
from miniproj.sync import SyncSpec


def build_parser():
    p = argparse.ArgumentParser()
    p.add_argument("--beat-interval", type=float, default=0.5)
    p.add_argument("--n-windows", type=int, default=8)
    p.add_argument("--sync", default="ssp:4")
    return p


def main(args):
    spec = SyncSpec.parse(args.sync)
    return JobConfig(beat_interval=args.beat_interval,
                     n_windows=args.n_windows,
                     staleness=spec.staleness)
"""

SYNCMOD = """
class SyncSpec:
    @staticmethod
    def parse(text):
        return None
"""

#: the PR 13 pre-fix spelling, reconstructed: the launcher re-spawns
#: the role but forwards only --n-windows — the child runs default
#: heartbeat timing and sync mode
LAUNCHER_LOSSY = """
import sys

from miniproj.config import JobConfig


def spawn(config: JobConfig):
    return [sys.executable, "-m", "miniproj.cli",
            "--n-windows", str(config.n_windows)]
"""

LAUNCHER_COMPLETE = """
import sys

from miniproj.config import JobConfig


def spawn(config: JobConfig):
    return [sys.executable, "-m", "miniproj.cli",
            "--n-windows", str(config.n_windows),
            "--beat-interval", str(config.beat_interval),
            "--sync", f"ssp:{config.staleness}"]
"""


def test_tda101_lossy_argv_handoff_flagged(tmp_path, monkeypatch):
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/config.py": CONFIG,
                 "miniproj/sync.py": SYNCMOD,
                 "miniproj/cli.py": MINICLI,
                 "miniproj/launcher.py": LAUNCHER_LOSSY},
                select=("TDA101",))
    msgs = [v.message for v in res.violations]
    assert [v.code for v in res.violations] == ["TDA101", "TDA101"]
    assert any("beat_interval" in m and "--beat-interval" in m
               for m in msgs)
    # one level of local dataflow: staleness came from
    # SyncSpec.parse(args.sync), so --sync is the owed flag
    assert any("staleness" in m and "--sync" in m for m in msgs)


def test_tda101_complete_argv_clean(tmp_path, monkeypatch):
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/config.py": CONFIG,
                 "miniproj/sync.py": SYNCMOD,
                 "miniproj/cli.py": MINICLI,
                 "miniproj/launcher.py": LAUNCHER_COMPLETE},
                select=("TDA101",))
    assert res.violations == []


TELMOD = """
def counter(name, n=1):
    pass


def gauge(name, value):
    pass
"""

EMITTER = """
from miniproj import tel


def work(code):
    tel.counter("seen.requests")
    tel.counter("unseen.leak")
    tel.counter(f"percode.{code}")
"""


def _report_mod(waivers):
    return f"""
SUMMARY_ONLY_COUNTERS = {waivers!r}
PER_WORKER_PREFIXES = ("col.",)


def render(s):
    return "requests: " + str(s.get("seen.requests"))
"""


def test_tda102_unrendered_counter_flagged(tmp_path, monkeypatch):
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/tel.py": TELMOD,
                 "miniproj/emitter.py": EMITTER,
                 "miniproj/report_mod.py": _report_mod(("x.y",))},
                select=("TDA102",))
    msgs = [v.message for v in res.violations]
    assert len(res.violations) == 3
    assert any("'unseen.leak'" in m for m in msgs)
    assert any("percode." in m and "f-string family" in m
               for m in msgs)
    # the 'x.y' waiver covers nothing this surface emits — the
    # stale-waiver direction reports it in the same pass
    assert any("waiver 'x.y'" in m and "matches no emitted" in m
               for m in msgs)


def test_tda102_waiver_and_render_cover_counters(tmp_path,
                                                 monkeypatch):
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/tel.py": TELMOD,
                 "miniproj/emitter.py": EMITTER,
                 "miniproj/report_mod.py": _report_mod(
                     ("unseen.leak", "percode.*"))},
                select=("TDA102",))
    assert res.violations == []


def _writer(name, lock, other=None):
    imp = f"from miniproj import {other}\n" if other else ""
    return f"""
import threading

from miniproj import shared
{imp}

{lock} = threading.Lock()


def {name}_loop():
    with {lock}:
        shared.BOX.buf = 1


def start():
    t = threading.Thread(target={name}_loop, daemon=True)
    t.start()
    return t
"""


def test_tda103_split_locks_across_modules_flagged(tmp_path,
                                                   monkeypatch):
    res = plint(tmp_path, monkeypatch, {
        "miniproj/__init__.py": "",
        "miniproj/shared.py": "class Box:\n    pass\n\n\n"
                              "BOX = Box()\n",
        "miniproj/writer_a.py": _writer("a", "A_LOCK"),
        "miniproj/writer_b.py": _writer("b", "B_LOCK",
                                        other="writer_a"),
    }, select=("TDA103",))
    assert [v.code for v in res.violations] == ["TDA103", "TDA103"]
    assert {v.path for v in res.violations} == {
        "miniproj/writer_a.py", "miniproj/writer_b.py"}
    assert all("no common lock" in v.message.lower()
               or "different lock" in v.message.lower()
               for v in res.violations)


def test_tda103_shared_lock_clean(tmp_path, monkeypatch):
    res = plint(tmp_path, monkeypatch, {
        "miniproj/__init__.py": "",
        "miniproj/shared.py": "import threading\n\n\n"
                              "class Box:\n    pass\n\n\n"
                              "BOX = Box()\n"
                              "BOX_LOCK = threading.Lock()\n",
        "miniproj/writer_a.py": _writer("a", "shared.BOX_LOCK"),
        "miniproj/writer_b.py": _writer("b", "shared.BOX_LOCK",
                                        other="writer_a"),
    }, select=("TDA103",))
    assert res.violations == []


def test_project_graph_cache_hits_and_invalidation(tmp_path,
                                                   monkeypatch):
    files = {"miniproj/__init__.py": "",
             "miniproj/trainer.py": TRAINER,
             "miniproj/ckpt.py": CKPT_DROPS_RES}
    res1 = plint(tmp_path, monkeypatch, files, select=("TDA100",),
                 cache_dir=".lintcache")
    assert res1.n_cached == 0
    assert len(res1.violations) == 1
    res2 = plint(tmp_path, monkeypatch, files, select=("TDA100",),
                 cache_dir=".lintcache")
    assert res2.n_cached == len(files)
    assert len(res2.violations) == 1   # cached summaries, same verdict
    # edit ONE file: only it re-extracts, and the verdict follows the
    # new content
    files2 = dict(files, **{"miniproj/ckpt.py": CKPT_CARRIES_RES})
    res3 = plint(tmp_path, monkeypatch, files2, select=("TDA100",),
                 cache_dir=".lintcache")
    assert res3.n_cached == len(files) - 1
    assert res3.violations == []


def test_changed_only_lints_subset_but_graph_sees_all(tmp_path,
                                                      monkeypatch):
    """--changed semantics: a per-file violation in an UNCHANGED file
    is not reported, but a project-graph violation anchored there
    still is — the graph always covers the whole surface."""
    files = {
        "miniproj/__init__.py": "",
        "miniproj/trainer.py": TRAINER,
        "miniproj/ckpt.py": CKPT_DROPS_RES,
        # a per-file finding (TDA021: bare Thread) in a file we will
        # NOT mark changed
        "miniproj/threads.py": "import threading\n\n\n"
                               "def go():\n"
                               "    threading.Thread(target=go)"
                               ".start()\n",
    }
    res = plint(tmp_path, monkeypatch, files,
                changed_only={"miniproj/trainer.py"})
    assert res.n_linted == 1
    codes_found = [v.code for v in res.violations]
    assert "TDA100" in codes_found          # graph: unchanged ckpt.py
    assert "TDA021" not in codes_found      # per-file: not re-linted
    # full run still sees both
    res_full = plint(tmp_path, monkeypatch, files)
    codes_full = [v.code for v in res_full.violations]
    assert "TDA100" in codes_full and "TDA021" in codes_full


def test_suppression_in_unchanged_file_still_covers_graph_finding(
        tmp_path, monkeypatch):
    pinned = CKPT_DROPS_RES.replace(
        'return {"w": c.w, "acc": c.acc}',
        '# tda: ignore[TDA100] -- fixture: res is rebuilt at load\n'
        '    return {"w": c.w, "acc": c.acc}')
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/trainer.py": TRAINER,
                 "miniproj/ckpt.py": pinned},
                changed_only={"miniproj/trainer.py"})
    assert [v for v in res.violations if v.code == "TDA100"] == []


def test_unused_suppression_reported_and_fix_removes(tmp_path,
                                                     monkeypatch):
    src = ("def f():\n"
           "    return 1  # tda: ignore[TDA001] -- stale: the clock "
           "call is long gone\n")
    res = plint(tmp_path, monkeypatch, {"miniproj/mod.py": src})
    assert len(res.violations) == 1
    v = res.violations[0]
    assert v.code == "TDA000" and "suppresses no findings" in v.message
    fixed, n = fixes.fix_source(src, [v])
    assert n == 1
    assert "tda: ignore" not in fixed
    assert "return 1" in fixed


def test_unused_own_line_suppression_fix_deletes_line(tmp_path,
                                                      monkeypatch):
    src = ("# tda: ignore[TDA002] -- stale pin on its own line\n"
           "def f():\n"
           "    return 1\n")
    res = plint(tmp_path, monkeypatch, {"miniproj/mod.py": src})
    assert [v.code for v in res.violations] == ["TDA000"]
    fixed, n = fixes.fix_source(src, res.violations)
    assert n == 1 and "tda: ignore" not in fixed
    assert fixed.startswith("def f():")


def test_unused_suppression_not_reported_under_select(tmp_path,
                                                      monkeypatch):
    """A --select run sees a FILTERED finding set; silence there must
    not read as rot."""
    src = ("def f():\n"
           "    return 1  # tda: ignore[TDA001] -- maybe used by a "
           "rule this run skipped\n")
    res = plint(tmp_path, monkeypatch, {"miniproj/mod.py": src},
                select=("TDA002",))
    assert res.violations == []


def test_used_suppression_not_reported_as_unused(tmp_path,
                                                 monkeypatch):
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/trainer.py": TRAINER,
                 "miniproj/ckpt.py": CKPT_DROPS_RES.replace(
                     'return {"w": c.w, "acc": c.acc}',
                     '# tda: ignore[TDA100] -- fixture: rebuilt at '
                     'load\n    return {"w": c.w, "acc": c.acc}')})
    assert [v for v in res.violations
            if "suppresses no findings" in v.message] == []


def test_cli_changed_flag_uses_git_view(tmp_path, monkeypatch,
                                        capsys):
    from tpu_distalg import cli

    for rel, src in {
            "miniproj/__init__.py": "",
            "miniproj/trainer.py": TRAINER,
            "miniproj/ckpt.py": CKPT_DROPS_RES}.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(lint_cli, "_git_changed",
                        lambda: {"miniproj/trainer.py"})
    rc = cli.main(["lint", "miniproj", "--no-ruff", "--changed"])
    out = capsys.readouterr().out
    assert rc == 1                      # the graph finding still gates
    assert "TDA100" in out
    assert "1 linted, graph over all" in out


def test_project_rules_have_codes_and_invariants():
    assert [r.code for r in analysis.PROJECT_RULES] == [
        "TDA100", "TDA101", "TDA102", "TDA103",
        "TDA110", "TDA111", "TDA112", "TDA113", "TDA114"]
    for rule in analysis.PROJECT_RULES:
        assert engine.CODE_RE.match(rule.code)
        assert rule.invariant and rule.name
        assert rule.check(None) == ()   # per-file hook is inert


def test_graph_tolerates_syntax_error_file(tmp_path, monkeypatch):
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/trainer.py": TRAINER,
                 "miniproj/ckpt.py": CKPT_DROPS_RES,
                 "miniproj/broken.py": "def broken(:\n"})
    by_code = {v.code for v in res.violations}
    assert "TDA000" in by_code          # the parse failure
    assert "TDA100" in by_code          # the graph still ran


def test_tda100_resolves_relative_reexport_in_package_init(
        tmp_path, monkeypatch):
    """`from .trainer import TrainCarry` inside the package __init__
    (a RELATIVE import in a package module — one level means the
    package itself, not its parent) still resolves."""
    res = plint(tmp_path, monkeypatch, {
        "miniproj/__init__.py":
            "from .trainer import TrainCarry\n",
        "miniproj/trainer.py": TRAINER,
        "miniproj/ckpt.py": """
            from miniproj import TrainCarry


            def payload(c: TrainCarry) -> dict:
                return {"w": c.w, "acc": c.acc}
            """,
    }, select=("TDA100",))
    assert [v.code for v in res.violations] == ["TDA100"]


def test_unused_multiline_pin_fix_removes_whole_block(tmp_path,
                                                      monkeypatch):
    src = ("def f():\n"
           "    # tda: ignore[TDA002] -- stale pin whose reason\n"
           "    # wraps onto a second and a third comment line\n"
           "    # before the code it once covered\n"
           "    return 1\n"
           "    # an unrelated comment at ANOTHER indent survives\n")
    res = plint(tmp_path, monkeypatch, {"miniproj/mod.py": src})
    assert [v.code for v in res.violations] == ["TDA000"]
    fixed, n = fixes.fix_source(src, res.violations)
    assert n == 3              # the pin line + its two continuations
    assert "tda: ignore" not in fixed
    assert "wraps onto" not in fixed and "once covered" not in fixed
    assert "unrelated comment" in fixed
    assert "return 1" in fixed


def test_cache_subset_run_does_not_evict_other_entries(tmp_path,
                                                       monkeypatch):
    files = {"miniproj/__init__.py": "",
             "miniproj/trainer.py": TRAINER,
             "miniproj/ckpt.py": CKPT_CARRIES_RES}
    plint(tmp_path, monkeypatch, files, select=("TDA100",),
          cache_dir=".lintcache")
    # a subset invocation must leave the other summaries cached
    projmod.lint_tree(["miniproj/trainer.py"], analysis.RULES,
                      analysis.PROJECT_RULES, select=("TDA100",),
                      cache_dir=".lintcache")
    res = plint(tmp_path, monkeypatch, files, select=("TDA100",),
                cache_dir=".lintcache")
    assert res.n_cached == len(files)


def test_git_changed_is_cwd_relative_from_subdir(tmp_path,
                                                 monkeypatch):
    import subprocess

    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "mod.py").write_text("x = 1\n")
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
    monkeypatch.chdir(tmp_path / "pkg")
    changed = lint_cli._git_changed()
    # git reports 'pkg/mod.py' (repo-root-relative); the lint file
    # list is cwd-relative, so the set must say 'mod.py'
    assert changed == {"mod.py"}


# ------------------------------------------- TDA11x: the wire protocol

TRANSPORT_STUB = """
def send_frame(sock, kind, meta, arrays=()):
    raise NotImplementedError


def request(sock, kind, meta, arrays=()):
    raise NotImplementedError


def recv_frame(sock):
    raise NotImplementedError
"""


def wire(tmp_path, monkeypatch, select, **mods):
    """A miniproj with the transport stub plus the given modules,
    linted with only ``select`` active."""
    files = {"miniproj/__init__.py": "",
             "miniproj/transport.py": TRANSPORT_STUB}
    files.update({f"miniproj/{name}.py": src
                  for name, src in mods.items()})
    return plint(tmp_path, monkeypatch, files, select=select)


PING_HANDLER = """
def handle(kind, meta, arrays):
    if kind == "ping":
        return ("pong", {}, ())
    return ("error", {"error": "unknown kind"}, ())
"""

#: kind-literal drift, reconstructed: the sender spells "pingg", the
#: dispatch knows "ping" — the frame rots into the unknown-kind error
#: fallthrough AND the branch goes dead, one finding per direction
PINGG_SENDER = """
from miniproj.transport import request


def probe(sock):
    k, m, a = request(sock, "pingg", {"slot": 0})
    if k != "pong":
        raise RuntimeError(m.get("error"))
    return m
"""

PING_SENDER = """
from miniproj.transport import request


def probe(sock):
    k, m, a = request(sock, "ping", {"slot": 0})
    if k != "pong":
        raise RuntimeError(m.get("error"))
    return m
"""


def test_tda110_kind_drift_flagged_both_directions(tmp_path,
                                                   monkeypatch):
    res = wire(tmp_path, monkeypatch, ("TDA110",),
               peer=PINGG_SENDER, serve=PING_HANDLER)
    assert [v.code for v in res.violations] == ["TDA110", "TDA110"]
    by_path = {v.path: v.message for v in res.violations}
    assert "'pingg'" in by_path["miniproj/peer.py"]
    assert "no handler" in by_path["miniproj/peer.py"]
    assert "'ping'" in by_path["miniproj/serve.py"]
    assert "nothing on the lint surface sends" \
        in by_path["miniproj/serve.py"]


def test_tda110_matched_kinds_clean(tmp_path, monkeypatch):
    res = wire(tmp_path, monkeypatch, ("TDA110",),
               peer=PING_SENDER, serve=PING_HANDLER)
    assert res.violations == []


def test_tda110_single_sided_surface_stays_silent(tmp_path,
                                                  monkeypatch):
    """A handler module linted without any requesting peer (or vice
    versa) supports no bijectivity claim — the rule must stay
    silent rather than flag every branch as dead."""
    res = wire(tmp_path, monkeypatch, ("TDA110",),
               serve=PING_HANDLER)
    assert res.violations == []


PUSH_HANDLER = """
def handle(kind, meta, arrays):
    if kind == "push":
        window = meta["window"]
        seq = meta.get("seq")
        return ("ok", {"version": window}, ())
    return ("error", {"error": "unknown kind"}, ())
"""

#: the dropped-key spelling: the handler indexes meta["window"], this
#: encoder ships only the slot — a KeyError one process away
PUSH_SENDER_NO_WINDOW = """
from miniproj.transport import request


def push(sock):
    k, m, a = request(sock, "push", {"slot": 1})
    if k != "ok":
        raise RuntimeError(m.get("error"))
    return m
"""

PUSH_SENDER_OK = """
from miniproj.transport import request


def push(sock, w):
    ident = {"slot": 1, "inc": 3}
    k, m, a = request(sock, "push", dict(ident, window=w))
    if k != "ok":
        raise RuntimeError(m.get("error"))
    return m
"""


def test_tda111_missing_required_key_flagged(tmp_path, monkeypatch):
    res = wire(tmp_path, monkeypatch, ("TDA111",),
               peer=PUSH_SENDER_NO_WINDOW, serve=PUSH_HANDLER)
    assert [v.code for v in res.violations] == ["TDA111"]
    v = res.violations[0]
    assert v.path == "miniproj/peer.py"
    assert "window" in v.message and "'push'" in v.message


def test_tda111_dataflow_resolved_keys_clean(tmp_path, monkeypatch):
    """dict(ident, window=w) over a literal ident resolves through
    the one-level dataflow; the handler's .get('seq') demands
    nothing."""
    res = wire(tmp_path, monkeypatch, ("TDA111",),
               peer=PUSH_SENDER_OK, serve=PUSH_HANDLER)
    assert res.violations == []


PULL_HANDLER = """
def handle(kind, meta, arrays):
    if kind == "pull":
        return ("chunk", {"seq": 0}, arrays)
    return ("error", {"error": "unknown kind"}, ())
"""

#: reply-kind drift: the site waits for "chunks", a kind no handler
#: of "pull" ever sends — the comparison can never come true
PULL_SENDER_WRONG_REPLY = """
from miniproj.transport import request


def pull(sock):
    k, m, a = request(sock, "pull", {"slot": 0})
    if k == "error":
        raise RuntimeError(m.get("error"))
    if k == "chunks":
        return a
    return None
"""

#: the PR 13 pre-fix spelling, reconstructed: any unexpected reply —
#: including a dying peer's ("error", ...) — reads as a genuine
#: "nothing for you" and the caller keeps going on stale state
PULL_SENDER_ADOPTS_ERROR = """
from miniproj.transport import request


def pull(sock):
    k, m, a = request(sock, "pull", {"slot": 0})
    if k == "chunk":
        return a
    return None
"""

PULL_SENDER_OK = """
from miniproj.transport import request


def pull(sock):
    k, m, a = request(sock, "pull", {"slot": 0})
    if k != "chunk":
        raise RuntimeError(m.get("error"))
    return a
"""


def test_tda112_impossible_reply_kind_flagged(tmp_path, monkeypatch):
    res = wire(tmp_path, monkeypatch, ("TDA112",),
               peer=PULL_SENDER_WRONG_REPLY, serve=PULL_HANDLER)
    assert [v.code for v in res.violations] == ["TDA112"]
    v = res.violations[0]
    assert "'chunks'" in v.message and "no handler" in v.message


def test_tda112_unchecked_error_reply_flagged(tmp_path, monkeypatch):
    res = wire(tmp_path, monkeypatch, ("TDA112",),
               peer=PULL_SENDER_ADOPTS_ERROR, serve=PULL_HANDLER)
    assert [v.code for v in res.violations] == ["TDA112"]
    v = res.violations[0]
    assert "'error'" in v.message
    assert "silently adopted" in v.message


def test_tda112_catch_all_rejection_clean(tmp_path, monkeypatch):
    res = wire(tmp_path, monkeypatch, ("TDA112",),
               peer=PULL_SENDER_OK, serve=PULL_HANDLER)
    assert res.violations == []


RESUME_HANDLER = """
def _fence_stale(meta):
    return int(meta.get("inc", -1)) < 0


def handle(kind, meta, arrays):
    if kind == "resume":
        if _fence_stale(meta):
            return ("error", {"error": "stale slot"}, ())
        return ("ok", {}, ())
    return ("error", {"error": "unknown kind"}, ())
"""

#: the token-less resume, reconstructed: the one frame the
#: incarnation fencing cannot see — it either bounces as a zombie's
#: or keeps a dead incarnation looking alive
RESUME_SENDER_NO_INC = """
from miniproj.transport import request


def resume(sock):
    k, m, a = request(sock, "resume", {"slot": 0})
    if k != "ok":
        raise RuntimeError(m.get("error"))
    return m
"""

RESUME_SENDER_OK = """
from miniproj.transport import request


def resume(sock):
    k, m, a = request(sock, "resume", {"slot": 0, "inc": 5})
    if k != "ok":
        raise RuntimeError(m.get("error"))
    return m
"""


def test_tda113_tokenless_fenced_frame_flagged(tmp_path, monkeypatch):
    res = wire(tmp_path, monkeypatch, ("TDA113",),
               peer=RESUME_SENDER_NO_INC, serve=RESUME_HANDLER)
    assert [v.code for v in res.violations] == ["TDA113"]
    v = res.violations[0]
    assert v.path == "miniproj/peer.py"
    assert "'inc' token" in v.message and "'resume'" in v.message


def test_tda113_token_carried_clean(tmp_path, monkeypatch):
    res = wire(tmp_path, monkeypatch, ("TDA113",),
               peer=RESUME_SENDER_OK, serve=RESUME_HANDLER)
    assert res.violations == []


#: ack-before-append, reconstructed: the peer observes an "ok" a
#: crashed recovery would forget it ever sent
ACK_FIRST_HANDLER = """
from miniproj.transport import send_frame


class Ledger:
    def handle(self, kind, meta, arrays):
        if kind == "commit":
            send_frame(self.conn, "ok", {})
            self.wal.append("commit", meta)
        return None
"""

APPEND_FIRST_HANDLER = """
from miniproj.transport import send_frame


class Ledger:
    def handle(self, kind, meta, arrays):
        if kind == "commit":
            self.wal.append("commit", meta)
            send_frame(self.conn, "ok", {})
        return None
"""


def test_tda114_ack_before_append_flagged(tmp_path, monkeypatch):
    res = wire(tmp_path, monkeypatch, ("TDA114",),
               serve=ACK_FIRST_HANDLER)
    assert [v.code for v in res.violations] == ["TDA114"]
    v = res.violations[0]
    assert "'ok'" in v.message and "'commit'" in v.message


def test_tda114_append_then_ack_clean(tmp_path, monkeypatch):
    res = wire(tmp_path, monkeypatch, ("TDA114",),
               serve=APPEND_FIRST_HANDLER)
    assert res.violations == []


# -------------------------------------------------- `tda protocol`


def test_protocol_check_matches_committed_doc(monkeypatch, capsys):
    """TIER-1 gate: docs/PROTOCOL.md IS the extracted contract — the
    same check scripts/lint_gate.sh runs."""
    from tpu_distalg import cli

    monkeypatch.delenv("TDA_TELEMETRY_DIR", raising=False)
    monkeypatch.chdir(REPO)
    assert cli.main(["protocol", "--check"]) == 0


def test_protocol_json_renders_the_cluster_contract(monkeypatch,
                                                    capsys):
    from tpu_distalg import cli

    monkeypatch.delenv("TDA_TELEMETRY_DIR", raising=False)
    monkeypatch.chdir(REPO)
    assert cli.main(["protocol", "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {"frames", "frame_sites", "wal_records",
                        "synthetics", "n_dynamic_sends"}
    kinds = {row["kind"] for row in doc["frames"]}
    assert {"join", "push", "pull", "poll", "beat", "bye"} <= kinds
    fenced = {row["kind"] for row in doc["frames"] if row["fenced"]}
    assert "push" in fenced and "skip" in fenced
    assert "reset" in doc["synthetics"]   # the link's local synthetic


# ------------------------------------------ lint surface invariants


def test_cli_json_schema_is_pinned(tmp_path, monkeypatch, capsys):
    """The --format json document is parsed by scripts/lint_gate.sh
    and editor tooling: its top-level keys and per-finding fields
    (suppression findings ride the same shape) are pinned here so
    schema drift is a deliberate edit, not an accident."""
    from tpu_distalg import cli

    monkeypatch.delenv("TDA_TELEMETRY_DIR", raising=False)
    pkg = tmp_path / "tpu_distalg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(VIOLATING)
    (pkg / "pinned.py").write_text(
        "# tda: ignore[TDA002] -- stale pin, nothing underneath\n"
        "X = 1\n")
    assert cli.main(["lint", str(pkg), "--no-ruff",
                     "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {"files", "linted", "cached", "graph_seconds",
                        "violations", "baselined", "stale_baseline",
                        "ruff_rc", "ruff_output"}
    assert doc["files"] == 2 and doc["linted"] == 2
    assert {v["code"] for v in doc["violations"]} \
        == {"TDA000", "TDA001"}   # a finding + a suppression record
    for v in doc["violations"]:
        assert set(v) == {"code", "message", "path", "line", "col",
                          "snippet", "fingerprint"}
    assert isinstance(doc["graph_seconds"], float)
    assert doc["baselined"] == 0 and doc["stale_baseline"] == []


def test_lint_graph_work_stays_incremental(tmp_path, monkeypatch):
    """TIER-1 perf tripwire: the protocol extraction rides every
    summary build, so the graph pass must stay incremental. Counted
    as work, not seconds (a wall clock on a machine running six test
    workers measures the neighbours): a cold full tree summarises
    every file once, a warm --changed-style run parses the one
    changed file and summarises none."""
    paths = [str(REPO / "tpu_distalg"), str(REPO / "tests"),
             str(REPO / "scripts")]
    files = engine.iter_python_files(paths)
    built = []
    for name in ("summarize_context", "extract_summary"):
        real = getattr(projmod, name)
        monkeypatch.setattr(
            projmod, name,
            lambda *a, _real=real, **k: built.append(1) or _real(*a, **k))
    cache = str(tmp_path / "graphcache")
    cold = projmod.lint_tree(files, analysis.RULES,
                             analysis.PROJECT_RULES, cache_dir=cache)
    assert cold.n_cached == 0 and cold.n_linted == len(files)
    assert len(built) == len(files)
    del built[:]
    warm = projmod.lint_tree(
        files, analysis.RULES, analysis.PROJECT_RULES,
        changed_only={engine.norm_path(files[0])}, cache_dir=cache)
    assert warm.n_cached == len(files) and warm.n_linted == 1
    assert built == []


# --------------------------------------- TDA102: stale-waiver audit

#: one entry per line — the committed report.py style the --fix path
#: assumes (it deletes the entry's line plus its riding comments)
STALE_WAIVER_REPORT = """
SUMMARY_ONLY_COUNTERS = (
    "unseen.leak",
    "percode.*",
    "ghost.metric",
    # the summary line it used to feed, retired three PRs ago
)
PER_WORKER_PREFIXES = ("col.",)


def render(s):
    return "requests: " + str(s.get("seen.requests"))
"""


def test_tda102_stale_waiver_flagged(tmp_path, monkeypatch):
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/tel.py": TELMOD,
                 "miniproj/emitter.py": EMITTER,
                 "miniproj/report_mod.py": _report_mod(
                     ("unseen.leak", "percode.*", "ghost.metric"))},
                select=("TDA102",))
    assert [v.code for v in res.violations] == ["TDA102"]
    v = res.violations[0]
    assert v.path == "miniproj/report_mod.py"
    assert "'ghost.metric'" in v.message
    assert "matches no emitted" in v.message


def test_tda102_waiver_audit_needs_an_emitting_surface(tmp_path,
                                                       monkeypatch):
    """A lone report-module lint sees no emissions at all: every
    waiver would read as stale — the audit must stay silent."""
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/tel.py": TELMOD,
                 "miniproj/report_mod.py": _report_mod(
                     ("ghost.metric",))},
                select=("TDA102",))
    assert res.violations == []


def test_tda102_stale_waiver_fix_removes_entry_line(tmp_path,
                                                    monkeypatch):
    res = plint(tmp_path, monkeypatch,
                {"miniproj/__init__.py": "",
                 "miniproj/tel.py": TELMOD,
                 "miniproj/emitter.py": EMITTER,
                 "miniproj/report_mod.py": STALE_WAIVER_REPORT},
                select=("TDA102",))
    assert [v.code for v in res.violations] == ["TDA102"]
    src = textwrap.dedent(STALE_WAIVER_REPORT)
    fixed, n = fixes.fix_source(src, res.violations)
    assert n == 2              # the entry line + the comment under it
    assert "ghost.metric" not in fixed
    assert "retired three PRs ago" not in fixed
    assert '"unseen.leak",' in fixed and '"percode.*",' in fixed
    assert "def render" in fixed
