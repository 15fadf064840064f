#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
would call, at smoke sizes (depth cut, weights random from a seed), on every TPU device it finds:

  * trainer: SSGD logistic regression, 2^20 rows x (125 features + bias)
    packed 128 wide, bf16, minibatch 0.1, one 1500-step segment through
    ``ssgd.train`` — the megakernel on one data shard, the block-gather
    kernel with its per-step psum on more — checked against the XLA
    Bernoulli trainer on the same rows; ``tda ssgd`` itself on the
    breast-cancer set against the reference band;
  * trainer -> server: ``tda als`` at 4096 x 16384 rank 64 writes an
    artifact, ``tda serve`` answers 64 requests from it through the
    compiled fused matmul+top-k kernel, checked against
    ``xla_matmul_topk`` on the same factors;
  * kernel roll-call: every ``pallas_call`` a default TPU path selects,
    compiled at its smoke geometry and compared with its in-repo XLA
    reference at the tolerance stated beside each check;
  * more than one device: the int8 / bucketed gradient-sync rings, and
    the shard-per-device + memory-in-use assertions.

Every stage runs even after one fails. Exit code 0 and a last stdout
line ``{"ok": true, "device": {...}}`` only when every stage passed and
no kernel was built with ``interpret=True``. With no TPU (including
``JAX_PLATFORMS=cpu``) it exits 2 before any stage and prints no
result. The full log (tracebacks, captured CLI output) goes to
``chiprun_out/chip_smoke_<n>dev.log``. Stage names as arguments run
those stages alone (``python chip_smoke.py ssgd_hashed``: what a
four-chip call is given when one stage's path is all that changed).
"""

from __future__ import annotations

import contextlib
import functools
import glob
import io
import json
import os
import re
import shutil
import sys
import tempfile
import time
import traceback

REFERENCE_BAND = 0.92       # BASELINE.md: SSGD/LR final acc 0.93-0.94
N_ROWS = 1 << 20            # the smoke sizes: 0.1 to 0.6 GB a stage
N_FEATURES = 125
N_STEPS = 1500
GATHER_BLOCK_ROWS = 8192
N_TEST = 16384              # held-out rows from the same generator
ALS_M, ALS_N, ALS_K = 4096, 16384, 64
ATTN_SEQ, ATTN_HEADS, ATTN_DIM = 32768, 8, 128
PR_VERTICES, PR_AVG_DEGREE = 1_000_000, 8.0


class Spy:
    """What the harness observes about a stage without changing it:
    every ``pallas_call`` built (name, interpret), every array the
    partition engine placed (by leaf name), and what JAX compiled:
    seconds, persistent-cache hits and misses, and the functions that
    compiled for over ``SLOW_COMPILE_S``, read from the program's own
    record (the ``jit:compile`` spans ``compile_cache.configure``
    starts, kept in memory by ``telemetry/events.py``)."""

    SLOW_COMPILE_S = 0.5

    def __init__(self):
        self.kernels: list[tuple[str, bool]] = []
        self.built: dict[str, set] = {}
        self.placed: dict[str, object] = {}
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        self._t_stage = time.perf_counter()

    def install(self):
        from jax.experimental import pallas as pl

        from tpu_distalg.parallel import partition

        real_call = pl.pallas_call

        def pallas_call(kernel, *a, **kw):
            fn = getattr(kernel, "func", kernel)
            name = (f"{getattr(fn, '__module__', '?').rsplit('.', 1)[-1]}"
                    f".{getattr(fn, '__name__', repr(fn))}")
            interp = bool(kw.get("interpret", False))
            self.kernels.append((name, interp))
            self.built.setdefault(name, set()).add(interp)
            return real_call(kernel, *a, **kw)

        pl.pallas_call = pallas_call

        real_put, real_place, real_reshard = (
            partition.put, partition.place, partition.reshard)

        def put(x, leaf_name, tbl, mesh):
            out = real_put(x, leaf_name, tbl, mesh)
            self.placed[leaf_name] = out
            return out

        def place(tree, tbl, mesh):
            out = real_place(tree, tbl, mesh)
            self.placed.update(partition.named_leaves(out))
            return out

        def reshard(tree, *a, **kw):
            out = real_reshard(tree, *a, **kw)
            self.placed.update(partition.named_leaves(out))
            return out

        partition.put, partition.place, partition.reshard = (
            put, place, reshard)

    def begin_stage(self):
        self.kernels = []
        self._t_stage = time.perf_counter()

    def end_stage(self) -> tuple[float, int, int, list[str]]:
        """The stage's compile seconds, cache hits, misses and slow
        compiles by function, added to the run's totals (a stage at a
        time, so the ring's bound never costs the totals a span)."""
        from tpu_distalg.telemetry import events
        from tpu_distalg.utils import compile_cache

        done = [sp for sp in events.finished()
                if sp.name == compile_cache.COMPILE
                and sp.t0 >= self._t_stage]
        comp = sum(sp.seconds for sp in done)
        hits = sum(sp.fields.get("hit") is True for sp in done)
        misses = sum(sp.fields.get("hit") is False for sp in done)
        self.compile_s += comp
        self.hits += hits
        self.misses += misses
        return comp, hits, misses, [
            f"{sp.fields['fun']} {sp.seconds:.1f}s" for sp in done
            if sp.seconds > self.SLOW_COMPILE_S]


class Smoke:
    def __init__(self, log_path: str, workdir: str):
        import jax

        self.devices = jax.devices()
        self.n = len(self.devices)
        self.spy = Spy()
        self.workdir = workdir
        self.tel_dir = os.path.join(workdir, "telemetry")
        self.log = open(log_path, "w")
        self.results: list[tuple[str, bool]] = []
        self.shared: dict = {}

    # ---- output ------------------------------------------------------

    def note(self, msg: str):
        """Log file only: captured CLI output, tracebacks."""
        self.log.write(msg + "\n")
        self.log.flush()

    def say(self, msg: str):
        print(msg, flush=True)
        self.note(msg)

    # ---- the entry points a user would call --------------------------

    def cli(self, argv: list[str]) -> str:
        """``tda <argv>`` in this process; returns its stdout. A
        non-zero exit is a stage failure."""
        from tpu_distalg import cli

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cli.main(argv)
        out = buf.getvalue()
        self.note(f"$ tda {' '.join(argv)}  -> rc {rc}\n{out}")
        if rc != 0:
            raise AssertionError(f"tda {' '.join(argv)} exited {rc}")
        return out

    def mesh(self, data=None, model: int = 1):
        from tpu_distalg.parallel import get_mesh

        return get_mesh(data=data, model=model)

    # ---- per-stage assertions ----------------------------------------

    def _device_events(self, since: float) -> list[dict]:
        evs = []
        for path in glob.glob(os.path.join(self.tel_dir, "*.jsonl")):
            with open(path) as f:
                for line in f:
                    try:
                        e = json.loads(line)
                    except ValueError:
                        continue  # a line still being written
                    if e.get("ev") == "device" \
                            and e.get("t_mono", 0.0) >= since:
                        evs.append(e)
        return evs

    def check_sharded(self, name: str, arr, *, replicated=False):
        """One shard per device, all equal; 1/n of the global size
        unless the rule table replicates the leaf."""
        shards = arr.addressable_shards
        devs = {s.device for s in shards}
        if len(shards) != self.n or len(devs) != self.n:
            raise AssertionError(
                f"{name}: {len(shards)} shard(s) on {len(devs)} "
                f"device(s), want one on each of {self.n}")
        sizes = {int(s.data.size) for s in shards}
        want = int(arr.size) if replicated else int(arr.size) // self.n
        if sizes != {want}:
            raise AssertionError(
                f"{name}: shard sizes {sorted(sizes)} of global "
                f"{arr.size}, want {want} each")
        return (f"{name}: {self.n} x {want} "
                f"({'replicated' if replicated else '1/n'})")

    def check_memory_everywhere(self):
        used = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            used.append(int(stats.get("bytes_in_use", 0)))
        if not all(u > 0 for u in used):
            raise AssertionError(
                f"bytes_in_use per device {used}: a device holds "
                f"nothing")
        return f"bytes_in_use/device: {used}"

    # ---- the runner --------------------------------------------------

    def stage(self, name: str, fn, *, kernels=(), min_devices: int = 1):
        if self.n < min_devices:
            self.say(f"[stage] {name}: not applicable "
                     f"(needs >= {min_devices} devices, have {self.n})")
            return
        from tpu_distalg import telemetry

        # library calls outside cli.main log here; cli.main reopens the
        # same directory itself (TDA_TELEMETRY_DIR)
        telemetry.configure(self.tel_dir)
        self.spy.begin_stage()
        t_mono = time.monotonic()
        t0 = time.perf_counter()
        detail, err = "", None
        try:
            detail = fn() or ""
            interp = sorted({k for k, i in self.spy.kernels if i})
            if interp:
                raise AssertionError(
                    f"kernel(s) built with interpret=True: {interp}")
            missing = [k for k in kernels
                       if False not in self.spy.built.get(k, ())]
            if missing:
                raise AssertionError(
                    f"expected compiled kernel(s) never built: "
                    f"{missing} (built so far: "
                    f"{sorted(self.spy.built)})")
            bad = [e for e in self._device_events(t_mono)
                   if e.get("pallas") != "compiled"
                   or e.get("platform") != "tpu"]
            if bad:
                raise AssertionError(
                    f"device mark says {bad[0].get('platform')}/"
                    f"{bad[0].get('pallas')}")
        except Exception as e:  # noqa: BLE001 — recorded, next stage runs
            err = e
            tb = traceback.format_exc()
            sys.stderr.write(tb)
            self.note(tb)
        wall = time.perf_counter() - t0
        comp, hits, misses, slow = self.spy.end_stage()
        built = sorted({k for k, _ in self.spy.kernels})
        self.results.append((name, err is None))
        self.say(
            f"[stage] {name}: {'ok' if err is None else 'FAILED'} "
            f"wall={wall:.1f}s compile={comp:.1f}s "
            f"cache={hits}hit/{misses}miss "
            + (f"slow_compiles={slow} " if slow else "")
            + f"kernels={built} "
            + (detail if err is None
               else f"{type(err).__name__}: {str(err)[:300]}"))
        self.spy.placed = {}   # drop the stage's arrays


# ---------------------------------------------------------------- stages


def _smoke_rows(s: Smoke):
    """The smoke's SSGD rows (+ a held-out tail from the same seed),
    generated once and shared by the stages that train on them."""
    if "rows" not in s.shared:
        from tpu_distalg.utils import datasets

        X, y = datasets.synthetic_two_class(
            N_ROWS + N_TEST, N_FEATURES, seed=0)
        X = datasets.add_bias_column(X)
        s.shared["rows"] = (X[:N_ROWS], y[:N_ROWS],
                            X[N_ROWS:], y[N_ROWS:])
    return s.shared["rows"]


def _held_out_acc(s: Smoke, w) -> float:
    """Held-out accuracy of trained weights under the reference's
    decision rule (utils/metrics.binary_accuracy), on the host."""
    import numpy as np

    _, _, X_te, y_te = _smoke_rows(s)
    w = np.asarray(w, np.float32)
    if w.shape != (N_FEATURES + 1,) or not np.isfinite(w).all():
        raise AssertionError(
            f"weights {w.shape}, finite={bool(np.isfinite(w).all())}")
    pred = np.where(X_te @ w < 0.0, 0.0, 1.0)
    return float((pred == y_te).mean())


def _xla_reference_acc(s: Smoke) -> float:
    """The XLA Bernoulli trainer (f32, no Pallas) on the same rows and
    schedule — the in-repo reference the kernel trainers are held to."""
    if "xla_acc" not in s.shared:
        from tpu_distalg.models import ssgd

        res = ssgd.train(
            *_smoke_rows(s), s.mesh(),
            ssgd.SSGDConfig(n_iterations=N_STEPS, eval_test=False,
                            init_seed=7))
        s.shared["xla_acc"] = _held_out_acc(s, res.w)
    return s.shared["xla_acc"]


def _acc_check(s: Smoke, name: str, w, tol: float = 0.03) -> str:
    acc, ref = _held_out_acc(s, w), _xla_reference_acc(s)
    if abs(acc - ref) > tol:
        raise AssertionError(
            f"{name} held-out acc {acc:.4f} vs XLA reference "
            f"{ref:.4f} (tolerance {tol})")
    return f"{name} acc {acc:.4f} (xla ref {ref:.4f}, tol {tol})"


def stage_ssgd_flagship(s: Smoke):
    """The smoke rows through ssgd.train; sampler chosen by the mesh
    (the megakernel on one data shard, the block gather on more)."""
    from tpu_distalg.models import ssgd

    mesh = s.mesh()
    sampler, kern = (("fused_train", "_train_kernel_gathered")
                     if s.n == 1 else
                     ("fused_gather", "_grad_kernel_gathered"))
    cfg = ssgd.SSGDConfig(
        n_iterations=N_STEPS, eval_test=False, x_dtype="bfloat16",
        sampler=sampler, gather_block_rows=GATHER_BLOCK_ROWS,
        shuffle_seed=0, init_seed=7)
    res = ssgd.train(*_smoke_rows(s), mesh, cfg)
    if (f"pallas_kernels.{kern}", False) not in s.spy.kernels:
        raise AssertionError(
            f"{sampler} did not build {kern}: {s.spy.kernels}")
    out = [_acc_check(s, sampler, res.w)]
    if s.n > 1:
        out.append(s.check_sharded("X2", s.spy.placed["X2"]))
        out.append(s.check_memory_everywhere())
    return " | ".join(out)


def _cli_ssgd(s: Smoke, sampler: str):
    """``tda ssgd`` on its hard-wired 398-row breast-cancer set, at the
    block geometry the repo's own on-chip convergence checks use
    (tests_tpu) and on ONE data shard: the
    reference band is a property of that geometry — at the CLI's
    default 1024-row blocks the set is a single block (full-batch GD,
    0.8947 on the CPU interpreter), and split four ways its ~100 rows
    per shard leave block sampling too coarse to hold the band (0.883
    there). dp>1 is covered by the flagship stage."""
    out = s.cli(["ssgd", "--sampler", sampler, "--n-iterations", "1500",
                 "--fused-pack", "4", "--gather-block-rows", "32",
                 "--shuffle-seed", "0", "--n-slices", "1", "--quiet"])
    acc = float(re.search(r"Final acc: ([0-9.]+)", out).group(1))
    if acc < REFERENCE_BAND:
        raise AssertionError(
            f"tda ssgd --sampler {sampler}: final acc {acc} below the "
            f"reference band {REFERENCE_BAND}")
    return f"breast-cancer final acc {acc:.4f} (band >= {REFERENCE_BAND})"


def stage_cli_ssgd_fused_train(s: Smoke):
    return _cli_ssgd(s, "fused_train")


def stage_cli_ssgd_fused_gather(s: Smoke):
    return _cli_ssgd(s, "fused_gather")


def _topk_against_xla(s: Smoke, art: str, model_slices: int):
    """The served (fused-kernel) top-k vs ``xla_matmul_topk`` on the
    same factors, as the server would call either: both score with one
    default-precision MXU pass (f32 operands rounded to bf16, f32
    accumulation), so indices must be equal and scores agree to 1e-5.
    The share of positions a full-f32 (``highest``) scoring would rank
    the same way is reported, not asserted — that is what the default
    precision costs, for both paths alike."""
    import jax
    import numpy as np

    from tpu_distalg.ops import pallas_topk as pt
    from tpu_distalg.serve import artifacts

    mesh = s.mesh(data=1, model=model_slices) if model_slices > 1 \
        else s.mesh()
    model = artifacts.load_artifact(art, mesh, k_top=10, use_fused=True)
    if not model.meta["fused"]:
        raise AssertionError("served model did not pick the fused path")
    ids = np.random.default_rng(1).integers(
        0, model.meta["n_users"], size=32).astype(np.int32)
    got = model.make_predict(32)(list(ids))
    g_vals = np.stack([v for v, _ in got])
    g_idx = np.stack([i for _, i in got])
    _root, state, _step = artifacts.load_artifact_state(art)
    U, V = state[0], state[1]
    r_vals, r_idx = (np.asarray(a) for a in pt.xla_matmul_topk(
        U[ids], V, 0, model.meta["n_items"], k=10))
    with jax.default_matmul_precision("highest"):
        h_idx = np.asarray(pt.xla_matmul_topk(
            U[ids], V, 0, model.meta["n_items"], k=10)[1])
    same = float((g_idx == r_idx).mean())
    if same < 1.0:
        raise AssertionError(
            f"fused top-k indices equal xla_matmul_topk's in only "
            f"{same:.4f} of positions (max score diff "
            f"{np.abs(g_vals - r_vals).max():.3e})")
    np.testing.assert_allclose(g_vals, r_vals, rtol=1e-5, atol=1e-5)
    placed = s.spy.placed.get("V")
    out = [f"fused top-k == xla_matmul_topk (32 queries x 10, scores "
           f"1e-5; {float((g_idx == h_idx).mean()):.3f} of positions "
           f"agree with full-f32 scoring)"]
    if s.n > 1 and model_slices == s.n:
        out.append(s.check_sharded("V", placed))
        out.append(s.check_memory_everywhere())
    return " | ".join(out)


def stage_als_train_serve(s: Smoke):
    art = os.path.join(s.workdir, "als_artifact")
    shape = ([] if s.n == 1 or s.n % 2
             else ["--mesh-shape", f"{s.n // 2}x2"])
    out = s.cli(["als", "--m", str(ALS_M), "--n", str(ALS_N), "--k",
                 str(ALS_K), "--n-iterations", "5", "--checkpoint-dir",
                 art, *shape])
    rmse = [float(x) for x in re.findall(r"rmse: ([0-9.eE+-]+)", out)]
    # lam=0.01 (the CLI default) floors the fit: rmse falls ~8x in
    # two sweeps, then creeps up toward the regularized optimum
    if len(rmse) != 5 or not rmse[-1] < 0.25 * rmse[0]:
        raise AssertionError(f"als rmse history {rmse}")
    slices = ["--model-slices", str(s.n)] if s.n > 1 else []
    out = s.cli(["serve", "--artifact", art, "--requests", "64",
                 "--max-batch", "32", "--k-top", "10", *slices])
    if "64/64 replies" not in out:
        raise AssertionError(
            f"serve did not answer all 64: "
            f"{re.findall(r'[0-9]+/[0-9]+ replies', out)}")
    return (f"rmse {rmse[0]:.4f}->{rmse[-1]:.4f} | serve 64/64 | "
            + _topk_against_xla(s, art, s.n))


def stage_flash_attention(s: Smoke):
    """Causal flash attention forward and backward at the smoke
    geometry (32k x 8 heads x 128, bf16), ring over all devices.
    Forward vs the XLA online-softmax ring at the same geometry
    (2e-2, bf16 outputs). Backward at 32k: finite, and dQ of the first
    2048 positions vs the XLA backward of that causal prefix (a causal
    row's dQ depends on nothing after it); the full dQ/dK/dV vs the XLA
    backward at 2048 tokens per device (1e-2 relative, the bound
    tests_tpu holds)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tpu_distalg.parallel import DATA_AXIS, data_parallel
    from tpu_distalg.parallel.ring import ring_attention
    from tpu_distalg.utils import prng

    mesh = s.mesh()
    S, H, d = ATTN_SEQ, ATTN_HEADS, ATTN_DIM
    key = prng.root_key(3)

    def qkv(n_tok, dtype):
        return tuple(
            jax.random.normal(jax.random.fold_in(key, i), (n_tok, H, d),
                              dtype) for i in range(3))

    def attn(m, **kw):
        return data_parallel(
            functools.partial(ring_attention, causal=True, **kw), m,
            in_specs=(P(DATA_AXIS, None, None),) * 3,
            out_specs=P(DATA_AXIS, None, None))

    def grads(f, *a):
        loss = lambda q, k, v: jnp.sum(  # noqa: E731
            f(q, k, v).astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*a)

    q, k, v = qkv(S, jnp.bfloat16)
    flash = np.asarray(jax.jit(attn(mesh, use_flash=True))(q, k, v),
                       np.float32)
    xla = np.asarray(jax.jit(attn(mesh, kv_chunk=2048))(q, k, v),
                     np.float32)
    np.testing.assert_allclose(flash, xla, rtol=2e-2, atol=2e-2)
    dq, dk, dv = grads(attn(mesh, use_flash=True), q, k, v)
    for name, g in (("dq", dq), ("dk", dk), ("dv", dv)):
        if not bool(jnp.isfinite(g.astype(jnp.float32)).all()):
            raise AssertionError(f"32k flash backward: {name} not finite")
    pre = 2048
    one = s.mesh(data=1)
    dq_ref = grads(attn(one, kv_chunk=1024),
                   q[:pre], k[:pre], v[:pre])[0]
    a = np.asarray(dq[:pre], np.float32)
    b = np.asarray(dq_ref, np.float32)
    rel_pre = float(np.abs(a - b).max() / np.abs(b).max())
    if rel_pre > 2e-2:
        raise AssertionError(
            f"32k flash dQ[:2048] vs XLA prefix backward: rel {rel_pre}")
    qs, ks, vs = qkv(2048 * s.n, jnp.float32)
    gf = grads(attn(mesh, use_flash=True), qs, ks, vs)
    gx = grads(attn(mesh, kv_chunk=1024), qs, ks, vs)
    rels = []
    for a, b in zip(gf, gx):
        a, b = np.asarray(a), np.asarray(b)
        rels.append(float(np.abs(a - b).max() / np.abs(b).max()))
    if max(rels) > 1e-2:
        raise AssertionError(f"flash-vs-xla grad rel errs {rels}")
    return (f"fwd 32k vs xla 2e-2 ok | bwd 32k finite, dQ prefix rel "
            f"{rel_pre:.1e} | bwd {2048 * s.n} tok rel "
            f"{max(rels):.1e} (<1e-2)")


def stage_pagerank(s: Smoke):
    """1M vertices / 8M edges, 3 sweeps under each scatter NAMED
    explicitly (``auto`` degrades spmv -> hybrid -> XLA without saying
    so): spmv and pallas vs the XLA sweep at 1e-5 relative (the bound
    tests_tpu holds at 200k vertices)."""
    import numpy as np

    from tpu_distalg.models import pagerank
    from tpu_distalg.utils import datasets

    t0 = time.perf_counter()
    edges = datasets.erdos_renyi_edges(PR_VERTICES, PR_AVG_DEGREE,
                                       seed=1)
    t_gen = time.perf_counter() - t0
    mesh = s.mesh()
    ranks, walls = {}, {}
    keep = {}
    for sc in ("spmv", "pallas", "xla"):
        t0 = time.perf_counter()
        res = pagerank.run(
            edges, mesh,
            pagerank.PageRankConfig(n_iterations=3, mode="standard",
                                    scatter=sc),
            n_vertices=PR_VERTICES)
        ranks[sc] = np.asarray(res.ranks)
        walls[sc] = time.perf_counter() - t0
        if sc == "spmv":
            keep = {"ranks": res.ranks}
    rel = {sc: float(np.abs(ranks[sc] - ranks["xla"]).max()
                     / ranks["xla"].max()) for sc in ("spmv", "pallas")}
    if max(rel.values()) > 1e-5 or not np.isfinite(ranks["xla"]).all():
        raise AssertionError(f"ranks vs xla sweep: rel errs {rel}")
    out = [f"rel vs xla {rel} | host+device wall/scatter "
           + ", ".join(f"{k} {v:.1f}s" for k, v in walls.items())
           + f" (edge synthesis {t_gen:.1f}s)"]
    if s.n > 1:
        # the 'pagerank' rule table replicates the rank vector (the
        # sweep's all-reduce owns combination) and shards the edge plan
        out.append(s.check_sharded("ranks", keep["ranks"],
                                   replicated=True))
        out.append(s.check_memory_everywhere())
    return " | ".join(out)


def stage_pagerank_rmat(s: Smoke):
    """Graph500 SCALE 20 drawn, deduplicated and planned on the device
    (``pagerank.build_rmat_graph`` / ``prepare_device_spmv``), 3 fused
    sweeps against the XLA sweep on the same edges pulled to the host,
    at 1e-5 relative; on several chips the graph is sharded by
    destination range, the ranges cut to equal loads: a chip's slots
    and plan hold the edges that point into its range (``pagerank``
    rule table), the ranks it reads
    and the ranks the run returns are whole on every chip, and the
    ranks a sweep writes are the chip's own range."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.models import pagerank
    from tpu_distalg.utils import datasets

    scale, seed = 20, 1
    mesh = s.mesh()
    t0 = time.perf_counter()
    graph = pagerank.build_rmat_graph(mesh, scale, 16, None, seed)
    slots = (s.check_sharded("slots", graph.src) if s.n > 1 else "")
    plan = pagerank.prepare_device_spmv(graph, mesh)
    t_plan = time.perf_counter() - t0
    if plan is None:
        raise AssertionError(f"the plan was refused at {graph.geom}")
    de = pagerank.spmv_device_edges(graph, mesh)
    fn = pagerank.make_run_fn(
        mesh, pagerank.PageRankConfig(n_iterations=3, mode="standard",
                                      scatter="spmv"),
        graph.n_vertices, None, plan)
    got = fn(de.src, de.dst, de.w_e, de.emask, de.has_out, de.n_ref)[0]
    src, dst = jax.jit(datasets.kronecker_edges(scale))(
        jnp.arange(graph.n_in, dtype=jnp.uint32), np.uint32(seed))
    want = np.asarray(pagerank.run(
        np.stack([np.asarray(src), np.asarray(dst)], axis=1), mesh,
        pagerank.PageRankConfig(n_iterations=3, mode="standard",
                                scatter="xla"),
        n_vertices=graph.n_vertices).ranks)
    rel = float(np.abs(np.asarray(got) - want).max() / want.max())
    if not rel <= 1e-5:
        raise AssertionError(f"device plan vs xla sweep: rel err {rel}")
    out = [f"rel vs xla {rel:.1e} | {graph.n_edges} distinct of "
           f"{graph.n_in} | ranks {plan.ranks_form} rg {plan.rg} ws "
           f"{plan.ws} | draw, dedup and plan {t_plan:.1f}s"]
    if s.n > 1:
        cuts = np.asarray(plan.bounds)
        if plan.ranks_out_form != "range" or cuts[-1] != plan.r8 \
                or not 0 <= np.diff(cuts).max() <= plan.rows_out < plan.r8:
            raise AssertionError(
                f"output ranks {plan.ranks_out_form}: ranges cut at "
                f"{cuts.tolist()} of {plan.r8} table rows, a chip's "
                f"table {plan.rows_out}")
        if sum(graph.shard_edges) != graph.n_edges \
                or max(graph.shard_edges) > graph.geom.shard_cap:
            raise AssertionError(
                f"shards hold {graph.shard_edges} of {graph.n_edges} "
                f"distinct edges, capacity {graph.geom.shard_cap}")
        out.append(f"ranks out: ranges cut at rows {cuts.tolist()}, "
                   f"edges a chip {list(graph.shard_edges)}")
        out.append(slots)
        out.append(s.check_sharded("ranks", got, replicated=True))
        out.append(s.check_sharded("has_out", de.has_out,
                                   replicated=True))
        out.append(s.check_sharded("src_lane", plan.src_lane))
        out.append(s.check_memory_everywhere())
    return " | ".join(out)


def stage_local_sgd_megakernel(s: Smoke):
    """MA with megakernel local rounds (300 rounds x 5 local steps
    over the smoke rows), vs the XLA trainer's held-out accuracy; and
    on breast-cancer against the reference MA golden 0.8538
    (ma.py:131)."""
    import warnings

    from tpu_distalg.models import ma
    from tpu_distalg.utils import datasets

    mesh = s.mesh()
    res = ma.train(*_smoke_rows(s), mesh, ma.MAConfig(
        n_iterations=300, n_local_iterations=5, sampler="fused_train",
        x_dtype="bfloat16", gather_block_rows=GATHER_BLOCK_ROWS,
        shuffle_seed=0, eval_test=False))
    out = [_acc_check(s, "ma/fused_train", res.w, tol=0.05)]
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message="fused_gather:")
        acc = ma.train(*datasets.breast_cancer_split(), mesh, ma.MAConfig(
            n_iterations=300, sampler="fused_train",
            gather_block_rows=64, fused_pack=4,
            shuffle_seed=0)).final_acc
    if acc < 0.85:
        raise AssertionError(f"ma breast-cancer acc {acc} < 0.85")
    out.append(f"breast-cancer acc {acc:.4f} (>= 0.85)")
    return " | ".join(out)


def stage_kmeans_kernel(s: Smoke):
    """ops/pallas_lloyd.lloyd_pass (the kernel ``kmeans20_100m_k10``
    runs) vs ops/kmeans on a small lanes geometry at the cell's dim and
    k. The kernel keeps every product in float32, so the reference
    assignment is ``ops/kmeans.assign_clusters`` at ``highest`` matmul
    precision; a point whose two nearest centres then tie to within
    f32 summation order may still land on either (at most 0.1% of
    points), and the sums are held to 1e-5 plus what those points can
    carry."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.ops import kmeans as kops
    from tpu_distalg.ops import pallas_lloyd as lloyd

    rng = np.random.default_rng(0)
    dim, k, n_blocks = 20, 10, 8
    geom = lloyd.lanes_geometry(dim, k, block_rows=32)
    n = n_blocks * geom.block_points
    n_valid = n - n // 10
    pts = (rng.normal(size=(n, dim)) * 3).astype(np.float32)
    centers = (rng.normal(size=(k, dim)) * 3).astype(np.float32)
    p, c = jnp.asarray(pts), jnp.asarray(centers)
    x4 = jax.vmap(geom.pack)(p.reshape(n_blocks, geom.block_points, dim))
    partial, = lloyd.lloyd_pass(x4, c, n_valid)
    sums, counts = lloyd.fold_stats(partial, k, dim)
    with jax.default_matmul_precision("highest"):
        assign = kops.assign_clusters(p, c)
    mask = (jnp.arange(n) < n_valid).astype(jnp.float32)
    s_ref, c_ref = kops.cluster_stats(p, mask, assign, k)
    moved = float(np.abs(np.asarray(counts) - np.asarray(c_ref)).sum())
    if moved > 0.001 * n:
        raise AssertionError(
            f"cluster counts differ by {moved} points of {n}")
    np.testing.assert_allclose(
        np.asarray(sums), np.asarray(s_ref), rtol=1e-5,
        atol=1e-4 + moved * float(np.abs(pts).max()))
    return (f"counts differ by {int(moved)} of {n} points (<= 0.1%), "
            f"sums 1e-5, cluster sums on the {lloyd.sums_form(k, dim)}")


def stage_kmeans_wide(s: Smoke):
    """The k-means scale path on the wide layout, as ``tda kmeans
    --scale-points`` runs it (``kmeans.build_scaled`` picks the layout
    from dim 64 and k; ``make_fit_seg_fn`` runs the
    ``ops/pallas_lloyd_wide`` kernels a shard and the psum of sums and
    counts over every chip the stage has) against ``ops/kmeans`` on the
    gathered rows at ``highest`` precision, at k 256 (the per-cluster
    sums as a one-hot product) and at k 2048 (as a scatter-add). Both
    sides score to float32 accuracy in different forms, so a point
    whose two nearest centres tie to within rounding may land on either
    (at most 0.1% of points); the centres of clusters both sides count
    alike agree to 1e-4 of the spread."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.models import kmeans
    from tpu_distalg.ops import kmeans as kops
    from tpu_distalg.utils import datasets

    dim, n = 64, 40_077
    make_rows, _ = datasets.gaussian_mixture_rows(k=10, dim=dim,
                                                  spread=8.0)
    mesh = s.mesh()
    said = []
    for k, form in ((256, "mxu"), (2048, "scatter")):
        data, valid, geom = kmeans.build_scaled(mesh, n, make_rows, k,
                                                data_seed=3)
        took = (kmeans.layout_of(geom), getattr(geom, "sums_form", None))
        if took != ("wide", form):
            raise AssertionError(f"k {k}: {took}, not ('wide', {form!r})")
        pts = np.asarray(geom.unpack(data))[:n]
        c0 = pts[np.random.default_rng(0).choice(n, k, replace=False)]
        centers, _, _, counts = kmeans.make_fit_seg_fn(
            mesh, kmeans.KMeansConfig(k=k, n_iterations=1), 1, geom)(
                data, valid, jnp.asarray(c0), jnp.float32(0),
                jnp.int32(0))
        counts = np.asarray(counts)
        p, c = jnp.asarray(pts), jnp.asarray(c0)
        with jax.default_matmul_precision("highest"):
            assign = kops.assign_clusters(p, c)
        s_ref, c_ref = map(np.asarray, kops.cluster_stats(
            p, jnp.ones((n,), jnp.float32), assign, k))
        moved = int(np.abs(counts - c_ref).sum())
        if int(counts.sum()) != n or moved > 0.001 * n:
            raise AssertionError(
                f"k {k}: counts add up to {int(counts.sum())} of {n}, "
                f"differ from the rows path's by {moved}")
        same = (counts == c_ref) & (counts > 0)
        want = s_ref[same] / c_ref[same][:, None]
        err = float(np.abs(np.asarray(centers)[same] - want).max() / 8.0)
        if err > 1e-4:
            raise AssertionError(
                f"k {k}: centres differ by {err:.3g} of the spread")
        said.append(
            f"k {k}, sums {form}: counts differ by {moved} of {n} points "
            f"(<= 0.1%), {int(same.sum())} of {k} centres within "
            f"{err:.2g} of the spread")
    return (f"dp={mesh.shape['data']} | wide blocks {tuple(data.shape)}, "
            f"distances {geom.dist_form} depth {geom.dist_depth} | "
            + " | ".join(said))


def stage_ssgd_hashed(s: Smoke):
    """SSGD over hashed rows as ``tda ssgd --hashed-rows`` runs it, on
    every chip the stage has: the loader's table (39 fields into 2**20
    weights, blocks of 8192 rows: the benchmark's widths at 400 000
    rows), 12 steps of the block-sampled trainer with both Mosaic
    passes compiled (21 of the 39 fields read by value against the
    loader's dictionaries, 18 by address) and, on several chips, the
    psum of the whole 4 MB gradient a step; against the same steps with
    the passes in their XLA form, to float32 rounding, and held-out
    rows scored better than zero weights score them."""
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.models import ssgd
    from tpu_distalg.ops import pallas_hashed

    mesh = s.mesh()
    n_rows, nnz, bits = 400_000, 39, 20
    cfg = ssgd.SSGDConfig(
        n_iterations=12, eval_test=False, sampler="fused_gather",
        gather_block_rows=8192, mini_batch_fraction=0.25)
    fn, X, w0, meta = ssgd.prepare_hashed_synthetic(
        n_rows, nnz, bits, mesh, cfg, data_seed=5)
    form = ssgd.hashed_geometry(cfg, meta).pass_form
    if form != "vmem":
        raise AssertionError(f"passes {form!r}, not 'vmem'")
    plan = ssgd.hashed_field_plan(cfg, meta)
    forms = (len(plan.dict_fields), len(plan.addr_fields), plan.n_values)
    if forms != (21, 18, 13027):
        raise AssertionError(
            f"fields by value, by address, values {forms}, not "
            f"(21, 18, 13027) at the published cardinalities")
    s.check_sharded("X", X)
    d = jnp.zeros((1,), jnp.float32)
    w, _ = fn(X, d, d, d, d, w0)
    real = pallas_hashed.pass_form
    pallas_hashed.pass_form = lambda *a: "xla"
    try:
        w_xla, _ = ssgd.make_train_fn_fused(mesh, cfg, meta)(
            X, d, d, d, d, w0)
    finally:
        pallas_hashed.pass_form = real
    w, w_xla = np.asarray(w, np.float64), np.asarray(w_xla, np.float64)
    err = float(np.linalg.norm(w - w_xla) / np.linalg.norm(w_xla))
    if not err < 1e-5:
        raise AssertionError(f"vmem passes differ from XLA's by {err:.3g}")
    acc, loss = ssgd.evaluate_hashed(w, meta, data_seed=5)
    if not loss < 0.68:
        raise AssertionError(f"held-out log-loss {loss:.4f} after 12 "
                             f"steps (zero weights: 0.6931)")
    return (f"dp={mesh.shape['data']} | table {tuple(X.shape)} | "
            f"{forms[0]} fields by value ({forms[2]} values), {forms[1]} "
            f"by address | vmem against xla passes {err:.2g} | held-out "
            f"log-loss {loss:.4f} acc {acc:.4f}")


def stage_ssgd_indexed(s: Smoke):
    """SSGD over indexed rows as ``tda ssgd --indexed-rows`` runs it,
    on every chip the stage has: the loader's table at a small size
    with the benchmark's eleven fields in their order and a field of
    every form at the real VMEM bound (three by value, six by address
    in three groups, two id fields of 5M and 4.5M values whose ranges
    stay in HBM: 18.0M weights), 12 steps of the block-sampled trainer
    with every Mosaic pass compiled, the two ranges in HBM gathered a
    row a DMA and summed by address in one accumulator in VMEM (2^16
    rows, 33.6 MB: a piece a field); against the same steps with every field in XLA's
    form over the whole table, which no chip had run past 2**22 slots
    before this stage, to float32 rounding; and held-out rows scored
    better than zero weights score them."""
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.models import ssgd
    from tpu_distalg.ops import pallas_hashed

    mesh = s.mesh()
    cards = (24323, 594098, 13745, 3, 3, 5_000_000, 1157062, 3750862,
             2936510, 4_500_000, 21)
    cfg = ssgd.SSGDConfig(
        n_iterations=12, eval_test=False, sampler="fused_gather",
        gather_block_rows=8192, mini_batch_fraction=0.25)
    fn, X, w0, meta = ssgd.prepare_hashed_synthetic(
        400_000, len(cards), 0, mesh, cfg, data_seed=5,
        cardinalities=cards, row_format="indexed")
    geom = ssgd.hashed_geometry(cfg, meta)
    plan = ssgd.hashed_field_plan(cfg, meta)
    forms = (geom.pass_form, plan.dict_fields,
             tuple(g.fields for g in plan.addr_groups), plan.hbm_fields)
    want = ("fields", (3, 4, 10), ((0, 1, 2, 6), (7,), (8,)), (5, 9))
    if forms != want:
        raise AssertionError(f"forms {forms}, not {want}")
    s.check_sharded("X", X)
    d = jnp.zeros((1,), jnp.float32)
    w, _ = fn(X, d, d, d, d, w0)
    real = pallas_hashed.pass_form
    pallas_hashed.pass_form = lambda *a: "xla"
    try:
        w_xla, _ = ssgd.make_train_fn_fused(mesh, cfg, meta)(
            X, d, d, d, d, w0)
    finally:
        pallas_hashed.pass_form = real
    w, w_xla = np.asarray(w, np.float64), np.asarray(w_xla, np.float64)
    err = float(np.linalg.norm(w - w_xla) / np.linalg.norm(w_xla))
    if not err < 1e-5:
        raise AssertionError(f"the fields' forms differ from XLA's over "
                             f"the whole table by {err:.3g}")
    acc, loss = ssgd.evaluate_hashed(w, meta, data_seed=5)
    if not loss < 0.68:
        raise AssertionError(f"held-out log-loss {loss:.4f} after 12 "
                             f"steps (zero weights: 0.6931)")
    return (f"dp={mesh.shape['data']} | table {tuple(X.shape)} | "
            f"{geom.n_slots} weights | by value {forms[1]}, by address "
            f"{forms[2]}, in HBM {forms[3]} | fields against xla passes "
            f"{err:.2g} | held-out log-loss {loss:.4f} acc {acc:.4f}")


def stage_ssgd_pairs(s: Smoke):
    """SSGD over rows of (feature, value) pairs as ``tda ssgd
    --row-format pairs`` runs it, on every chip the stage has: the
    loader's table at a small size (40 000 ragged rows of 8 to 16 384
    pairs, 20M pairs, 2M weights, blocks of 2^16 pair slots sharded
    over the data axis), 12 steps of the block-sampled trainer (the
    valued gather and scatter by address, the 8 MB model vector resident
    in VMEM a pass: ``pairs.pass_form`` must say ``vmem`` here, on a
    mesh each shard its own blocks; the row sums by vectors, on a mesh
    the 8 MB gradient psummed); against the
    same steps in float64 on the host over the table's own CSR arrays
    on the first step's blocks, to float32 rounding; and held-out rows
    scored better than zero weights score them."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.models import ssgd, ssgd_pairs
    from tpu_distalg.ops import pairs, sampling
    from tpu_distalg.utils import prng

    mesh = s.mesh()
    shards = mesh.shape["data"]
    spec = ssgd_pairs.PairsSpec(
        n_rows=40_000, n_features=2_000_003,
        length_mu=ssgd_pairs.length_mu_for(40_000, 500.0,
                                           length_max=1 << 14),
        block_slots=1 << 16, block_rows=256, n_blocks=352,
        length_max=1 << 14, scatter_c=12345)
    cfg = ssgd.SSGDConfig(
        n_iterations=1, eval_test=False, sampler="fused_gather",
        mini_batch_fraction=0.05)
    fn, X, w0, meta = ssgd_pairs.prepare_synthetic(spec, mesh, cfg,
                                                   data_seed=5)
    s.check_sharded("X", X)
    geom = ssgd_pairs.geometry(meta)
    if geom.pass_form != "vmem":
        raise AssertionError(f"pass form {geom.pass_form} on a TPU whose "
                             f"VMEM holds the {4 * geom.w_len} B vector")
    d = jnp.zeros((1,), jnp.float32)
    w1, _ = fn(X, d, d, d, d, w0, t0=7)
    # the first step again in float64, from the table's own rows
    n_blocks, n_sampled = ssgd.fused_gather_geometry(cfg, meta, shards)
    draws = np.asarray(sampling.sample_block_ids(
        jax.random.fold_in(prng.root_key(cfg.seed), 7), shards, n_blocks,
        n_sampled))
    drawn = (draws + np.arange(shards)[:, None] * n_blocks).reshape(-1)
    indptr, ids, vals, y, _ = pairs.csr_from_blocks(
        np.asarray(X)[drawn], geom)
    g = np.zeros(geom.n_features + 1)
    r = 0.5 - y.astype(np.float64)          # zero weights: sigmoid(0)
    np.add.at(g, ids, np.repeat(r, np.diff(indptr)) * vals)
    g[-1] = r.sum()
    want = -cfg.eta * g / len(y)
    got = np.asarray(w1, np.float64)[:geom.n_features + 1]
    err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    if not err < 1e-4:
        raise AssertionError(f"a step differs from float64 over the "
                             f"drawn blocks' {len(y)} rows by {err:.3g}")
    if len(y) != int(meta["block_counts"][drawn].sum()):
        raise AssertionError("the drawn blocks' rows")
    run = ssgd.make_train_fn_fused(
        mesh, dataclasses.replace(cfg, n_iterations=12), meta)
    w, _ = run(X, d, d, d, d, w0)
    acc, loss = ssgd_pairs.evaluate(w, meta, data_seed=5)
    if not loss < 0.69:
        raise AssertionError(f"held-out log-loss {loss:.4f} after 12 "
                             f"steps (zero weights: 0.6931)")
    return (f"dp={shards} | table {tuple(X.shape)} | {geom.n_slots} "
            f"weights, passes {geom.pass_form} | {meta['n_pairs']} pairs in "
            f"{meta['blocks_used']} of {meta['n_blocks']} blocks | a "
            f"step of {len(y)} rows against float64 {err:.2g} | "
            f"held-out log-loss {loss:.4f} acc {acc:.4f}")


def stage_als_sparse(s: Smoke):
    """ALS on a ratings list as ``tda als --ratings`` runs it, on every
    chip the stage has: the seeded loader (2 000 000 ratings of 20 000
    users by 12 000 items at rank 100, the benchmark's segments, classes
    and pieces at a batch of 768 so that every class and the pieces
    occur), three iterations of the sparse trainer, and on several chips
    the all-gather of each half's rows; against the plain reference
    (``benchmarks/reference/als_sparse_ref.py``) on the followed owners
    of the last half, to float32 rounding; every rating entered each
    half once; the held-out RMSE falls. On one chip the gather runs in
    its Mosaic form, on several in XLA's, with the same two RMSEs; the
    solve is shard-local and runs in its Mosaic form on every chip."""
    import numpy as np

    from tpu_distalg.models import als

    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmarks")
    if here not in sys.path:
        sys.path.insert(0, here)
    from reference import als_sparse_ref as ref_mod

    mesh = s.mesh()
    n, m_u, m_i, k = 2_000_000, 20_000, 12_000, 100
    gen = dict(d_min=20, user_d_max=20_000, item_d_max=40_000)
    geometry = dict(seg_slots=32, piece_segs=64, batch=768,
                    classes=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48))
    arrays, meta = als.build_ratings_table(
        n, m_u, m_i, k, mesh, data_seed=5, n_heldout=65_536,
        geometry=geometry, **gen)
    s.check_sharded("user idx", arrays[0])
    # the gather's form follows the mesh: one shard's table has one
    # heavy range, which the Mosaic kernel keeps in VMEM
    form = meta["forms"]["als_gather_form"]
    if form != ("mosaic" if mesh.shape["data"] == 1 else "xla"):
        raise AssertionError(f"gather form {form} on {mesh.shape}")
    solve = meta["forms"]["als_solve_form"]
    if (solve, meta["solve"].tile_systems) != ("mosaic", 128):
        raise AssertionError(f"solve form {meta['solve']} on {mesh.shape}")
    cfg = als.ALSConfig(lam=1.4, m=m_u, n=m_i, k=k, n_iterations=1, seed=3)
    fn = als.make_fit_fn(mesh, cfg, meta)
    X, Theta = als.start_factors(meta, mesh, cfg.seed)
    pu, pi = meta["user"], meta["item"]
    held = []
    for _ in range(3):
        X, Theta, errs, seen = fn(*arrays, X, Theta)
        held.append(float(errs[0, 1]))
        if np.asarray(seen).tolist() != [[n, n]]:
            raise AssertionError(f"ratings entered {seen}, not {n} a half")
    U = np.asarray(als.owners_from_rows(X, pu, k))
    V = np.asarray(als.owners_from_rows(Theta, pi, k))
    ref = ref_mod.Reference(
        config=dict(k=k, lam=1.4, n_users=m_u, n_items=m_i, n_ratings=n,
                    n_heldout=65_536, rating_low=0.0, rating_high=100.0,
                    reference_sample=2048, reference_heavy_over=2048,
                    generator=dict(als.RATINGS_DEFAULTS, **gen)),
        data_seed=5, start_seed=3)
    own, want = ref.half(1, U)          # the last half: items from U
    err = ref_mod.rel_err(V[own], want, np.zeros_like(want))
    if not err < 1e-4:
        raise AssertionError(f"item factors differ from the reference's "
                             f"by {err:.3g}")
    if not held[-1] < held[0] < 30:
        raise AssertionError(f"held-out RMSE {held}")
    return (f"dp={mesh.shape['data']} | blocks a side {meta['blocks']} | "
            f"slots held / ratings {meta['padding_share']:.3f} | "
            f"gather {form}, resident share "
            f"{meta['gather_resident_share']:.4f} | gramians by "
            f"{meta['forms']['als_gram_layout']}, solve: {solve} | "
            f"{len(own)} owners against the reference {err:.2g} | "
            f"held-out RMSE {held[0]:.3f} -> {held[-1]:.3f}")


def stage_closure(s: Smoke):
    """Both forms of transitive closure on every chip the stage has.
    Dense, as ``tda closure --grid-side`` runs it: BigDatalog's grid at
    side 63 (4096 vertices, 8064 arcs, 126 across: 8 doubling rounds),
    labels permuted, against the generator's closed form; on one chip
    the round is the Mosaic byte kernel (``ops/pallas_closure.py``), on
    several XLA's product of row-sharded paths. The pair-set form's
    semi-naive round (one chip's worth, whatever the mesh): the grid at
    side 50 (2 601 vertices, 1 755 675 pairs, 100 linear rounds, many
    derivations a pair) through ``run_sparse``, its pair set against the
    dense form's matrix, cell for cell, and the closed form; and a tree
    of height 11 (69 398 vertices, 783 991 pairs, 12 rounds) to its
    fixpoint as ``tda closure --tree-height`` runs it."""
    import numpy as np

    from tpu_distalg.models import transitive_closure as tc
    from tpu_distalg.utils import datasets

    side = 63
    t0 = time.perf_counter()
    out = s.cli(["closure", "--grid-side", str(side), "--seed", "5"])
    t_dense = time.perf_counter() - t0
    pairs = datasets.grid_closure_pairs(side)
    if f"has {pairs} paths (8 rounds)" not in out \
            or "pairs (equal)" not in out:
        raise AssertionError(f"dense closure of Grid{side}: {out!r}")
    mesh = s.mesh()
    geom = tc.dense_geometry((side + 1) ** 2, mesh)
    want_form = "mosaic" if s.n == 1 else "xla"
    if geom.form != want_form:
        raise AssertionError(f"compose form {geom.form} on {mesh.shape}")
    kernel = "pallas_closure._compose_kernel"
    if (s.n == 1) != (False in s.spy.built.get(kernel, ())):
        raise AssertionError(
            f"{kernel} compiled: {s.spy.built.get(kernel)} on {s.n} chip(s)")
    side_s, v = 50, 51 * 51
    small = datasets.grid_edges(side_s, 3)
    dense = tc.run(small, mesh, n_vertices=v)
    t0 = time.perf_counter()
    sparse = tc.run_sparse(small, mesh, tc.SparseClosureConfig(
        capacity=1 << 21, delta_capacity=1 << 16, join_capacity=1 << 17),
        n_vertices=v)
    t_sparse = time.perf_counter() - t0
    got = np.zeros((v, v), bool)
    got[sparse.paths[:, 0], sparse.paths[:, 1]] = True
    if not (sparse.n_paths == dense.n_paths
            == datasets.grid_closure_pairs(side_s)) \
            or sparse.n_rounds != 2 * side_s \
            or not np.array_equal(got, np.asarray(dense.paths)[:v, :v]):
        raise AssertionError(
            f"sparse {sparse.n_paths} pairs in {sparse.n_rounds} rounds, "
            f"dense {dense.n_paths} in {dense.n_rounds}")
    t0 = time.perf_counter()
    out = s.cli(["closure", "--tree-height", "11", "--seed", "5"])
    t_tree = time.perf_counter() - t0
    tree_pairs = datasets.tree_closure_pairs(11)
    if f"has {tree_pairs} paths (12 rounds)" not in out \
            or "[closure] sparse:" not in out \
            or "pairs (equal)" not in out:
        raise AssertionError(f"pair-set closure of Tree11: {out!r}")
    return (f"dp={mesh.shape['data']} | dense Grid{side}: {pairs} pairs, 8 "
            f"rounds, compose {geom.form}, padded {geom.v_padded}, "
            f"{t_dense:.1f}s | sparse Grid{side_s}: {sparse.n_paths} pairs "
            f"in {sparse.n_rounds} rounds = dense ({dense.n_rounds} "
            f"rounds), {t_sparse:.1f}s | Tree11: {tree_pairs} pairs in 12 "
            f"rounds, {t_tree:.1f}s")


def _comm_stage(s: Smoke, comm: str):
    from tpu_distalg.models import ssgd

    res = ssgd.train(*_smoke_rows(s), s.mesh(), ssgd.SSGDConfig(
        n_iterations=300, eval_test=False, x_dtype="bfloat16",
        sampler="fused_gather", gather_block_rows=GATHER_BLOCK_ROWS,
        shuffle_seed=0, init_seed=7, comm=comm))
    return (f"dp={s.n} | "
            + _acc_check(s, f"fused_gather/{comm} 300 steps", res.w,
                         tol=0.05))


STAGES = (
    ("ssgd_flagship", stage_ssgd_flagship, {}),  # kernel checked inside
    ("cli_ssgd_fused_train", stage_cli_ssgd_fused_train,
     dict(kernels=("pallas_kernels._train_kernel_gathered",))),
    ("cli_ssgd_fused_gather", stage_cli_ssgd_fused_gather,
     dict(kernels=("pallas_kernels._grad_kernel_gathered",))),
    ("als_train_serve", stage_als_train_serve,
     dict(kernels=("pallas_topk._topk_kernel",))),
    ("flash_attention_32k", stage_flash_attention,
     dict(kernels=("pallas_attention._kernel",
                   "pallas_attention._bwd_dq_kernel",
                   "pallas_attention._bwd_dkv_kernel"))),
    ("pagerank_1m", stage_pagerank,
     dict(kernels=("pallas_pagerank._spmv_kernel",
                   "pallas_pagerank._kernel"))),
    ("pagerank_rmat_20", stage_pagerank_rmat,
     dict(kernels=("pallas_pagerank._spmv_kernel",))),
    ("local_sgd_megakernel", stage_local_sgd_megakernel,
     dict(kernels=("pallas_kernels._train_kernel_gathered",))),
    ("kmeans_kernel", stage_kmeans_kernel,
     dict(kernels=("pallas_lloyd._lloyd_kernel",))),
    ("kmeans_wide", stage_kmeans_wide,
     dict(kernels=("pallas_lloyd_wide._wide_assign_kernel",
                   "pallas_lloyd_wide._wide_stats_kernel",
                   "pallas_lloyd_wide._wide_scatter_kernel"))),
    ("ssgd_hashed", stage_ssgd_hashed,
     dict(kernels=("pallas_hashed._hashed_gather_kernel",
                   "pallas_hashed._hashed_scatter_kernel",
                   "pallas_hashed._hashed_rows_kernel",
                   "pallas_hashed._hashed_value_gather_kernel",
                   "pallas_hashed._hashed_value_sums_kernel"))),
    ("ssgd_indexed", stage_ssgd_indexed,
     dict(kernels=("pallas_hashed._hashed_gather_kernel",
                   "pallas_hashed._hashed_scatter_kernel",
                   "pallas_hashed._hashed_rows_kernel",
                   "pallas_hashed._hashed_value_gather_kernel",
                   "pallas_hashed._hashed_value_sums_kernel",
                   "pallas_hashed._hashed_hbm_gather_kernel",
                   "pallas_hashed._hashed_field_scatter_kernel"))),
    ("ssgd_pairs", stage_ssgd_pairs,
     dict(kernels=("pallas_pairs._pairs_gather_kernel",
                   "pallas_pairs._pairs_scatter_kernel"))),
    # one chip builds pallas_als._als_gather_kernel, a mesh no kernel
    ("als_sparse", stage_als_sparse, {}),
    # one chip builds pallas_closure._compose_kernel, a mesh no kernel
    ("closure", stage_closure, {}),
    ("ssgd_comm_int8", functools.partial(_comm_stage, comm="int8"),
     dict(min_devices=2)),
    ("ssgd_comm_bucketed",
     functools.partial(_comm_stage, comm="bucketed"),
     dict(min_devices=2)),
)


def _versions() -> str:
    from importlib import metadata

    out = []
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out.append(f"{pkg} {metadata.version(pkg)}")
        except metadata.PackageNotFoundError:
            out.append(f"{pkg} (not installed)")
    return ", ".join(out)


def main() -> int:
    try:
        import jax

        from tpu_distalg.utils import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the system ({e}); run it from "
              f"the root of a checkout", file=sys.stderr)
        return 2
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU — the default jax backend is "
              f"{backend!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}); nothing was run",
              file=sys.stderr)
        return 2
    cache_dir = compile_cache.configure()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    os.makedirs("chiprun_out", exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    os.environ["TDA_TELEMETRY_DIR"] = os.path.join(workdir, "telemetry")
    s = Smoke(os.path.join(
        "chiprun_out", f"chip_smoke_{device['count']}dev.log"), workdir)
    try:
        s.say(f"chip_smoke: {_versions()}, python "
              f"{sys.version.split()[0]}")
        s.say(f"chip_smoke: platform: {device['platform']}, device_kind: "
              f"{device['kind']}, devices: {device['count']}")
        n_cached = len(os.listdir(cache_dir)) \
            if os.path.isdir(cache_dir) else 0
        s.say(f"chip_smoke: compile cache: {cache_dir} "
              f"({n_cached} entries at start -> "
              f"{'warm' if n_cached else 'cold'} run)")
        s.spy.install()
        t0 = time.perf_counter()
        only = sys.argv[1:]
        unknown = sorted(set(only) - {name for name, _, _ in STAGES})
        if unknown:
            raise SystemExit(f"chip_smoke: no stage named {unknown}")
        for name, fn, opts in STAGES:
            if not only or name in only:
                s.stage(name, functools.partial(fn, s), **opts)
        wall = time.perf_counter() - t0
        failed = [n for n, ok in s.results if not ok]
        s.say(f"chip_smoke: {len(s.results) - len(failed)}/"
              f"{len(s.results)} stages ok in {wall:.0f}s; compile "
              f"{s.spy.compile_s:.1f}s "
              f"({'warm' if n_cached else 'cold'}: {s.spy.hits} cache "
              f"hit(s), {s.spy.misses} miss(es)); kernels compiled: "
              f"{sorted(k for k, v in s.spy.built.items() if False in v)}"
              + (f"; FAILED: {failed}" if failed else ""))
        if failed:
            print(json.dumps({"ok": False, "failed": failed,
                              "device": device}), flush=True)
            return 1
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    finally:
        from tpu_distalg import telemetry

        telemetry.configure(False)
        s.log.close()
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
