#!/usr/bin/env python3
"""What each part of a sparse ALS iteration costs alone, on the chip, at
the cell's shape (``als100_253m_sweep1``: blocks of 6144 segments of 32
rating slots, rank 100 in 128 lanes, tables of 1.0M and 0.63M rows): ms,
least of three.

    chiprun -- python3 benchmarks/tools/step0_als.py [--quick]

Rows of the output, one a line as ``[step0] <name> <ms> <derived>``:

  gather                  ``Theta[idx]`` for one block of 196 608 slots
                          (GB/s moved at 512 B a row, needed at 400)
  gram.K<n>               the block's float32-accurate Gramians at
                          ``Precision.HIGHEST``: batch/K owners, K x 32
                          deep (TFLOP/s as needed: 2 x slots x 100^2)
  gram.pieces.add         a block's pieces' scatter-add into their owners
  solve.xla.<n>           ``cho_factor`` + ``cho_solve`` on 8192 systems
  solve.lanes             the program's batch-along-the-lanes Cholesky on
                          a batch of systems of 100 in 104 (panels of 16
                          columns compile for five minutes: not timed)
  ref.segment_sum         the plain full-size form on 16 384 ratings: the
                          outer products' ``segment_sum`` and the rate it
                          gives for 4 x 252.8M ratings
  loader.*                the program's loader at full size (not --quick)
  sweep                   the program's iteration at full size, three
                          calls, with its RMSEs (not --quick)
A summary lands in ``chiprun_out/step0_als.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest as mf  # noqa: E402

CELL = "als100_253m_sweep1"


def least_ms(fn, *args, n: int = 3) -> float:
    import jax

    jax.block_until_ready(fn(*args))          # compile, warm
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from families import als_sparse as fam
    from tpu_distalg.models import als
    from tpu_distalg.ops import als_sparse as ops
    from tpu_distalg.parallel import get_mesh
    from tpu_distalg.telemetry import events as tevents
    from tpu_distalg.utils import compile_cache

    if jax.devices()[0].platform != "tpu":
        print("step0_als: needs the chip", file=sys.stderr)
        return 2
    compile_cache.configure()
    quick = "--quick" in argv
    cell = mf.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    c, t = cell.config, cell.traffic
    geom = ops.SparseGeometry(k=c["k"], **c["geometry"])
    B, L, W, k, P = (geom.batch, geom.seg_slots, geom.width, geom.k,
                     geom.piece_segs)
    out: dict = {}

    def say(name, ms, derived=""):
        out[name] = ms
        print(f"[step0] {name} {ms:.3f} ms {derived}", flush=True)

    key = jax.random.key(0)
    rows = c["n_items"] + 8
    table = jax.random.uniform(key, (rows, W), jnp.float32)
    table = table.at[:, k:].set(0.0)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (B, L), 0,
                             rows - 8, jnp.int32)
    val = jax.random.uniform(jax.random.fold_in(key, 2), (B, L)) * 100.0
    slots = B * L

    gather = jax.jit(lambda tb, ix: tb.at[ix.reshape(-1)].get(
        mode="promise_in_bounds"))
    ms = least_ms(gather, table, idx)
    say("gather", ms, f"{slots * W * 4 / ms / 1e6:.1f} GB/s moved, "
        f"{slots * k * 4 / ms / 1e6:.1f} GB/s needed")
    G = gather(table, idx)

    def gram_only(K):
        def f(G):
            G = G.reshape(B // K, K * L, W)
            return jnp.einsum("osd,ose->ode", G, G,
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
        return jax.jit(f)

    for K in (*geom.classes, P):
        ms = least_ms(gram_only(K), G)
        say(f"gram.K{K}.product", ms,
            f"{2 * slots * k * k / ms / 1e9:.2f} TFLOP/s needed")
        full = jax.jit(lambda tb, ix, v, K=K: ops.block_gramians(
            tb, ix, v, K, geom, rows - 8))
        ms = least_ms(full, table, idx, val)
        say(f"gram.K{K}.with_gather", ms)
    pieces = gram_only(P)(G)
    slot = jnp.arange(B // P, dtype=jnp.int32) // 4
    acc0 = jnp.zeros((8192 + 1, W, W), jnp.float32)
    add = jax.jit(lambda a, s, p: a.at[s].add(p), donate_argnums=0)
    acc0 = add(acc0, slot, pieces)
    t0 = time.perf_counter()
    for _ in range(5):
        acc0 = add(acc0, slot, pieces)
    jax.block_until_ready(acc0)
    say("gram.pieces.add", (time.perf_counter() - t0) / 5 * 1e3,
        f"{B // P} tiles of {W} x {W}")
    del acc0

    def spd(n_sys, n):
        g = jax.random.normal(jax.random.fold_in(key, n), (n_sys, 2 * n, n))
        return jnp.einsum("bsd,bse->bde", g, g, precision="highest") \
            + n * jnp.eye(n), jnp.ones((n_sys, n, 1), jnp.float32)

    def xla_solve(A, b):
        return jax.scipy.linalg.cho_solve(
            jax.scipy.linalg.cho_factor(A, lower=True), b)

    for n in (100, 128):
        A, b = spd(8192, n)
        ms = least_ms(jax.jit(xla_solve), A, b)
        say(f"solve.xla.{n}", ms, f"{ms / 8192 * 1e3:.2f} us a system")
        del A, b
    Ap = gram_only(1)(G)
    Ap = ops.to_lanes(Ap.at[:, k + 1, k + 1].set(float(L)))
    lanes = jax.jit(lambda a: ops.solve_batch(a, c["lam"], geom))
    ms = least_ms(lanes, Ap)
    say("solve.lanes", ms, f"{ms / B * 1e3:.2f} us a system, {B} systems")
    del Ap

    n_seg = 16384
    Gs = G[:n_seg, :k]
    owner = jnp.sort(jax.random.randint(jax.random.fold_in(key, 5),
                                        (n_seg,), 0, 64, jnp.int32))
    ref = jax.jit(lambda g, o: jax.ops.segment_sum(
        g[:, :, None] * g[:, None, :], o, num_segments=64))
    ms = least_ms(ref, Gs, owner)
    say("ref.segment_sum", ms,
        f"{n_seg} ratings; 4 x 252.8M ratings at this rate: "
        f"{ms / n_seg * 4 * c['n_ratings'] / 1e3:.0f} s")
    del G, Gs, table

    if not quick:
        mesh = get_mesh(data=1, model=1)
        t0 = time.perf_counter()
        arrays, meta = als.build_ratings_table(
            c["n_ratings"], c["n_users"], c["n_items"], c["k"], mesh,
            data_seed=12345, **fam.loader_args(c))
        say("loader.total", (time.perf_counter() - t0) * 1e3)
        for sp in tevents.finished():
            if sp.name.startswith("als:"):
                say("loader." + sp.name, sp.seconds * 1e3)
        fn = als.make_fit_fn(mesh, fam.program_config(c, t), meta)
        X, Theta = als.start_factors(meta, mesh, 7)
        for call in range(3):
            t0 = time.perf_counter()
            X, Theta, errs, seen = fn(*arrays, X, Theta)
            jax.block_until_ready(Theta)
            say(f"sweep.call{call}", (time.perf_counter() - t0) * 1e3,
                f"errs {errs.tolist()} seen {seen.tolist()}")
        stats = jax.devices()[0].memory_stats() or {}
        say("memory_peak_gb", stats.get("peak_bytes_in_use", 0) / 1e9)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step0_als.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
