#!/usr/bin/env python3
"""Step 0 of the pair-set closure's round
(``models/transitive_closure.make_sparse_round_fn``): what a round costs
at BigDatalog's Tree17, part by part, and what a chain of donated rounds
holds on the chip. Kept as the way to re-read the round's design (one
sort of set and candidates, two order-preserving compactions, the
join's gathers):

    chiprun -- python3 scripts/step0_closure_sparse.py
    JAX_PLATFORMS=cpu python3 scripts/step0_closure_sparse.py --rehearse

Rows of the output, one a line as ``[step0] <what> ...``:

  parts    ms of each part alone at the round's sizes, best of
           ``--reps``: XLA's two-key sort of ``capacity + join_capacity``
           slots, ``ops/graph.compact_front`` of as many with a far and a
           near bound, a gather and a scatter of ``join_capacity`` words,
           a running maximum
  job      one whole job to the fixpoint through the compiled round, the
           host waiting for every round: round, pairs, new pairs,
           candidates, ms; then the chip's ``peak_bytes_in_use``

A summary lands in ``chiprun_out/step0_closure_sparse.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def say(msg):
    print(f"[step0] {msg}", flush=True)


def best_ms(fn, args, reps):
    import jax

    jax.block_until_ready(fn(*args))
    got = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        got.append((time.perf_counter() - t0) * 1e3)
    return min(got)


def parts(capacity: int, join: int, n_vertices: int, reps: int) -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import graph as gops

    n = capacity + join
    key = jax.random.key(0)
    x = jax.random.randint(key, (n,), 0, n_vertices, jnp.int32)
    z = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0,
                           2 * n_vertices, jnp.int32)
    keep = (z & 7) != 0
    idx = jax.random.randint(jax.random.fold_in(key, 2), (join,), 0,
                             n_vertices, jnp.int32)
    table = jnp.arange(n_vertices + 1, dtype=jnp.int32)
    out = {}
    for name, fn, args in (
            ("sort", jax.jit(lambda a, b: jax.lax.sort((a, b), num_keys=2)),
             (x, z)),
            ("compact_far", jax.jit(lambda k, a, b: gops.compact_front(
                k, (a, b), (0, 0), join)), (keep, x, z)),
            ("compact_near", jax.jit(lambda k, a, b: gops.compact_front(
                k, (a, b), (0, 0), capacity, max_shift=join)), (keep, x, z)),
            ("gather", jax.jit(lambda t, i: t[i]), (table, idx)),
            ("scatter_max", jax.jit(lambda i: jnp.zeros(
                (join,), jnp.int32).at[i].max(i, mode="drop")), (idx,)),
            ("cummax", jax.jit(jax.lax.cummax), (idx,))):
        out[name] = best_ms(fn, args, reps)
        say(f"parts {name}: {out[name]:.1f} ms")
    return out


def job(height: int, seed: int, caps: dict | None) -> dict:
    import jax
    import numpy as np

    from tpu_distalg.models import transitive_closure as tc
    from tpu_distalg.ops import graph as gops
    from tpu_distalg.parallel import get_mesh
    from tpu_distalg.utils import datasets

    mesh = get_mesh(data=1, model=1, devices=jax.devices()[:1])
    t0 = time.perf_counter()
    edges = datasets.tree_edges(height, seed)
    t1 = time.perf_counter()
    n_vertices = len(edges) + 1
    config = tc.SparseClosureConfig(**(caps or {
        "capacity": max(datasets.tree_closure_pairs(height), 1024),
        "delta_capacity": len(edges), "join_capacity": len(edges)}))
    prepared = tc.prepare_sparse(edges, mesh, n_vertices, config)
    t2 = time.perf_counter()
    say(f"job tree height {height}: {n_vertices} vertices made in "
        f"{t1 - t0:.2f} s, on the device in {t2 - t1:.2f} s; {prepared.geom}")
    round_fn = tc.make_sparse_round_fn(mesh, prepared.geom)
    state, prepared.state = prepared.state, None
    rounds, still = [], False
    while not still:
        t0 = time.perf_counter()
        state, count, flag, stats = round_fn(state, prepared.arcs)
        still = bool(flag)
        ms = (time.perf_counter() - t0) * 1e3
        joined, found, over = (int(v) for v in np.asarray(stats))
        rounds.append({"round": len(rounds) + 1,
                       "pairs": gops.count_of(count), "new": found,
                       "candidates": joined, "ms": ms})
        say(f"job round {rounds[-1]}")
        if over:
            raise RuntimeError("a buffer overflowed")
    want = datasets.tree_closure_pairs(height)
    stats = jax.devices()[0].memory_stats() or {}
    say(f"job done: {rounds[-1]['pairs']} pairs in {len(rounds)} rounds "
        f"(closed form {want}: "
        f"{'equal' if want == rounds[-1]['pairs'] else 'NOT EQUAL'}), "
        f"{sum(r['ms'] for r in rounds[1:]) / 1e3:.2f} s after the first "
        f"round; peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
        f"bytes_in_use {stats.get('bytes_in_use')}")
    return {"rounds": rounds, "memory": {k: stats.get(k) for k in (
        "peak_bytes_in_use", "bytes_in_use", "bytes_limit")}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: the paths, not the times")
    ap.add_argument("--height", type=int, default=17)
    ap.add_argument("--seed", type=int, default=2**31 + 17)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--skip-parts", action="store_true")
    a = ap.parse_args(argv)
    from tpu_distalg.utils import compile_cache

    compile_cache.configure()       # the cell's runs find the round there
    out = {}
    if a.rehearse:
        out["parts"] = parts(1 << 12, 1 << 8, 1000, 1)
        out["job"] = job(5, a.seed, None)
    else:
        if not a.skip_parts:
            out["parts"] = parts(1 << 28, 1 << 24, 13_766_856, a.reps)
        caps = {"capacity": 1 << 28, "delta_capacity": 1 << 24,
                "join_capacity": 1 << 24} if a.height == 17 else None
        out["job"] = job(a.height, a.seed, caps)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "step0_closure_sparse.json"), "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
