#!/usr/bin/env python3
"""Step 0 of the wide k-means pass, read on the chip at the cell's shape
(2 025 000 points x 784 float32 dimensions, 4096 centres): what each
part of an iteration costs alone, in the forms considered (PERF.md §6,
PR 30 has the table).

    chiprun -- python3 benchmarks/tools/step0_kmeans_wide.py [--quick]

Parts: the table's read; the distance product at float32 accuracy as
XLA blocks it (``lax.map`` of a ``HIGHEST`` ``dot_general``) with a min
and with an argmin after it, and as the program's kernel over tiles of
points and centres; an argmin alone; the per-cluster sums as the
program's one-hot kernel, as XLA's tiled one-hot product and as XLA's
``segment_sum`` (a scatter-add, on a slice); one block of the plain
reference's distance by its definition. Values do not matter to a dense
pass: the table is normal noise. One line a reading, least of the
repeats, and a JSON object at the end (also written to
``chiprun_out/step0_kmeans_wide.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

N, DIM, K = 2_025_000, 784, 4096


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="the shipped geometry only")
    ap.add_argument("--n", type=int, default=N)
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU, kernels interpreted, a small --n: "
                         "finds wrong paths; its times mean nothing")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import pallas_lloyd_wide as wide
    from tpu_distalg.utils import compile_cache

    if jax.devices()[0].platform != "tpu" and not a.rehearse:
        print("step0: no chip", file=sys.stderr)
        return 2
    compile_cache.configure()
    HIGHEST = jax.lax.Precision.HIGHEST
    out: dict[str, float] = {}

    def read(name, fn, *args, repeats=3):
        try:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            first = time.perf_counter() - t0
            got = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                got.append(time.perf_counter() - t0)
            out[name] = min(got) * 1e3
            print(f"[step0] {name}: {min(got) * 1e3:.3f} ms (first call "
                  f"{first:.2f} s)", flush=True)
        except Exception as e:   # a form the compiler refuses is a reading
            out[name] = None
            print(f"[step0] {name}: FAILED {type(e).__name__}: "
                  f"{str(e)[:300]}", flush=True)

    def table(p):
        nb = -(-a.n // p)

        @jax.jit
        def gen(key):
            return jax.lax.map(
                lambda b: jax.random.normal(
                    jax.random.fold_in(key, b), (DIM, p), jnp.float32),
                jnp.arange(nb))

        return jax.block_until_ready(gen(jax.random.key(1)))

    centers = jax.random.normal(jax.random.key(2), (K, DIM), jnp.float32)
    tiles = {512: [512]} if a.quick or a.rehearse else {
        512: [512, 256, 1024], 1024: [512, 1024], 256: [512]}
    for p, tns in tiles.items():
        x3 = table(p)
        read(f"read_table.P{p}", jax.jit(jnp.max), x3)
        for tn in tns:
            g = wide.WideGeometry(DIM, K, p, tn, wide.STATS_TILE)
            read(f"assign_kernel.P{p}.TN{tn}",
                 lambda x, c: wide.wide_assign(
                     x, c, geom=g, interpret=a.rehearse), x3, centers)
        asg = wide.wide_assign(x3, centers, geom=g, interpret=a.rehearse)
        read(f"stats_kernel.P{p}",
             lambda x, s: wide.wide_stats(
                 x, s, a.n, geom=g, interpret=a.rehearse), x3, asg)
        if p == 512:
            xla_forms(read, x3, asg, centers, HIGHEST)
        x3.delete()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "step0_kmeans_wide.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


def xla_forms(read, x3, asg, centers, HIGHEST):
    """The forms XLA writes, over the table in blocks of 512 points,
    four blocks a step of the ``lax.map``."""
    import jax
    import jax.numpy as jnp

    nb, dim, p = x3.shape
    grp = 4
    ng = nb // grp
    cm2 = -2.0 * centers
    c2 = jnp.sum(centers * centers, axis=1)

    def scores(xb):                      # (grp, dim, p) -> (grp, k, p)
        return jnp.einsum("cd,gdp->gcp", cm2, xb, precision=HIGHEST,
                          preferred_element_type=jnp.float32) \
            + c2[None, :, None]

    def over_groups(f):
        def run(x):
            return jax.lax.map(
                f, x[:ng * grp].reshape(ng, grp, dim, p))
        return jax.jit(run)

    read("xla_product_min", over_groups(
        lambda xb: jnp.min(scores(xb), axis=1)), x3)
    read("xla_product_argmin", over_groups(
        lambda xb: jnp.argmin(scores(xb), axis=1).astype(jnp.int32)), x3)
    s = jax.random.normal(jax.random.key(3), (K, 1 << 16), jnp.float32)
    read("xla_argmin_alone_65536_points",
         jax.jit(lambda s: jnp.argmin(s, axis=0)), s)
    read("xla_min_alone_65536_points",
         jax.jit(lambda s: jnp.min(s, axis=0)), s)
    del s

    from tpu_distalg.ops import pallas_lloyd_wide as wide

    def onehot_sums(args):
        xb, ab = args                    # (grp, dim, p), (grp, 1, p)
        hot = (ab == jnp.arange(K, dtype=jnp.int32)[None, :, None]
               ).astype(jnp.bfloat16)    # (grp, k, p)
        return sum(jnp.einsum("gcp,gdp->cd", hot, piece,
                              preferred_element_type=jnp.float32)
                   for piece in wide.split3(xb))

    def xla_sums(x, s):
        xs = x[:ng * grp].reshape(ng, grp, dim, p)
        ss = s[:ng * grp].reshape(ng, grp, 1, p)

        def body(acc, args):
            return acc + onehot_sums(args), None

        return jax.lax.scan(body, jnp.zeros((K, dim), jnp.float32),
                            (xs, ss))[0]

    read("xla_onehot_sums", jax.jit(xla_sums), x3, asg)
    rows = jax.random.normal(jax.random.key(4), (1 << 16, dim),
                             jnp.float32)
    ids = jax.random.randint(jax.random.key(5), (1 << 16,), 0, K)
    read("xla_segment_sum_65536_points",
         jax.jit(lambda r, i: jax.ops.segment_sum(r, i, num_segments=K)),
         rows, ids)
    read("xla_counts_by_sort",
         jax.jit(lambda s: jnp.diff(jnp.searchsorted(
             jnp.sort(s.reshape(-1)), jnp.arange(K + 1)))), asg)

    def by_definition(xb, c):            # (dim, 2048) against all k
        def tile(ct):                    # (256, dim)
            d2 = jnp.sum((xb[None] - ct[:, :, None]) ** 2, axis=1)
            return jnp.min(d2, axis=0), jnp.argmin(d2, axis=0)
        m, i = jax.lax.map(tile, c.reshape(-1, 256, dim))
        return jnp.min(m, axis=0), i[0]

    xb = jnp.concatenate([x3[0], x3[1], x3[2], x3[3]], axis=1)
    read("reference_by_definition_2048_points",
         jax.jit(by_definition), xb, centers)


if __name__ == "__main__":
    sys.exit(main())
