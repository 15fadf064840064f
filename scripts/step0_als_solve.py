#!/usr/bin/env python3
"""Step 0 of sparse ALS' Mosaic solve: what a batch of 6144
normal-equation systems at rank 100 costs in each form, on the chip at
the ``als100_253m_sweep1`` cell's shape (extended Gramians ``(6144, 128,
128)`` float32, owner-major as the product makes them, drawn like the
cell's: rows of eighths, ratings 0 to 100, ``lam n_u`` on the diagonal),
every form inside ONE program over eight blocks (ms a block: a form
timed on one block alone reads its dispatch too, PR 37's lesson; the
kernels take the eight as one batch of 49 152, XLA's form in a scan).
Kept as the way to re-read ``SOLVE_VMEM_BYTES`` in
``tpu_distalg/ops/als_sparse.py`` (the tile ``solve_plan`` admits) and
the layout the batch travels in (``owners`` against ``tile128+turn``):

    chiprun -- python3 scripts/step0_als_solve.py [form ...]
    JAX_PLATFORMS=cpu python3 scripts/step0_als_solve.py --rehearse
    JAX_PLATFORMS=cpu python3 scripts/step0_als_solve.py --bundles

Rows of the output, one a line as ``[step0] <form> <ms a block> ...``:

  copies        (i) the shipped kernel's blocks and nothing else: a tile
                of ``(128, 104, 128)`` owners' rows read, two of ``(104,
                128)`` written (the floor)
  owners        (ii) ``pallas_als.solve_lanes``, what ships since PR 50:
                128 systems a tile read owner-major and turned to lanes
                in VMEM (a sublane-strided load and a transpose a
                column), a vector 8 rows of one column
  owners+quad   the same with ``x^T A x`` taken from the block in the
                kernel (a second turn of every column): what PR 50
                weighed against ``quad.xla`` and did not ship
  quad.xla      the error's quadratic form as ``solve_batch`` takes it,
                a product of the owner-major batch and a sum down its
                second dimension (given ``x``; no solve)
  tile128       what shipped from PR 41 to PR 49: the same
                factorisation over blocks ``(104, 104, 128)`` of a batch
                held along the lanes, ``(128, 128, batch)``
  tile128+turn  the same fed the owner-major batch through the copy it
                needs (XLA's transpose in HBM in front of the call)
  tile1024      (iii) the lanes batch viewed as ``(128, 128, 6, 8,
                128)`` and the kernel below on it: 1024 systems a tile,
                a matrix entry one whole vector, every step a plain
                elementwise operation, nothing spread along sublanes.
                The view is not free: the tiles of ``(8, 128)`` change
                from (8 rows, 128 systems) to (8 groups of systems,
                128), so XLA copies the batch
  wide1024      the same kernel on blocks viewed before the program
                began: (iii) less its view
  xla           (v) ``als_sparse.solve_batch`` in XLA's form (it turns
                the batch to lanes itself)

(iv), the panel loop rolled and the diagonal block unrolled, is how all
kernels are written. Each form's rows are compared with XLA's and with a
float64 solve of 256 of the systems, and ``tile128``'s with ``owners``'
bit for bit. ``--bundles`` compiles the kernels for a described v5e with
libtpu's dump in a temporary directory and prints each loop of the
static schedule with its trips a tile, the bundles a system that makes,
and the compile's seconds. A summary lands in
``chiprun_out/step0_als_solve.json``.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

K, BATCH, LAM = 100, 6144, 1.4      # the cell's rank, batch and ridge
BLOCKS, SLOTS = 8, 128              # blocks a program; rows a Gramian
WIDE = 1024                         # systems a tile of (iii)


def say(msg):
    print(msg, flush=True)


# ---- (iii): a tile of 1024 systems, a matrix entry a vector -------------

def _wide_kernel(a_ref, x_ref, p_ref, y_ref, *, k: int, lam: float):
    """``a_ref`` ``(ext, ext, 8, 128)``: entry (column, row) of 1024
    systems' extended Gramians, factored in place; ``x_ref`` ``(n, 8,
    128)``; ``p_ref`` ``(8, n + 1, 8, 128)`` the finished panel (row
    ``n`` the right-hand side's); ``y_ref`` ``(n, 8, 128)`` the
    right-hand side."""
    import jax
    import jax.numpy as jnp

    n = x_ref.shape[0]
    w = 8
    n_tiles = n // w
    f32 = jnp.float32
    cnt = a_ref[k + 1, k + 1]
    ridge = jnp.where(cnt > 0, f32(lam) * cnt, f32(1.0))

    def build(ct, carry):
        c0 = ct * w
        for u in range(w):
            y_ref[c0 + u] = jnp.where(c0 + u < k, a_ref[c0 + u, k], f32(0.0))

        def rows(i, carry):
            for u in range(w):
                c = c0 + u
                for s in range(w):
                    r = i * w + s
                    v = jnp.where((r < k) & (c < k), a_ref[c, r], f32(0.0))
                    a_ref[c, r] = v + jnp.where(
                        r == c, jnp.where(c < k, ridge, f32(1.0)), f32(0.0))
            return carry

        jax.lax.fori_loop(ct, n_tiles, rows, 0)
        return carry

    jax.lax.fori_loop(0, n_tiles, build, 0)

    def factor(p, carry):
        q = p * w
        L = [[None] * w for _ in range(w)]      # L[column][row]
        for j in range(w):
            for s in range(j, w):
                v = a_ref[q + j, q + s]
                for t in range(j):
                    v = v - L[t][s] * L[t][j]
                L[j][s] = jnp.sqrt(v) if s == j else v / L[j][j]
                a_ref[q + j, q + s] = L[j][s]
                p_ref[j, q + s] = L[j][s]

        def chain(X):
            for j in range(w):
                v = X[j]
                for t in range(j):
                    v = v - X[t] * L[t][j]
                X[j] = v / L[j][j]
            return X

        def below(g, carry):
            rows = [q + w + 2 * g, q + w + 2 * g + 1]
            Xs = [chain([a_ref[q + t, r] for t in range(w)]) for r in rows]
            for r, X in zip(rows, Xs):
                for t in range(w):
                    a_ref[q + t, r] = X[t]
                    p_ref[t, r] = X[t]
            return carry

        jax.lax.fori_loop(0, (n - q - w) // 2, below, 0)
        Y = chain([y_ref[q + t] for t in range(w)])
        for t in range(w):
            y_ref[q + t] = Y[t]
            p_ref[t, n] = Y[t]

        # the trailing matrix: four columns by eight rows a trip
        def columns(cb, carry):
            c0 = cb * 4
            C = [[p_ref[t, c0 + u] for u in range(4)] for t in range(w)]
            for u in range(4):
                upd = None
                for t in range(w):
                    term = p_ref[t, n] * C[t][u]
                    upd = term if upd is None else upd + term
                y_ref[c0 + u] = y_ref[c0 + u] - upd

            def rows(i, carry):
                r0 = i * w
                upd = [[None] * w for _ in range(4)]
                for t in range(w):
                    X = [p_ref[t, r0 + s] for s in range(w)]
                    for u in range(4):
                        c = p_ref[t, c0 + u]
                        for s in range(w):
                            term = X[s] * c
                            upd[u][s] = term if t == 0 \
                                else upd[u][s] + term
                M = [[a_ref[c0 + u, r0 + s] - upd[u][s] for s in range(w)]
                     for u in range(4)]
                for u in range(4):
                    for s in range(w):
                        a_ref[c0 + u, r0 + s] = M[u][s]
                return carry

            jax.lax.fori_loop(c0 // w, n_tiles, rows, 0)
            return carry

        jax.lax.fori_loop((q + w) // 4, n // 4, columns, 0)
        return carry

    jax.lax.fori_loop(0, n_tiles, factor, 0)

    def backward(ip, carry):
        p = n_tiles - 1 - ip
        q = p * w

        def dots(g, acc):
            out = list(acc)
            for r in (q + w + 2 * g, q + w + 2 * g + 1):
                x = x_ref[r]
                out = [a + a_ref[q + j, r] * x for j, a in enumerate(out)]
            return tuple(out)

        acc = jax.lax.fori_loop(
            0, (n - q - w) // 2, dots,
            tuple(jnp.zeros((8, 128), f32) for _ in range(w)))
        xp = [None] * w
        for j in reversed(range(w)):
            v = y_ref[q + j] - acc[j]
            for t in range(j + 1, w):
                v = v - a_ref[q + j, q + t] * xp[t]
            xp[j] = v / a_ref[q + j, q + j]
            x_ref[q + j] = xp[j]
        return carry

    jax.lax.fori_loop(0, n_tiles, backward, 0)


def wide_view(Ap):
    """``(width, width, batch)`` as ``(width, width, batch / 1024, 8,
    128)``: a matrix entry of 1024 systems one ``(8, 128)`` tile."""
    W, _, B = Ap.shape
    return Ap.reshape(W, W, B // WIDE, 8, 128)


def solve_wide(Ap5, k: int, lam: float, interpret: bool = False):
    """(iii) on the view: ``(round_up(k, 8), batch)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, ext = -(-k // 8) * 8, -(-(k + 2) // 8) * 8
    tiles = Ap5.shape[2]
    tile = 4 * WIDE
    x = pl.pallas_call(
        functools.partial(_wide_kernel, k=k, lam=lam),
        name="_als_solve_wide_kernel",
        grid=(tiles,),
        in_specs=[pl.BlockSpec((ext, ext, None, 8, 128),
                               lambda g: (0, 0, g, 0, 0),
                               pipeline_mode=pl.Buffered(1))],
        out_specs=pl.BlockSpec((n, None, 8, 128), lambda g: (0, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, tiles, 8, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, n + 1, 8, 128), jnp.float32),
                        pltpu.VMEM((n, 8, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=tile * (ext * ext + 8 * (n + 1) + 3 * n)
            + (8 << 20)),
        interpret=interpret,
    )(Ap5)
    return x.reshape(n, tiles * WIDE)


# ---- the error's quadratic form inside the kernel (weighed, not shipped) ---

def _quad_kernel(a_ref, x_ref, b_ref, q_ref, m_ref, p_ref, *, k: int,
                 lam: float):
    """The shipped kernel, then ``x^T A x`` of the tile's systems from
    the block as it came in (it is still whole in VMEM; ``x`` is zero
    from ``k`` on): a second turn of every column, a system a lane."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from tpu_distalg.ops import pallas_als

    pallas_als._solve_build(a_ref, b_ref, m_ref, k=k, lam=lam)
    pallas_als._solve_factor(x_ref, m_ref, p_ref)
    n, w = x_ref.shape[0], 8
    column = pallas_als._column_of(a_ref)
    xs = [x_ref[i * w:(i + 1) * w, :] for i in range(n // w)]

    def quad(ct, acc):
        c0 = pl.multiple_of(ct * w, w)
        for u in range(w):
            col = column(c0 + u)
            s = col[:w, :] * xs[0]
            for i in range(1, n // w):
                s = s + col[i * w:(i + 1) * w, :] * xs[i]
            acc = acc + x_ref[pl.ds(c0 + u, 1), :] * jnp.sum(
                s, axis=0, keepdims=True)
        return acc

    q_ref[...] = jax.lax.fori_loop(0, n // w, quad,
                                   jnp.zeros((1, 128), jnp.float32))


def solve_with_quad(Ap, k: int, lam: float, interpret: bool = False):
    """``pallas_als.solve_lanes`` with a third result, ``x^T A x`` ``(1,
    batch)``."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_distalg.ops import pallas_als

    n, ext = pallas_als._solve_sizes(k)
    batch, width, _ = Ap.shape

    def out(rows):
        return (pl.BlockSpec((rows, 128), lambda i: (0, i)),
                jax.ShapeDtypeStruct((rows, batch), Ap.dtype))

    specs, shapes = zip(out(n), out(n), out(1))
    return pl.pallas_call(
        functools.partial(_quad_kernel, k=k, lam=lam),
        name="_als_solve_quad_kernel",
        grid=(batch // 128,),
        in_specs=[pl.BlockSpec((128, ext, width), lambda i: (i, 0, 0))],
        out_specs=list(specs), out_shape=list(shapes),
        scratch_shapes=[pltpu.VMEM((n, n + 16, 128), Ap.dtype),
                        pltpu.VMEM((8, n + 16, 128), Ap.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=pallas_als.solve_tile_bytes(k) + (8 << 20)),
        interpret=interpret,
    )(Ap)


# ---- what shipped from PR 41 to PR 49: blocks of a batch along the lanes --

def _lanes_kernel(a_ref, x_ref, m_ref, p_ref, *, k: int, lam: float):
    """``a_ref`` ``(ext, ext, 128)``, a system a lane: the matrix built
    entry by entry from a block that already lies as the factorisation
    wants it, then the shipped kernel's factorisation."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from tpu_distalg.ops import pallas_als

    n = x_ref.shape[0]
    w = 8
    n_tiles = n // w
    f32 = jnp.float32
    sub = jax.lax.broadcasted_iota(jnp.int32, (w, 128), 0)
    rows8, tile = pallas_als._rows8, pallas_als._tile
    cnt = a_ref[k + 1, pl.ds(k + 1, 1), :]
    ridge = rows8(jnp.where(cnt > 0, f32(lam) * cnt, f32(1.0)))

    def build(ct, carry):
        c0 = pl.multiple_of(ct * w, w)
        live = [c0 + u < k for u in range(w)]
        for u in range(w):      # the diagonal block, with the ridge
            v = jnp.where((sub + c0 < k) & live[u],
                          a_ref[c0 + u, tile(ct), :], f32(0.0))
            m_ref[c0 + u, tile(ct), :] = v + jnp.where(
                sub == u, jnp.where(live[u], ridge, f32(1.0)), f32(0.0))
            b = rows8(a_ref[c0 + u, pl.ds(k, 1), :])
            m_ref[c0 + u, pl.ds(n, w), :] = jnp.where(
                (sub == 0) & live[u], b, f32(0.0))

        def one(i, carry):
            keep = sub + i * w < k
            for u in range(w):
                m_ref[c0 + u, tile(i), :] = jnp.where(
                    keep & live[u], a_ref[c0 + u, tile(i), :], f32(0.0))
            return carry

        jax.lax.fori_loop(ct + 1, n_tiles, one, 0)
        return carry

    jax.lax.fori_loop(0, n_tiles, build, 0)
    pallas_als._solve_factor(x_ref, m_ref, p_ref)


def solve_from_lanes(Ap, k: int, lam: float, interpret: bool = False):
    """(ii) as it was: ``Ap`` ``(width, width, batch)`` -> ``(round_up(k,
    8), batch)``."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_distalg.ops import pallas_als

    n, ext = pallas_als._solve_sizes(k)
    batch = Ap.shape[2]
    return pl.pallas_call(
        functools.partial(_lanes_kernel, k=k, lam=lam),
        name="_als_solve_lanes_kernel",
        grid=(batch // 128,),
        in_specs=[pl.BlockSpec((ext, ext, 128), lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((n, 128), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, batch), Ap.dtype),
        scratch_shapes=[pltpu.VMEM((n, n + 16, 128), Ap.dtype),
                        pltpu.VMEM((8, n + 16, 128), Ap.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=pallas_als.solve_tile_bytes(k) + (8 << 20)),
        interpret=interpret,
    )(Ap)


def copies(Ap, k: int):
    """(i): the shipped kernel's blocks moved and nothing computed."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_distalg.ops import pallas_als

    n, ext = pallas_als._solve_sizes(k)
    batch, width, _ = Ap.shape

    def body(a_ref, x_ref, b_ref):
        x_ref[...] = a_ref[pl.ds(0, n), 0, :]
        b_ref[...] = a_ref[pl.ds(0, n), 1, :]

    return pl.pallas_call(
        body, name="_als_solve_copies", grid=(batch // 128,),
        in_specs=[pl.BlockSpec((128, ext, width), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((n, 128), lambda i: (0, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((n, batch), Ap.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=pallas_als.solve_tile_bytes(k) + (8 << 20)),
    )(Ap)[0]


# ---- the static schedule, without the chip -------------------------------

def trips(form: str, k: int) -> dict:
    """Trips a tile of each loop of a kernel, by its path in the loop
    nest (a loop's children in the order the schedule lists them)."""
    n, w = -(-k // 8) * 8, 8
    T, RT = n // w, n // w + 1
    if form in ("owners", "owners+quad", "tile128"):
        two = sum(-(-(RT - jt) // 2) for p in range(T)
                  for jt in range(p + 1, T))
        # the owner-major build stores every row tile of a column: no
        # loop inside it
        build = {"build": T, "build.rows": T * (T - 1) // 2} \
            if form == "tile128" else {"build": T}
        quad = {"quad": T} if form == "owners+quad" else {}
        return {**build, **quad,
                "factor": T,
                "factor.below": sum((RT - p) // 2 for p in range(T)),
                "factor.trailing": T * (T - 1) // 2,
                "factor.trailing.two": two,
                "backward": T, "backward.dots": T * (T - 1) // 2}
    cols = sum(n // 4 - (8 * p + 8) // 4 for p in range(T))
    rows = sum(T - (4 * cb) // 8 for p in range(T)
               for cb in range((8 * p + 8) // 4, n // 4))
    return {"build": T, "build.rows": T * (T + 1) // 2,
            "factor": T,
            "factor.below": sum((n - 8 * p - 8) // 2 for p in range(T)),
            "factor.columns": cols, "factor.columns.rows": rows,
            "backward": T,
            "backward.dots": sum((n - 8 * p - 8) // 2 for p in range(T))}


def compile_one(form: str, k: int):
    """In a child with the dump on: compile one kernel for a v5e."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tpu_distalg.ops import pallas_als

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    W = -(-(k + 2) // 128) * 128
    t0 = time.perf_counter()
    if form in ("owners", "owners+quad"):
        Ap = jax.ShapeDtypeStruct((BATCH, W, W), jnp.float32, sharding=one)
        jax.jit(lambda a: (solve_with_quad if form == "owners+quad"
                           else pallas_als.solve_lanes)(a, k, LAM)).lower(
            Ap).compile()
    elif form == "tile128":
        Ap = jax.ShapeDtypeStruct((W, W, BATCH), jnp.float32, sharding=one)
        jax.jit(lambda a: solve_from_lanes(a, k, LAM)).lower(Ap).compile()
    else:
        Ap = jax.ShapeDtypeStruct((W, W, BATCH // WIDE, 8, 128),
                                  jnp.float32, sharding=one)
        jax.jit(lambda a: solve_wide(a, k, LAM)).lower(Ap).compile()
    say(f"[compiled] {time.perf_counter() - t0:.2f}")


def read_loops(dump: str, kernel: str):
    """The loop nest of a kernel's post-RA schedule: ``(first bundle,
    last, children)`` of every backward branch, outermost first."""
    files = [f for f in sorted(glob.glob(os.path.join(
        dump, f"*{kernel}*packed-bundles-post-ra.txt")))
        if "schedule-analysis" not in f]
    if not files:
        return None
    spans = []
    for line in open(files[-1]):
        m = re.match(r"\s*(0x[0-9a-f]+)\s+\w*\s*:", line)
        if not m:
            continue
        no = int(m.group(1), 16)
        for b in re.finditer(r"sbr\.rel \(!?%\w+\) target bundleno = (\d+)",
                             line):
            if int(b.group(1)) <= no:
                spans.append((int(b.group(1)), no))
    spans.sort(key=lambda s: (s[0], -s[1]))

    def nest(items):
        out = []
        while items:
            lo, hi = items.pop(0)
            inner = [s for s in items if s[1] <= hi]
            items = [s for s in items if s[1] > hi]
            out.append((lo, hi, nest(inner)))
        return out

    return nest(spans)


def bundles(forms, k: int):
    factor = ["factor", ["below", "trailing", ["two"]], "backward", ["dots"]]
    names = {"owners": ("_als_solve_kernel", ["build", *factor]),
             "owners+quad": ("_als_solve_quad_kernel",
                             ["build", *factor, "quad"]),
             "tile128": ("_als_solve_lanes_kernel",
                         ["build", ["rows"], *factor]),
             "tile1024": ("_als_solve_wide_kernel",
                          ["build", ["rows"], "factor",
                           ["below", "columns", ["rows"]],
                           "backward", ["dots"]])}
    out = {}
    for form in forms:
        kernel, shape = names[form]
        # once plainly for the compile's seconds, once with the dump
        # (under TMPDIR, gone with the reading: 25 MB a kernel; the
        # dump may abort the child at its very end, its files written
        # by then: read them whatever the exit code)
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
        cmd = [sys.executable, __file__, "--compile-one", form, str(k)]
        plain = subprocess.run(cmd, env=env, capture_output=True, text=True)
        took = re.search(r"\[compiled\] ([\d.]+)", plain.stdout)
        with tempfile.TemporaryDirectory(prefix="llo_step0_") as dump:
            done = subprocess.run(
                cmd, capture_output=True, text=True, env=dict(
                    env, LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                    "--xla_jf_dump_llo_text=true"))
            nest = read_loops(dump, kernel)
        if not nest:
            say(f"[bundles] {form}: no schedule (exit {done.returncode}): "
                + (done.stderr.strip().splitlines() or ["?"])[-1][:300])
            continue
        (lo, hi, top), = nest[-1:]           # the grid's loop is the last

        def name_loops(loops, shape, prefix):
            named, at = [], 0
            for item in shape:
                if isinstance(item, list):
                    named += name_loops(loops[at - 1][2], item,
                                        named[-1][0] + ".")
                else:
                    lo, hi, inner = loops[at]
                    own = hi - lo + 1 - sum(h - l + 1 for l, h, _ in inner)
                    named.append((prefix + item, own))
                    at += 1
            return named

        named = name_loops(top, shape, "")
        per = trips(form, k)
        body = hi - lo + 1 - sum(h - l + 1 for l, h, _ in top)
        total = body + sum(own * per[name] for name, own in named)
        systems = WIDE if form == "tile1024" else 128
        out[form] = dict(bundles_a_tile=total, systems=systems,
                         bundles_a_system=total / systems,
                         compile_s=float(took.group(1)) if took else None,
                         loops={name: (own, per[name])
                                for name, own in named})
        say(f"[bundles] {form}: {total} a tile of {systems} = "
            f"{total / systems:.1f} a system (the grid step's own {body}; "
            + ", ".join(f"{name} {own} x {per[name]}"
                        for name, own in named)
            + f"; compiled in {took.group(1) if took else '?'} s)")
    return out


# ---- on the chip (or interpreted) -----------------------------------------

def draw_blocks(k: int, batch: int, blocks: int, slots: int, width: int):
    """``blocks`` batches of extended Gramians ``(blocks, batch, width,
    width)`` on the device, owner-major as the product makes them."""
    import jax
    import jax.numpy as jnp

    def one(key):
        kf, kr, kn = jax.random.split(key, 3)
        n_u = jax.random.randint(kn, (batch,), 0, slots + 1)
        ok = (jnp.arange(slots)[None, :] < n_u[:, None]).astype(jnp.float32)
        G = jax.random.randint(kf, (batch, slots, width), -8, 9) / 8.0
        r = jax.random.randint(kr, (batch, slots), 0, 101).astype(
            jnp.float32)
        lane = jnp.arange(width)[None, None, :]
        G = jnp.where(lane == k, r[..., None],
                      jnp.where(lane == k + 1, 1.0,
                                jnp.where(lane < k, G, 0.0)))
        G = G * ok[..., None]
        return jnp.einsum("osd,ose->ode", G, G,
                          precision=jax.lax.Precision.HIGHEST)

    return jax.jit(lambda keys: jax.lax.map(one, keys))(
        jax.random.split(jax.random.PRNGKey(41), blocks))


def main(argv) -> int:
    flags = [a for a in argv if a.startswith("--")]
    if "--compile-one" in flags:
        compile_one(argv[argv.index("--compile-one") + 1],
                    int(argv[argv.index("--compile-one") + 2]))
        return 0
    if "--bundles" in flags:
        bundles([a for a in argv if not a.startswith("--")]
                or ["owners", "owners+quad", "tile128", "tile1024"], K)
        return 0

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.ops import als_sparse, pallas_als

    interp = "--rehearse" in flags
    if jax.devices()[0].platform != "tpu" and not interp:
        print("step0_als_solve: no chip", file=sys.stderr)
        return 2
    k, batch, blocks, slots = (12, WIDE, 2, 32) if interp else (
        K, BATCH, BLOCKS, SLOTS)
    geom = als_sparse.SparseGeometry(k=k, batch=batch, classes=(1,),
                                     piece_segs=batch)
    Aps = draw_blocks(k, batch, blocks, slots, geom.width)
    jax.block_until_ready(Aps)

    W = geom.width

    def xla(Aps):
        def one(c, Ap):
            x = als_sparse.solve_batch(Ap, LAM, geom)[0][:, :k].T
            return c, x
        xs = jax.lax.scan(one, 0, Aps)[1]           # (blocks, k, batch)
        return jnp.moveaxis(xs, 0, 1).reshape(k, -1)

    def quad_xla(A, x):
        rows = x.T
        Ax = jnp.sum(A[:, :k, :k] * rows[:, :, None], axis=1)
        return jnp.sum(rows * Ax, axis=1)[None, :]

    # the Mosaic forms take the blocks as one batch, block after block:
    # a scan's slice of a block would be a copy of 400 MB a step in
    # front of each call (1.1 ms, read once as the floor: PR 41's call
    # 2), which the trainer's step does not make. Each form is handed
    # its input as the program would hand it over: ``owners`` the
    # product's own layout, the lanes forms a batch turned beforehand,
    # ``tile128+turn`` the owner-major batch and the turn inside
    to_lanes = als_sparse.to_lanes

    forms = {
        "xla": (xla, "blocks"),
        "copies": (lambda A: copies(A, k)[:k], "owners"),
        "owners": (lambda A: pallas_als.solve_lanes(
            A, k, LAM, interpret=interp)[0][:k], "owners"),
        "owners+quad": (lambda A: solve_with_quad(
            A, k, LAM, interp)[2], "owners"),
        "quad.xla": (quad_xla, "owners+x"),
        "tile128+turn": (lambda A: solve_from_lanes(
            to_lanes(A), k, LAM, interp)[:k], "owners"),
        "tile128": (lambda A: solve_from_lanes(
            A, k, LAM, interp)[:k], "lanes"),
        "tile1024": (lambda A: solve_wide(
            wide_view(A), k, LAM, interpret=interp)[:k], "lanes"),
        "wide1024": (lambda A5: solve_wide(
            A5, k, LAM, interpret=interp)[:k], "wide"),
    }
    if interp:
        del forms["copies"]
    asked = [a for a in argv if not a.startswith("--")]
    out: dict = {"k": k, "batch": batch, "blocks": blocks}

    # a float64 solve of the first block's first 256 systems
    A0 = np.asarray(Aps[0][:256], np.float64)
    cnt = A0[:, k + 1, k + 1]
    want = np.stack([np.linalg.solve(
        A0[i, :k, :k] + (LAM * cnt[i] if cnt[i] else 1.0) * np.eye(k),
        A0[i, :k, k]) for i in range(256)]).T
    base = xs_owners = None
    held: dict = {"blocks": Aps}

    def arg_of(kind):
        """A form's input, made when first asked for; the blocks'
        3.2 GB go once the batch is one array."""
        if kind not in held:
            if "owners" not in held:
                held["owners"] = jax.jit(
                    lambda a: a.reshape(-1, W, W))(held.pop("blocks"))
            if kind == "owners+x":
                held[kind] = (held["owners"], jnp.asarray(xs_owners))
            elif kind in ("lanes", "wide"):
                if "lanes" not in held:
                    held["lanes"] = jax.jit(to_lanes)(held["owners"])
                if kind == "wide":
                    held["wide"] = jax.jit(wide_view)(held["lanes"])
        a = held[kind]
        return a if isinstance(a, tuple) else (a,)

    for name, (fn, kind) in forms.items():
        if asked and name not in asked and name not in ("xla", "owners"):
            continue
        arg = arg_of(kind)
        t0 = time.perf_counter()
        done = jax.jit(fn).lower(*arg).compile()
        took = time.perf_counter() - t0
        xs = jax.block_until_ready(done(*arg))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(done(*arg))
            best = min(best, time.perf_counter() - t0)
        ms = best * 1e3 / blocks
        row = dict(ms_a_block=ms, us_a_system=ms * 1e3 / batch,
                   compile_s=took)
        text = (f"[step0] {name:12s} {ms:9.4f} ms a block  "
                f"{ms * 1e3 / batch:7.4f} us a system  "
                f"(compiled in {took:.1f} s)")
        if name in ("owners+quad", "quad.xla"):
            x0 = np.asarray(xs_owners[:, :256], np.float64)
            q = np.einsum("do,ode,eo->o", x0, A0[:, :k, :k], x0)
            row["rel_err_f64"] = float(
                np.abs(np.asarray(xs)[0, :256] - q).max() / np.abs(q).max())
            text += f"  x^T A x against float64 {row['rel_err_f64']:.3g}"
        elif name != "copies":
            x0 = np.asarray(xs[:, :256], np.float64)
            row["rel_err_f64"] = float(
                np.linalg.norm(x0 - want) / np.linalg.norm(want))
            text += f"  against float64 {row['rel_err_f64']:.3g}"
            if name == "xla":
                base = np.asarray(xs)
            else:
                row["max_diff_xla"] = float(np.abs(
                    np.asarray(xs) - base).max() / np.abs(base).max())
                text += f"  against xla {row['max_diff_xla']:.3g}"
            if name == "owners":
                xs_owners = np.asarray(xs)
            elif name.startswith("tile128"):
                row["bitwise_owners"] = bool(
                    np.array_equal(np.asarray(xs), xs_owners))
                text += f"  owners' bit for bit: {row['bitwise_owners']}"
        out[name] = row
        say(text)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step0_als_solve.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
