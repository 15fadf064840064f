#!/usr/bin/env python3
"""Step 0 of PR 39: the fused SpMV's one-hot scatter product as it ships
(three single bf16 passes of the contributions' exact pieces) against
the product until PR 39 (one ``Precision.HIGHEST`` product, six passes).
PERF.md section 6 has the readings, and those of the stackings tried and
not kept (pieces along the rows, along the contraction, bfloat16
operands: the same to 0.4%).

    JAX_PLATFORMS=cpu python3 scripts/step0_pagerank_scatter.py --bundles
    chiprun -- python3 scripts/step0_pagerank_scatter.py [scale ...]
    chiprun -- python3 scripts/step0_pagerank_scatter.py --hybrid
    JAX_PLATFORMS=cpu python3 scripts/step0_pagerank_scatter.py --rehearse

``--bundles`` compiles ``_spmv_kernel`` in both forms for a described
v5e at the benchmark cell's geometry (rg 512, ws 224), no chip attached,
with libtpu's LLO dump in a temporary directory, and reads the static
schedule: bundles of a live chunk, of them before the first MXU
operation (the gather) and from it on (the scatter). Without a flag, on
the chip: the plan of Graph500 SCALE 24 as the cell holds it (or of the
scales named: 20 is rg 128, ws 72), then ms a sweep in both forms and
the output table against the first form's. ``--rehearse`` interprets
both at SCALE 10 on the CPU and compares the tables, no times.
``--hybrid`` times the hybrid scatter's kernel (``scatter_table``,
``scatter='pallas'``) at ``chip_smoke.py pagerank_1m``'s size as the
tree that holds this script ships it: copy the script into a checkout
of another commit to time that commit's.

``highest`` stands in for ``pallas_pagerank.scatter_window`` (same
arguments, same result) while it is traced; ``shipped`` is the module's
own.
"""

from __future__ import annotations

import glob
import os
import re
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "benchmarks"),
          os.path.join(ROOT, "scripts")):
    if p not in sys.path:
        sys.path.insert(0, p)

def say(msg):
    print(msg, flush=True)


def highest(pieces, row, lane, n_rows):
    """The product until PR 39, as the kernels held it: per sublane the
    whole float32 contribution under the rows' mask, times the lanes'
    one-hot, one ``Precision.HIGHEST`` product."""
    import jax
    import jax.numpy as jnp

    c = sum(pieces)
    n_slots = row.shape[1]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_slots), 0)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (128, n_slots), 0)
    upd = jnp.zeros((n_rows, 128), jnp.float32)
    for s in range(row.shape[0]):
        m = jnp.where(
            jnp.broadcast_to(row[s:s + 1, :], row_iota.shape) == row_iota,
            jnp.broadcast_to(c[s:s + 1, :], row_iota.shape), 0.0)
        onehot_t = (jnp.broadcast_to(lane[s:s + 1, :], lane_iota.shape)
                    == lane_iota).astype(jnp.float32)
        upd += jax.lax.dot_general(
            m, onehot_t, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    return upd


FORMS = ("highest", "shipped")


def install(name):
    """Put a form in the module's place of ``scatter_window``."""
    import jax

    from tpu_distalg.ops import pallas_pagerank as ppr

    if not hasattr(install, "shipped"):
        install.shipped = ppr.scatter_window
    ppr.scatter_window = {"shipped": install.shipped,
                          "highest": highest}[name]
    jax.clear_caches()


# ---- chipless: the static schedule ------------------------------------

def compile_one(name, rg, ws):
    """In a child with the dump on: compile the kernel in one form."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tpu_distalg.ops import pallas_pagerank as ppr

    install(name)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    geom = ppr.spmv_geometry(1 << 24, 16 << 24)
    assert (geom.rg, geom.ws) == (512, 224), geom
    if (rg, ws) != (512, 224):
        import dataclasses
        geom = dataclasses.replace(geom, rg=rg, ws=ws,
                                   n_groups=geom.r8 // rg)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    per_slot = (geom.n_chunks * 8, 128)
    jax.jit(lambda *a: ppr.spmv_table(
        *a, rg=geom.rg, ws=geom.ws, r8=geom.r8, blk=geom.blk,
        seg_steps=geom.seg_steps)).lower(
            arr((geom.n_chunks,), jnp.int32),
            arr((geom.n_chunks,), jnp.int32),
            arr((geom.n_groups * geom.rg, 128), jnp.float32),
            *[arr(per_slot, jnp.int32)] * 4,
            arr(per_slot, jnp.float32)).compile()


def read_schedule(dump):
    """Bundles of a live chunk from the kernel's post-RA schedule: the
    longest region a forward branch skips (``pl.when(sb >= 0)``), and
    where in it the first MXU operation sits."""
    files = [f for f in sorted(glob.glob(os.path.join(
        dump, "*_spmv_kernel*packed-bundles-post-ra.txt")))
        if "schedule-analysis" not in f]
    if not files:
        return None
    best = (0, 0, 0)
    lines = open(files[0]).read().splitlines()
    at = {}
    for line in lines:
        m = re.match(r"\s*(0x[0-9a-f]+|\d+)\s+:", line)
        if m:
            at[int(m.group(1), 0)] = line
    for no, line in at.items():
        m = re.search(r"sbr\.rel .*target bundleno = (\d+)", line)
        if m and int(m.group(1)) - no > best[0]:
            best = (int(m.group(1)) - no, no, int(m.group(1)))
    length, lo, hi = best
    first = next((no for no in range(lo, hi)
                  if re.search(r"vmatpush|vmatmul", at.get(no, ""))), hi)
    ops = {}
    for no in range(lo, hi):
        for op in re.findall(r"= (v[a-z0-9_.]+)", at.get(no, "")):
            key = ("vmatmul" if op.startswith("vmatmul") else
                   "vmatpush" if op.startswith("vmatpush") else
                   "vpop.mrf" if op.startswith("vpop.f32.mrf") else
                   op.split(".")[0])
            ops[key] = ops.get(key, 0) + 1
    return length, first - lo, hi - first, ops


def bundles(forms, rg, ws):
    keep = ("vmatmul", "vmatpush", "vpop.mrf", "vadd", "vsel", "vcmp",
            "vand", "vsub", "vpack", "vld", "vst")
    for name in forms:
        # (under TMPDIR, gone with the reading: 25 MB a kernel)
        with tempfile.TemporaryDirectory(prefix="llo_step0_") as dump:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       TPU_LOG_DIR="disabled",
                       LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                                        "--xla_jf_dump_llo_text=true")
            t0 = time.perf_counter()
            # (the dump may abort the child at its very end, its files
            # written by then: read them whatever the exit code)
            done = subprocess.run(
                [sys.executable, __file__, "--compile-one", name,
                 str(rg), str(ws)], env=env, capture_output=True,
                text=True)
            got = read_schedule(dump)
        if got is None:
            say(f"[bundles] {name}: no schedule (exit {done.returncode}): "
                + done.stderr.strip().splitlines()[-1][:300])
            continue
        length, gather, scatter, ops = got
        say(f"[bundles] {name:8s} rg {rg} ws {ws}: chunk {length} = "
            f"gather {gather} + scatter {scatter}  ("
            + ", ".join(f"{k} {ops[k]}" for k in keep if k in ops)
            + f"; {time.perf_counter() - t0:.0f} s)")


# ---- on the chip (or interpreted): a sweep in each form ----------------

def sweeps(forms, rehearse, scale):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import step0_pagerank_resident as step0
    from tpu_distalg.parallel import get_mesh
    from tpu_distalg.utils import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    say(f"[step0] {dev.platform} {dev.device_kind!r}; scale {scale}")
    mesh = get_mesh(data=1, model=1)
    graph, spmv = step0.plan_once(mesh, scale, 77)
    V = 1 << scale
    rng = np.random.default_rng(3)
    # ranks with full significands, so that a dropped piece shows
    rt = jnp.asarray(rng.random((spmv.n_groups * spmv.rg, 128),
                                np.float32) * (2.0 / V))

    def table():
        from tpu_distalg.ops import pallas_pagerank as ppr

        return ppr.spmv_table(
            spmv.gbase, spmv.sbase, rt, spmv.src_lane, spmv.src_row,
            spmv.dst_row, spmv.dst_lane, spmv.w_e, rg=spmv.rg,
            ws=spmv.ws, r8=spmv.r8, blk=spmv.blk,
            seg_steps=spmv.seg_steps or None,
            interpret=dev.platform != "tpu")

    want = None
    for name in forms:
        install(name)
        try:
            t0 = time.perf_counter()
            got = table().block_until_ready()
            first = time.perf_counter() - t0
        except Exception as e:  # a form the compiler refuses is a row
            say(f"[sweep] {name}: refused: {str(e).splitlines()[0][:300]}")
            continue
        each = float("nan")
        if not rehearse:
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                table().block_until_ready()
                times.append(time.perf_counter() - t0)
            each = min(times)
        got = np.asarray(got)
        if want is None:
            want = got
        diff = np.abs(got.astype(np.float64) - want)
        say(f"[sweep] {name:8s} rg {spmv.rg} ws {spmv.ws}: "
            f"{each * 1e3:.1f} ms a sweep (first call {first:.2f} s); "
            f"against {forms[0]}: {int((got != want).sum())} of "
            f"{got.size} cells differ, largest {diff.max():.3g} of "
            f"{np.abs(want).max():.3g}, sum {got.sum(dtype=np.float64):.9g}")
    install("shipped")


def hybrid(V):
    """ms a call of the hybrid scatter's kernel over ``V`` vertices'
    8 V uniform edges (``chip_smoke.py pagerank_1m``: 1M, 8M)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.ops import pallas_pagerank as ppr
    from tpu_distalg.utils import compile_cache, datasets

    compile_cache.configure()
    dev = jax.devices()[0]
    dst = np.sort(datasets.erdos_renyi_edges(V, 8.0, seed=1)[:, 1])
    plan = ppr.plan_scatter(dst, V)
    # contributions with full significands, so that a dropped piece shows
    c = np.random.default_rng(3).random(plan.row.shape, np.float32)
    c *= np.float32(2.0 / V)
    args = [jnp.asarray(a) for a in (plan.base, c, plan.row, plan.lane)]
    call = jax.jit(lambda *a: ppr.scatter_table(
        *a, w=plan.w, r8=plan.r8, blk=plan.blk,
        interpret=dev.platform != "tpu"))
    got = call(*args).block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(10):
            out = call(*args)
        out.block_until_ready()
        times.append((time.perf_counter() - t0) / 10)
    want = np.zeros(got.size, np.float64)
    np.add.at(want, (plan.row.astype(np.int64) * 128 + plan.lane).ravel(),
              c.astype(np.float64).ravel())
    got = np.asarray(got, np.float64).ravel()
    say(f"[hybrid] {ROOT}: {dev.device_kind!r}, {V} vertices, "
        f"{plan.n_chunks} chunks of {plan.chunk}, window {8 * plan.w} "
        f"rows: {min(times) * 1e3:.3f} ms a call (10 calls a reading: "
        + ", ".join(f"{t * 1e3:.3f}" for t in times)
        + f"); against float64: largest {np.abs(got - want).max():.3g} "
        f"of {want.max():.3g}, sum {got.sum():.12g} of {want.sum():.12g}")


def main(argv) -> int:
    if argv[:1] == ["--compile-one"]:
        compile_one(argv[1], int(argv[2]), int(argv[3]))
        return 0
    flags = [a for a in argv if a.startswith("--")]
    if "--hybrid" in flags:
        hybrid(*[int(a) for a in argv if a.isdigit()] or [1_000_000])
        return 0
    forms = tuple(a for a in argv if not a.startswith("--")
                  and not a.isdigit()) or FORMS
    if "--bundles" in flags:
        sizes = [int(a) for a in argv if a.isdigit()] or [512, 224]
        bundles(forms, *sizes)
        return 0
    rehearse = "--rehearse" in flags
    for scale in [int(a) for a in argv if a.isdigit()] or [
            10 if rehearse else 24]:
        sweeps(forms, rehearse, scale)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
