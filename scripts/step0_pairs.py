#!/usr/bin/env python3
"""Step 0 of the pairs passes by address (``ops/pallas_pairs.py``): what
Mosaic grants and what a pair costs on the chip at the
``lrpairs3728_350k_frac01`` cell's shape (52 sampled blocks of 2048
vectors of 128 pair slots, 16 609 143 features: a model vector of
66.4 MB), before anything is built on it.

    chiprun -- python3 -u scripts/step0_pairs.py [--only grant,seeded,flat]
    JAX_PLATFORMS=cpu python3 scripts/step0_pairs.py --compile
    JAX_PLATFORMS=cpu python3 scripts/step0_pairs.py --bundles
    JAX_PLATFORMS=cpu python3 scripts/step0_pairs.py --rehearse

Rows of the output, one a line as ``[step0] <name> <ms a call> <ns a
pair slot>`` (least of five; a call is one pass over the 52 blocks'
13 631 488 slots, 12.9M of which hold a pair):

  grant.<MB>                 whether Mosaic grants one single-buffered
                             copy of a model vector of so many MB beside
                             the chunk buffers (the cell's 66.4; 75.2,
                             the largest ``pairs.VMEM_BUDGET_BYTES``
                             admits, over 4 blocks of uniform ids), each
                             pass compared with XLA's over the same
                             blocks
  <draw>.gather.t<trip>      ``pallas_pairs.vector_products`` at
                             ``trip`` pairs a trip of the inner loop
  <draw>.scatter.t<trip>     ``pallas_pairs.slot_sums``: its ONE accumulator
  <draw>.gather|scatter.skip as they ship (``TRIP_PAIRS``), the vectors
                             past a block's last row not read
                             (``pairs.used_vectors``; every other row
                             reads every slot)
  <draw>.xla.gather|scatter  XLA's ``w[idx] * val`` and
                             ``zeros.at[idx].add`` over 4 of the blocks
                             (a trip of the ``xla`` form's loop), ms and
                             ns a slot of those 4

``<draw>`` is ``seeded`` (the configuration's power law of 1.1 through
its bijection, the program's loader at 7000 rows: about 105 blocks, the
first 52 sampled) or ``flat`` (``zipf_exponent`` 0). ``--compile``
compiles both kernels at the cell's width and at the budget's for a
described v5e here (no chip: the grant as the compiler sees it, nothing
runs); ``--bundles`` compiles each kernel at each trip with libtpu's
dump in a temporary directory and prints the bundles of a trip of its
inner loop in the static schedule, post-RA (a bundle is 0.667 ns and a
trip's taken branch 3.8 ns on one v5e, ``pallas_hashed``'s finding; it
says nothing about what a load waits for); ``--rehearse`` runs every
row at a CPU size, interpreted. A summary lands in
``chiprun_out/step0_pairs.json`` (``step0_pairs_bundles.json``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the cell's shape (benchmarks/configs/lr-webspam-tri16m.json)
N_FEATURES, BLOCK_SLOTS, BLOCK_ROWS, N_SAMPLED = 16_609_143, 1 << 18, 512, 52
LENGTH_MU, SCATTER_C = 7.72585391998291, 0
BUDGET_FEATURES = 18_800_000      # 75.2 MB: under pairs.VMEM_BUDGET_BYTES
TRIPS = (32, 64)
XLA_BLOCKS = 4


def say(msg):
    print(msg, flush=True)


def least_ms(fn, *args, reps: int = 5) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t)
    return best * 1e3


def uniform_blocks(geom, n: int, seed: int):
    """``n`` blocks whose every slot holds a pair: ids uniform over the
    features, values normal (the grant's rows need no loader)."""
    import jax
    import jax.numpy as jnp

    k1, k2 = jax.random.split(jax.random.key(seed))
    V = geom.vectors
    idx = jax.random.randint(k1, (n, V, 128), 0, geom.n_features, jnp.int32)
    val = jax.lax.bitcast_convert_type(
        jax.random.normal(k2, (n, V, 128), jnp.float32), jnp.int32)
    rest = jnp.zeros((n, geom.held_rows - 2 * V, 128), jnp.int32)
    return jnp.concatenate([idx, val, rest], axis=1)


def passes(geom):
    """``(gather(trip), scatter(trip), xla_gather, xla_scatter)``: the
    jitted calls of one geometry (interpreted where it lies on no TPU);
    the scatters take ``back``, a residual a vector. A trip other than
    the module's own is set there for the trace (``None``: its own)."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import pairs, pallas_pairs

    shipped = pallas_pairs.TRIP_PAIRS

    def used(X, ids, skip):
        return pairs.used_vectors(X, ids, geom) if skip else jnp.full(
            ids.shape, geom.vectors, jnp.int32)

    def at_trip(trip, kernel):
        def run(X, second, ids, skip):
            pallas_pairs.TRIP_PAIRS = trip or shipped   # read when traced
            try:
                return kernel(X, second, ids, used(X, ids, skip), geom)
            finally:
                pallas_pairs.TRIP_PAIRS = shipped
        return run

    def gather(trip, skip=False):
        run = at_trip(trip, pallas_pairs.vector_products)
        return jax.jit(lambda X, w, ids: run(X, w, ids, skip))

    def scatter(trip, skip=False):
        run = at_trip(trip, pallas_pairs.slot_sums)
        return jax.jit(lambda X, back, ids: run(X, back, ids, skip))

    @jax.jit
    def xla_gather(X, w, ids):
        return w[pairs._rows(X, ids, 0, geom.vectors)] \
            * pairs._values(X, ids, geom)

    @jax.jit
    def xla_scatter(X, back, ids):
        return jnp.zeros((geom.w_len,), jnp.float32).at[
            pairs._rows(X, ids, 0, geom.vectors)].add(
                pairs._values(X, ids, geom) * back[..., None])

    return gather, scatter, xla_gather, xla_scatter


def grant(n_features: int, block_slots: int, block_rows: int,
          on_tpu: bool, out: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.ops import pairs

    geom = pairs.PairsGeometry(n_features, block_slots, block_rows,
                               XLA_BLOCKS, on_tpu)
    name = f"grant.{4 * geom.w_len / 1e6:.1f}"
    X = uniform_blocks(geom, XLA_BLOCKS, 5)
    w = jax.random.normal(jax.random.key(6), (geom.w_len,), jnp.float32)
    add = jax.random.normal(jax.random.key(7), (XLA_BLOCKS, geom.vectors),
                            jnp.float32)
    ids = jnp.arange(XLA_BLOCKS, dtype=jnp.int32)[::-1]
    gather, scatter, xla_gather, xla_scatter = passes(geom)
    try:
        say(f"[step0] {name}: the gather starting")     # one may hang
        got = jnp.sum(gather(None)(X, w, ids), axis=-1)
        want = jnp.sum(xla_gather(X, w, ids), axis=-1)
        g_err = float(jnp.max(jnp.abs(got - want)))
        say(f"[step0] {name}: the gather {g_err:.3g}; the scatter starting")
        s_err = float(jnp.max(jnp.abs(
            scatter(None)(X, add, ids) - xla_scatter(X, add, ids))))
    except Exception as e:      # the refusal is the reading
        say(f"[step0] {name} REFUSED: {type(e).__name__}: "
            f"{str(e)[:600]}")
        out[name] = {"granted": False, "error": str(e)[:2000]}
        return
    say(f"[step0] {name} granted: vmem asked "
        f"{pairs.vmem_bytes(geom.w_len) / 1e6:.1f} MB a pass, against XLA "
        f"over "
        f"{XLA_BLOCKS} blocks gather {g_err:.3g} scatter {s_err:.3g} "
        f"(largest |difference|; sums of {np.sqrt(128):.0f}-ish normals)")
    out[name] = {"granted": True, "gather_err": g_err, "scatter_err": s_err}


def draw(name: str, zipf: float, sizes: dict, out: dict) -> None:
    import jax
    import jax.numpy as jnp

    from tpu_distalg.models import ssgd_pairs
    from tpu_distalg.parallel import get_mesh

    spec = ssgd_pairs.PairsSpec(
        n_rows=sizes["n_rows"], n_features=sizes["n_features"],
        length_mu=sizes["length_mu"], block_slots=sizes["block_slots"],
        block_rows=sizes["block_rows"], zipf_exponent=zipf,
        length_max=sizes["block_slots"] // 4, scatter_c=SCATTER_C)
    X, meta = ssgd_pairs.build_table(spec, get_mesh(data=1, model=1),
                                     data_seed=56)
    geom = ssgd_pairs.geometry(meta)
    ns = min(sizes["n_sampled"], meta["blocks_used"])
    ids = jnp.arange(ns, dtype=jnp.int32)
    pairs_held = int(meta["block_pairs"][:ns].sum())
    slots = ns * geom.block_slots
    say(f"[step0] {name}: {meta['n_blocks']} blocks, the first {ns} "
        f"sampled: {pairs_held} pairs in {slots} slots")
    w = jax.random.normal(jax.random.key(1), (geom.w_len,), jnp.float32)
    add = jax.random.normal(jax.random.key(2), (ns, geom.vectors),
                            jnp.float32)       # a residual a vector
    gather, scatter, xla_gather, xla_scatter = passes(geom)

    def row(key, ms, n):
        say(f"[step0] {name}.{key} {ms:.3f} ms {ms * 1e6 / n:.3f} ns")
        out[f"{name}.{key}"] = {"ms": ms, "ns_per_slot": ms * 1e6 / n}

    row("gather.skip", least_ms(gather(None, True), X, w, ids), slots)
    row("scatter.skip", least_ms(scatter(None, True), X, add, ids),
        slots)
    for trip in sizes["trips"]:
        row(f"gather.t{trip}", least_ms(gather(trip), X, w, ids), slots)
        row(f"scatter.t{trip}", least_ms(scatter(trip), X, add, ids), slots)
    few = ids[:XLA_BLOCKS]
    n = XLA_BLOCKS * geom.block_slots
    row("xla.gather", least_ms(xla_gather, X, w, few), n)
    row("xla.scatter", least_ms(xla_scatter, X, add[:XLA_BLOCKS], few), n)
    # float32 up to the order of a vector's adds, which follows the trip
    # (a partial sum a segment of ``trip`` lanes)
    a, b = (jnp.sum(gather(t)(X, w, few), -1) for t in sizes["trips"][:2])
    c = jnp.sum(xla_gather(X, w, few), -1)
    skipped = float(jnp.max(jnp.abs(
        jnp.sum(gather(None, True)(X, w, ids), -1)
        - jnp.sum(gather(None)(X, w, ids), -1)))) + float(jnp.max(jnp.abs(
            scatter(None, True)(X, add, ids)
            - scatter(None)(X, add, ids))))
    say(f"[step0] {name}: skipping the blocks' tails changes the two "
        f"passes by {skipped:.3g} (every block)")
    want = xla_scatter(X, add[:XLA_BLOCKS], few)
    say(f"[step0] {name}: gather trips differ by "
        f"{float(jnp.max(jnp.abs(a - b))):.3g}, from XLA's by "
        f"{float(jnp.max(jnp.abs(a - c))):.3g}; scatter from XLA's by "
        f"""{float(jnp.max(jnp.abs(
            scatter(None)(X, add[:XLA_BLOCKS], few) - want))):.3g}""")


def described(n_features: int):
    """``(gather, scatter, X, ids, w, back)``: a geometry's calls and
    the shapes of their operands on one described v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tpu_distalg.ops import pairs

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    geom = pairs.PairsGeometry(n_features, BLOCK_SLOTS, BLOCK_ROWS, 5248,
                               on_tpu=True)
    gather, scatter, _, _ = passes(geom)
    return (gather, scatter,
            shape((geom.n_blocks, geom.held_rows, 128), jnp.int32),
            shape((N_SAMPLED,), jnp.int32),
            shape((geom.w_len,), jnp.float32),
            shape((N_SAMPLED, geom.vectors), jnp.float32))


BUNDLE_ROWS = [(what, trip) for trip in (16,) + TRIPS
               for what in ("gather", "scatter")]


def compile_one(spec: str) -> None:
    """In a child with the dump on: row ``spec`` of ``BUNDLE_ROWS``."""
    what, trip = BUNDLE_ROWS[int(spec)]
    gather, scatter, X, ids, w, back = described(N_FEATURES)
    if what == "gather":
        gather(trip).lower(X, w, ids).compile()
    else:
        scatter(trip).lower(X, back, ids).compile()


def bundles() -> int:
    """The post-RA bundles of a trip of each kernel's inner loop."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    from step0_als_solve import read_loops

    out = {}
    for n, (what, trip) in enumerate(BUNDLE_ROWS):
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
        with tempfile.TemporaryDirectory(prefix="llo_step0_") as dump:
            done = subprocess.run(
                [sys.executable, __file__, "--compile-one", str(n)],
                capture_output=True, text=True, env=dict(
                    env, LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                    "--xla_jf_dump_llo_text=true"))
            nest = read_loops(dump, f"_pairs_{what}_kernel")
        name = f"{what}.t{trip}"
        if not nest:
            say(f"[bundles] {name}: no schedule (exit {done.returncode}): "
                + (done.stderr.strip().splitlines() or ["?"])[-1][:300])
            continue

        def flat(items, depth=0):
            for lo, hi, inner in items:
                yield depth, hi - lo + 1
                yield from flat(inner, depth + 1)

        loops = list(flat(nest))
        deepest = max(d for d, _ in loops)
        trips = [n for d, n in loops if d == deepest]
        out[name] = {"trip_bundles": trips, "pairs": trip,
                     "bundles_a_pair": max(trips) / trip, "loops": loops}
        say(f"[bundles] {name}: a trip of {trip} pairs {trips} bundles "
            f"({max(trips) / trip:.2f} a pair: "
            f"{(max(trips) * 0.667 + 3.8) / trip:.2f} ns with the trip's "
            f"branch); loops by depth {loops}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "step0_pairs_bundles.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0 if out else 1


def compile_only() -> int:
    """Both kernels at the cell's width and at the budget's for a
    described v5e: what the chip's compiler grants, nothing run."""
    from tpu_distalg.ops import pairs

    bad = 0
    for n_features in (N_FEATURES, BUDGET_FEATURES):
        gather, scatter, X, ids, w, back = described(n_features)
        geom = pairs.PairsGeometry(n_features, BLOCK_SLOTS, BLOCK_ROWS,
                                   5248)
        for what, fn, second in (("gather", gather, w),
                                 ("scatter", scatter, back)):
            for trip in TRIPS:
                t = time.perf_counter()
                try:
                    mem = fn(trip).lower(X, second, ids).compile() \
                        .memory_analysis()
                    say(f"[compile] {what}.t{trip} at "
                        f"{4 * geom.w_len / 1e6:.1f} MB: ok in "
                        f"{time.perf_counter() - t:.1f} s, temporaries "
                        f"{mem.temp_size_in_bytes / 1e6:.1f} MB")
                except Exception as e:
                    bad += 1
                    say(f"[compile] {what}.t{trip} at "
                        f"{4 * geom.w_len / 1e6:.1f} MB REFUSED: "
                        f"{str(e)[:800]}")
    return 1 if bad else 0


def main(argv) -> int:
    if "--compile" in argv:
        return compile_only()
    if "--compile-one" in argv:
        compile_one(argv[argv.index("--compile-one") + 1])
        return 0
    if "--bundles" in argv:
        return bundles()
    rehearse = "--rehearse" in argv
    import jax

    from tpu_distalg.utils import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    if not rehearse and dev.platform != "tpu":
        say("[step0] no TPU: --rehearse interprets a CPU size, --compile "
            "compiles for a described chip")
        return 2
    say(f"[step0] device {dev.platform} {dev.device_kind}")
    only = argv[argv.index("--only") + 1].split(",") \
        if "--only" in argv else ["grant", "seeded", "flat"]
    path = os.path.join(ROOT, "chiprun_out", "step0_pairs.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    out = json.load(open(path)) if os.path.exists(path) else {}
    out["device"] = dev.device_kind
    if rehearse:
        sizes = dict(n_rows=300, n_features=50_000, length_mu=5.5,
                     block_slots=1 << 13, block_rows=32, n_sampled=6,
                     trips=(32, 64))
        widths = (50_000,)
    else:
        sizes = dict(n_rows=7000, n_features=N_FEATURES,
                     length_mu=LENGTH_MU, block_slots=BLOCK_SLOTS,
                     block_rows=BLOCK_ROWS, n_sampled=N_SAMPLED,
                     trips=TRIPS)
        widths = (N_FEATURES, BUDGET_FEATURES)
    for part in only:
        if part == "grant":
            for n_features in widths:
                grant(n_features, sizes["block_slots"], sizes["block_rows"],
                      not rehearse, out)
        elif part == "grant_small":     # 8 MB: where a hang is looked for
            grant(2_000_003, 1 << 16, 256, not rehearse, out)
        else:
            draw(part, {"seeded": 1.1, "flat": 0.0}[part], sizes, out)
        with open(path, "w") as f:      # a part at a time: one may hang
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
