#!/usr/bin/env python3
"""Step 0 of sparse ALS' resident gather: what one block's row gather
costs alone on the chip at the ``als100_253m_sweep1`` cell's shape (a
block of 6144 segments x 32 slots = 196 608 rows of 128 float32 lanes,
from the items' table of 663 560 rows and the users' of 1 032 200), ms a
block, least of three, each with its dispatch. Kept as the way to
re-read ``GATHER_VMEM_BYTES`` in ``tpu_distalg/ops/als_sparse.py`` (the
``heavy`` / ``five`` / ``budget`` rows):

    chiprun -- python3 scripts/step0_als_gather.py
    JAX_PLATFORMS=cpu python3 scripts/step0_als_gather.py --rehearse

Rows of the output, one a line as ``[step0] <name> <ms> [<ns a slot>]``:

  <table>.xla.<block>            XLA's ``other[idx]``
  <table>.<range>.<block>[.cN]   the Mosaic kernel with that resident
                                 range, chunks of N rows of 128 slots
                                 (the module's own where none is named)
  <...>.x8                       eight blocks in one program (a scan
                                 that keeps a column sum of each), ms a
                                 block: no dispatch in it

``<table>`` is ``items`` (what the user half reads) or ``users``;
``<range>`` is ``heavy`` (the heavy class and the zero rows, 9.4 MB),
``five`` (the last five classes, 31.5 / 40.9 MB) or ``budget`` (what
``als_sparse.resident_row0`` picks under ``GATHER_VMEM_BYTES``);
``<block>`` is ``hot`` (every slot in the heavy class), ``pad`` (every
slot the zero row), ``cold`` (every slot below every range) or ``mix``
(the cell's shares for that half: the plan's count of slots in the heavy
class, in the classes between and below). Every kernel result is
compared with XLA's, bit for bit. A summary lands in
``chiprun_out/step0_als_gather.json``.

PR 37's readings (``PERF.md`` section 6) also name forms of the kernel
that are not in the code any more, each timed once on the ``.x8`` rows:
8 slots a trip of pass 1 (7% slower than 16), 8 copies a trip of pass 2
(5% slower on a cold block than 16), the resident range copied in by 4
or 16 DMAs (the same as one), a chunk's slots in parts of 2048 with a
part's copies started before the next part's pass 1 (nothing over
chunks of 2048).
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the cell's shape (benchmarks/configs/als-yahoomusic-f100.json)
N_RATINGS, N_USERS, N_ITEMS, K = 252_800_275, 1_000_990, 624_961, 100
GEOMETRY = dict(seg_slots=32, piece_segs=64, batch=6144)
FIVE = 4           # light classes before the heavy one in ``five``


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
    import numpy as np

    from tpu_distalg.models import als
    from tpu_distalg.ops import als_sparse, pallas_als

    interp = "--rehearse" in argv
    if jax.devices()[0].platform != "tpu" and not interp:
        print("step0_als_gather: no chip", file=sys.stderr)
        return 2
    if interp:
        meta = als.plan_ratings(60_000, 900, 500, 12, geometry=dict(
            seg_slots=32, piece_segs=64, batch=192))
    else:
        meta = als.plan_ratings(N_RATINGS, N_USERS, N_ITEMS, K,
                                geometry=GEOMETRY)
    geom = meta["geometry"]
    rows_b = geom.block_shape[0]
    slots = geom.block_slots
    rng = np.random.default_rng(37)
    out: dict = {"slots": slots}

    def row(name, ms):
        out[name] = ms
        print(f"[step0] {name} {ms:.4f} ms  {ms * 1e6 / slots:.2f} ns/slot",
              flush=True)

    # the half that reads a table is the OTHER side's
    for table, own, other in (("items", meta["user"], meta["item"]),
                              ("users", meta["item"], meta["user"])):
        st = other.static
        heavy0 = st.heavy[2]
        starts = [r0 for _, _, n, r0 in st.light if n]
        ranges = {
            "heavy": heavy0,
            "five": starts[-FIVE] if len(starts) >= FIVE else starts[0],
            "budget": als_sparse.resident_row0(st, geom)}
        T = jnp.asarray(rng.standard_normal(
            (st.table_rows, geom.width), np.float32)).at[st.zero_row:].set(0)
        # the cell's shares of this half's slots: heavy class and
        # padding, the classes of ``five`` below it, the rest
        held = own.slots_held
        share = {name: als_sparse.resident_slots(own, other, r0) / held
                 for name, r0 in ranges.items()}
        out[f"{table}.share"] = share
        out[f"{table}.rows"] = {n: st.table_rows - r for n, r in
                                ranges.items()}
        print(f"[step0] {table}: table rows {st.table_rows}, resident "
              f"rows {out[table + '.rows']}, shares {share}", flush=True)
        lo = min(ranges.values())
        u = rng.random(slots)
        blocks = {
            "hot": rng.integers(heavy0, st.zero_row, slots),
            "pad": np.full(slots, st.zero_row),
            "cold": rng.integers(0, max(lo, 1), slots),
            "mix": np.where(
                u < share["heavy"],
                rng.integers(heavy0, st.zero_row + 1, slots),
                np.where(u < share["five"],
                         rng.integers(ranges["five"], max(
                             heavy0, ranges["five"] + 1), slots),
                         rng.integers(0, max(ranges["five"], 1), slots)))}
        blocks = {k: jnp.asarray(v.astype(np.int32).reshape(rows_b, -1))
                  for k, v in blocks.items()}
        xla = jax.jit(lambda T, i: als_sparse.gather_rows(T, i))

        def kernel(r0, chunk=None):
            return jax.jit(lambda T, i: pallas_als.gather_rows_resident(
                T, i, r0, interpret=interp, chunk=chunk))

        def eight(fn):
            def run(T, i8):
                def one(c, i):
                    return c, jnp.sum(fn(T, i), axis=0)

                return jax.lax.scan(one, 0, i8)[1]

            return jax.jit(run)

        want = {}
        for b, idx in blocks.items():
            want[b] = xla(T, idx)
            row(f"{table}.xla.{b}", least_ms(xla, T, idx))
        i8 = {b: jnp.stack([jnp.roll(idx, s, axis=0) for s in range(8)])
              for b, idx in blocks.items()}
        for b in blocks:
            row(f"{table}.xla.{b}.x8", least_ms(eight(xla), T, i8[b]) / 8)
        for name, r0 in ranges.items():
            if name == "budget" and r0 in (ranges["heavy"], ranges["five"]):
                continue
            for b, idx in blocks.items():
                fn = kernel(r0)
                if not bool(jnp.array_equal(fn(T, idx), want[b])):
                    print(f"[step0] {table}.{name}.{b}: NOT the rows "
                          f"XLA returns", flush=True)
                    return 1
                row(f"{table}.{name}.{b}", least_ms(fn, T, idx))
            for b in blocks:
                row(f"{table}.{name}.{b}.x8",
                    least_ms(eight(kernel(r0)), T, i8[b]) / 8)
        if not interp:
            for chunk in (8, 16, 32):
                row(f"{table}.heavy.mix.x8.c{chunk}", least_ms(
                    eight(kernel(ranges["heavy"], chunk)), T, i8["mix"]) / 8)
        del T, want
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step0_als_gather.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
