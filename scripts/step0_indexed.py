#!/usr/bin/env python3
"""Step 0 of the indexed-row cell (``lrwide11_150m_frac01``): what each
form of each pass costs alone on the chip at the cell's shape (183
sampled blocks of 8192 rows, 11 slots a row, 54 686 452 weights), ms a
call, least of three.

    chiprun -- python3 scripts/step0_indexed.py [--quick | --trips]
    chiprun -- timeout 300 python3 -u scripts/step0_indexed.py \
        --field-scatter <field>
    chiprun -- timeout 120 python3 -u scripts/step0_indexed.py \
        --grant <slots>
    JAX_PLATFORMS=cpu python3 scripts/step0_indexed.py --rehearse
    JAX_PLATFORMS=cpu python3 scripts/step0_indexed.py --bundles

The table is the program's (``ssgd.build_hashed_table`` with the
cell's generator parameters). Rows of the output, one a line as
``[step0] <name> <ms>``:

  xla.whole.gather/.scatter   ``w[idx]`` summed over all 11 fields and
                              ``.at[idx].add`` over the whole table: the
                              form the parent's ``pass_form`` falls to
  fields.gather/.scatter      what ships: every field its own form
  dict.*, group<k>.*          the shipped form's parts alone
  group<k>.xla.*              a group's fields through XLA instead
  hbm.[<field>].xla.*         one field past VMEM through XLA alone
  update                      ``w - eta * g / n`` over the model vector
  hbm.gather / hbm.scatter    the fields past VMEM as they ship: the
                              gather a DMA a pair from the table in HBM
                              (``_hashed_hbm_gather_kernel``, no resident
                              head), the scatter XLA's
  hbm.xla.gather              those fields' gather through XLA's
                              ``w[idx]`` instead (what shipped first)
  step.fields / step.xla      a call of 4 steps of the trainer, ms a step
  group<k>.<pass>.rows<r>
  hbm.gather.rows<r>          PR 48's sweep: a by-address call at ``r``
                              rows a trip of its loop (``SINGLE_ROWS`` for
                              a group of one field, ``GROUP_ROWS`` for
                              more, ``HBM_ROWS`` for the third pass of the
                              gather from HBM); every trip's output
                              against ``rows2``'s, bit for bit
  hashed4x20.<pass>.rows<r>   a hashed table of 4 fields and 2**20 slots
                              (no cell has so few fields): the rule's
                              trip and the parent's 2 rows
  hbm.scatter.rows256         PR 59: the fields past VMEM's sums by
                              address in ONE accumulator in VMEM, a piece
                              of a field's range at a time
                              (``_hashed_field_scatter_kernel``, one call)
``--trips`` runs the sweep, ``fields.*`` and ``step.fields`` alone.
``--field-scatter <field>`` (PR 59's Step 0; a field a process, each
under a ``timeout`` of its own: an accumulator of a field's whole range,
87.7 and 97.2 MB, never came back) reads for that field alone, over the
seeded table and over a flat draw of the field's range: whether the call
runs three times to the same bits, ``hbm.[<field>].scatter`` against
``hbm.[<field>].xla.scatter`` (ms a call and ns a pair) and both forms'
sums against float64 on the host; its summary lands in
``chiprun_out/step0_indexed_field<field>.json``. ``--grant <slots>``
reads the same over a table of its own with one field of ``slots`` slots.
``--bundles`` needs no chip: it compiles each call of the sweep for a
described ``v5e:2x2`` with the schedule dumped and counts the post-RA
bundles of a trip of its loop (where a trip stops being one chain's
latency and starts being bound by issue).
A summary lands in ``chiprun_out/step0_indexed.json``
(``step0_indexed_bundles.json``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "lrwide11_150m_frac01"
# rows a trip the sweep reads: a call of one field, of several, and the
# third pass of the gather from HBM
SINGLE_ROWS = (2, 8, 16, 32, 64)
GROUP_ROWS = (2, 4, 8, 16)
HBM_ROWS = (2, 8, 16)
KERNELS = {"gather": "_hashed_gather_kernel",
           "scatter": "_hashed_scatter_kernel",
           "hbm": "_hashed_hbm_gather_kernel",
           "field": "_hashed_field_scatter_kernel"}


def least_ms(fn, *args, n: int = 3) -> float:
    import jax

    jax.block_until_ready(fn(*args))          # compile, warm
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def sweep_calls(plan, rehearse: bool = False):
    """``(tag, pass, group or None, rows)`` of every call of the sweep
    (``pass`` is gather, scatter, hbm or, with the fields in HBM where
    the group is, field)."""
    def some(rows):
        return rows[::len(rows) - 1] if rehearse else rows

    for k, group in enumerate(plan.addr_groups):
        tag = f"group{k}{list(group.fields)}"
        for which in ("gather", "scatter"):
            for rows in some(SINGLE_ROWS if len(group.fields) == 1
                             else GROUP_ROWS):
                yield f"{tag}.{which}", which, group, rows
    if plan.hbm_fields:
        for rows in some(HBM_ROWS):
            yield "hbm.gather", "hbm", None, rows
    if plan.hbm_fields:
        from tpu_distalg.ops import pallas_hashed as ph

        yield "hbm.scatter", "field", plan.hbm_fields, ph.CHUNK_ROWS


def call_of(ph, which, geom, plan, group, rows, interpret=False):
    """One call of the sweep as a function of (X, w or r, ids); with no
    ``group`` a gather or a scatter runs over every field of a hashed
    ``geom``."""
    import jax.numpy as jnp

    if which == "gather":
        return lambda X, w, ids: ph.margins_vmem(
            X, w, ids, geom, interpret=interpret, group=group, rows=rows)
    if which == "scatter":
        return lambda X, r, ids: ph.slot_sums_vmem(
            X, r, ids, geom, interpret=interpret, group=group, rows=rows)
    if which == "field":
        return lambda X, r, ids: jnp.concatenate(ph.slot_sums_fields(
            X, r, ids, geom, group, interpret=interpret))
    return lambda X, w, ids: ph.margins_hbm(
        X, w, ids, geom, plan.hbm_fields, interpret=interpret, rows=rows)


def cell_geometry():
    """The cell's geometry and plan from its files alone (no table)."""
    from harness import manifest as mf
    from families import ssgd_indexed as fam
    from tpu_distalg.ops import pallas_hashed as ph
    from tpu_distalg.utils import datasets

    cell = mf.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    c = dict(cell.config)
    cards = fam.loader_args(c)["cardinalities"]
    geom = ph.HashedGeometry(nnz=c["nnz"], hash_bits=0,
                             block_rows=c["gather_block_rows"],
                             field_sizes=tuple(cards))
    plan = ph.field_plan(geom, datasets.indexed_field_dictionaries(cards))
    return geom, plan, fam.shapes(c, cell.traffic)


def compile_one(spec: str) -> None:
    """In a child with the dump on: compile call ``spec`` (``<index in
    sweep_calls>``) for a described v5e."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tpu_distalg.ops import pallas_hashed as ph

    geom, plan, sh = cell_geometry()
    _, which, group, rows = list(sweep_calls(plan))[int(spec)]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ns = sh["n_sampled"]
    second = arr((ns, geom.block_rows), jnp.float32) \
        if which in ("scatter", "field") else arr((geom.w_len,), jnp.float32)
    jax.jit(call_of(ph, which, geom, plan, group, rows)).lower(
        arr((sh["n_blocks"], geom.fields_held, geom.block_rows), jnp.int32),
        second, arr((ns,), jnp.int32)).compile()


def bundles() -> int:
    """The post-RA bundles of a trip of each call's loop, and of the
    grid step round it, chiplessly."""
    from step0_als_solve import read_loops

    geom, plan, _ = cell_geometry()
    out = {}
    for n, (tag, which, group, rows) in enumerate(sweep_calls(plan)):
        # a grid step of the field scatter serves one field's chunk
        fields = (group[0],) if which == "field" else \
            plan.hbm_fields if group is None else group.fields
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
        with tempfile.TemporaryDirectory(prefix="llo_step0_") as dump:
            done = subprocess.run(
                [sys.executable, __file__, "--compile-one", str(n)],
                capture_output=True, text=True, env=dict(
                    env, LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                    "--xla_jf_dump_llo_text=true"))
            nest = read_loops(dump, KERNELS[which])
        name = f"{tag}.rows{rows}"
        if not nest:
            print(f"[bundles] {name}: no schedule (exit "
                  f"{done.returncode}): "
                  + (done.stderr.strip().splitlines() or ["?"])[-1][:300],
                  flush=True)
            continue
        (lo, hi, inner), = nest[-1:]         # the grid's loop is the last
        loops = [h - l + 1 for l, h, _ in inner]
        # the by-address loop; the one-field scatter has none: a chunk
        # is the grid step's own basic block
        trip = hi - lo + 1 - sum(loops) if which == "field" else loops[-1]
        pairs = rows * len(fields)
        out[name] = {"trip_bundles": trip, "pairs": pairs,
                     "bundles_a_pair": trip / pairs,
                     "chunk_bundles": trip * geom.chunk_rows // rows,
                     "loops": loops,
                     "grid_step_own": hi - lo + 1 - sum(loops)}
        print(f"[bundles] {name}: a trip {trip} bundles for {pairs} pairs "
              f"({trip / pairs:.2f} a pair, {out[name]['chunk_bundles']} a "
              f"chunk of {geom.chunk_rows} rows); the step's loops {loops}, "
              f"its own {out[name]['grid_step_own']}", flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "step0_indexed_bundles.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


def parts(say, out, ph, X, w, r, ids, geom, plan, interpret, quick,
          gather_of, scatter_of):
    """The shipped form's parts alone, and the same fields through XLA."""
    import jax
    import jax.numpy as jnp

    if plan.dict_fields:
        say("dict.gather", least_ms(jax.jit(
            lambda X, w, ids: ph.margins_dict(
                X, w, ids, geom, plan, interpret=interpret)), X, w, ids))
        say("dict.scatter", least_ms(jax.jit(
            lambda X, r, ids: ph.slot_sums_dict(
                X, r, ids, geom, plan, interpret=interpret)[1]), X, r, ids))
    for k, group in enumerate(plan.addr_groups):
        tag = f"group{k}{list(group.fields)}"
        say(f"{tag}.gather", least_ms(jax.jit(
            lambda X, w, ids, group=group: ph.margins_vmem(
                X, w, ids, geom, interpret=interpret, group=group)),
            X, w, ids))
        say(f"{tag}.scatter", least_ms(jax.jit(
            lambda X, r, ids, group=group: ph.slot_sums_vmem(
                X, r, ids, geom, interpret=interpret, group=group)),
            X, r, ids))
        if not quick:
            say(f"{tag}.xla.gather",
                least_ms(gather_of(group.fields), X, w, ids))
            say(f"{tag}.xla.scatter",
                least_ms(scatter_of(group.fields), X, r, ids))
    if plan.hbm_fields:
        by_dma = jax.jit(lambda X, w, ids: ph.margins_hbm(
            X, w, ids, geom, plan.hbm_fields, interpret=interpret))
        say("hbm.gather", least_ms(by_dma, X, w, ids))
        say("hbm.xla.gather",
            least_ms(gather_of(plan.hbm_fields), X, w, ids))
        diff = float(jnp.abs(by_dma(X, w, ids) - gather_of(
            plan.hbm_fields)(X, w, ids)).max())
        print(f"[step0] hbm.gather against XLA's: max diff {diff:.3g}",
              flush=True)
        out["hbm_gather_diff"] = diff
        say("hbm.scatter", least_ms(scatter_of(plan.hbm_fields), X, r, ids))
        if not quick:
            for f in plan.hbm_fields:
                say(f"hbm.[{f}].xla.gather",
                    least_ms(gather_of((f,)), X, w, ids))
                say(f"hbm.[{f}].xla.scatter",
                    least_ms(scatter_of((f,)), X, r, ids))
    say("update", least_ms(jax.jit(
        lambda w, g: w - 0.1 * (g / 1499136.0)), w, w))


def sweep(say, out, ph, X, w, r, ids, geom, plan, interpret, rehearse):
    """PR 48: every by-address call at each trip; each trip's output
    against the first's (2 rows a trip: the parent's), bit for bit."""
    import jax
    import jax.numpy as jnp

    first = {}
    for tag, which, group, rows in sweep_calls(plan, rehearse):
        second = r if which in ("scatter", "field") else w
        fn = jax.jit(call_of(ph, which, geom, plan, group, rows, interpret))
        name = f"{tag}.rows{rows}"
        say(name, least_ms(fn, X, second, ids))
        got = fn(X, second, ids)
        same = bool(jnp.array_equal(first.setdefault(tag, got), got))
        out[name + ".equal"] = same
        if not same:
            print(f"[step0] {name} DIFFERS from {tag}'s first trip",
                  flush=True)
    print(f"[step0] sweep: every trip bit for bit its first: "
          f"{all(v for k, v in out.items() if k.endswith('.equal'))}",
          flush=True)


def field_scatter(say, out, ph, geom, field, r, draws, interpret):
    """PR 59's Step 0 for one field past VMEM: its sums by address in
    an accumulator in VMEM (``ph.slot_sums_fields``) against XLA's
    scatter-add (``ph.slot_sums_hbm``) and against float64 on the
    host, over each of ``draws`` (a name, a table, the sampled blocks'
    ids)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lo, hi = geom.offsets[field], geom.offsets[field + 1]
    pairs = r.size
    kern = jax.jit(lambda X, r, ids: ph.slot_sums_fields(
        X, r, ids, geom, (field,), interpret=interpret)[0])
    xla = jax.jit(lambda X, r, ids: ph.slot_sums_hbm(
        X, r, ids, geom, (field,))[lo:hi])
    rows, phases = ph.field_phases(geom, (field,))
    print(f"[step0] field {field}: range {hi - lo} "
          f"({4 * (hi - lo) / 1e6:.1f} MB) in {len(phases)} piece(s) of "
          f"{rows} rows, asks "
          f"{ph._vmem_limit(geom, 1, rows * ph.LANES) / 1e6:.1f} MB of "
          f"VMEM, form {ph.field_scatter_form(hi - lo, not interpret)}",
          flush=True)
    r64 = np.asarray(r, np.float64).reshape(-1)
    for draw, Xd, idd in draws:
        tag = f"{draw}.hbm.[{field}]"
        got = [jax.block_until_ready(kern(Xd, r, idd)) for _ in range(3)]
        out[tag + ".same_bits"] = all(
            bool(jnp.array_equal(got[0], g)) for g in got[1:])
        print(f"[step0] {tag}.scatter ran three times, the same bits: "
              f"{out[tag + '.same_bits']}", flush=True)
        for name, fn in (("scatter", kern), ("xla.scatter", xla)):
            ms = least_ms(fn, Xd, r, idd)
            say(f"{tag}.{name}", ms)
            out[f"{tag}.{name}.ns_a_pair"] = ms * 1e6 / pairs
            print(f"[step0] {tag}.{name} {ms * 1e6 / pairs:.3f} ns a pair",
                  flush=True)
        slots = np.asarray(Xd[idd][:, field, :]).reshape(-1) - lo
        want = np.bincount(slots, weights=r64, minlength=hi - lo)
        scale = np.linalg.norm(want)
        for name, g in (("scatter", got[0]), ("xla.scatter",
                                              xla(Xd, r, idd))):
            err = float(np.linalg.norm(np.asarray(g, np.float64) - want)
                        / scale)
            out[f"{tag}.{name}.err64"] = err
            print(f"[step0] {tag}.{name} off float64 in norm {err:.3g}",
                  flush=True)
        top = int(np.bincount(slots).max())
        out[tag + ".hottest"] = top
        print(f"[step0] {tag}: the hottest slot holds {top} of {pairs} "
              f"pairs", flush=True)


def flat_draw(geom, field, ns: int, seed: int):
    """``ns`` blocks whose ``field`` holds a flat draw of its range (the
    other fields slot 0: the field scatter reads one row of a block)."""
    import jax
    import jax.numpy as jnp

    lo, hi = geom.offsets[field], geom.offsets[field + 1]
    return jnp.zeros((ns, geom.fields_held, geom.block_rows),
                     jnp.int32).at[:, field, :].set(jax.random.randint(
                         jax.random.key(seed), (ns, geom.block_rows), lo,
                         hi, jnp.int32))


def grant(slots: int, interpret: bool, rows: int = 8192, ns: int = 183):
    """Whether the chip runs the field scatter over a field of
    ``slots`` slots (a table of its own: a field of 1000 values and one
    of ``slots``, a flat draw, ``ns`` blocks; 16 777 000 slots are one
    piece of ``FIELD_PIECE_ROWS`` rows, 67.1 MB). A size a process,
    under a ``timeout``: an accumulator of a whole range of 87.7 MB did
    not come back (PR 59)."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import pallas_hashed as ph

    geom = ph.HashedGeometry(nnz=2, hash_bits=0, block_rows=rows,
                             field_sizes=(1000, slots))
    r = jax.random.normal(jax.random.key(slots), (ns, rows))
    out = {}
    field_scatter(lambda name, ms: print(f"[step0] {name} {ms:.3f}",
                                         flush=True),
                  out, ph, geom, 1, r,
                  [("flat", flat_draw(geom, 1, ns, slots),
                    jnp.arange(ns, dtype=jnp.int32))], interpret)
    return 0 if out["flat.hbm.[1].same_bits"] \
        and out["flat.hbm.[1].scatter.err64"] < 1e-5 else 1


def few_fields(say, out, ph, ns, interpret, rehearse):
    """A hashed table of 4 fields and 2**20 slots (random slots; blocks
    of the cell's height): the by-address passes over all four at the
    parent's 2 rows a trip and at the rule's."""
    import jax
    import jax.numpy as jnp

    geom = ph.HashedGeometry(nnz=4, hash_bits=12 if rehearse else 20,
                             block_rows=256 if rehearse else 8192)
    key = jax.random.key(4)
    nb = 2 * ns
    X = jax.random.randint(key, (nb, geom.fields_held, geom.block_rows), 0,
                           geom.n_slots, jnp.int32)
    ids = jnp.arange(0, nb, 2, dtype=jnp.int32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (geom.w_len,))
    r = jax.random.normal(jax.random.fold_in(key, 2),
                          (ns, geom.block_rows))
    rule = ph._loop_rows(geom, geom.nnz)
    for which, second in (("gather", w), ("scatter", r)):
        got = {}
        for rows in (ph.LOOP_ROWS, rule):
            fn = jax.jit(call_of(ph, which, geom, None, None, rows,
                                 interpret))
            say(f"hashed4x{geom.hash_bits}.{which}.rows{rows}",
                least_ms(fn, X, second, ids))
            got[rows] = fn(X, second, ids)
        out[f"hashed4.{which}.equal"] = bool(
            jnp.array_equal(got[ph.LOOP_ROWS], got[rule]))
        print(f"[step0] hashed4 {which}: rows {rule} bit for bit rows "
              f"{ph.LOOP_ROWS}: {out[f'hashed4.{which}.equal']}", flush=True)


def main(argv) -> int:
    if "--compile-one" in argv:
        compile_one(argv[argv.index("--compile-one") + 1])
        return 0
    if "--bundles" in argv:
        return bundles()

    import jax
    import jax.numpy as jnp

    from harness import manifest as mf
    from families import ssgd_indexed as fam
    from tpu_distalg.models import ssgd
    from tpu_distalg.ops import pallas_hashed as ph
    from tpu_distalg.parallel import get_mesh

    rehearse, quick = "--rehearse" in argv, "--quick" in argv
    trips = "--trips" in argv
    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not rehearse:
        print("step0_indexed: no chip (--rehearse interprets a tiny "
              "shape here)", file=sys.stderr)
        return 2
    if "--grant" in argv:
        slots = int(argv[argv.index("--grant") + 1])
        return grant(slots, not on_tpu, *((256, 3) if rehearse else ()))
    cell = mf.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    c, t = dict(cell.config), cell.traffic
    if rehearse:
        ph.VMEM_BITS = 12
        c.update(n_rows=20034, gather_block_rows=256, n_features=25477,
                 field_cardinalities=[300, 2000, 150, 3, 3, 9000, 1500,
                                      3000, 2500, 7000, 21])
        t = dict(t, mini_batch_fraction=0.25)
    sh = fam.shapes(c, t)
    mesh = get_mesh(data=1, model=1, devices=jax.devices()[:1])
    config = fam.program_config(c, t)
    interpret = not on_tpu
    t0 = time.perf_counter()
    X, meta = ssgd.build_hashed_table(
        c["n_rows"], c["nnz"], 0, mesh, config, data_seed=1234567,
        **fam.loader_args(c))
    out = {"loader_s": time.perf_counter() - t0}
    print(f"[step0] loader {out['loader_s']:.2f} s (compile in it) table "
          f"{X.shape} {X.nbytes / 1e9:.3f} GB", flush=True)
    geom = ssgd.hashed_geometry(config, meta)
    plan = ssgd.hashed_field_plan(config, meta)
    ns, B = sh["n_sampled"], geom.block_rows
    key = jax.random.key(0)
    ids = jnp.sort(jax.random.permutation(key, sh["n_blocks"])[:ns]
                   ).astype(jnp.int32)
    w = 0.05 * jax.random.normal(jax.random.fold_in(key, 1),
                                 (geom.w_len,)
                                 ).at[geom.n_slots + 1:].set(0)
    r = jax.random.normal(jax.random.fold_in(key, 2), (ns, B))

    def say(name, ms):
        out[name] = ms
        print(f"[step0] {name} {ms:.3f}", flush=True)

    if "--field-scatter" in argv:
        field = int(argv[argv.index("--field-scatter") + 1])
        if field not in plan.hbm_fields:
            print(f"step0_indexed: field {field} is not in HBM "
                  f"{plan.hbm_fields}", file=sys.stderr)
            return 2
        field_scatter(say, out, ph, geom, field, r, [
            ("seeded", X, ids),
            ("flat", flat_draw(geom, field, ns, 59),
             jnp.arange(ns, dtype=jnp.int32))], interpret)
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               f"step0_indexed_field{field}.json"),
                  "w") as f:
            json.dump(out, f, indent=1)
        return 0

    def gather_of(fields):
        return jax.jit(
            lambda X, w, ids: ph.margins_hbm_xla(X, w, ids, fields))

    def scatter_of(fields):
        return jax.jit(lambda X, r, ids: ph.slot_sums_hbm(
            X, r, ids, geom, fields))

    m_f = jax.jit(lambda X, w, ids: ph.margins(
        X, w, ids, geom, plan=plan, interpret=interpret))
    g_f = jax.jit(lambda X, r, ids: ph.slot_sums(
        X, r, ids, geom, plan=plan, interpret=interpret))
    if not trips:
        # the form the parent's pass_form falls to: XLA over the whole
        # table
        m_x = jax.jit(lambda X, w, ids: ph.margins_xla(X, w, ids, geom))
        g_x = jax.jit(lambda X, r, ids: ph.slot_sums_xla(X, r, ids, geom))
        say("xla.whole.gather", least_ms(m_x, X, w, ids))
        say("xla.whole.scatter", least_ms(g_x, X, r, ids))
    # what ships
    say("fields.gather", least_ms(m_f, X, w, ids))
    say("fields.scatter", least_ms(g_f, X, r, ids))
    if not trips:
        err_m = float(jnp.abs(m_f(X, w, ids) - m_x(X, w, ids)).max())
        g1, g2 = g_f(X, r, ids), g_x(X, r, ids)
        err_g = float(jnp.abs(g1 - g2).max() / jnp.abs(g2).max())
        print(f"[step0] fields against xla.whole: margins max diff "
              f"{err_m:.3g}, sums max diff over max {err_g:.3g}",
              flush=True)
        out.update(margins_diff=err_m, sums_diff=err_g)
        del g1, g2
        parts(say, out, ph, X, w, r, ids, geom, plan, interpret, quick,
              gather_of, scatter_of)
    sweep(say, out, ph, X, w, r, ids, geom, plan, interpret, rehearse)
    few_fields(say, out, ph, ns, interpret, rehearse)
    # a call of the trainer, both forms
    d = jnp.zeros((1,), jnp.float32)
    w0 = jnp.zeros((geom.w_len,), jnp.float32)
    steps = config.n_iterations
    fn = ssgd.make_train_fn_fused(mesh, config, meta)
    say("step.fields", least_ms(
        lambda: fn(X, d, d, d, d, w0, t0=0)[0]) / steps)
    if not trips:
        real = ph.pass_form
        ph.pass_form = lambda *a: "xla"
        try:
            fn_x = ssgd.make_train_fn_fused(mesh, config, meta)
            say("step.xla", least_ms(
                lambda: fn_x(X, d, d, d, d, w0, t0=0)[0]) / steps)
        finally:
            ph.pass_form = real
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
    print(f"[step0] peak bytes in use {out['peak_bytes_in_use']}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step0_indexed.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
