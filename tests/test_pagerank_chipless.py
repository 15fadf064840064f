"""The fused SpMV kernel compiled for a described v5e at the benchmark
cell's real shapes, with no chip attached: what interpret mode cannot
show (the 64 MB output table in VMEM, a kernel call's scalars in SMEM's
1 MB, the tiling of the windows). Nothing runs: a compile that passes
is not a chip run. The topology is described inside a fixture, in this
one file (only one process may hold the TPU's library), so sparse ALS'
solve kernel is compiled here too: a tile of 128 systems at rank 100
from an owner-major batch of 6144, and the widest rank ``solve_plan``
admits; a half-sweep's steps at the cell's batch, whose compiled module
copies no batch of Gramians into another layout; indexed LR's gather
from a weight table in HBM at KDD Cup 2012's shape; and the dense
closure's donated round at BigDatalog's Grid250, which holds the matrix
it reads and the one it writes and no third; and the pairs passes by
address at webspam's width, the 66.4 MB model vector one copy in VMEM,
alone and in the trainer's whole segment."""

import re

import numpy as np

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpu_distalg.ops import pallas_pagerank as ppr


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(geom, one_chip, seg_steps=None):
    """A shard's kernel calls: its own chunks, its own range's table."""
    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_chunks = geom.n_chunks // geom.n_shards
    per_slot = (n_chunks * 8, 128)
    return jax.jit(lambda *a: ppr.spmv_table(
        *a, rg=geom.rg, ws=geom.ws, r8=geom.rows_out, blk=geom.blk,
        seg_steps=seg_steps or geom.seg_steps)).lower(
            arr((n_chunks,), jnp.int32),
            arr((n_chunks,), jnp.int32),
            arr((geom.n_groups * geom.rg, 128), jnp.float32),
            *[arr(per_slot, jnp.int32)] * 4,
            arr(per_slot, jnp.float32)).compile()


def test_kernel_compiles_at_graph500_scale_24(one_chip):
    geom = ppr.spmv_geometry(1 << 24, 16 << 24)
    mem = _compile(geom, one_chip).memory_analysis()
    assert mem.output_size_in_bytes == (geom.r8 + geom.ws) * 512
    assert 5.4e9 < mem.argument_size_in_bytes < 5.6e9


@pytest.mark.parametrize("scale,ws", [(26, None), (25, None),
                                      (26, ppr.SPMV_WS_CAP)])
def test_kernel_compiles_at_a_quarter_of_graph500_scale_26(one_chip,
                                                           scale, ws):
    """The four-chip cell's shard (and SCALE 25's, the cut the cell
    would take): groups of the whole 524 288-row table, the output
    table of the shard's own rows (a quarter of them and room for a
    range that is cut wide) in VMEM with the wider window a sparser
    block needs; and the widest window the geometry
    admits beside that table."""
    import dataclasses

    geom = ppr.spmv_geometry(1 << scale, 16 << scale, 4)
    assert geom.r8 / 4 < geom.rows_out < 1.05 * geom.r8 / 4
    if ws:
        geom = dataclasses.replace(geom, ws=ws)
    mem = _compile(geom, one_chip).memory_analysis()
    assert mem.output_size_in_bytes == (geom.rows_out + geom.ws) * 512
    if scale == 26:
        assert 5.5e9 < mem.argument_size_in_bytes < 6.0e9


def test_a_whole_sweeps_scalars_do_not_fit_smem(one_chip):
    """Why a sweep is several kernel calls: one call's worth of every
    chunk's base is past SMEM at this size."""
    geom = ppr.spmv_geometry(1 << 24, 16 << 24)
    with pytest.raises(Exception, match="smem|SMEM"):
        _compile(geom, one_chip, seg_steps=geom.n_steps)


def test_kernel_compiles_at_the_vmem_budgets_edge(one_chip):
    """The tallest table the geometry admits (just under
    ``SPMV_VMEM_BUDGET``) is one the chip's compiler takes."""
    v = (ppr.SPMV_VMEM_BUDGET // 512 - 4096) * 128
    geom = ppr.spmv_geometry(v, 8 * v)
    assert geom is not None
    assert ppr.spmv_geometry(v + (1 << 20), 8 * v) is None
    _compile(geom, one_chip)


@pytest.mark.parametrize("k,batch", [(100, 6144), (126, 1024)])
def test_als_solve_kernel_compiles_for_the_chip(one_chip, k, batch):
    """The rank of the benchmark's cell, and the widest whose tile
    ``als_sparse.solve_plan`` lets into VMEM (27 MB of the budget's 40;
    from rank 127 an owner's row is two vectors and the tile's block of
    them 46 MB): both fit what the chip's compiler allows a kernel that
    is handed the batch owner-major and turns its tile in VMEM."""
    from tpu_distalg.ops import als_sparse, pallas_als

    geom = als_sparse.SparseGeometry(k=k, batch=batch, classes=(1,),
                                     piece_segs=batch)
    assert als_sparse.solve_plan(geom, True).form == "mosaic"
    assert als_sparse.solve_plan(
        als_sparse.SparseGeometry(k=127), True).form == "xla"
    Ap = jax.ShapeDtypeStruct((batch, geom.width, geom.width),
                              jnp.float32, sharding=one_chip)
    done = jax.jit(lambda a: pallas_als.solve_lanes(a, k, 1.4)).lower(
        Ap).compile()
    assert "_als_solve_kernel" in done.as_text()
    # the unknowns and the right-hand sides, a system a lane (and the
    # pair's table)
    assert 0 <= done.memory_analysis().output_size_in_bytes \
        - 2 * geom.solve_n * batch * 4 <= 1024


@pytest.mark.parametrize("gather_form", ["xla", "mosaic"])
@pytest.mark.parametrize("with_error", [True, False],
                         ids=["item_half", "user_half"])
def test_a_half_sweep_copies_no_batch_of_gramians(one_chip, with_error,
                                                  gather_form):
    """One shard's half at the cell's batch, rank and width (6144, 100,
    128) with a step of every kind (a class of one segment an owner, a
    class staged in two parts, the heavy class's accumulator), the
    Mosaic solve and the gather in either form (XLA's, as a mesh's shard
    runs it; the kernel's, as the cell does, the heavy class's 18 440
    rows of a table of 700 000 resident): the batch travels owner-major
    from the product to the kernel, which takes it inside
    ``solve_tile_bytes`` + ``VMEM_SLACK`` (the limit it is compiled
    under), and no ``copy`` of the
    compiled module has a batch's 128 x 128 x 6144 floats in any order
    (until PR 50 three had, 12% of an iteration: a turn to lanes for the
    kernel in each half and one more for the item half's error sums,
    which the user half drops). Nor is the gathered block copied (the
    same count of floats: 196 608 rows of 128), nor a block's indices,
    resident rows or ratings, nor a side's: the gather kernel reads
    them where a slice left them, four int32 operands (the counts by
    scalar prefetch, then the indices, the resident rows and the cold
    list through SMEM: one more than until PR 53) and two float32 (the
    turned ratings, the table)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_distalg.ops import als_sparse, pallas_als

    geom = als_sparse.SparseGeometry(k=100)
    B, W = geom.batch, geom.width
    plan = als_sparse.plan_side(np.concatenate([
        np.full(B, 20), np.full(B // 2, 50), np.full(40, 5000)]), geom)
    st = plan.static
    assert [n for _, _, n, _ in st.light][:2] == [1, 1] and st.heavy[3] == B
    solve = als_sparse.solve_plan(geom, True)
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    rows = 700_000

    def arr(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    block = (st.n_blocks, *geom.block_shape)
    how, lists = {}, ()
    if gather_form == "mosaic":
        how = {"gather": als_sparse.GatherPlan("mosaic", rows - 18440,
                                               18440)}
        chunks = block[1] // pallas_als.chunk_rows(block[1])
        lists = (arr(block, jnp.int32, row),
                 arr((st.n_blocks, geom.block_slots // 2), jnp.int32, row),
                 arr((st.n_blocks, chunks), jnp.int32, row))

    def run(idx, val, pieces, other, own, *cold):
        table, sse, seen = als_sparse.half_sweep(
            idx, val, pieces, other, own, static=st,
            other_zero_row=rows - 8, geom=geom, lam=1.4, axis="data",
            solve=solve, cold=cold, **how)
        return (table, sse, seen) if with_error else (table, seen)

    done = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(P("data"),) * 3 + (P(), P()) + (P("data"),) * len(lists),
        out_specs=(P(),) * (3 if with_error else 2),
        check_vma=False)).lower(
            arr(block, jnp.int32, row), arr(block, jnp.float32, row),
            arr(plan.piece_slot.shape, jnp.int32, row),
            arr((rows, W), jnp.float32),
            arr((st.table_rows, W), jnp.float32), *lists).compile()
    text = done.as_text()
    assert "_als_solve_kernel" in text
    sizes = {B * W * W, geom.block_slots, st.n_blocks * geom.block_slots}
    copies = [m.group(0) for m in re.finditer(
        r"= [fs]32\[([\d,]+)\]\S* copy\(", text)
        if np.prod([int(d) for d in m.group(1).split(",")]) in sizes]
    assert not copies, copies
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "_als_gather_kernel" in ln]
    if gather_form == "xla":
        assert not calls
        return
    assert calls
    slots = geom.block_slots
    for ln in calls:
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}\}, ",
                             ln).group(1)
        assert re.findall(r"([fs]32\[[\d,]+\])", operands) == [
            f"s32[{chunks}]", f"s32[{slots}]", f"s32[{slots}]",
            f"s32[{slots // 2}]", f"f32[{block[1]},128]",
            f"f32[{rows},128]"], operands


def test_hbm_gather_kernel_compiles_at_kdd12s_shape(one_chip):
    """Indexed LR's gather of the two fields past VMEM at the
    benchmark's shape (183 sampled blocks of 8192 rows, the query and
    user ids' columns of an ``int32[18267, 16, 8192]`` table, the model
    vector of 54 686 452 weights read in HBM as 427 238 rows of 128
    lanes): the chip's compiler takes the table in ``ANY`` memory, the
    landing rows in VMEM and a DMA a pair."""
    from tpu_distalg.ops import pallas_hashed as ph

    cards = (24323, 594098, 13745, 3, 3, 24296581, 1157062, 3750862,
             2936510, 21913244, 21)
    geom = ph.HashedGeometry(11, 0, 8192, field_sizes=cards)
    assert geom.w_len == 427238 * 128
    X = jax.ShapeDtypeStruct((18267, 16, 8192), jnp.int32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((geom.w_len,), jnp.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((183,), jnp.int32, sharding=one_chip)
    done = jax.jit(lambda X, w, ids: ph.margins_hbm(
        X, w, ids, geom, (5, 9))).lower(X, w, ids).compile()
    assert "_hashed_hbm_gather_kernel" in done.as_text()
    # (the margins' share, 183 rows of 8192 padded to a tile of 8)
    assert done.memory_analysis().output_size_in_bytes == 184 * 8192 * 4


KDD12 = (24323, 594098, 13745, 3, 3, 24296581, 1157062, 3750862, 2936510,
         21913244, 21)


def test_field_scatter_kernel_compiles_at_kdd12s_two_ranges(one_chip):
    """Indexed LR's sums of the two id fields at the benchmark's shape
    (183 sampled blocks of 8192 rows; the query ids' 24 296 581 slots,
    the user ids' 21 913 244): one call, four phases, and the chip's
    compiler grants ONE accumulator of 2^17 rows (67.1 MB, no second
    buffer) as a VMEM scratch beside a chunk's residuals; each range
    comes back cut from wherever in a row it starts."""
    from tpu_distalg.ops import pallas_hashed as ph

    geom = ph.HashedGeometry(11, 0, 8192, field_sizes=KDD12)
    assert [ph.field_scatter_form(KDD12[f], True) for f in (5, 9)] == [
        "vmem", "vmem"]
    assert geom.offsets[5] % 128 and geom.offsets[9] % 128
    rows, phases = ph.field_phases(geom, (5, 9))
    assert (rows, len(phases)) == (1 << 17, 4)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    done = jax.jit(lambda X, r, ids: ph.slot_sums_fields(
        X, r, ids, geom, (5, 9))).lower(
            arr((18267, 16, 8192), jnp.int32),
            arr((183, 8192), jnp.float32), arr((183,), jnp.int32)).compile()
    assert len(re.findall(r"%_hashed_field_scatter_kernel[.\d]* = ",
                          done.as_text())) == 1
    mem = done.memory_analysis()
    slots = KDD12[5] + KDD12[9]
    assert 4 * slots <= mem.output_size_in_bytes <= 4 * slots + 8192
    # the residuals a lane each (768 MB) and the four pieces' copies in
    # HBM before the ranges are cut from them
    assert mem.temp_size_in_bytes < 183 * 8192 * 512 + 4 * (64 << 20) \
        + (1 << 20)


def test_indexed_trainer_on_a_tpu_mesh_scatters_its_id_fields_in_vmem(
        one_chip, monkeypatch, tmp_path):
    """The trainer's segment over an indexed table on a described chip
    (``mesh_on_tpu``: the passes compile), the VMEM bound shrunk so that
    two fields are past it: one call of ``_hashed_field_scatter_kernel``
    for both, ``_hashed_hbm_gather_kernel`` still their gather, and the
    step says which form each field's sums took."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_distalg.models import ssgd
    from tpu_distalg.ops import pallas_hashed as ph
    from tpu_distalg.telemetry import events, report
    from tpu_distalg.utils import datasets

    monkeypatch.setattr(ph, "VMEM_BITS", 12)
    cards = (300, 2000, 150, 3, 3, 9000, 1500, 3000, 2500, 7000, 21)
    mesh = Mesh(np.array([one_chip._device]).reshape(1, 1),
                ("data", "model"))
    rep = NamedSharding(mesh, P())
    cfg = ssgd.SSGDConfig(
        n_iterations=2, eta=0.1, lam=0.0, mini_batch_fraction=0.25,
        seed=42, eval_test=False, sampler="fused_gather",
        gather_block_rows=256)
    meta = dict(row_format="indexed", nnz=11, hash_bits=0, pack=1,
                n_rows=20000, n_padded=20224, d_total=25600,
                cardinalities=cards,
                dictionaries=datasets.indexed_field_dictionaries(cards))
    assert ssgd.hashed_field_plan(cfg, meta).hbm_fields == (5, 9)
    fields = ssgd._hashed_fields(cfg, meta, mesh)
    assert (fields["fields_hbm"], fields["fields_hbm_scatter_vmem"],
            fields["fields_hbm_scatter_xla"]) == (2, 2, 0)
    d = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=rep)
    events.configure(str(tmp_path))
    try:
        text = ssgd.make_train_fn_fused(mesh, cfg, meta).lower(
            jax.ShapeDtypeStruct((79, 16, 256), jnp.int32,
                                 sharding=NamedSharding(
                                     mesh, P("data", None, None))),
            d, d, d, d,
            jax.ShapeDtypeStruct((25600,), jnp.float32, sharding=rep),
            t0=0).as_text(debug_info=True)
    finally:
        events.configure(False)
    # one call, under the scatter's scope of the table in HBM
    assert text.count('kernel_name = "_hashed_field_scatter_kernel"') == 1
    assert ("tda.ssgd.scatter/tda.ssgd.table_hbm/"
            "_hashed_field_scatter_kernel") in text
    assert "_hashed_hbm_gather_kernel" in text
    said = sorted((e["field"], e["form"], e["kernel"], e["pieces"])
                  for e in report.load_events(str(tmp_path))
                  if e["ev"] == "ssgd:field_scatter")
    assert said == [(5, "vmem", "_hashed_field_scatter_kernel", 1),
                    (9, "vmem", "_hashed_field_scatter_kernel", 1)]


def test_a_hashed_tables_step_on_a_tpu_mesh_has_no_field_scatter(one_chip):
    """The control: a hashed table has no field ranges, so its plan
    leaves nothing in HBM and its segment on a described chip lowers
    the by-address and by-value kernels alone."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_distalg.models import ssgd
    from tpu_distalg.utils import datasets

    cards = datasets.click_field_cardinalities(8)
    mesh = Mesh(np.array([one_chip._device]).reshape(1, 1),
                ("data", "model"))
    rep = NamedSharding(mesh, P())
    cfg = ssgd.SSGDConfig(
        n_iterations=2, eta=0.1, lam=0.0, mini_batch_fraction=0.25,
        seed=42, eval_test=False, sampler="fused_gather",
        gather_block_rows=1024)
    meta = dict(row_format="hashed", nnz=8, hash_bits=14, pack=1,
                n_rows=20000, n_padded=20480, d_total=(1 << 14) + 128,
                cardinalities=cards,
                dictionaries=datasets.click_field_dictionaries(cards, 14))
    plan = ssgd.hashed_field_plan(cfg, meta)
    assert plan is not None and plan.hbm_fields == ()
    fields = ssgd._hashed_fields(cfg, meta, mesh)
    assert (fields["fields_hbm"], fields["fields_hbm_scatter_vmem"],
            fields["fields_hbm_scatter_xla"]) == (0, 0, 0)
    d = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=rep)
    text = ssgd.make_train_fn_fused(mesh, cfg, meta).lower(
        jax.ShapeDtypeStruct((20, 16, 1024), jnp.int32,
                             sharding=NamedSharding(
                                 mesh, P("data", None, None))),
        d, d, d, d,
        jax.ShapeDtypeStruct(((1 << 14) + 128,), jnp.float32, sharding=rep),
        t0=0).as_text(debug_info=True)
    for kernel in ("_hashed_gather_kernel", "_hashed_scatter_kernel",
                   "_hashed_value_gather_kernel",
                   "_hashed_value_sums_kernel"):
        assert kernel in text, kernel
    assert "_hashed_field_scatter_kernel" not in text
    assert "_hashed_hbm_gather_kernel" not in text


def test_closure_round_compiles_at_grid250_and_holds_two_matrices(one_chip):
    """The byte kernel at the shipped tiles on the padded 63 488 vertices
    (an 8 MB float32 accumulator, the byte tiles and their bfloat16
    turns under the VMEM limit the call states), inside the donated
    round ``transitive_closure.make_round_fn`` compiles: both matrices
    are aliased to the outputs, and the module holds no third beside
    them and makes no copy of one. The start state's scatter, cut into
    blocks of rows, holds the matrix and three blocks."""
    from jax.sharding import Mesh

    from tpu_distalg.models import transitive_closure as tc
    from tpu_distalg.ops import pallas_closure

    v = pallas_closure.padded_vertices(63001, "mosaic", 1)
    assert v == 63488
    mesh = Mesh(np.array([one_chip._device]).reshape(1, 1),
                ("data", "model"))
    geom = tc.DenseGeometry(63001, v, "mosaic", False)
    matrix = jax.ShapeDtypeStruct((v, v), jnp.int8, sharding=one_chip)
    words = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    compiled = tc.make_round_fn(mesh, geom).lower(
        matrix, matrix, words).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"= s8\[63488,63488\]\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert 2 * v * v < mem.argument_size_in_bytes < 2 * v * v + 4096
    assert mem.alias_size_in_bytes == 2 * v * v
    assert mem.temp_size_in_bytes < 1 << 20

    assert tc.start_blocks(v) == 8 and tc.start_blocks(46340) == 1
    arcs = jax.ShapeDtypeStruct((125500,), jnp.int32, sharding=one_chip)
    start = tc.make_start_fn(mesh, geom).lower(arcs, arcs).compile()
    mem = start.memory_analysis()
    assert mem.temp_size_in_bytes < 3.1 * (v // 8) * v


WEBSPAM = dict(n_features=16_609_143, block_slots=1 << 18, block_rows=512,
               n_blocks=5248, on_tpu=True)


@pytest.mark.parametrize("which", ["gather", "scatter"])
def test_pairs_kernels_compile_at_webspams_width(one_chip, which):
    """``ops/pallas_pairs.py`` at the ``lrpairs3728_350k_frac01`` cell's
    shape (52 sampled blocks of 2048 vectors, the model vector
    ``f32[129759, 128]``): the chip's compiler grants ONE single-buffered
    copy of 66.4 MB as a VMEM scratch beside the chunk's buffers (every
    DMA a whole array of whole tiles, 129 760 rows), the table itself
    read where it lies (no copy of a sampled block)."""
    from tpu_distalg.ops import pairs, pallas_pairs

    geom = pairs.PairsGeometry(**WEBSPAM)
    assert pairs.vmem_bytes(geom.w_len) < pairs.VMEM_BUDGET_BYTES

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    X = arr((geom.n_blocks, geom.held_rows, 128), jnp.int32)
    ids = arr((52,), jnp.int32)
    if which == "gather":
        done = jax.jit(lambda X, w, ids: pallas_pairs.vector_products(
            X, w, ids, ids, geom)).lower(
                X, arr((geom.w_len,), jnp.float32), ids).compile()
        out = 52 * 2048 * 128 * 4
    else:
        done = jax.jit(lambda X, back, ids: pallas_pairs.slot_sums(
            X, back, ids, ids, geom)).lower(
                X, arr((52, 2048), jnp.float32), ids).compile()
        out = 129759 * 128 * 4
    assert f"_pairs_{which}_kernel" in done.as_text()
    mem = done.memory_analysis()
    assert out <= mem.output_size_in_bytes <= out + 4096   # 1-D tiles
    # one copy of the vector in whole tiles (129 760 rows) beside it
    assert mem.temp_size_in_bytes < 129760 * 512 + (1 << 20)


def test_pairs_segment_compiles_with_both_kernels(one_chip):
    """The trainer's segment at the cell's shape on a described chip
    (its ``meta`` says a TPU's loader wrote it: the ``vmem`` form): one
    call of each kernel a trip of 13 blocks, XLA's sort and scatter of
    the pairs gone, and the step's temporaries (a trip's 13.6 MB of
    products, the vectors of sums) far under a trip's of the ``xla``
    form."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_distalg.models import ssgd

    mesh = Mesh(np.array([one_chip._device]).reshape(1, 1),
                ("data", "model"))
    rep = NamedSharding(mesh, P())
    meta = dict(WEBSPAM, row_format="pairs", pack=1, n_rows=350_000,
                d_total=129759 * 128)
    cfg = ssgd.SSGDConfig(
        n_iterations=2, eta=0.1, lam=0.0, mini_batch_fraction=0.01,
        seed=42, eval_test=False, sampler="fused_gather")
    d = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=rep)
    done = ssgd.make_train_fn_fused(mesh, cfg, meta).lower(
        jax.ShapeDtypeStruct((5248, 4120, 128), jnp.int32,
                             sharding=NamedSharding(
                                 mesh, P("data", None, None))),
        d, d, d, d,
        jax.ShapeDtypeStruct((129759 * 128,), jnp.float32, sharding=rep),
        t0=0).compile()
    text = done.as_text()
    for kernel in ("_pairs_gather_kernel", "_pairs_scatter_kernel"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    # the only scatters left are the row sums' (2048 numbers a block)
    assert " sort(" not in text
    for line in text.splitlines():
        if " scatter(" in line:
            assert "tda.ssgd.rowsum/" in line, line[:300]
    assert done.memory_analysis().temp_size_in_bytes < 256 << 20
