"""SSGD over rows of (feature, value) pairs: ``models/ssgd.py``'s third
row format, ``pairs`` (``ops/pairs.py`` documents the block layout, the
packing rule and the two passes).

What is here is what the format adds: the loader of a seeded table of
ragged rows (``datasets.ragged_pair_rows`` laid out block by block on
the device), the local gradient of a step, the held-out score. The
scan, the draw of blocks, the update, the psum and the refusals are
``models/ssgd.py``'s: ``ssgd.make_train_fn_fused`` hands a ``meta``
whose ``row_format`` is ``pairs`` to :func:`make_train_fn`, as it hands
a hashed or an indexed one to its own builder.

A block holds whole rows, so the rows of a sampled block are data: a
step's divisor is the count of valid rows in the blocks it drew, added
up on the device (and over the shards), not a number the geometry
knows.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tpu_distalg.models import ssgd
from tpu_distalg.ops import pairs, sampling
from tpu_distalg.parallel import (
    DATA_AXIS,
    data_parallel,
    get_mesh,
    mesh_on_tpu,
    partition,
    tree_allreduce_sum,
)
from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import names
from tpu_distalg.utils import metrics, prng

ROW_FORMAT = "pairs"
BIAS_BLOCKS = 64           # blocks of the stream the label's bias is set on
HELDOUT_BLOCKS = 64        # ... and of the stream that is scored
HELDOUT_OFFSET = 1 << 20   # where that stream starts past the table's rows
FILL_BLOCKS = 8            # blocks the loader draws at a time, at most
STEP_BLOCKS = {"xla": 4, "vmem": 16}
#                          sampled blocks a trip of a step's loop takes, at
#                          most, by the passes' form: their per-slot sums
#                          are finished in a vector of their own before
#                          they are added to the step's. A slot that most
#                          rows hold then gets a trip's addends, not a
#                          step's, one after another in float32: at
#                          webspam's shape on the chip the ``vmem`` form's
#                          whole step in one call read ``w_rel_err`` up to
#                          1.6e-4 of a limit of 3e-4, four trips of 13
#                          blocks 5.8e-5 at most on 14 readings, for 2.5 ms
#                          of a step of 82 (a copy of the vector in and of
#                          the sums out a trip; PR 56). The ``xla`` form's
#                          temporaries are a trip's too


def trip_blocks(n_sampled: int, form: str) -> int:
    """Blocks a trip: the largest divisor of a step's ``n_sampled`` that
    ``STEP_BLOCKS`` allows the form (webspam's 52: 4 and 13)."""
    return max(d for d in range(1, STEP_BLOCKS[form] + 1)
               if n_sampled % d == 0)


@dataclasses.dataclass(frozen=True)
class PairsSpec:
    """A table of ragged rows: what of a configuration reaches the
    loader. ``n_blocks`` ``None`` holds exactly the blocks the rows
    need (a shape that follows the seed); a number is a static shape
    that every seed shares, and a table that does not fit is refused."""

    n_rows: int
    n_features: int
    length_mu: float
    block_slots: int              # pair slots a block (2^18 at the cell)
    block_rows: int = pairs.BLOCK_ROWS    # row slots a block
    n_blocks: int | None = None
    length_sigma: float = 1.0
    length_min: int = 8
    length_max: int = 1 << 16
    zipf_exponent: float = 1.1
    scatter_a: int = 251
    scatter_c: int = 0
    planted_scale: float = 0.25
    positive_rate: float = 0.6

    def generator(self):
        from tpu_distalg.utils import datasets as dsets

        return dsets.ragged_pair_rows(
            self.n_rows, self.n_features, length_mu=self.length_mu,
            length_sigma=self.length_sigma, length_min=self.length_min,
            length_max=self.length_max, zipf_exponent=self.zipf_exponent,
            scatter_a=self.scatter_a, scatter_c=self.scatter_c,
            planted_scale=self.planted_scale,
            positive_rate=self.positive_rate)


def length_mu_for(n_rows: int, mean_pairs: float, *, sigma: float = 1.0,
                  length_min: int = 8, length_max: int = 1 << 16) -> float:
    """The ``length_mu`` under which ``n_rows`` quantiles of the clipped
    log-normal average ``mean_pairs`` (host, float64: how a
    configuration's ``length_mu`` is found from its source's mean; the
    file, like ``tda ssgd --length-mu``, states the number itself)."""
    from statistics import NormalDist

    q = (np.arange(min(n_rows, 1 << 16)) + 0.5) / min(n_rows, 1 << 16)
    z = np.asarray([NormalDist().inv_cdf(float(x)) for x in q])

    def mean(mu):
        return np.clip(np.round(np.exp(mu + sigma * z)), length_min,
                       length_max).mean()

    lo, hi = math.log(max(length_min, 1)) - 4.0, math.log(length_max) + 4.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        lo, hi = (lo, mid) if mean(mid) > mean_pairs else (mid, hi)
    return float(np.float32(0.5 * (lo + hi)))


def geometry(meta: dict) -> pairs.PairsGeometry:
    """The table's geometry; ``on_tpu`` is what the loader's mesh said
    (a ``meta`` that does not say lies on no TPU)."""
    return pairs.PairsGeometry(
        n_features=meta["n_features"], block_slots=meta["block_slots"],
        block_rows=meta["block_rows"], n_blocks=meta["n_blocks"],
        on_tpu=meta.get("on_tpu", False))


def blocks_geometry(config: ssgd.SSGDConfig, meta: dict, n_shards: int):
    """(blocks a shard, blocks sampled a shard and step): the grid of
    ``ssgd.fused_gather_geometry`` over blocks of pair slots."""
    if meta["n_blocks"] % n_shards:
        raise ValueError(
            f"{meta['n_blocks']} blocks do not divide over {n_shards} "
            f"data shards")
    n_blocks = meta["n_blocks"] // n_shards
    n_sampled = max(1, round(config.mini_batch_fraction * n_blocks))
    ssgd.warn_quantized_fraction(
        "fused_gather", n_blocks, n_sampled, config.mini_batch_fraction,
        "lower block_slots for a finer grid")
    return n_blocks, n_sampled


def fields(meta: dict) -> dict:
    """What the spans of a pairs run say (``tda report`` prints it)."""
    geom = geometry(meta)
    return {"row_format": ROW_FORMAT, "table_bytes": 4 * geom.n_slots,
            "gather_form": geom.pass_form, "scatter_form": geom.pass_form,
            "rowsum_form": "vectors",
            "pairs_rows": meta["n_rows"], "pairs": meta["n_pairs"],
            "pair_slots": meta["slots_held"],
            "padding_share": round(
                meta["slots_held"] / max(meta["n_pairs"], 1), 6),
            "longest_row": meta["longest_row"],
            "pair_blocks": meta["n_blocks"],
            "pair_blocks_used": meta["blocks_used"],
            "pair_block_slots": meta["block_slots"]}


def describe_forms(meta: dict) -> str:
    geom = geometry(meta)
    where = {"vmem": "in VMEM for a pass", "xla": "in HBM"}[geom.pass_form]
    return (f"row format pairs: {geom.n_slots} weights "
            f"({4 * geom.n_slots / 1e6:.1f} MB) {where}, "
            f"{meta['n_rows']} rows of {meta['n_pairs']} pairs (longest "
            f"{meta['longest_row']}) in {meta['blocks_used']} of "
            f"{meta['n_blocks']} blocks of {meta['block_slots']} slots "
            f"({(1 - meta['n_pairs'] / max(meta['slots_held'], 1)) * 100:.2f}"
            f"% of the slots held hold no pair), gather pass "
            f"{geom.pass_form}, row sums by vectors of {pairs.LANES}, "
            f"scatter pass {geom.pass_form}")


def make_train_fn(mesh: Mesh, config: ssgd.SSGDConfig, meta: dict):
    """``ssgd.make_train_fn_fused`` for a pairs ``meta``: the same scan
    (``fn(X, dummy, dummy, dummy, dummy, w0, t0=, acc0=)``), draw,
    update and psum; the local gradient is ``ops/pairs.py``'s two
    passes and the count of the sampled blocks' valid rows. The carried
    ``w`` is ``f32[geom.w_len]``: weights, bias, zeros."""
    from jax import lax

    ssgd._check_hashed_config(config, ROW_FORMAT)
    geom = geometry(meta)
    n_shards = mesh.shape[DATA_AXIS]
    n_blocks, n_sampled = blocks_geometry(config, meta, n_shards)
    key = prng.root_key(config.seed)

    def prep_xs(ts):
        with jax.named_scope(names.SSGD_DRAW):
            return jax.vmap(
                lambda t: sampling.sample_block_ids(
                    jax.random.fold_in(key, t),
                    n_shards, n_blocks, n_sampled))(ts)     # (T, S, ns)

    per = trip_blocks(n_sampled, geom.pass_form)

    def _local_grad(X, w, idx_shards):
        shard = lax.axis_index(DATA_AXIS)
        ids = lax.dynamic_index_in_dim(idx_shards, shard, keepdims=False)

        def some(carry, ids):
            g, cnt = carry
            with jax.named_scope(names.SSGD_GATHER):
                m = pairs.margins(X, w, ids, geom)
                y, valid = pairs.labels(X, ids, geom)
                r = (jax.nn.sigmoid(m) - y) * valid
            with jax.named_scope(names.SSGD_SCATTER):
                # finished before it is added: XLA would else scatter
                # into the running vector, a step's addends in a row
                part = lax.optimization_barrier(
                    pairs.slot_sums(X, r, ids, geom))
                return (g + part, cnt + jnp.sum(valid)), None

        (g, cnt), _ = lax.scan(
            some, (jnp.zeros((geom.w_len,), jnp.float32), jnp.float32(0)),
            ids.reshape(n_sampled // per, per))
        with jax.named_scope(names.SSGD_SYNC):
            return tree_allreduce_sum((g, cnt))

    grad_fn = data_parallel(
        _local_grad, mesh,
        in_specs=(P("data", None, None), P(), P()),
        out_specs=(P(), P()))

    def sample_and_grad(X, y, valid, w, x):
        del y, valid                 # labels and validity ride in X
        return grad_fn(X, w, x)

    return ssgd._build_scan(dataclasses.replace(config, eval_test=False),
                            sample_and_grad, prep_xs=prep_xs)


# ---- the loader ----------------------------------------------------------

def _block_fn(spec: PairsSpec, geom: pairs.PairsGeometry, row0: int):
    """``block(seed, bias, lengths, start, count)``: one block of the
    stream of rows that starts at row id ``row0`` (``lengths`` are that
    stream's, padded by ``block_rows``): its rows ``start .. start +
    count`` laid out as ``ops/pairs.py`` says, and the rows' planted
    scores and coins."""
    gen = spec.generator()
    R, V, L = geom.block_rows, geom.vectors, pairs.LANES

    def pad(x, rows, fill=0):
        return jnp.pad(x, (0, rows * L - x.shape[0]),
                       constant_values=fill).reshape(rows, L)

    def block(seed, bias, lengths, start, count):
        k = jnp.arange(R, dtype=jnp.int32)
        lens = jax.lax.dynamic_slice(lengths, (start - row0,), (R,))
        lens = jnp.where(k < count, lens, 0)
        took = (lens + (L - 1)) // L
        vend = jnp.cumsum(took)
        v = jnp.arange(V, dtype=jnp.int32)
        # the first row whose vectors end past v
        vrow = jnp.sum((vend[None, :] <= v[:, None]).astype(jnp.int32),
                       axis=1)
        used = v < vend[R - 1]
        vrow = jnp.where(used, jnp.minimum(vrow, R - 1), 0)
        place = ((v - (vend - took)[vrow]) * L)[:, None] \
            + jnp.arange(L, dtype=jnp.int32)[None, :]
        live = used[:, None] & (place < lens[vrow][:, None])
        ids, raw = gen.pairs((start + vrow)[:, None], place, seed)
        ids = jnp.where(live, ids, 0)
        raw = jnp.where(live, raw, 0)
        # integer sums: a row's labels do not depend on their order
        squares = jax.ops.segment_sum(
            jnp.sum(raw * raw, axis=1), vrow, num_segments=R)
        weighted = jax.ops.segment_sum(
            jnp.sum(gen.planted(ids, seed) * raw, axis=1), vrow,
            num_segments=R)
        val = jnp.where(
            live, gen.unit_values(raw, squares[vrow][:, None]), 0.0)
        z = gen.scores(weighted, squares)
        coin = gen.coins(start + k, seed)
        lab = jnp.where(k < count, gen.labels(z, coin, bias), pairs.NO_ROW)
        held = jnp.concatenate([
            ids, jax.lax.bitcast_convert_type(val, jnp.int32),
            pad(vrow, geom.vector_rows),
            pad(lab, geom.label_rows, pairs.NO_ROW),
            pad(lens, geom.label_rows)], axis=0)
        held = jnp.pad(held, ((0, geom.held_rows - held.shape[0]), (0, 0)))
        return held, z, coin, k < count

    return block


@functools.lru_cache(maxsize=8)
def table_fn(mesh: Mesh, spec: PairsSpec, geom: pairs.PairsGeometry):
    """The compiled loader of one geometry: ``f(seed, bias, lengths,
    starts, counts) -> X``; the seed and the blocks' rows are its
    arguments, so a second seed costs no compile."""
    n_local = geom.n_blocks // mesh.shape[DATA_AXIS]
    per = math.gcd(n_local, FILL_BLOCKS)
    block = _block_fn(spec, geom, 0)

    def table(seed, bias, lengths, starts, counts):
        def some(at):
            s = jax.lax.dynamic_slice(starts, (at * per,), (per,))
            c = jax.lax.dynamic_slice(counts, (at * per,), (per,))
            return jax.vmap(
                lambda s, c: block(seed, bias, lengths, s, c)[0])(s, c)

        return jax.lax.map(some, jnp.arange(n_local // per)).reshape(
            n_local, geom.held_rows, pairs.LANES)

    return jax.jit(
        jax.shard_map(table, mesh=mesh,
                      in_specs=(P(), P(), P(), P(DATA_AXIS), P(DATA_AXIS)),
                      out_specs=P(DATA_AXIS, None, None)),
        out_shardings=partition.leaf_sharding("ssgd", "X", mesh))


@functools.lru_cache(maxsize=8)
def stream_fn(spec: PairsSpec, geom: pairs.PairsGeometry, row0: int):
    """``f(seed, bias, lengths, starts, counts) -> (X, z, coin, live)``
    of the blocks of a stream of rows past the table (the bias set, the
    held-out rows): small, one device, a block at a time."""
    block = _block_fn(spec, geom, row0)

    def stream(seed, bias, lengths, starts, counts):
        return jax.lax.map(
            lambda sc: block(seed, bias, lengths, sc[0], sc[1]),
            (starts, counts))

    return jax.jit(stream)


def _blocks_of(cuts, n_blocks: int, row0: int):
    """``(starts, counts, used)`` from ``pairs.pack_rows``' cuts: int32
    row ids and row counts of ``n_blocks`` blocks of a stream that
    starts at row ``row0`` (the blocks past the last row empty), and how
    many hold rows."""
    used = min(len(cuts) - 1, n_blocks)
    starts = np.full((n_blocks,), row0 + cuts[used], np.int64)
    counts = np.zeros((n_blocks,), np.int64)
    starts[:used] = row0 + cuts[:used]
    counts[:used] = np.diff(cuts)[:used]
    return starts.astype(np.int32), counts.astype(np.int32), used


@functools.lru_cache(maxsize=16)
def lengths_fn(spec: PairsSpec, row0: int, n: int):
    """``f(seed) -> int32[n]``: the lengths of rows ``row0 .. row0 + n``."""
    gen = spec.generator()

    def lengths(seed):
        return gen.lengths(row0 + jnp.arange(n), seed)

    return jax.jit(lengths)


@functools.lru_cache(maxsize=8)
def _bias_fn(spec: PairsSpec):
    gen = spec.generator()

    def set_bias(z, coin, live):
        return gen.set_bias(z.reshape(-1), coin.reshape(-1),
                            live.reshape(-1))

    return jax.jit(set_bias)


def _stream(spec: PairsSpec, geom, row0: int, n_blocks: int, seed, bias):
    """A stream's first ``n_blocks`` blocks: ``(X, z, coin, live)``."""
    n = n_blocks * geom.block_rows
    lengths = lengths_fn(spec, row0, n + geom.block_rows)(seed)
    starts, counts, _ = _blocks_of(
        pairs.pack_rows(np.asarray(lengths)[:n], geom.block_slots,
                        geom.block_rows), n_blocks, row0)
    return stream_fn(spec, geom, row0)(
        seed, np.float32(bias), lengths, starts, counts)


def planted_bias(spec: PairsSpec, geom, seed):
    """The bias under which ``positive_rate`` of the rows of the first
    ``BIAS_BLOCKS`` blocks past the table are positive."""
    if BIAS_BLOCKS * geom.block_rows > HELDOUT_OFFSET:
        raise ValueError(f"block_rows={geom.block_rows}: the bias's "
                         f"stream would reach the held-out rows")
    _, z, coin, live = _stream(spec, geom, spec.n_rows, BIAS_BLOCKS, seed,
                               0.0)
    return _bias_fn(spec)(z, coin, live)


def build_table(spec: PairsSpec, mesh: Mesh, *, data_seed: int = 0):
    """The loader of ragged rows: ``spec.n_rows`` seeded rows of
    (feature, value) pairs made ON DEVICE, shard by shard, as
    ``int32[n_blocks, held_rows, 128]`` (``ops/pairs.py``), and the
    ``meta`` that states the format. The rows' lengths come to the host
    (4 B a row) to be packed into blocks by the rule; the pairs never
    do. Returns ``(X, meta)``."""
    n_shards = mesh.shape[DATA_AXIS]
    devices = mesh.local_devices
    on_tpu = mesh_on_tpu(mesh)
    seed = np.int32(data_seed)
    with tevents.span("ssgd:prepare", devices, rows=spec.n_rows,
                      row_format=ROW_FORMAT):
        with tevents.span("ssgd:pack_pairs", devices, rows=spec.n_rows):
            lengths = lengths_fn(
                spec, 0, spec.n_rows + spec.block_rows)(seed)
            host = np.asarray(lengths)[:spec.n_rows]
            cuts = pairs.pack_rows(host, spec.block_slots, spec.block_rows)
            need = len(cuts) - 1
            n_blocks = spec.n_blocks if spec.n_blocks is not None \
                else need + (-need) % n_shards
            n_pairs = int(host.sum(dtype=np.int64))
            if need > n_blocks or n_blocks % n_shards:
                raise ValueError(
                    f"pairs table: {spec.n_rows} rows of {n_pairs} pairs "
                    f"need {need} blocks of {spec.block_slots} slots; "
                    f"n_blocks={n_blocks} over {n_shards} shard(s) does "
                    f"not hold them (no row is dropped or cut)")
            geom = pairs.PairsGeometry(spec.n_features, spec.block_slots,
                                       spec.block_rows, n_blocks, on_tpu)
            starts, counts, used = _blocks_of(cuts, n_blocks, 0)
            ends = np.concatenate([[0], np.cumsum(host, dtype=np.int64)])
            meta = dict(
                row_format=ROW_FORMAT, pack=1, n_rows=spec.n_rows,
                n_features=spec.n_features, n_slots=geom.n_slots,
                d_total=geom.w_len, n_blocks=n_blocks,
                block_slots=spec.block_slots, block_rows=spec.block_rows,
                blocks_used=used, n_pairs=n_pairs,
                slots_held=n_blocks * spec.block_slots,
                longest_row=int(host.max()) if host.size else 0,
                block_starts=starts, block_counts=counts,
                block_pairs=ends[starts + counts] - ends[starts],
                spec=spec, on_tpu=on_tpu)
            tevents.current().fields.update(
                blocks=n_blocks, blocks_used=used, pairs=n_pairs)
        for name, n in (("rows", spec.n_rows), ("pairs", n_pairs),
                        ("slots", meta["slots_held"]),
                        ("padding_slots", meta["slots_held"] - n_pairs),
                        ("longest_row", meta["longest_row"]),
                        ("blocks", n_blocks), ("blocks_used", used)):
            tevents.counter("ssgd.pairs_" + name, n)
        tevents.current().fields.update(
            bytes=n_blocks * geom.block_bytes, **fields(meta))
        with tevents.span("ssgd:generate", devices, rows=spec.n_rows,
                          pairs=n_pairs):
            bias = planted_bias(spec, geom, seed)
            X = table_fn(mesh, spec, geom)(seed, bias, lengths, starts,
                                           counts)
            if geom.pass_form == "vmem":
                # the kernels' module (Pallas' first import: a second on
                # the chip's host) while the device fills the table
                from tpu_distalg.ops import pallas_pairs  # noqa: F401
            X.block_until_ready()
            meta["bias"] = float(bias)
            tevents.current().fields["bytes"] = metrics.nbytes(X)
    return X, meta


def prepare_synthetic(spec: PairsSpec, mesh: Mesh, config: ssgd.SSGDConfig,
                      *, data_seed: int = 0):
    """``ssgd.prepare_hashed_synthetic`` for ragged rows: ``(fn, X, w0,
    meta)``, the weights zero as the source's."""
    ssgd._check_hashed_config(config, ROW_FORMAT)
    X, meta = build_table(spec, mesh, data_seed=data_seed)
    w0 = partition.put(jnp.zeros((meta["d_total"],), jnp.float32), "w",
                       "ssgd", mesh)
    return ssgd.make_train_fn_fused(mesh, config, meta), X, w0, meta


def evaluate(w, meta: dict, *, data_seed: int = 0,
             n_blocks: int = HELDOUT_BLOCKS):
    """``(accuracy, log-loss)`` of the model vector ``w`` on the rows of
    the first ``n_blocks`` blocks of a stream the table does not hold
    (row ids from ``n_rows + HELDOUT_OFFSET``), float32."""
    spec, geom = meta["spec"], geometry(meta)
    X, _, _, _ = _stream(spec, geom, spec.n_rows + HELDOUT_OFFSET,
                         n_blocks, np.int32(data_seed), meta["bias"])
    # one device's program: a mesh's replicated weights go where the
    # stream lies (a Mosaic kernel under a plain jit is not partitioned):
    # the table's rule for ``w`` on the mesh of that one device
    w = partition.put(jnp.asarray(w, jnp.float32), "w", "ssgd",
                      get_mesh(data=1, devices=list(X.devices())))
    return _score_fn(geom, n_blocks)(X, w)


@functools.lru_cache(maxsize=8)
def _score_fn(geom: pairs.PairsGeometry, n_blocks: int):
    @jax.jit
    def score(X, w):
        ids = jnp.arange(n_blocks)
        m = pairs.margins(X, w, ids, geom)
        y, valid = pairs.labels(X, ids, geom)
        n = jnp.maximum(jnp.sum(valid), 1.0)
        loss = jnp.sum((jax.nn.softplus(m) - y * m) * valid) / n
        hit = ((m > 0) == (y > 0.5)).astype(jnp.float32) * valid
        return jnp.sum(hit) / n, loss

    return lambda X, w: tuple(map(float, score(X, w)))


def train(spec: PairsSpec, mesh: Mesh, config: ssgd.SSGDConfig, *,
          data_seed: int = 0, checkpoint_dir: str | None = None,
          checkpoint_every: int = 500) -> ssgd.HashedResult:
    """End-to-end training on ragged rows (``tda ssgd --row-format
    pairs``): the loader's table, the block-sampled BSP trainer,
    held-out rows scored at the end; checkpointed and resumable like
    ``ssgd.train_hashed``."""
    fn, X, w0, meta = prepare_synthetic(spec, mesh, config,
                                        data_seed=data_seed)
    w, accs = ssgd.run_index_rows(
        fn, X, w0, meta, mesh, config, fields(meta),
        tag=f"ssgd:pairs:{spec.n_rows}x{spec.n_features}",
        what="SSGD (pairs) weights", checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every)
    with tevents.span("ssgd:heldout"):
        acc, loss = evaluate(w, meta, data_seed=data_seed)
    return ssgd.HashedResult(w=jnp.asarray(w), accs=jnp.asarray(accs),
                             heldout_acc=acc, heldout_log_loss=loss,
                             forms=describe_forms(meta))
