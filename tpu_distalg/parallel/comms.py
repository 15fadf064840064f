"""Communication-efficient collectives — the instrumented comms layer.

The reference's entire aggregation story is Spark's ``treeAggregate`` +
``broadcast``; our original replacement was a naive per-leaf ``lax.psum``
(``collectives.tree_allreduce_sum``) — full-precision, unbucketed,
unoverlapped gradient traffic on every sync round of every SGD-family
trainer. This module is the single choke point that traffic now routes
through: a :class:`CommSpec`-driven schedule selected per run, with
per-sync wire-byte accounting so the artifact can finally say how many
bytes a trainer moved.

Schedules (all deterministic and bitwise-replayable — fixed reduction
order, counter-based PRNG only):

  ``dense``     today's fused psum per leaf, bitwise-identical to
                ``tree_allreduce_sum`` — the default.
  ``bucketed``  the pytree is flattened into fixed-size buckets; each
                bucket is reduced by a ``ppermute``-chunk ring
                (reduce-scatter + all-gather, the ``ring.py``
                ``fori_loop`` idiom), scanned bucket-by-bucket so the
                collective of bucket *b* overlaps the unpacking compute
                of bucket *b−1* (cf. the chunked, topology-aware
                schedules of arXiv:2112.01075).
  ``hier``      hierarchical: ring reduce-scatter INSIDE each group
                (the intra-host/ICI axis), a cross-group ring of the
                owned chunk (the DCN axis — 1/m of the payload crosses
                the slow links), then an intra-group all-gather.
                Groups come from the mesh's hybrid layout
                (``slice_index``/``process_index`` of the data-axis
                devices) or from ``hier_groups``.
  ``bf16``      cast to bfloat16 on the wire, one psum, cast back —
                half the bytes, the standard gradient-compression
                baseline (a bf16 psum really moves bf16).
  ``int8``      the NATIVE compressed ring (round 11 closed PR 5's
                int32-psum caveat): per bucket, seeded STOCHASTIC
                rounding to int8 against a pmax-shared scale, an
                ``all_to_all`` chunk scatter that puts int8 on the
                wire, EXACT int32 accumulation of the integer
                contributions at the chunk owner (order-free, so
                deterministic for free), a second seeded stochastic
                requantization of the reduced chunk (scale ``n·s`` —
                the integer sum is bounded by ``127·n``), and an int8
                ppermute ring all-gather. Both phases move int8, so
                the ~4x wire reduction is ON the wire, not in the
                accounting. Unbiased in expectation,
                bitwise-replayable: rounding noise is
                threefry(seed, step, shard, bucket·stage).
  ``topk``      top-k sparsification with ERROR FEEDBACK: each shard
                keeps the k largest-|.| entries of (gradient +
                residual), combines only those via
                :func:`sparse_allreduce` (the generalized ring
                all-gather of (value, index) pairs), and carries the
                unsent remainder in the scan state so nothing is ever
                lost — the sparse-allreduce construction of
                arXiv:1312.3020 with the EF-SGD residual correction
                that preserves convergence.

Overlap (round 11): the bucketed flat-vector schedules (``bucketed``,
``int8``) run their buckets through a DOUBLE-BUFFERED software
pipeline — the collective chain of bucket *b* is launched while bucket
*b−1*'s unpack/dequantize compute finishes, so XLA's latency-hiding
scheduler can hide the wire time behind the math instead of running
them back to back (cf. the chunked, portable collective schedules of
arXiv:2112.01075). On by default; spell ``<schedule>@seq`` to force the
sequential exchange (the pipeline and the sequential loop are
bitwise-identical — same per-bucket math, different interleaving — so
``@seq`` exists for A/B timing, not for correctness). ``hier`` rides
the same code path but always as ONE bucket, and ``topk``'s pair
exchange is its own single in-flight buffer — ``@seq`` is accepted on
both and is a no-op by construction. ``reduce`` also
takes a ``compute=`` thunk of trainer math that is independent of the
sync (e.g. the regularization gradient); it is evaluated next to the
first in-flight bucket so the scheduler can hide the exchange behind
it. The pipeline drains inside every sync, so the only cross-step comm
state remains the error-feedback residual — which rides the scan carry
and the checkpoint exactly as before (a resume mid-schedule is bitwise).

Compression applies to float leaves with more than one element; scalars
and integer leaves (step counts, minibatch counts) always go dense — a
compressed count would corrupt the update denominators for no
measurable byte win.

Byte accounting (:meth:`CommSync.stats`): ``bytes_wire`` is the
per-shard payload that crosses the interconnect per sync under a
bandwidth-optimal ring at the schedule's wire precision
(``2·B·(n−1)/n`` for an allreduce of B bytes); ``bytes_logical`` is the
f32 payload the sync logically reduces. Trainers multiply by the sync
count and bump the ``comm.bytes_wire`` / ``comm.bytes_logical`` /
``comm.rounds`` telemetry counters, so ``tda report`` shows the
compression ratio actually achieved.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

#: mirror of ``parallel.mesh.DATA_AXIS`` — deliberately NOT imported:
#: mesh.py imports jax at module level, and the cluster tier's
#: jax-free host processes (coordinator, transport-only tools) import
#: this module for the HOST-SIDE CODECS below; the device schedules
#: keep importing jax lazily inside their functions as before
DATA_AXIS = "data"

SCHEDULES = ("dense", "bucketed", "hier", "bf16", "int8", "topk")

#: float leaves with more elements than this are compressed; at or
#: below it (and for every integer leaf) the schedule falls back to a
#: dense psum — the (grad, count) pairs every trainer syncs keep their
#: count exact.
MIN_COMPRESS_ELEMS = 1


def psum(x, axis_name: str = DATA_AXIS):
    """The blessed raw psum — same op as ``lax.psum``, imported from
    the comms layer so ``tda lint`` (TDA050) can keep every cross-shard
    reduction in ``models/`` behind this instrumentable choke point."""
    from jax import lax

    return lax.psum(x, axis_name)


def pmean(x, axis_name: str = DATA_AXIS):
    """Blessed raw pmean (see :func:`psum`)."""
    from jax import lax

    return lax.pmean(x, axis_name)


def pmax(x, axis_name: str = DATA_AXIS):
    """Blessed raw pmax (see :func:`psum`)."""
    from jax import lax

    return lax.pmax(x, axis_name)


def pmin(x, axis_name: str = DATA_AXIS):
    """Blessed raw pmin (see :func:`psum`)."""
    from jax import lax

    return lax.pmin(x, axis_name)


@dataclasses.dataclass(frozen=True)
class CommSpec:
    """One run's aggregation schedule + knobs.

    ``parse`` accepts the CLI spelling: a schedule name with an
    optional ``:arg`` — ``topk:0.01`` (kept fraction), ``bucketed:65536``
    (elements per bucket), ``hier:2`` (group count; 0 = infer from the
    mesh topology), ``int8:7`` (stochastic-rounding seed;
    ``int8:7:4096`` also sets the overlap-bucket element count) — plus
    an optional ``@seq`` suffix that disables the double-buffered
    bucket-overlap pipeline (``int8@seq``, ``topk:0.05@seq``).
    Overlapped and sequential schedules are bitwise-identical; ``@seq``
    is the A/B-timing spelling.
    """

    schedule: str = "dense"
    bucket_elems: int = 1 << 16      # 'bucketed'/'int8': elems/bucket
    topk_fraction: float = 0.01      # 'topk': fraction of entries kept
    hier_groups: int = 0             # 'hier': 0 = infer from topology
    seed: int = 0                    # 'int8': stochastic-rounding seed
    overlap: bool = True             # double-buffered bucket pipeline

    def __post_init__(self):
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"unknown comm schedule {self.schedule!r}; want one of "
                f"{', '.join(SCHEDULES)}")
        if not (0.0 < self.topk_fraction <= 1.0):
            raise ValueError(
                f"topk_fraction must be in (0, 1], got "
                f"{self.topk_fraction}")
        if self.bucket_elems < 1:
            raise ValueError(
                f"bucket_elems must be >= 1, got {self.bucket_elems}")

    @classmethod
    def parse(cls, text: str | "CommSpec" | None) -> "CommSpec":
        if isinstance(text, cls):
            return text
        if not text:
            return cls()
        text = str(text)
        kw = {}
        if text.endswith("@seq"):
            text, kw["overlap"] = text[: -len("@seq")], False
        elif text.endswith("@ov"):
            text = text[: -len("@ov")]  # explicit spelling of default
        name, _, arg = text.partition(":")
        if arg:
            if name == "topk":
                kw["topk_fraction"] = float(arg)
            elif name == "bucketed":
                kw["bucket_elems"] = int(arg)
            elif name == "hier":
                kw["hier_groups"] = int(arg)
            elif name == "int8":
                seed, _, bucket = arg.partition(":")
                kw["seed"] = int(seed)
                if bucket:
                    kw["bucket_elems"] = int(bucket)
            else:
                raise ValueError(
                    f"comm schedule {name!r} takes no argument "
                    f"(got {text!r})")
        return cls(schedule=name, **kw)

    @property
    def stateful(self) -> bool:
        """Whether the schedule carries error-feedback residuals."""
        return self.schedule == "topk"


def infer_groups(mesh, axis_name: str = DATA_AXIS) -> int:
    """Group count for the hierarchical schedule, off the mesh's hybrid
    layout: the number of distinct slices (TPU multi-slice DCN
    boundary) or host processes among the data-axis devices. Falls back
    to 2 when the topology is flat but even (so CPU-emulated meshes
    still exercise both levels), else 1 (plain ring)."""
    axis = list(mesh.axis_names).index(axis_name)
    n = mesh.devices.shape[axis]
    # one representative device per data-axis coordinate
    devs = np.moveaxis(mesh.devices, axis, 0).reshape(n, -1)[:, 0]
    for attr in ("slice_index", "process_index"):
        marks = [getattr(d, attr, 0) or 0 for d in devs]
        g = len(set(marks))
        if 1 < g < n and n % g == 0:
            return g
    return 2 if n % 2 == 0 and n > 2 else 1


def _eligible(leaf) -> bool:
    """Compressible: a float leaf with more than MIN_COMPRESS_ELEMS
    elements (works on arrays and ShapeDtypeStructs)."""
    dt = np.dtype(leaf.dtype)
    return (dt.kind == "f"
            and int(np.prod(leaf.shape)) > MIN_COMPRESS_ELEMS)


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def _ring_allgather(buf, axis_name: str, n: int):
    """Origin-placed ring all-gather of one per-shard buffer (or a
    pytree of them): ``n−1`` ``ppermute`` hops of ``buf``-sized
    messages (the wire carries each leaf's own dtype); each leaf comes
    back ``(n, *leaf.shape)`` with row *j* = shard *j*'s buffer,
    bitwise-identical on every shard. All leaves hop inside the SAME
    fori_loop, so a pair exchange (topk's value+index buffers) pays
    ``n−1`` hop latencies, not ``2(n−1)`` back-to-back loops. The ONE
    ring-gather implementation — the sparse pair exchange and the
    native int8 ring both ride it, so a hop-ordering fix can never
    land in one and not the other (the bug class PR 5's review caught
    in the hier schedule)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    my = lax.axis_index(axis_name)
    perm = _ring_perm(n)
    acc0 = jax.tree.map(
        lambda b: lax.dynamic_update_index_in_dim(
            jnp.zeros((n,) + b.shape, b.dtype), b, my, 0), buf)

    def hop(s, carry):
        b, acc = carry
        b = jax.tree.map(
            lambda x: lax.ppermute(x, axis_name, perm), b)
        src = (my - s - 1) % n
        acc = jax.tree.map(
            lambda a, x: lax.dynamic_update_index_in_dim(a, x, src, 0),
            acc, b)
        return b, acc

    _, acc = lax.fori_loop(0, n - 1, hop, (buf, acc0))
    return acc


#: public name for the origin-placed ring all-gather: the serving
#: layer's sharded top-k candidate merge rides the SAME pair exchange
#: the topk gradient schedule and sparse_allreduce do (each shard
#: contributes its k (value, index) pairs — ``8k(n−1)`` wire bytes per
#: sync instead of an O(length) dense gather), so a hop-ordering fix
#: can never land in one rider and not another
ring_allgather = _ring_allgather


def _ring_allreduce(v, axis_name: str, n: int):
    """Bandwidth-optimal ring allreduce of a flat ``(n·chunk,)`` f32
    vector: n−1 reduce-scatter steps then n−1 all-gather steps, all
    ``ppermute`` chunk rotations (the ``ring.py`` fori_loop idiom).
    Deterministic: the accumulation order around the ring is fixed."""
    import jax.numpy as jnp
    from jax import lax

    if n == 1:
        return v
    my = lax.axis_index(axis_name)
    chunk = v.shape[0] // n
    blocks = v.reshape(n, chunk)
    perm = _ring_perm(n)

    # reduce-scatter: at step s shard i sends its partial of block
    # (i − s) mod n and accumulates the arriving partial of block
    # (i − s − 1) mod n; after n−1 steps shard i owns the fully
    # reduced block (i + 1) mod n
    def rs(s, blocks):
        send_id = (my - s) % n
        buf = lax.dynamic_index_in_dim(blocks, send_id, keepdims=False)
        buf = lax.ppermute(buf, axis_name, perm)
        recv_id = (my - s - 1) % n
        old = lax.dynamic_index_in_dim(blocks, recv_id, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            blocks, old + buf, recv_id, 0)

    blocks = lax.fori_loop(0, n - 1, rs, blocks)

    # all-gather: rotate the finished blocks around the ring; at step s
    # shard i holds (and forwards) the reduced block owned by shard
    # (i − s) mod n, i.e. block (i − s + 1) mod n
    own_id = (my + 1) % n
    out0 = lax.dynamic_update_index_in_dim(
        jnp.zeros_like(blocks),
        lax.dynamic_index_in_dim(blocks, own_id, keepdims=False),
        own_id, 0)

    def ag(s, carry):
        buf, out = carry
        buf = lax.ppermute(buf, axis_name, perm)
        blk_id = (my - s) % n  # arrived from shard (i−s−1): its block
        out = lax.dynamic_update_index_in_dim(out, buf, blk_id, 0)
        return buf, out

    buf0 = lax.dynamic_index_in_dim(blocks, own_id, keepdims=False)
    _, out = lax.fori_loop(0, n - 1, ag, (buf0, out0))
    return out.reshape(-1)


def _hier_allreduce(v, axis_name: str, n: int, g: int):
    """Two-level allreduce of a flat ``(m·chunk,)`` vector over ``g``
    groups of ``m = n/g`` shards: intra-group ring reduce-scatter (the
    fast/ICI links carry the full payload), a cross-group ring of the
    owned chunk (only 1/m of the payload crosses the slow/DCN links),
    then an intra-group all-gather."""
    import jax.numpy as jnp
    from jax import lax

    m = n // g
    if m == 1 or g == 1:
        # no intra-group phase: the caller padded v to a multiple of n
        # for exactly this flat-ring fallback
        return _ring_allreduce(v, axis_name, n)
    my = lax.axis_index(axis_name)
    grp, loc = my // m, my % m
    chunk = v.shape[0] // m
    blocks = v.reshape(m, chunk)
    # intra-group ring: i → (same group, local+1)
    perm_in = [(G * m + L, G * m + (L + 1) % m)
               for G in range(g) for L in range(m)]
    # cross-group ring between same-local shards: i → (group+1, local)
    perm_x = [(G * m + L, ((G + 1) % g) * m + L)
              for G in range(g) for L in range(m)]

    def rs(s, blocks):
        send_id = (loc - s) % m
        buf = lax.dynamic_index_in_dim(blocks, send_id, keepdims=False)
        buf = lax.ppermute(buf, axis_name, perm_in)
        recv_id = (loc - s - 1) % m
        old = lax.dynamic_index_in_dim(blocks, recv_id, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            blocks, old + buf, recv_id, 0)

    blocks = lax.fori_loop(0, m - 1, rs, blocks)
    own_id = (loc + 1) % m
    own = lax.dynamic_index_in_dim(blocks, own_id, keepdims=False)

    # cross-group all-gather of the owned chunk, then ORIGIN-ORDER
    # accumulation (group 0 first): every shard with the same local
    # index owns the SAME block id, and summing the g group-partials
    # in a fixed order keeps the result bitwise-identical on every
    # shard — an accumulate-and-forward would sum in each group's own
    # rotational order and silently de-replicate the output for g >= 3
    # (float addition is not associative; same reason the topk path
    # gathers before accumulating)
    all_c = lax.dynamic_update_index_in_dim(
        jnp.zeros((g,) + own.shape, own.dtype), own, grp, 0)

    def xg(s, carry):
        buf, all_c = carry
        buf = lax.ppermute(buf, axis_name, perm_x)
        src = (grp - s - 1) % g
        all_c = lax.dynamic_update_index_in_dim(all_c, buf, src, 0)
        return buf, all_c

    _, all_c = lax.fori_loop(0, g - 1, xg, (own, all_c))
    own = lax.fori_loop(
        0, g, lambda j, acc: acc + all_c[j], jnp.zeros_like(own))

    # intra-group all-gather of the m finished blocks
    out0 = lax.dynamic_update_index_in_dim(
        jnp.zeros_like(blocks), own, own_id, 0)

    def ag(s, carry):
        buf, out = carry
        buf = lax.ppermute(buf, axis_name, perm_in)
        blk_id = (loc - s) % m
        out = lax.dynamic_update_index_in_dim(out, buf, blk_id, 0)
        return buf, out

    _, out = lax.fori_loop(0, m - 1, ag, (own, out0))
    return out.reshape(-1)


def sparse_allreduce(vals, idx, length: int, *,
                     axis_name: str = DATA_AXIS, n: int | None = None):
    """Sparse-vector allreduce: every shard contributes ``k`` (value,
    index) pairs; returns the dense ``(length,)`` f32 sum, replicated
    bitwise-identically on every shard.

    The exchange is a ring all-gather of the pair buffers — ``n−1``
    ``ppermute`` hops of ``8k`` bytes each, so the bytes crossing the
    interconnect are exactly the sparse payload (a psum of a
    zero-padded dense vector would move full-length f32). Every shard
    then scatter-accumulates the ``n`` contributions in ORIGIN order
    (shard 0 first): float addition is not associative, and per-shard
    arrival order would silently de-replicate the result — this is the
    replicated-output contract psum gives for free, earned without
    psum.

    Generalized out of the top-k gradient schedule (PR 5) so any sparse
    combine can ride it — e.g. power-law rank deltas in graph workloads
    (arXiv:1312.3020 is explicitly about power-law data). Duplicate
    indices within one shard's contribution accumulate additively.
    """
    import jax.numpy as jnp
    from jax import lax

    if n is None:
        n = lax.axis_size(axis_name)
    if n == 1:
        return jnp.zeros((length,), vals.dtype).at[idx].add(vals)
    all_v, all_i = _ring_allgather((vals, idx), axis_name, n)
    return lax.fori_loop(
        0, n,
        lambda j, out: out.at[all_i[j]].add(all_v[j]),
        jnp.zeros((length,), vals.dtype))


def _pipelined_buckets(buckets, exchange, finish, overlap: bool,
                       compute=None):
    """Run ``finish(exchange(bucket_i, i))`` over every bucket.

    ``overlap=True`` is the double-buffered schedule: the scan carry
    holds the in-flight (exchanged-but-unfinished) bucket, so iteration
    *i* launches bucket *i*'s collective chain with no data dependence
    on bucket *i−1*'s ``finish`` compute — XLA's latency-hiding
    scheduler overlaps the two. ``overlap=False`` chains them
    (exchange → finish per bucket). Both orders evaluate the identical
    per-bucket composition, so the outputs are BITWISE equal — the
    pipeline buys wall-clock, never numerics. ``compute`` (optional
    thunk of sync-independent caller math) is evaluated next to the
    first in-flight bucket and its result returned alongside, giving
    the scheduler trainer compute to hide the first exchange behind.
    Returns ``(stacked_outputs, aux)``.
    """
    import jax.numpy as jnp
    from jax import lax

    nb = buckets.shape[0]
    idx = jnp.arange(nb)
    if not overlap:
        aux = compute() if compute is not None else None

        def one(_, x):
            b, i = x
            return None, finish(exchange(b, i))

        _, out = lax.scan(one, None, (buckets, idx))
        return out, aux

    inflight = exchange(buckets[0], idx[0])
    # evaluated AFTER the first exchange is in flight and independent
    # of it — the scheduler may run it under the collective's latency
    aux = compute() if compute is not None else None

    def one(inflight, x):
        b, i = x
        nxt = exchange(b, i)        # bucket i's collective chain ...
        out = finish(inflight)      # ... overlaps bucket i−1's unpack
        return nxt, out

    last, head = lax.scan(one, inflight, (buckets[1:], idx[1:]))
    tail = finish(last)
    return jnp.concatenate([head, tail[None]], axis=0), aux


class CommSync:
    """One sync point's compiled-in schedule: built once per trainer
    from the spec, the mesh and an example pytree (shapes/dtypes), then
    called INSIDE the shard_map body every sync round.

    ``reduce(tree, res, t)`` returns ``(summed_tree, res_new)`` where
    ``res`` is the flat error-feedback residual — shape ``(1, ef_elems)``
    inside the body (the caller shards the ``(n_shards, ef_elems)``
    state over the data axis, exactly like per-replica models), or
    ``None`` for stateless schedules. ``t`` is the absolute sync/step id
    — the int8 stochastic-rounding key folds it in, so segmented
    checkpoint/resume replays identical rounding noise.
    """

    def __init__(self, spec: CommSpec, mesh, example, *,
                 axis_name: str = DATA_AXIS):
        import jax

        self.spec = spec
        self.axis_name = axis_name
        self.n_shards = int(mesh.shape[axis_name])
        self.groups = (spec.hier_groups
                       or infer_groups(mesh, axis_name))
        if self.spec.schedule == "hier" and self.n_shards % self.groups:
            raise ValueError(
                f"hier: {self.groups} groups do not divide the "
                f"'{axis_name}' axis size {self.n_shards}")
        leaves = jax.tree.leaves(example)
        self._eligible_mask = [_eligible(x) for x in leaves]
        self._sizes = [int(np.prod(x.shape)) for x in leaves]
        self.ef_elems = sum(
            s for s, e in zip(self._sizes, self._eligible_mask) if e)

    # ---------------------------------------------------------- state

    @property
    def stateful(self) -> bool:
        return self.spec.stateful and self.ef_elems > 0

    def init_state(self):
        """Host-side zero residual, ``(n_shards, ef_elems)`` — shard it
        ``P(axis, None)`` and thread it through the trainer's scan
        carry. Zero-WIDTH (``(n_shards, 0)``) for stateless schedules,
        so callers keep one uniform carry/checkpoint layout per comm
        run instead of a stateful/stateless fork."""
        width = self.ef_elems if self.stateful else 0
        return np.zeros((self.n_shards, width), np.float32)

    # ------------------------------------------------------- schedule

    def reduce(self, tree, res=None, t=0, compute=None):
        """Allreduce-SUM ``tree`` across the axis under the schedule.
        Returns ``(tree_summed, res_new)``; ``res_new`` is ``None``
        exactly when :attr:`stateful` is false.

        ``compute`` (optional zero-arg thunk of caller math that is
        INDEPENDENT of the sync — e.g. the regularization gradient) is
        evaluated next to the first in-flight bucket of the overlap
        pipeline so the scheduler can hide the exchange behind it; its
        result is returned as a third element:
        ``(tree_summed, res_new, aux)``."""
        import jax

        if self.spec.schedule == "dense" or self.n_shards == 1:
            from jax import lax

            out = jax.tree.map(
                lambda x: lax.psum(x, self.axis_name), tree)
            if compute is None:
                return out, res
            return out, res, compute()
        return self._reduce_split(tree, res, t, compute)

    def reduce_mean(self, tree, res=None, t=0, compute=None):
        """Allreduce-MEAN: ``dense`` uses ``lax.pmean`` (bitwise-equal
        to ``tree_allreduce_mean``); compressed schedules sum then
        divide. Error feedback is applied to the MEAN's deviation, so
        the topk residual correction carries the right scale.
        ``compute`` as in :meth:`reduce`."""
        import jax

        if self.spec.schedule == "dense" or self.n_shards == 1:
            from jax import lax

            out = jax.tree.map(
                lambda x: lax.pmean(x, self.axis_name), tree)
            if compute is None:
                return out, res
            return out, res, compute()
        if self.spec.schedule == "topk":
            # compress x/n so the residual tracks the mean-scale error
            scaled = jax.tree.map(lambda x: x / self.n_shards, tree)
            return self._reduce_split(scaled, res, t, compute)
        ret = self._reduce_split(tree, res, t, compute)
        out, res = ret[0], ret[1]
        out = jax.tree.map(lambda x: x / self.n_shards, out)
        return (out, res) if compute is None else (out, res, ret[2])

    def _reduce_split(self, tree, res, t, compute=None):
        """Dense-psum the ineligible leaves, run the schedule on the
        eligible ones."""
        import jax
        from jax import lax

        leaves, treedef = jax.tree.flatten(tree)
        comp = [x for x, e in zip(leaves, self._eligible_mask) if e]
        if len(self._eligible_mask) != len(leaves):
            raise ValueError(
                f"CommSync built for {len(self._eligible_mask)} leaves,"
                f" got {len(leaves)}")
        comp_out, res_new, aux = self._run_schedule(comp, res, t,
                                                    compute)
        it = iter(comp_out)
        out = [next(it) if e else lax.psum(x, self.axis_name)
               for x, e in zip(leaves, self._eligible_mask)]
        out = jax.tree.unflatten(treedef, out)
        return (out, res_new) if compute is None \
            else (out, res_new, aux)

    def _run_schedule(self, comp, res, t, compute=None):
        import jax
        import jax.numpy as jnp
        from jax import lax

        sched = self.spec.schedule
        shapes = [x.shape for x in comp]
        dtypes = [x.dtype for x in comp]
        sizes = [int(np.prod(s)) for s in shapes]

        def flatten(xs):
            return jnp.concatenate(
                [x.astype(jnp.float32).ravel() for x in xs]) \
                if xs else jnp.zeros((0,), jnp.float32)

        def unflatten(v):
            out, off = [], 0
            for shape, dt, sz in zip(shapes, dtypes, sizes):
                out.append(v[off:off + sz].reshape(shape).astype(dt))
                off += sz
            return out

        aux = None

        if sched == "bf16":
            aux = compute() if compute is not None else None
            out = [lax.psum(x.astype(jnp.bfloat16), self.axis_name)
                   .astype(x.dtype) for x in comp]
            return out, res, aux

        if sched == "topk":
            n = self.n_shards
            flat = flatten(comp) + res[0]
            k = max(1, int(round(self.spec.topk_fraction
                                 * max(1, self.ef_elems))))
            _, idx = lax.top_k(jnp.abs(flat), k)
            vals = flat[idx]
            # independent caller math next to the pair exchange — the
            # sparse all-gather is the schedule's one in-flight bucket
            aux = compute() if compute is not None else None
            out = sparse_allreduce(vals, idx, flat.shape[0],
                                   axis_name=self.axis_name, n=n)
            contrib = jnp.zeros_like(flat).at[idx].set(vals)
            return unflatten(out), (flat - contrib)[None, :], aux

        if sched in ("bucketed", "hier", "int8"):
            n = self.n_shards
            g = self.groups if sched == "hier" else 1
            m = max(1, n // g)
            # ring chunking granularity: n blocks for the flat ring,
            # n/g intra-group blocks for the two-level ring. g == n or
            # g == 1 degenerate to the flat ring (m == 1 has no
            # intra-group phase), whose padding granularity is n.
            n_blocks = m if (sched == "hier" and m > 1 and g > 1) \
                else n
            flat = flatten(comp)
            e = flat.shape[0]
            if sched in ("bucketed", "int8"):
                n_buckets = max(1, math.ceil(e / self.spec.bucket_elems))
            else:
                n_buckets = 1
            bucket = n_blocks * math.ceil(
                max(1, e) / (n_buckets * n_blocks))
            pad = n_buckets * bucket - e
            flat = jnp.pad(flat, (0, pad))
            buckets = flat.reshape(n_buckets, bucket)

            if sched == "int8":
                exchange, finish = self._int8_bucket_ring(bucket, t)
            else:
                ring = (_ring_allreduce if sched == "bucketed"
                        else lambda v, a, nn: _hier_allreduce(
                            v, a, nn, g))

                def exchange(b, i):
                    del i
                    return ring(b, self.axis_name, n)

                def finish(b):
                    return b

            # double-buffered bucket pipeline: bucket b's collective
            # chain overlaps bucket b−1's unpack/dequantize (and the
            # caller's `compute` thunk rides next to the first bucket)
            out, aux = _pipelined_buckets(
                buckets, exchange, finish, self.spec.overlap, compute)
            return unflatten(out.reshape(-1)[:e]), res, aux

        raise AssertionError(f"unreachable schedule {sched!r}")

    def _int8_bucket_ring(self, bucket: int, t):
        """The native int8 ring's per-bucket (exchange, finish) pair.

        ``exchange``: quantize the f32 bucket against a pmax-shared
        scale (seeded stochastic rounding), ``all_to_all`` the int8
        chunks so chunk *c* of every shard lands on shard *c* (int8 on
        the wire), accumulate the n integer contributions EXACTLY in
        int32 (order-free ⇒ bitwise-deterministic and replicated by
        construction), requantize the reduced chunk with a second
        seeded stochastic rounding (scale ``n·s`` bounds the integer
        sum, |Σq| ≤ 127n), then ring all-gather the int8 result chunk
        with origin placement. ``finish``: dequantize — the only f32
        work, pipelined against the NEXT bucket's exchange. The int32
        widening happens strictly AFTER the collectives (TDA051 polices
        the opposite order — the int32-psum wire this replaced)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        n = self.n_shards
        chunk = bucket // n
        axis = self.axis_name
        my = lax.axis_index(axis)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(self.spec.seed), t), my)

        def exchange(b, i):
            scale = lax.pmax(jnp.max(jnp.abs(b)), axis) / 127.0
            scale = jnp.maximum(scale, jnp.float32(1e-30))
            u = jax.random.uniform(
                jax.random.fold_in(key, 2 * i), b.shape)
            q = jnp.clip(jnp.floor(b / scale + u),
                         -127, 127).astype(jnp.int8)
            # chunk c of every shard → shard c, as int8
            recv = lax.all_to_all(
                q.reshape(n, chunk), axis,
                split_axis=0, concat_axis=0, tiled=True)
            s_int = jnp.sum(recv.astype(jnp.int32), axis=0)  # exact
            u2 = jax.random.uniform(
                jax.random.fold_in(key, 2 * i + 1), s_int.shape)
            q2 = jnp.clip(jnp.floor(s_int.astype(jnp.float32) / n + u2),
                          -127, 127).astype(jnp.int8)
            return _ring_allgather(q2, axis, n), scale

        def finish(carry):
            all_q2, scale = carry
            # chunk c sits in row c: row-major reshape restores order
            return (all_q2.astype(jnp.float32)
                    * (scale * n)).reshape(-1)

        return exchange, finish

    # ---------------------------------------------------------- stats

    def stats(self) -> dict:
        """Per-sync byte accounting (host-side, static): per-shard
        ``bytes_wire`` under a bandwidth-optimal ring at the schedule's
        wire precision, the f32 ``bytes_logical`` payload, and the
        collective ``rounds`` launched per sync.

        This is the SCHEDULE'S payload accounting — what each sync
        fundamentally has to move — and since round 11 every schedule
        matches it on the wire: a bf16 psum moves bf16, topk's ring
        all-gather moves exactly the 8k-byte pair buffers, and int8
        runs the NATIVE compressed ring (``all_to_all`` chunk scatter +
        int8 ring all-gather, 1 byte/elem in both phases — the int32
        widening happens locally after the exchange, never on the
        wire; PR 5's int32-psum caveat is closed, and lint rule TDA051
        keeps it closed)."""
        dense_elems = sum(
            s for s, e in zip(self._sizes, self._eligible_mask)
            if not e)
        return schedule_stats(
            self.spec.schedule, n_shards=self.n_shards,
            compressible_elems=self.ef_elems, dense_elems=dense_elems,
            bucket_elems=self.spec.bucket_elems,
            topk_fraction=self.spec.topk_fraction, groups=self.groups)


def schedule_stats(schedule: str, *, n_shards: int,
                   compressible_elems: int, dense_elems: int = 0,
                   bucket_elems: int = 1 << 16,
                   topk_fraction: float = 0.01,
                   groups: int = 1) -> dict:
    """The closed-form per-sync byte/round accounting of one schedule
    — ``CommSync.stats`` minus the live sync object, callable from a
    plain parameter set (numpy-free, jax-free).

    This module-level spelling exists for the autotuner: the
    ``tune/resolve.py`` cost model joins these counts against a
    measured :mod:`tpu_distalg.tune.profile` (wire bandwidth, RTT,
    codec throughput) to predict per-sync seconds per candidate
    schedule, so the resolver and the live accounting can never
    disagree about what a schedule moves."""
    n = n_shards
    ce = compressible_elems
    ring = 2.0 * (n - 1) / n if n > 1 else 0.0
    b_logical = 4 * (ce + dense_elems)
    dense_wire = 4 * dense_elems * ring
    if schedule == "dense" or n == 1:
        wire = 4 * ce * ring + dense_wire
        rounds = 1
    elif schedule == "bf16":
        wire = 2 * ce * ring + dense_wire
        rounds = 1 + (1 if dense_elems else 0)
    elif schedule == "int8":
        # native ring: int8 both phases (scatter (n−1)/n + gather
        # (n−1)/n = the ring constant at 1 byte/elem), one f32
        # pmax per BUCKET for the shared scale (the requant scale
        # n·s is derived, no extra collective)
        nb = max(1, math.ceil(max(1, ce) / bucket_elems))
        wire = ce * ring + 4 * nb * ring + dense_wire
        rounds = 3 * nb + (1 if dense_elems else 0)
    elif schedule == "topk":
        k = max(1, int(round(topk_fraction * max(1, ce))))
        # k (value, index) pairs exchanged all-gather-style
        wire = 8 * k * (n - 1) + dense_wire
        rounds = 1 + (1 if dense_elems else 0)
    elif schedule == "bucketed":
        wire = 4 * ce * ring + dense_wire
        rounds = max(1, math.ceil(max(1, ce) / bucket_elems)) \
            + (1 if dense_elems else 0)
    elif schedule == "hier":
        g = max(1, groups)
        m = max(1, n // g)
        ici = 4 * ce * (2.0 * (m - 1) / m if m > 1 else 0.0)
        dcn = 4 * (ce / m) * (2.0 * (g - 1) / g if g > 1 else 0.0)
        wire = ici + dcn + dense_wire
        rounds = 3 + (1 if dense_elems else 0)
    else:  # pragma: no cover
        raise AssertionError(schedule)
    return {"bytes_wire": int(round(wire)),
            "bytes_logical": int(round(b_logical)),
            "rounds": int(rounds)}


def make_sync(spec, mesh, example, *, axis_name: str = DATA_AXIS):
    """Build a :class:`CommSync` — ``spec`` may be a :class:`CommSpec`
    or its CLI string spelling."""
    return CommSync(CommSpec.parse(spec), mesh, example,
                    axis_name=axis_name)


def emit_sync_counters(sync: CommSync, n_syncs: int) -> dict:
    """Bump the ``comm.*`` telemetry counters for a run of ``n_syncs``
    sync rounds (a no-op when telemetry is disabled) and return the
    per-sync stats for callers that also report them inline."""
    from tpu_distalg.telemetry import events as tevents

    st = sync.stats()
    tevents.counter("comm.bytes_wire", st["bytes_wire"] * n_syncs)
    tevents.counter("comm.bytes_logical",
                    st["bytes_logical"] * n_syncs)
    tevents.counter("comm.rounds", st["rounds"] * n_syncs)
    tevents.counter("comm.syncs", n_syncs)
    return st


def rank_combine_stats(k: int, length: int, n: int) -> dict:
    """Byte accounting for a window-sparse vector combine — the graph
    engine's rank-contribution reduce (``graphs/engine.py``), where
    every shard contributes ``k`` (value, index) pairs covering the
    destination window its edge blocks touch.

    ``bytes_wire`` is the sparse exchange's per-shard payload: the ring
    all-gather of the pair buffers (:func:`sparse_allreduce`) moves
    ``8k`` bytes per hop over ``n−1`` hops — on power-law graphs ``k``
    (the shard's distinct-destination count) is a small fraction of the
    vertex count, the observation Sparse Allreduce (arXiv:1312.3020)
    is built on. ``bytes_dense_ring`` is what the dense alternative — a
    psum of the O(length) zero-padded vector under a bandwidth-optimal
    ring — would move: ``4·length·2(n−1)/n``. ``bytes_logical`` is the
    f32 payload logically reduced (the dense length), so the standard
    ``comm.bytes_wire``/``bytes_logical`` counters render the achieved
    compression in ``tda report`` exactly like the gradient schedules'.
    """
    ring = 2.0 * (n - 1) / n if n > 1 else 0.0
    return {
        "bytes_wire": int(8 * k * max(0, n - 1)),
        "bytes_dense_ring": int(round(4 * length * ring)),
        "bytes_logical": int(4 * length),
        "rounds": 1,
    }


def emit_rank_combine_counters(k: int, length: int, n: int, *,
                               n_syncs: int = 1,
                               combine: str = "sparse") -> dict:
    """Bump the telemetry counters for ``n_syncs`` rank combines and
    return the per-sync accounting. ``comm.bytes_wire`` carries the
    payload of the combine actually run (``combine='dense'`` runs the
    psum, so its wire bytes are the dense-ring figure); the
    ``graph.combine_*`` pair records BOTH accountings so the report can
    state the sparse-vs-dense win for the run whichever was selected.
    No-op when telemetry is disabled."""
    from tpu_distalg.telemetry import events as tevents

    st = rank_combine_stats(k, length, n)
    wire = (st["bytes_wire"] if combine == "sparse"
            else st["bytes_dense_ring"])
    tevents.counter("comm.bytes_wire", wire * n_syncs)
    tevents.counter("comm.bytes_logical", st["bytes_logical"] * n_syncs)
    tevents.counter("comm.rounds", st["rounds"] * n_syncs)
    tevents.counter("comm.syncs", n_syncs)
    tevents.counter("graph.combine_bytes_wire", wire * n_syncs)
    tevents.counter("graph.combine_bytes_dense_ring",
                    st["bytes_dense_ring"] * n_syncs)
    tevents.counter("graph.combine_syncs", n_syncs)
    return st


# --------------------------------------------------------------------
# Host-side wire codecs — the cluster tier's spelling of the schedules.
#
# The device schedules above compress SPMD collectives; the
# multi-process cluster (tpu_distalg/cluster/) moves the same payloads
# over a real TCP wire, host-to-host, where the quantize/dequantize +
# error-feedback stages run in numpy BEFORE transport framing. These
# codecs are that reusable stage: pure functions of (spec.seed, the
# caller's integer path) — the host-side counterpart of the device
# threefry fold-in chain — so every process reconstructs identical
# bytes and a chaos replay stays bitwise. numpy + stdlib only: the
# coordinator process never imports jax.
#
#   int8  seeded stochastic rounding against a shared max-abs scale;
#         the decoder widens int8 -> int32 EXACTLY before the single
#         f32 scale multiply (the wire itself carries 1 byte/elem —
#         TDA051 polices the opposite order).
#   topk  the k largest-|.| entries as (value, index) pairs — 8k pair
#         bytes on the wire; the decoder scatter-adds them exactly.
#
# Both run under ERROR FEEDBACK when the caller carries a residual:
# ``encode(vec)`` compresses ``vec + residual`` and returns the new
# residual (what the wire did not carry), so nothing is ever lost —
# the EF-SGD correction of the device topk schedule, applied uniformly
# (stochastic int8 is unbiased already; EF additionally bounds its
# worst case). The residual is the caller's to checkpoint/resume.


#: seed-path direction tags — a cluster push folds in
#: ``(PUSH_SEED_TAG, slot, window)``, a pull ``(PULL_SEED_TAG, slot,
#: have, version)``: the two directions can never share a rounding
#: stream
PUSH_SEED_TAG = 1
PULL_SEED_TAG = 2


def host_rng(seed: int, *path: int) -> np.random.Generator:
    """Counter-based generator keyed by ``(seed, path...)`` — the
    host-side stand-in for ``jax.random.fold_in`` chains (Philox under
    a SeedSequence; both are spec-fixed, so the stream is stable
    across platforms and numpy versions)."""
    ss = np.random.SeedSequence(
        entropy=int(seed) & 0xFFFFFFFFFFFFFFFF,
        spawn_key=tuple(int(p) & 0xFFFFFFFF for p in path))
    return np.random.Generator(np.random.Philox(ss))


class HostCodec:
    """Base: a stateless vector codec; EF residual rides the caller."""

    #: frames-on-the-wire name (welcome meta / telemetry)
    name = "?"

    def __init__(self, spec: "CommSpec"):
        self.spec = spec

    def encode(self, vec: np.ndarray, residual: np.ndarray | None,
               *path: int):
        """``(arrays, residual_new)`` for one f32 vector. ``path`` is
        the deterministic seed path — (direction, slot, window) for a
        worker push, (direction, slot, have, version) for a pull."""
        raise NotImplementedError

    def decode(self, arrays: dict, length: int) -> np.ndarray:
        """The dense f32 ``(length,)`` reconstruction — exact integer
        widening / scatter-add, deterministic on every host."""
        raise NotImplementedError


class Int8HostCodec(HostCodec):
    """Seeded stochastic rounding to int8 against a max-abs scale."""

    name = "int8"

    def encode(self, vec, residual, *path):
        x = np.asarray(vec, np.float32)
        if residual is not None:
            x = x + residual
        scale = np.float32(max(float(np.max(np.abs(x)))
                               if x.size else 0.0, 1e-30) / 127.0)
        u = host_rng(self.spec.seed, *path).random(
            x.shape, dtype=np.float32)
        q = np.clip(np.floor(x / scale + u), -127, 127).astype(np.int8)
        # shape (1,): the transport frames scalars at min-ndim 1
        arrays = {"q": q, "scale": np.full((1,), scale, np.float32)}
        res_new = (x - q.astype(np.float32) * scale
                   if residual is not None else None)
        return arrays, res_new

    def decode(self, arrays, length):
        q = np.asarray(arrays["q"])
        # EXACT widening strictly after the wire (TDA051's contract),
        # then the one f32 scale multiply
        wide = q.astype(np.int32)
        return (wide.astype(np.float32)
                * np.float32(arrays["scale"])).reshape(length)


class TopkHostCodec(HostCodec):
    """The k largest-|.| entries as (value, index) pairs."""

    name = "topk"

    def k_for(self, length: int) -> int:
        return max(1, int(round(self.spec.topk_fraction
                                * max(1, length))))

    def encode(self, vec, residual, *path):
        x = np.asarray(vec, np.float32)
        if residual is not None:
            x = x + residual
        k = self.k_for(x.size)
        # stable sort => deterministic tie-breaks on every host
        idx = np.argsort(-np.abs(x), kind="stable")[:k].astype(np.int32)
        vals = x[idx]
        arrays = {"vals": vals, "idx": idx}
        if residual is None:
            return arrays, None
        res_new = x.copy()
        res_new[idx] = 0.0
        return arrays, res_new

    def decode(self, arrays, length):
        out = np.zeros((length,), np.float32)
        # exact scatter-add (duplicate indices accumulate additively)
        np.add.at(out, np.asarray(arrays["idx"], np.int64),
                  np.asarray(arrays["vals"], np.float32))
        return out


#: schedules the cluster wire admits (the device-only schedules —
#: bucketed/hier/bf16 — have no host spelling worth framing: bf16
#: halves bytes where int8 quarters them, bucketing is a collective-
#: overlap concern, and hier is a topology concern)
HOST_SCHEDULES = ("dense", "int8", "topk")


def make_host_codec(spec) -> HostCodec | None:
    """The host codec for a :class:`CommSpec` (or its CLI string) —
    ``None`` for ``dense`` (callers keep their uncompressed path
    verbatim, which is what pins dense bitwise to history)."""
    spec = CommSpec.parse(spec)
    if spec.schedule not in HOST_SCHEDULES:
        raise ValueError(
            f"comm schedule {spec.schedule!r} has no host-wire "
            f"codec; the cluster tier takes one of "
            f"{', '.join(HOST_SCHEDULES)}")
    if spec.schedule == "int8":
        return Int8HostCodec(spec)
    if spec.schedule == "topk":
        return TopkHostCodec(spec)
    return None


def make_host_pull_codec(spec) -> HostCodec | None:
    """The PULL-direction codec: int8 under EVERY compressed mode
    (``None`` for dense). The push direction can afford topk's biased
    truncation because the worker-side EF residual re-sends dropped
    mass later; the pull direction has no residual channel — pair
    pulls would silently lose the untransmitted (1−frac) of every
    center delta from the worker's cached view forever, or require
    durable per-worker residual state at the coordinator that every
    ack would have to WAL before leaving. int8's stochastic rounding
    is unbiased and stateless, so a recovered coordinator re-serves
    bit-identical pulls from the replayed center history alone. Both
    ends derive this codec from the same spec, so they can never
    disagree on the wire format."""
    spec = CommSpec.parse(spec)
    return (None if make_host_codec(spec) is None
            else Int8HostCodec(spec))


def encode_tree(codec: HostCodec, tree: dict,
                residuals: dict | None, *path: int):
    """Per-leaf host encode of a flat ``{name: ndarray}`` tree (the
    cluster center/delta vocabulary): each float leaf flattens, rides
    the codec under seed path ``(*path, leaf_index)``, and lands as
    ``{name}.{part}`` wire arrays. Returns ``(arrays,
    residuals_new)``; ``residuals`` maps name -> flat f32 residual
    (or ``None`` for EF-free encoding)."""
    arrays: dict = {}
    res_new: dict | None = None if residuals is None else {}
    for i, name in enumerate(sorted(tree)):
        leaf = np.asarray(tree[name], np.float32).ravel()
        res = None if residuals is None else residuals.get(
            name, np.zeros_like(leaf))
        parts, r = codec.encode(leaf, res, *path, i)
        for part, arr in parts.items():
            arrays[f"{name}.{part}"] = arr
        if res_new is not None:
            res_new[name] = r
    return arrays, res_new


def decode_tree(codec: HostCodec, arrays: dict,
                template: dict) -> dict:
    """Inverse of :func:`encode_tree` under a shape template
    ``{name: ndarray-like}`` (the model's known center layout)."""
    out = {}
    for name in sorted(template):
        shape = np.asarray(template[name]).shape
        length = int(np.prod(shape, dtype=np.int64)) if shape else 1
        prefix = f"{name}."
        parts = {k[len(prefix):]: v for k, v in arrays.items()
                 if k.startswith(prefix)}
        out[name] = codec.decode(parts, length).reshape(shape)
    return out


def merge_topk_pairs_host(all_vals, all_idx, *, k: int):
    """Host spelling of ``ops.pallas_topk.merge_topk_pairs`` — the
    cross-PROCESS half of the sparse candidate merge. A router holding
    per-replica (S, B, K) pair stacks gathered over the framed
    transport merges them with the same two-key order the in-process
    ring all-gather path uses: value DESCENDING, ties toward the LOWER
    global index (``lax.top_k``'s rule). Scores are computed and
    compared as the same f32 bits on both paths, so routed sharded
    replies stay bitwise-identical to a single-replica run."""
    v = np.moveaxis(np.asarray(all_vals, np.float32), 0, 1)
    i = np.moveaxis(np.asarray(all_idx, np.int32), 0, 1)
    B = v.shape[0]
    v = v.reshape(B, -1)
    i = i.reshape(B, -1)
    out_v = np.empty((B, k), np.float32)
    out_i = np.empty((B, k), np.int32)
    for b in range(B):
        # lexsort: LAST key is primary — (-value asc, index asc)
        order = np.lexsort((i[b], -v[b]))[:k]
        out_v[b] = v[b][order]
        out_i[b] = i[b][order]
    return out_v, out_i


def zero_residuals(template: dict) -> dict:
    """Fresh EF residuals for a tree template — one flat f32 zero
    vector per leaf (what a brand-new or reset worker carries)."""
    return {name: np.zeros(
        int(np.prod(np.asarray(template[name]).shape,
                    dtype=np.int64)), np.float32)
        for name in template}


def emit_overlap_counters(hidden_ms: float, comm_ms: float) -> None:
    """Bump the overlap-efficiency counters ``tda report`` renders:
    ``comm.overlap_hidden_ms`` is comm time HIDDEN behind compute
    (measured as the sequential-vs-overlapped step-time delta × sync
    count — the honest host-side observable), ``comm.sync_ms`` the comm
    time still exposed (schedule-vs-dense delta under overlap). The
    report line shows hidden / (hidden + exposed) as the fraction of
    comm time the pipeline hid. No-op when telemetry is disabled."""
    from tpu_distalg.telemetry import events as tevents

    tevents.counter("comm.overlap_hidden_ms",
                    max(0, int(round(hidden_ms))))
    tevents.counter("comm.sync_ms", max(0, int(round(comm_ms))))
