"""Plain reference for alternating least squares on a ratings list (the
configuration ``als-yahoomusic-f100``): ALS with weighted-lambda
regularisation, one normal-equation system an owner.

For owner ``u`` of one side with ratings ``r_uv`` of the other side's
``v in Omega_u``, all float32 at ``precision=highest``:

    A_u = sum_v f_v f_v^T + lam * |Omega_u| * I
    b_u = sum_v r_uv f_v
    x_u = solve(A_u, b_u)                       (jnp.linalg.solve)

with ``f_v`` the other side's factor rows. An ALS iteration is the user
half from the item factors, then the item half from the new user
factors.

Nothing of the program is imported. What is shared is restated here, in
owner ids (the program's packed blocks, factor rows and classes do not
exist here):

* the degrees: a bounded power law per side, a function of the sizes
  alone (``degrees``);
* the pairing: user stub ``p`` (user ``u`` repeated ``d_u`` times, in id
  order) is paired with item stub ``pi(p)``, ``pi`` a four-round Feistel
  permutation keyed by the seed (``Permutation``);
* the planted model, the noise and the rounding (``Model``), and the
  start: item factors uniform on [0, 1) hashed from the seed and the id.

**What is followed.** A system is one owner's alone, so a half-sweep
is checked owner by owner: for every owner of more than ``heavy_over``
ratings (half of all ratings sit in those) and a seeded sample of
``sample`` owners a side, the reference regenerates the owner's ratings
from the seed, builds ``A_u`` and ``b_u`` against the other side's
factors *as the program had them when the half began* (the start, or
what the program returned from the half before), and solves. The
full-size form (``segment_sum`` of 252.8M outer products a half) does
not end inside a run's time (``PERF.md`` section 2 has the measured
rate); the two global quantities (ratings entered a half, the held-out
RMSE) cover every rating.

``dtype=bfloat16`` rounds the gathered factors before the products: the
control, which has to come out as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

U32 = np.uint32


def _mix32(x):
    x = x ^ (x >> U32(16))
    x = x * U32(0x7FEB352D)
    x = x ^ (x >> U32(15))
    x = x * U32(0x846CA68B)
    return x ^ (x >> U32(16))


def _const(x: int):
    return U32(x & 0xFFFFFFFF)


def degrees(n_owners: int, total: int, d_min: int, d_max: int,
            salt: int) -> np.ndarray:
    """The side's degree sequence, int64 ``(n_owners,)``."""
    q = (np.arange(n_owners, dtype=np.float64) + 0.5) / n_owners

    def seq(a):
        a1 = 1.0 - a
        lo, hi = float(d_min) ** a1, (d_max + 1.0) ** a1
        return np.clip(np.floor((lo + q * (hi - lo)) ** (1.0 / a1)),
                       d_min, d_max)

    lo, hi = 1.0 + 1e-6, 16.0
    if seq(hi).sum() > total:
        d = np.full(n_owners, float(d_min))
    else:
        for _ in range(80):
            mid = 0.5 * (lo + hi)
            if seq(mid).sum() > total:
                lo = mid
            else:
                hi = mid
        d = seq(hi)
    d = d.astype(np.int64)
    left, at = int(total - d.sum()), n_owners - 1
    while left > 0:
        take = min(left, int(d_max - d[at]))
        d[at] += take
        left -= take
        at -= 1
    ids = np.arange(n_owners, dtype=np.uint32)
    order = np.argsort(_mix32(ids * U32(0x9E3779B1) + U32(salt)),
                       kind="stable")
    out = np.empty(n_owners, np.int64)
    out[order] = d
    return out


class Permutation:
    """``pi`` over ``[0, n)`` and its inverse, keyed."""

    def __init__(self, n: int):
        bits = max(2, int(n - 1).bit_length())
        bits += bits % 2
        self.n, self.half = n, bits // 2
        self.mask = U32((1 << self.half) - 1)

    def _round(self, v, k):
        return _mix32(v * U32(0x85EBCA6B) + k) & self.mask

    def _once(self, x, keys, back: bool):
        h = U32(self.half)
        left, right = x >> h, x & self.mask
        if back:
            for k in reversed(keys):
                left, right = right ^ self._round(left, k), left
        else:
            for k in keys:
                left, right = right, left ^ self._round(right, k)
        return (left << h) | right

    def apply(self, x, key, back: bool = False):
        keys = [_mix32(key + _const(0x9E3779B1 * (r + 1)))
                for r in range(4)]
        n = U32(self.n)
        y = self._once(x.astype(jnp.uint32), keys, back)
        return jax.lax.while_loop(
            lambda y: jnp.any(y >= n),
            lambda y: jnp.where(y >= n, self._once(y, keys, back), y), y)


class Model:
    """The planted model, the noise, the start, of one configuration."""

    def __init__(self, k: int, mean: float, scale: float, noise: float,
                 low: float, high: float):
        self.k, self.mean, self.scale, self.noise = k, mean, scale, noise
        self.low, self.high = low, high

    @staticmethod
    def key(seed, stream: int):
        return _mix32(seed.astype(jnp.uint32) * U32(0x9E3779B1)
                      + _const(stream * 0x85EBCA6B + 1))

    def planted(self, ids, seed, side: int):
        at = ids.astype(jnp.uint32)[:, None] * U32(self.k) \
            + jnp.arange(self.k, dtype=jnp.uint32)[None, :]
        bits = _mix32(at ^ self.key(seed, 8 + side)) >> U32(28)
        return (bits.astype(jnp.float32) - 8.0) * 0.125

    def _unit(self, p, seed, stream: int):
        bits = _mix32(p.astype(jnp.uint32) ^ self.key(seed, stream))
        return (bits >> U32(8)).astype(jnp.float32) * (2.0 ** -23) - 1.0

    def rating(self, dot, p, seed, stream: int = 0):
        eps = self._unit(p, seed, 2 + 2 * stream) \
            + self._unit(p, seed, 3 + 2 * stream)
        r = jnp.float32(self.mean) + jnp.float32(self.scale) * dot \
            + jnp.float32(self.noise) * eps
        return jnp.clip(jnp.round(r), self.low, self.high)

    def start_items(self, n_items: int, seed):
        at = jnp.arange(n_items, dtype=jnp.uint32)[:, None] * U32(self.k) \
            + jnp.arange(self.k, dtype=jnp.uint32)[None, :]
        key = _mix32(seed.astype(jnp.uint32) * U32(0x9E3779B1)
                     + U32(0x51ED270B))
        return (_mix32(at ^ key) >> U32(8)).astype(jnp.float32) \
            * (2.0 ** -24)


def rel_err(got, want, before) -> float:
    """Norm of the difference over the norm of the reference's change."""
    got, want, before = (np.asarray(a, np.float64)
                         for a in (got, want, before))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want - before), 1e-30))


class Reference:
    """The owners of one cell that are followed, from its seeds."""

    SLOTS = 1 << 19         # rating slots a batch of rows
    LIGHT, WIDE = 128, 4096  # slots a row of a small owner, of any other
    CHUNK = 4096            # systems a call of the solve

    def __init__(self, *, config: dict, data_seed: int, start_seed: int,
                 sample: int | None = None, heavy_over: int | None = None,
                 degrees_of=None):
        c, g = config, config["generator"]
        self.k, self.lam = c["k"], float(c["lam"])
        self.n = (c["n_users"], c["n_items"])
        self.nz = c["n_ratings"]
        self.n_heldout = c["n_heldout"]
        # ``degrees_of``: a test's own two sequences
        self.deg = tuple(np.asarray(d, np.int64) for d in degrees_of) \
            if degrees_of is not None else (
            degrees(self.n[0], self.nz, g["d_min"], g["user_d_max"], 1),
            degrees(self.n[1], self.nz, g["d_min"], g["item_d_max"], 2))
        self.off = tuple(np.cumsum(d) - d for d in self.deg)
        self.perm = Permutation(self.nz)
        self.model = Model(self.k, g["mean"], g["scale"], g["noise"],
                           c["rating_low"], c["rating_high"])
        self.data_seed = jnp.int32(data_seed)
        self.start_seed = jnp.int32(start_seed)
        self.sample = c["reference_sample"] if sample is None else sample
        self.heavy_over = c["reference_heavy_over"] \
            if heavy_over is None else heavy_over
        self._stub_owner = [None, None]
        self._lists: dict = {}
        self._solvers: dict = {}

    # ------------------------------------------------------ the ratings

    def stub_owner(self, side: int):
        """The owner of every stub of a side: owner ``u`` repeated
        ``d_u`` times, in id order."""
        if self._stub_owner[side] is None:
            # a mark where each owner's stubs begin, then the running
            # count of marks (an owner with no rating shares its place
            # with the next one and both are counted)
            starts = jnp.asarray(self.off[side][1:], jnp.int32)
            self._stub_owner[side] = jax.jit(lambda at: jnp.cumsum(
                jnp.zeros((self.nz,), jnp.int32).at[at].add(
                    1, mode="drop")))(starts)
        return self._stub_owner[side]

    def followed(self, side: int) -> np.ndarray:
        """Owner ids of a side that are followed: every owner of more
        than ``heavy_over`` ratings and a seeded sample of the rest."""
        d = self.deg[side]
        heavy = np.flatnonzero(d > self.heavy_over)
        rest = np.flatnonzero((d <= self.heavy_over) & (d > 0))
        rng = np.random.default_rng([int(self.data_seed), side, 77])
        take = min(self.sample, rest.shape[0])
        picked = rng.choice(rest, size=take, replace=False)
        return np.sort(np.concatenate([heavy, picked]))

    def _ratings_fn(self, side: int, width: int):
        k, model, perm = self.k, self.model, self.perm

        def make(off, count, owners, owner_other, seed):
            lane = jnp.arange(width, dtype=jnp.int32)[None, :]
            ok = lane < count[:, None]
            mine = jnp.where(ok, off[:, None] + lane, 0).astype(jnp.uint32)
            key = model.key(seed, 0)
            if side == 0:
                pair = mine
                theirs = perm.apply(mine, key)
            else:
                theirs = perm.apply(mine, key, back=True)
                pair = theirs
            other = owner_other[theirs.astype(jnp.int32)]
            a = model.planted(owners, seed, side)
            b = model.planted(other.reshape(-1), seed, 1 - side)
            dot = jnp.sum(a[:, None, :] * b.reshape(-1, width, k), axis=-1)
            return other, jnp.where(ok, model.rating(dot, pair, seed), 0.0), \
                ok

        return jax.jit(make)

    def lists(self, side: int):
        """The followed owners' ratings, regenerated from the seed once
        and kept on the device, in rows of two widths (two shapes to
        compile, whatever the degrees): an owner of at most ``LIGHT``
        ratings is one row of ``LIGHT`` slots, any other owner
        ``ceil(d / WIDE)`` rows of ``WIDE``. ``[(owners of the group,
        [(row -> owner of the group, other ids, ratings, valid), ...]),
        ...]``, a batch of rows a tuple."""
        if side in self._lists:
            return self._lists[side]
        own = self.followed(side)
        d, off = self.deg[side][own], self.off[side][own]
        owner_other = self.stub_owner(1 - side)
        out = []
        for width, pick in ((self.LIGHT, d <= self.LIGHT),
                            (self.WIDE, d > self.LIGHT)):
            group, gd, goff = own[pick], d[pick], off[pick]
            n_rows = -(-gd // width)
            owner_of = np.repeat(np.arange(group.shape[0]), n_rows)
            within = np.arange(int(n_rows.sum())) - np.repeat(
                np.cumsum(n_rows) - n_rows, n_rows)
            row_off = goff[owner_of] + within * width
            row_n = np.minimum(width, gd[owner_of] - within * width)
            fn = self._ratings_fn(side, width)
            step = self.SLOTS // width
            batches = []
            for at in range(0, owner_of.shape[0], step):
                sl = slice(at, at + step)
                pad = step - owner_of[sl].shape[0]

                def padded(x, fill):
                    return jnp.asarray(np.concatenate(
                        [x[sl], np.full(pad, fill, x.dtype)]), jnp.int32)

                # a padding row has no rating and goes to a dump owner
                seg = padded(owner_of, group.shape[0])
                other, r, ok = fn(
                    padded(row_off, 0), padded(row_n, 0),
                    padded(group[owner_of], 0), owner_other,
                    self.data_seed)
                batches.append((seg, other, r, ok))
            out.append((group, batches))
        self._lists[side] = out
        return out

    # ------------------------------------------------------- the solves

    def _fns(self, dtype):
        if dtype in self._solvers:
            return self._solvers[dtype]
        k, lam = self.k, jnp.float32(self.lam)
        low = dtype != jnp.float32

        def add(A, b, n, F, seg, other, r, ok):
            """One batch of rows into its owners' normal equations."""
            G = F[other] * ok[..., None].astype(jnp.float32)
            if low:                # XLA may not drop the rounding
                G = jax.lax.reduce_precision(G, 8, 7)
            A = A.at[seg].add(
                jnp.einsum("npd,npe->nde", G, G, precision="highest"))
            b = b.at[seg].add(
                jnp.einsum("npd,np->nd", G, r, precision="highest"))
            return A, b, n.at[seg].add(jnp.sum(ok, axis=1))

        def solve(A, b, n):
            n = n.astype(jnp.float32)
            ridge = jnp.where(n > 0, lam * n, 1.0)
            A = A + ridge[:, None, None] * jnp.eye(k, dtype=jnp.float32)
            return jnp.linalg.solve(A, b[..., None])[..., 0]

        self._solvers[dtype] = (jax.jit(add, donate_argnums=(0, 1, 2)),
                                jax.jit(solve))
        return self._solvers[dtype]

    def half(self, side: int, other_factors, dtype=jnp.float32):
        """``(owners, their new factor rows)`` of one half: the
        followed owners of ``side`` from the other side's factors."""
        F = jnp.asarray(other_factors, jnp.float32)
        add, solve = self._fns(dtype)
        k, chunk = self.k, self.CHUNK
        owners, rows = [], []
        with jax.default_matmul_precision("highest"):
            for group, batches in self.lists(side):
                size = -(-(group.shape[0] + 1) // chunk) * chunk
                A = jnp.zeros((size, k, k), jnp.float32)
                b = jnp.zeros((size, k), jnp.float32)
                n = jnp.zeros((size,), jnp.int32)
                for seg, other, r, ok in batches:
                    A, b, n = add(A, b, n, F, seg, other, r, ok)
                x = np.concatenate([
                    np.asarray(solve(A[at:at + chunk], b[at:at + chunk],
                                     n[at:at + chunk]))
                    for at in range(0, size, chunk)])
                owners.append(group)
                rows.append(x[:group.shape[0]])
        order = np.argsort(np.concatenate(owners), kind="stable")
        return np.concatenate(owners)[order], np.concatenate(rows)[order]

    def start_items(self) -> np.ndarray:
        return np.asarray(jax.jit(
            lambda s: self.model.start_items(self.n[1], s))(self.start_seed))

    # ----------------------------------------------------- the held-out

    def heldout(self):
        """``(user ids, item ids, ratings)`` never trained on."""
        model, nz = self.model, U32(self.nz)

        @jax.jit
        def draw(own_u, own_i, seed):
            i = jnp.arange(self.n_heldout, dtype=jnp.uint32)
            ju = _mix32(i ^ model.key(seed, 16)) % nz
            jv = _mix32(i ^ model.key(seed, 17)) % nz
            u, v = own_u[ju.astype(jnp.int32)], own_i[jv.astype(jnp.int32)]
            dot = jnp.sum(model.planted(u, seed, 0)
                          * model.planted(v, seed, 1), axis=1)
            return u, v, model.rating(dot, i, seed, 1)

        return draw(self.stub_owner(0), self.stub_owner(1), self.data_seed)

    @staticmethod
    def rmse(pairs, U, V) -> float:
        u, v, r = pairs

        @jax.jit
        def score(U, V):
            d = jnp.sum(U[u] * V[v], axis=1) - r
            return jnp.sqrt(jnp.mean(d * d))

        return float(score(jnp.asarray(U, jnp.float32),
                           jnp.asarray(V, jnp.float32)))

    def free(self):
        self._stub_owner = [None, None]
        self._lists = {}
