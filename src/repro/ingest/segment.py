"""Append-only delta segments: the mutable tier's brute-force substrate.

A ``DeltaSegment`` is a fixed-capacity host-side row buffer (vectors,
external ids, live flags).  Inserts append; deletes flip ``live``; neither
touches the frozen base index.  At query time each segment is scanned
exactly (the same ``ops.l2_exact_batch`` path the IVF searcher uses —
a segment is small, so brute force beats any structure) and its top-k is
merged with the base engine's results by the ``MutableIndex``.

Device buffers are shaped by the segment CAPACITY, not its fill level, so
the jitted scan compiles once per (capacity, batch) shape and appends /
deletes never retrace — they only flip rows of the ``live`` mask, exactly
like the engine-side tombstones.

Segments align with ``ivf.ShardedLayout``: ``shard_delta`` deals rows
round-robin (``j::n_shards``, the same rule ``ivf.sharded_layout`` applies
per cluster) so a delta segment places onto the serving mesh next to the
main sharded stream and is scanned under the same ``shard_map`` collective
idiom (local top-k, survivor-only gather).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed as dist
from repro.index import search as search_mod
from repro.kernels import ops

LANE = 128


class DeltaSegment:
    """Fixed-capacity append-only row buffer with tombstone flags.

    External ids are assigned by the owning ``MutableIndex`` and must fit
    int32 (the device id dtype across the repo's kernel paths).
    ``version`` bumps on every append/delete so scan-side device caches
    know when their copy is stale.
    """

    def __init__(self, capacity: int, d: int):
        if capacity < 1:
            raise ValueError(f"segment capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.d = int(d)
        self.vectors = np.zeros((self.capacity, self.d), np.float32)
        self.ids = np.full((self.capacity,), -1, np.int64)
        self.live = np.zeros((self.capacity,), bool)
        self.size = 0          # rows ever appended (dead rows included)
        self.version = 0

    @property
    def room(self) -> int:
        """Rows that can still be appended."""
        return self.capacity - self.size

    @property
    def full(self) -> bool:
        """True when no more rows fit (dead rows still occupy their slot)."""
        return self.size >= self.capacity

    @property
    def n_live(self) -> int:
        """Live (not tombstoned) row count."""
        return int(self.live.sum())

    def append(self, vecs: np.ndarray, ids: np.ndarray) -> int:
        """Append rows (must fit: check ``room`` first).  Returns the count."""
        vecs = np.atleast_2d(np.asarray(vecs, np.float32))
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        n = len(ids)
        if n > self.room:
            raise ValueError(f"segment overflow: {n} rows into {self.room}")
        s = self.size
        self.vectors[s:s + n] = vecs
        self.ids[s:s + n] = ids
        self.live[s:s + n] = True
        self.size += n
        self.version += 1
        return n

    def delete(self, ext_id: int) -> bool:
        """Tombstone one external id; False if it is not live here."""
        hit = np.nonzero((self.ids[:self.size] == ext_id)
                         & self.live[:self.size])[0]
        if len(hit) == 0:
            return False
        self.live[hit[0]] = False
        self.version += 1
        return True


@functools.partial(jax.jit, static_argnames=("k", "backend"))
def delta_scan(vectors: jax.Array, ids: jax.Array, live: jax.Array,
               qs: jax.Array, *, k: int, backend: str | None = None):
    """Exact masked scan of one segment: (B, k') ascending distances +
    external ids (k' = min(k, capacity); -1 ids past the live rows).

    Dead and never-filled rows ride the same mask the engine's tombstones
    use — their distances are INF, so they can never enter the top-k.
    """
    d = ops.l2_exact_batch(vectors, qs, backend=backend)
    d = jnp.where(live[None, :], d, search_mod.INF)
    kk = min(k, vectors.shape[0])
    neg, pos = jax.lax.top_k(-d, kk)
    out_ids = jnp.where(jnp.isfinite(neg), ids[pos], -1)
    return -neg, out_ids


def shard_delta(seg: DeltaSegment, n_shards: int, lane: int = LANE):
    """Deal a segment's rows round-robin over ``n_shards`` (row j to shard
    ``j % n_shards`` — the ``j::n_shards`` rule ``ivf.sharded_layout``
    applies per cluster), padded to a common lane-rounded width.

    Returns host arrays ``(svecs (S, F, d) f32, sids (S, F) i32,
    slive (S, F) bool)``; padding rows are dead (id -1, live False).  The
    FULL capacity is dealt (dead rows included) so the placed arrays keep
    one static shape for the segment's whole lifetime.
    """
    cap = seg.capacity
    f = (cap + n_shards - 1) // n_shards
    f = max(((f + lane - 1) // lane) * lane, lane)
    svecs = np.zeros((n_shards, f, seg.d), np.float32)
    sids = np.full((n_shards, f), -1, np.int32)
    slive = np.zeros((n_shards, f), bool)
    for j in range(n_shards):
        rows = np.arange(j, cap, n_shards)
        svecs[j, :len(rows)] = seg.vectors[rows]
        sids[j, :len(rows)] = seg.ids[rows].astype(np.int32)
        slive[j, :len(rows)] = seg.live[rows]
    return svecs, sids, slive


def place_delta(mesh, seg: DeltaSegment):
    """Shard + device_put a segment onto the serving mesh (the delta tier's
    analogue of the engine's build-time stream placement)."""
    axes = search_mod._shard_axes(mesh)
    svecs, sids, slive = shard_delta(seg, search_mod._n_shards(mesh))
    return (jax.device_put(svecs, NamedSharding(mesh, P(axes, None, None))),
            jax.device_put(sids, NamedSharding(mesh, P(axes, None))),
            jax.device_put(slive, NamedSharding(mesh, P(axes, None))))


@functools.partial(jax.jit, static_argnames=("mesh", "k", "backend"))
def delta_scan_sharded(mesh, qs: jax.Array, svecs: jax.Array,
                       sids: jax.Array, slive: jax.Array, *, k: int,
                       backend: str | None = None):
    """Mesh-sharded exact segment scan: each shard scans only its dealt
    rows, keeps a local top-k', and the survivor-only gather assembles the
    replicated (B, S*k') pool (same collective idiom as the main sharded
    searchers — a segment's candidates never cross the interconnect in
    bulk).  Returns (dists, ids); the caller's merge re-sorts.
    """
    axes = search_mod._shard_axes(mesh)

    def body(qs, vecs, ids, live):
        vecs, ids, live = vecs[0], ids[0], live[0]
        d = ops.l2_exact_batch(vecs, qs, backend=backend)
        d = jnp.where(live[None, :], d, search_mod.INF)
        kk = min(k, vecs.shape[0])
        neg, pos = jax.lax.top_k(-d, kk)
        lids = jnp.where(jnp.isfinite(neg), ids[pos], -1)
        return dist.gather_survivors(axes, -neg, lids)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axes, None, None), P(axes, None), P(axes, None)),
        out_specs=(P(), P()), check_vma=False)
    return fn(qs, svecs, sids, slive)
