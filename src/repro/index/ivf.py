"""IVF coarse index: k-means partition, padded-cluster layout, query routing.

Layout: clusters are stored as a dense (n_clusters, cap) id matrix with a
validity mask — XLA needs static shapes, and the padded layout is also what a
TPU serving deployment uses (fixed-size cluster tiles streaming HBM->VMEM).
``cap`` is the max cluster size rounded up to the lane width.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.index import kmeans as km


class IVFIndex(NamedTuple):
    """Coarse IVF index: centroids plus the padded per-cluster member table."""
    centroids: jax.Array      # (n_clusters, d)
    member_ids: jax.Array     # (n_clusters, cap) int32, -1 padded
    member_valid: jax.Array   # (n_clusters, cap) bool
    cluster_sizes: jax.Array  # (n_clusters,)

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def cap(self) -> int:
        return self.member_ids.shape[1]


def build(key: jax.Array, x: jax.Array, n_clusters: int, n_iter: int = 10,
          lane: int = 128) -> IVFIndex:
    """k-means + padded member table (see ``build_assigned``)."""
    return build_assigned(key, x, n_clusters, n_iter, lane)[0]


def build_assigned(key: jax.Array, x: jax.Array, n_clusters: int,
                   n_iter: int = 10,
                   lane: int = 128) -> tuple[IVFIndex, jax.Array]:
    """k-means + padded member table, and the (n,) assignment it packed:
    row i is a member of list ``a[i]``.  Members are packed on the host by
    one stable argsort of the assignment, so each list holds its ids in
    ascending order (build is offline)."""
    n = x.shape[0]
    chunks = km.n_chunks(n, n_clusters)
    with jax.profiler.TraceAnnotation("index.kmeans", rows=n, chunks=chunks,
                                      n_clusters=n_clusters):
        cent = jax.block_until_ready(km.lloyd(key, x, n_clusters, n_iter))
    with jax.profiler.TraceAnnotation("index.assign", rows=n, chunks=chunks,
                                      n_clusters=n_clusters):
        a = jax.block_until_ready(km.assign_chunked(x, cent))
        a_np = np.asarray(a)
    sizes = np.bincount(a_np, minlength=n_clusters)
    cap = int(max(int(sizes.max()), 1))
    cap = ((cap + lane - 1) // lane) * lane
    with jax.profiler.TraceAnnotation("index.pack", rows=n,
                                      n_clusters=n_clusters, cap=cap):
        order = np.argsort(a_np, kind="stable").astype(np.int32)
        starts = np.cumsum(sizes) - sizes
        slot = np.arange(n) - np.repeat(starts, sizes)
        ids = np.full((n_clusters, cap), -1, np.int32)
        ids[np.repeat(np.arange(n_clusters), sizes), slot] = order
        index = IVFIndex(
            centroids=cent,
            member_ids=jnp.asarray(ids),
            member_valid=jnp.asarray(ids >= 0),
            cluster_sizes=jnp.asarray(sizes.astype(np.int32)),
        )
    return index, a


def route(index: IVFIndex, q: jax.Array, n_probe: int) -> jax.Array:
    """Nearest-first probed cluster list (paper Alg. 4 relies on this order:
    'clusters are traversed from nearest to farthest')."""
    d2 = jnp.sum((index.centroids - q) ** 2, axis=-1)
    return jax.lax.top_k(-d2, n_probe)[1].astype(jnp.int32)


def route_batch_centroids(centroids: jax.Array, qs: jax.Array,
                          n_probe: int) -> tuple[jax.Array, jax.Array]:
    """Centroids-level batch routing: (B, n_probe) nearest-first probed
    clusters + the (B, C) squared query-centroid distances.

    Uses the same per-query distance expression as ``route`` (broadcast
    difference, not the norm-identity matmul) so the probed sets match the
    single-query path bit-for-bit; the centroid table is small enough that
    the (B, C, d) broadcast is cheap.  ``d2`` is returned so estimators that
    need the query-centroid norms (RaBitQ) don't rebuild the broadcast.
    The mesh-sharded searchers call this form directly inside their
    shard_map bodies (replicated routing) — single-device and sharded paths
    MUST route identically, so keep this the one implementation.
    """
    d2 = jnp.sum((centroids[None, :, :] - qs[:, None, :]) ** 2, axis=-1)
    return jax.lax.top_k(-d2, n_probe)[1].astype(jnp.int32), d2


def route_batch_d2(index: IVFIndex, qs: jax.Array,
                   n_probe: int) -> tuple[jax.Array, jax.Array]:
    """(B, n_probe) probed clusters + (B, C) squared distances — one shared
    routing pass (see ``route_batch_centroids``)."""
    return route_batch_centroids(index.centroids, qs, n_probe)


def route_batch(index: IVFIndex, qs: jax.Array, n_probe: int) -> jax.Array:
    """(B, n_probe) probed clusters (see ``route_batch_d2``)."""
    return route_batch_d2(index, qs, n_probe)[0]


def gather_candidates(
    index: IVFIndex, probed: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(n_probe, cap) candidate ids + validity for the probed clusters."""
    ids = index.member_ids[probed]
    valid = index.member_valid[probed]
    return ids, valid


# --------------------------------------------------------------------------
# Compact flat layout (batched search substrate)
# --------------------------------------------------------------------------

class FlatLayout(NamedTuple):
    """Corpus ids re-ordered by cluster, with zero per-cluster padding.

    The padded (n_clusters, cap) member table wastes (cap - |cluster|) lanes
    per probed cluster — on skewed corpora that is most of the scan.  The
    flat layout is the batched-search substrate: the candidate stream is
    gathered ONCE per batch in cluster order, and each query selects its
    probed lanes with a boolean mask (``probe_mask``).  Only the stream tail
    is padded (to the lane width).

    ``order``      : (n_flat,) int32 corpus ids, cluster-major.
    ``cluster_of`` : (n_flat,) int32 owning cluster; ``n_clusters`` on the
                     padding tail (maps to the always-False probe-mask slot).
    ``offsets``    : (n_clusters + 1,) int32 start offset of each cluster.
    ``valid``      : (n_flat,) bool, False on the padding tail.
    """

    order: jax.Array
    cluster_of: jax.Array
    offsets: jax.Array
    valid: jax.Array

    @property
    def n_flat(self) -> int:
        return self.order.shape[0]


def flat_layout(index: IVFIndex, lane: int = 128) -> FlatLayout:
    """Host-side packing of the member table into a FlatLayout (offline):
    the valid member ids row by row, which is cluster-major."""
    ids = np.asarray(index.member_ids)
    sizes = np.asarray(index.cluster_sizes).astype(np.int64)
    n_clusters = ids.shape[0]
    n = int(sizes.sum())
    n_flat = ((n + lane - 1) // lane) * lane
    order = np.zeros(n_flat, np.int32)
    order[:n] = ids[ids >= 0]
    cluster_of = np.full(n_flat, n_clusters, np.int32)
    cluster_of[:n] = np.repeat(np.arange(n_clusters, dtype=np.int32), sizes)
    offsets = np.zeros(n_clusters + 1, np.int32)
    offsets[1:] = np.cumsum(sizes)
    valid = np.arange(n_flat) < n
    return FlatLayout(
        order=jnp.asarray(order),
        cluster_of=jnp.asarray(cluster_of),
        offsets=jnp.asarray(offsets),
        valid=jnp.asarray(valid),
    )


def probe_mask(layout: FlatLayout, probed: jax.Array,
               n_clusters: int) -> jax.Array:
    """(B, n_flat) lane mask: lane j is live for query b iff its cluster is
    in ``probed[b]`` (and j is not stream-tail padding)."""
    b = probed.shape[0]
    hit = jnp.zeros((b, n_clusters + 1), bool)
    hit = hit.at[jnp.arange(b, dtype=jnp.int32)[:, None], probed].set(True)
    hit = hit.at[:, n_clusters].set(False)   # padding-tail slot stays dead
    return hit[:, layout.cluster_of] & layout.valid[None, :]


def tile_positions(layout: FlatLayout, clusters: jax.Array,
                   cap: int) -> tuple[jax.Array, jax.Array]:
    """Stream positions of the members of ``clusters`` (B, t), padded to
    ``cap`` lanes per cluster.

    Returns (positions (B, t * cap) int32, valid (B, t * cap)).  Used to
    gather per-query views (codebook samples, per-cluster re-rank tiles)
    out of batched (B, n_flat) stream quantities.
    """
    offs = layout.offsets[clusters]                       # (B, t)
    sizes = layout.offsets[clusters + 1] - offs           # (B, t)
    lane = jnp.arange(cap, dtype=jnp.int32)
    pos = offs[..., None] + lane[None, None, :]           # (B, t, cap)
    ok = lane[None, None, :] < sizes[..., None]
    pos = jnp.where(ok, pos, 0)
    b, t = clusters.shape
    return pos.reshape(b, t * cap), ok.reshape(b, t * cap)


# --------------------------------------------------------------------------
# Mesh-sharded layout (distributed search substrate)
# --------------------------------------------------------------------------

class ShardedLayout(NamedTuple):
    """Row-sharded partition of the ``FlatLayout`` candidate stream.

    Each cluster's members are dealt round-robin across shards, so every chip
    holds ~1/S of EVERY cluster — the per-chip scan work is balanced no
    matter which clusters a query probes, and the global top-k of any probe
    set spreads evenly over shards (which is what makes a small fixed
    per-shard survivor budget safe; see ``core.distributed``).

    All arrays are stacked with a leading shard axis so they shard over the
    mesh's ``model`` axis with ``P("model", None)`` and each chip's block is
    itself a valid ``FlatLayout`` (same field meanings, global corpus ids):

    ``order``      : (S, F) int32 global corpus ids, cluster-major per shard.
    ``cluster_of`` : (S, F) int32 owning cluster; ``n_clusters`` on padding.
    ``offsets``    : (S, C + 1) int32 per-shard cluster start offsets.
    ``valid``      : (S, F) bool, False on each shard's padding tail.

    Built host-side (offline, like ``flat_layout``); ``cap_shard`` — the max
    per-shard cluster segment length, needed as a static width by
    ``tile_positions`` on shard-local layouts — is returned alongside.
    """

    order: jax.Array
    cluster_of: jax.Array
    offsets: jax.Array
    valid: jax.Array

    @property
    def n_shards(self) -> int:
        return self.order.shape[0]

    @property
    def shard_flat(self) -> int:
        return self.order.shape[1]

    def local(self, j: int | jax.Array) -> FlatLayout:
        """Shard j's block as a FlatLayout (use inside shard_map bodies on
        the squeezed per-shard arrays, or host-side for tests)."""
        return FlatLayout(order=self.order[j], cluster_of=self.cluster_of[j],
                          offsets=self.offsets[j], valid=self.valid[j])


def sharded_layout(index: IVFIndex, n_shards: int,
                   lane: int = 128) -> tuple[ShardedLayout, int]:
    """Partition the member table into ``n_shards`` stream segments
    (host-side, offline).  Returns ``(layout, cap_shard)``.

    Shard j takes members ``j::n_shards`` of every cluster, preserving the
    cluster-major order inside each shard, so concatenating the shards'
    per-cluster segments reconstructs each cluster's member set exactly
    (asserted by tests/test_sharded.py).
    """
    ids = np.asarray(index.member_ids)
    sizes = np.asarray(index.cluster_sizes).astype(np.int64)
    n_clusters = ids.shape[0]
    seg = [[ids[c, : sizes[c]][j::n_shards] for c in range(n_clusters)]
           for j in range(n_shards)]
    flat_sizes = [sum(len(s) for s in segs) for segs in seg]
    f = max(max(flat_sizes), 1)
    f = ((f + lane - 1) // lane) * lane
    order = np.zeros((n_shards, f), np.int32)
    cluster_of = np.full((n_shards, f), n_clusters, np.int32)
    offsets = np.zeros((n_shards, n_clusters + 1), np.int32)
    valid = np.zeros((n_shards, f), bool)
    cap_shard = 1
    for j in range(n_shards):
        pos = 0
        for c in range(n_clusters):
            s = seg[j][c]
            offsets[j, c] = pos
            order[j, pos:pos + len(s)] = s
            cluster_of[j, pos:pos + len(s)] = c
            pos += len(s)
            cap_shard = max(cap_shard, len(s))
        offsets[j, n_clusters] = pos
        valid[j, :pos] = True
    return (
        ShardedLayout(
            order=jnp.asarray(order),
            cluster_of=jnp.asarray(cluster_of),
            offsets=jnp.asarray(offsets),
            valid=jnp.asarray(valid),
        ),
        int(cap_shard),
    )


