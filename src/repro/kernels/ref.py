"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pq_adc(codes: jax.Array, lut: jax.Array) -> jax.Array:
    """ADC estimate: est[n] = sum_m lut[m, codes[n, m]] (squared distance)."""
    take = jax.vmap(lambda row, c: row[c], in_axes=(0, 1), out_axes=1)(
        lut, codes.astype(jnp.int32))
    return jnp.sum(take, axis=1)


def bucketize(dists: jax.Array, d_min: jax.Array, delta: jax.Array,
              ew_map: jax.Array, m: int) -> jax.Array:
    """Eq. 6 bucket ids with overflow bucket m."""
    n_ew = ew_map.shape[0]
    bin_id = jnp.floor((dists - d_min) / delta)
    overflow = bin_id >= n_ew
    bin_id = jnp.clip(bin_id, 0, n_ew - 1).astype(jnp.int32)
    bucket = ew_map[bin_id]
    return jnp.where(overflow, m, bucket).astype(jnp.int32)


def bucket_hist(dists: jax.Array, valid: jax.Array, d_min, delta,
                ew_map: jax.Array, m: int) -> tuple[jax.Array, jax.Array]:
    b = bucketize(dists, d_min, delta, ew_map, m)
    w = jnp.where(valid, 1, 0).astype(jnp.int32)
    hist = jnp.zeros((m + 1,), jnp.int32).at[b].add(w)
    return b, hist


def l2_exact(x: jax.Array, q: jax.Array) -> jax.Array:
    """Exact Euclidean distance of rows of x to q."""
    xq = jnp.matmul(x, q, precision="highest")
    return jnp.sqrt(jnp.maximum(
        jnp.sum(x * x, -1) - 2.0 * xq + jnp.sum(q * q), 0.0))


# --------------------------------------------------------------------------
# Batched (multi-query) oracles — also the CPU fast path behind ops.*_batch
# --------------------------------------------------------------------------

def pq_adc_batch(codes: jax.Array, luts: jax.Array) -> jax.Array:
    """(n, M) shared codes + (B, M, K) per-query LUTs -> (B, n) squared
    estimates.  Sequential map over queries keeps the (n, M) take
    intermediate B-independent (the batched axis is the LUT, not the codes)."""
    return jax.lax.map(lambda lut: pq_adc(codes, lut), luts)


def bucketize_batch(dists: jax.Array, d_min: jax.Array, delta: jax.Array,
                    ew_maps: jax.Array, m: int) -> jax.Array:
    """(B, n) distances, per-query codebook params -> (B, n) bucket ids."""
    return jax.vmap(bucketize, in_axes=(0, 0, 0, 0, None))(
        dists, d_min, delta, ew_maps, m)


def bucket_hist_batch(dists: jax.Array, valid: jax.Array, d_min, delta,
                      ew_maps: jax.Array, m: int):
    """Batched Eq. 6 + histogram.  Returns (bucket (B, n), hist (B, m+1)).

    The histogram comes from a sort + searchsorted (cumulative counts at
    the bucket edges) rather than a scatter-add: XLA lowers CPU scatters
    to a serial element loop, and on the host-emulated mesh (S shards
    round-robin on one core) that serial cost lands S times per batch —
    the vectorized sort is ~2x faster at bench shapes and bit-identical."""
    bucket = bucketize_batch(dists, d_min, delta, ew_maps, m)
    masked = jnp.where(valid, bucket, m + 1)       # invalid past every edge
    s = jax.lax.sort(masked, dimension=1)
    edges = jnp.arange(m + 1, dtype=jnp.int32)
    cum = jax.vmap(lambda row: jnp.searchsorted(row, edges, side="right"))(s)
    hist = jnp.diff(cum, prepend=0, axis=-1).astype(jnp.int32)
    return bucket, hist


def spec_compact_batch(bucket: jax.Array, valid: jax.Array,
                       tau_spec: jax.Array, budget: int):
    """Stream-order compaction of the lanes at or below ``tau_spec`` into a
    fixed ``budget`` position buffer (the speculative half of the fused
    shard collector).  Returns ``(pos (B, budget) int32 — sentinel n beyond
    the fill, ok (B, budget), count (B,) int32 — the TOTAL matching-lane
    count, possibly above ``budget``: the overflow signal)``."""
    n = bucket.shape[1]
    specm = valid & (bucket <= tau_spec[:, None])
    # stream-order compaction as a sort: matching lanes keep their stream
    # position as the key, everything else sorts past them as the sentinel
    # n — ascending sort + prefix slice IS "first budget matches in stream
    # order", without the serial CPU scatter
    key = jnp.where(specm, jnp.arange(n, dtype=jnp.int32)[None, :], n)
    pos = jax.lax.sort(key, dimension=1)[:, :budget]
    if budget > n:    # static: pad sentinel columns up to the budget width
        pad = jnp.full((bucket.shape[0], budget - n), n, jnp.int32)
        pos = jnp.concatenate([pos, pad], axis=1)
    return pos, pos < n, jnp.sum(specm, axis=1).astype(jnp.int32)


def shard_collect_batch(dists: jax.Array, valid: jax.Array, d_min, delta,
                        ew_maps: jax.Array, m: int, tau_spec: jax.Array,
                        budget: int):
    """Oracle for the fused shard-collect kernel: bucketize + histogram +
    speculative stream-order compaction at the provisional ``tau_spec``
    (-1 compacts nothing).  Returns ``(bucket (B, n), hist (B, m+1),
    spec_pos (B, budget), spec_ok (B, budget), spec_count (B,))``.

    One composite-key sort serves both halves instead of the two
    full-stream sorts of ``bucket_hist_batch`` + ``spec_compact_batch``:
    ``key = masked_bucket * n + lane`` is bucket-major with stream order
    inside each bucket, so cumulative counts at the bucket edges give the
    histogram and — whenever every row's match count fits ``budget`` — the
    sorted prefix holds ALL matching lanes, and a budget-width re-sort by
    lane index restores the exact stream-order buffer the Pallas kernel
    emits.  A row overflowing ``budget`` truncates stream-first, which the
    bucket-major prefix cannot reproduce, so that (rare: the survivor
    tiers discard the buffer anyway) batch falls back to the dedicated
    position sort under a ``cond``.  Requires ``n * (m + 2) < 2**31``."""
    bucket = bucketize_batch(dists, d_min, delta, ew_maps, m)
    bq, n = bucket.shape
    # key max is (m+1)*n + (n-1) < (m+2)*n; past int32 the sort silently
    # corrupts the histogram and buffer, so fail loudly at trace time
    assert n * (m + 2) < 2**31, (
        f"shard_collect_batch composite key overflows int32: "
        f"n={n}, m={m} needs n*(m+2) < 2**31")
    lane = jnp.arange(n, dtype=jnp.int32)[None, :]
    key = jnp.where(valid, bucket, m + 1) * n + lane
    skeys = jax.lax.sort(key, dimension=1)
    edges = (jnp.arange(m + 1, dtype=jnp.int32) + 1) * n
    cum = jax.vmap(
        lambda row: jnp.searchsorted(row, edges, side="left"))(skeys)
    hist = jnp.diff(cum, prepend=0, axis=-1).astype(jnp.int32)
    t = jnp.clip(tau_spec, -1, m).astype(jnp.int32)
    csum = jnp.concatenate(
        [jnp.zeros((bq, 1), jnp.int32), cum.astype(jnp.int32)], axis=1)
    count = jnp.take_along_axis(csum, (t + 1)[:, None], axis=1)[:, 0]

    pw = min(budget, n)

    def fast(_):
        prefix = skeys[:, :pw]
        match = prefix < (t[:, None] + 1) * n
        pos = jax.lax.sort(jnp.where(match, prefix % n, n), dimension=1)
        if budget > n:
            pad = jnp.full((bq, budget - n), n, jnp.int32)
            pos = jnp.concatenate([pos, pad], axis=1)
        return pos

    def slow(_):
        p, _, _ = spec_compact_batch(bucket, valid, tau_spec, budget)
        return p

    pos = jax.lax.cond(jnp.all(count <= budget), fast, slow, None)
    return bucket, hist, pos, pos < n, count


def l2_exact_batch(x: jax.Array, qs: jax.Array) -> jax.Array:
    """(n, d) shared vectors, (B, d) queries -> (B, n) exact distances via
    one norm-identity matmul."""
    x_sq = jnp.sum(x * x, axis=-1)
    q_sq = jnp.sum(qs * qs, axis=-1)
    xv = jnp.matmul(qs, x.T, precision="highest")
    return jnp.sqrt(jnp.maximum(
        x_sq[None, :] - 2.0 * xv + q_sq[:, None], 0.0))


def fused_scan_batch(
    codes: jax.Array,    # (n, M) shared PQ codes
    vectors: jax.Array,  # (n, d) shared fp32 vectors
    valid: jax.Array,    # (B, n) per-query lane validity
    luts: jax.Array,     # (B, M, K)
    qs: jax.Array,       # (B, d)
    d_min, delta,        # (B,)
    ew_maps: jax.Array,  # (B, n_ew)
    m: int,
    tau_pred: jax.Array, # (B,) int32
):
    """Oracle for the batched fused kernel.

    Returns (est (B, n), bucket (B, n), hist (B, m+1), early (B, n),
    nmiss (B,)) where nmiss counts the valid lanes NOT covered inline
    (bucket > tau_pred) — the upper bound on second-pass gather work."""
    est = jnp.sqrt(jnp.maximum(pq_adc_batch(codes, luts), 0.0))
    est = jnp.where(valid, est, jnp.inf)
    b = bucketize_batch(est, d_min, delta, ew_maps, m)
    w = jnp.where(valid, 1, 0).astype(jnp.int32)
    hist = jax.vmap(
        lambda bb, ww: jnp.zeros((m + 1,), jnp.int32).at[bb].add(ww))(b, w)
    ex = l2_exact_batch(vectors, qs)
    pred = valid & (b <= tau_pred[:, None])
    early = jnp.where(pred, ex, jnp.inf)
    nmiss = jnp.sum(valid & ~pred, axis=1).astype(jnp.int32)
    return est, b, hist, early, nmiss


def rabitq_bounds_stream(codes_s: jax.Array, norm_o: jax.Array,
                         f_o: jax.Array, cl: jax.Array,
                         centroids: jax.Array, rot: jax.Array,
                         qs: jax.Array, d2: jax.Array,
                         lane_valid: jax.Array, eps0: float):
    """Batched RaBitQ estimator over a candidate stream (the CPU production
    bounds pass AND the inner math of the fused-kernel mirror; a shard's
    local stream is just a shorter stream).

    The per-(query, cluster) rotated residual decomposes as
    ``P(q - c) = Pq - Pc``, so the code inner products for every query are
    ONE (n_stream, d) x (d, B) matmul plus a per-lane centroid correction —
    the batched-native form of ``rabitq.query_factors`` + ``estimate``
    (mathematically identical; floating-point association differs from the
    per-cluster matvec of the single-query path).  ``d2`` is the (B, C)
    squared query-centroid distance matrix the routing pass already built;
    ``cl`` maps each stream lane to its (clamped) owning cluster.
    """
    g = jnp.matmul(qs, rot.T, precision="highest")            # (B, d) = Pq
    h = jnp.matmul(centroids, rot.T, precision="highest")     # (C, d) = Pc
    s1 = jnp.matmul(codes_s, g.T, precision="highest")    # (n_stream, B)
    s2 = jnp.sum(codes_s * h[cl], axis=1)                     # (n_stream,)
    nq = jnp.sqrt(d2)                                         # (B, C) norm_q
    nq_lane = nq[:, cl]                                       # (B, n_stream)
    d = codes_s.shape[1]
    xv = (s1.T - s2[None, :]) / (
        jnp.sqrt(jnp.float32(d)) * jnp.maximum(nq_lane, 1e-12))
    ip = xv / f_o[None, :]
    err = eps0 * jnp.sqrt((1.0 - f_o ** 2) / (f_o ** 2 * (d - 1)))
    scale = 2.0 * nq_lane * norm_o[None, :]
    base = nq_lane ** 2 + norm_o[None, :] ** 2
    zero = jnp.zeros_like(base)
    est = jnp.sqrt(jnp.maximum(base - scale * ip, zero))
    lb = jnp.sqrt(jnp.maximum(base - scale * (ip + err[None, :]), zero))
    ub = jnp.sqrt(jnp.maximum(base - scale * (ip - err[None, :]), zero))
    bad = ~lane_valid
    inf = jnp.inf
    return (jnp.where(bad, inf, est), jnp.where(bad, inf, lb),
            jnp.where(bad, inf, ub))


def fused_rabitq_scan_batch(
    codes_s: jax.Array,   # (n, d) ±1 stream codes (fp32)
    vectors: jax.Array,   # (n, d) shared fp32 re-rank vectors
    norm_o: jax.Array,    # (n,)
    f_o: jax.Array,       # (n,)
    cl: jax.Array,        # (n,) clamped owning cluster per lane
    centroids: jax.Array,  # (C, d)
    rot: jax.Array,       # (d, d)
    qs: jax.Array,        # (B, d)
    d2: jax.Array,        # (B, C) squared query-centroid distances
    valid: jax.Array,     # (B, n)
    d_min, delta,         # (B,)
    ew_maps: jax.Array,   # (B, n_ew)
    m: int,
    tau_inline: jax.Array,  # (B,) int32; -1 certifies nothing
    eps0: float = 3.0,
):
    """Oracle for the bound-fused RaBitQ kernel.

    Returns ``(est, lb, ub, bucket_lb, bucket_ub, hist_lb, hist_ub, exact,
    certified, nmiss)`` where ``exact`` carries the inline exact re-rank of
    bound-certified lanes (lower-bound bucket at or below ``tau_inline``)
    and +inf elsewhere, and ``nmiss`` counts the valid lanes the inline
    pass left to the second gather.  ``hist_ub`` is the band anchor (the
    codebook is built from upper bounds) and the cross-batch predictor's
    EMA input; ``hist_lb`` feeds the certain-in threshold.
    """
    est, lb, ub = rabitq_bounds_stream(codes_s, norm_o, f_o, cl, centroids,
                                       rot, qs, d2, valid, eps0)
    bucket_lb = bucketize_batch(lb, d_min, delta, ew_maps, m)
    bucket_ub = bucketize_batch(ub, d_min, delta, ew_maps, m)
    w = jnp.where(valid, 1, 0).astype(jnp.int32)
    hist = jax.vmap(
        lambda bb, ww: jnp.zeros((m + 1,), jnp.int32).at[bb].add(ww))
    hist_lb = hist(bucket_lb, w)
    hist_ub = hist(bucket_ub, w)
    ex = l2_exact_batch(vectors, qs)
    certified = valid & (bucket_lb <= tau_inline[:, None])
    exact = jnp.where(certified, ex, jnp.inf)
    nmiss = jnp.sum(valid & ~certified, axis=1).astype(jnp.int32)
    return (est, lb, ub, bucket_lb, bucket_ub, hist_lb, hist_ub, exact,
            certified, nmiss)


def fused_scan(
    codes: jax.Array,    # (n, M) uint8/int32 PQ codes
    vectors: jax.Array,  # (n, d) fp32
    valid: jax.Array,    # (n,)
    lut: jax.Array,      # (M, K)
    q: jax.Array,        # (d,)
    d_min, delta,
    ew_map: jax.Array,   # (n_ew,)
    m: int,
    tau_pred: jax.Array, # scalar int32
):
    """Oracle for the fused estimate+bucketize+hist+early-exact kernel.

    Returns (est, bucket, hist, early_exact, nmiss) where early_exact[i] is
    the exact distance when bucket[i] <= tau_pred (and valid), else +inf, and
    nmiss is the scalar count of valid lanes not covered inline.
    """
    est2 = pq_adc(codes, lut)
    est = jnp.sqrt(jnp.maximum(est2, 0.0))
    est = jnp.where(valid, est, jnp.inf)
    b = bucketize(est, d_min, delta, ew_map, m)
    w = jnp.where(valid, 1, 0).astype(jnp.int32)
    hist = jnp.zeros((m + 1,), jnp.int32).at[b].add(w)
    ex = l2_exact(vectors, q)
    pred = valid & (b <= tau_pred)
    early = jnp.where(pred, ex, jnp.inf)
    nmiss = jnp.sum(valid & ~pred).astype(jnp.int32)
    return est, b, hist, early, nmiss
