"""Distributed BBC: shard_map search step over the production mesh.

This is the beyond-paper extension recorded in DESIGN.md §2/§4: the paper's
L1-resident bucket histogram becomes the *collective payload* of a sharded
search.  The corpus (codes + vectors) is sharded row-wise over the ``model``
axis; query batches are sharded over ``data`` (and replicated groups over
``pod``).  One search step per query:

  1. every chip scans its local shard -> local estimated distances,
  2. local (m+1)-histogram; ``psum`` over 'model'   <- m*4 bytes, NOT k*8,
  3. global threshold bucket tau from the summed histogram,
  4. local relaxed-threshold pruning + cumsum compaction to a fixed
     per-chip survivor budget  ~ k / n_shards * slack,
  5. ``all_gather`` of survivors only (~k total, vs n_scanned naively),
  6. final in-threshold-bucket selection (Alg. 1 Collect).

A naive distributed top-k instead all-gathers each chip's running top-k
(k * 8 bytes per chip).  ``collective_cost_model`` quantifies both for the
roofline table.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import buffer as rb

INF = jnp.inf


def _axes_tuple(axis_name) -> tuple[str, ...]:
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


def hier_psum(x: jax.Array, axis_name) -> jax.Array:
    """Hierarchical all-reduce: psum over the stream-sharding axes one at a
    time, innermost (last) first.  On a 1-D ("model",) mesh this is a plain
    psum; on a 2-D ("host", "model") mesh it is the intra-host ICI reduce
    followed by an inter-host psum of the already-reduced per-host partial —
    so the DCN tier carries the same O(m) histogram payload as the ICI tier
    instead of S_model copies of it."""
    for ax in reversed(_axes_tuple(axis_name)):
        x = jax.lax.psum(x, ax)
    return x


def _gather_cols(r: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """all_gather along axis=1, innermost mesh axis first (intra-host
    concatenation, then the inter-host hop carries whole per-host blocks)."""
    for ax in reversed(axes):
        r = jax.lax.all_gather(r, ax, axis=1, tiled=True)
    return r


def _gather_rows(r: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """all_gather along axis=0, innermost mesh axis first — row order after
    reassembly matches the outer-major composite shard index."""
    for ax in reversed(axes):
        r = jax.lax.all_gather(r, ax, axis=0, tiled=True)
    return r


def shard_rows(axis_name, sizes: tuple, fn, *arrays: jax.Array):
    """Split a REPLICATED per-row computation over the shard axes.

    Inside a shard_map body, math after a gather/psum runs identically on
    every shard — S serialized copies on an emulated host mesh, S-1 idle
    chips on real hardware.  For row-independent ``fn`` (a per-query sort /
    top-k over replicated input), each shard instead computes only its
    contiguous slice of the rows and the slices are all_gathered back, so
    the work is done once, spread across the axis.  ``sizes`` are the mesh
    axis sizes matching ``axis_name`` (static, from the caller's mesh).
    Rows are padded to a multiple of the shard count by wrapping, then
    trimmed after the gather.  Returns ``fn``'s output(s), replicated,
    with the original row count."""
    axes = _axes_tuple(axis_name)
    if not axes or len(sizes) != len(axes):
        return fn(*arrays)
    s = 1
    for z in sizes:
        s *= int(z)
    b = arrays[0].shape[0]
    rows = -(-b // s)
    bp = rows * s
    idx = jnp.int32(0)
    for ax, sz in zip(axes, sizes):      # outer-major composite index
        idx = idx * int(sz) + jax.lax.axis_index(ax)

    def _pad(a):
        if bp == b:
            return a
        return jnp.take(a, jnp.arange(bp) % b, axis=0)

    sls = [jax.lax.dynamic_slice_in_dim(_pad(a), idx * rows, rows, axis=0)
           for a in arrays]
    out = fn(*sls)
    leaves, treedef = jax.tree_util.tree_flatten(out)
    g = [_gather_rows(o, axes)[:b] for o in leaves]
    return jax.tree_util.tree_unflatten(treedef, g)


class ShardedSearchResult(NamedTuple):
    """Sharded BBC collective output: global top-k, tau, per-shard survivor
    counts."""
    topk_dists: jax.Array
    topk_ids: jax.Array
    tau: jax.Array
    survivors_per_shard: jax.Array


def survivor_budget(k: int, n_shards: int, slack: float = 2.0) -> int:
    """Fixed per-chip survivor budget: balanced shards hold ~k/n_shards of the
    global top-k; ``slack`` covers shard skew.  128-lane aligned."""
    b = int(k / max(n_shards, 1) * slack) + 128
    return ((b + 127) // 128) * 128


def bbc_shard_search(
    local_dists: jax.Array,   # (n_local,) estimated distances of this shard
    local_ids: jax.Array,     # (n_local,) global ids
    local_valid: jax.Array,   # (n_local,) bool
    cb: rb.BucketCodebook,    # replicated per-query codebook
    k: int,
    n_shards: int,
    axis_name: str = "model",
    budget: int | None = None,
) -> ShardedSearchResult:
    """Per-shard body (call under shard_map).  Single query; vmap for batches.

    ``n_shards`` must be the static size of ``axis_name`` (budgets are shapes).
    """
    m = cb.m
    if budget is None:
        budget = survivor_budget(k, n_shards)

    bucket_ids = rb.bucketize(cb, jnp.where(local_valid, local_dists, INF))
    local_hist = rb.histogram(bucket_ids, m, local_valid)

    # THE collective: m+1 int32 counters instead of k (dist,id) pairs.
    global_hist = jax.lax.psum(local_hist, axis_name)
    tau, _ = rb.threshold_bucket(global_hist, k)

    # Local relaxed-threshold pruning + O(n) compaction to the fixed budget.
    survive = local_valid & (bucket_ids <= tau)
    idx, ok = rb.compact_mask(survive, budget)
    safe = jnp.minimum(idx, local_dists.shape[0] - 1)
    sd = jnp.where(ok, local_dists[safe], INF)
    si = jnp.where(ok, local_ids[safe], -1)

    # Gather only survivors (~k total across shards).
    gd = jax.lax.all_gather(sd, axis_name, tiled=True)
    gi = jax.lax.all_gather(si, axis_name, tiled=True)

    # Final selection (replicated, tiny: budget * n_shards elements).
    neg, order = jax.lax.top_k(-gd, k)
    return ShardedSearchResult(
        topk_dists=-neg,
        topk_ids=gi[order],
        tau=tau,
        survivors_per_shard=jnp.sum(survive),
    )


# --------------------------------------------------------------------------
# Batched collective primitives (the real-index path; see index/search.py)
# --------------------------------------------------------------------------

def bbc_survivors_batch(
    bucket: jax.Array,   # (B, F) local bucket ids
    key: jax.Array,      # (B, F) local selection keys (distance-like, asc)
    valid: jax.Array,    # (B, F) local live-lane mask
    hist: jax.Array,     # (B, m+1) local histograms
    count: int,          # global selection size (k, or n_cand for IVF+PQ)
    budget: int,         # static per-shard survivor budget
    axis_name="model",   # str, or a tuple for the hierarchical schedule
    tau_floor: jax.Array | None = None,  # scalar int32 predicted threshold
    spec: tuple | None = None,  # speculative buffer (pos, ok, count, tau)
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Batched core of the distributed BBC collector (call under shard_map).

    THE collective is the ``psum`` of (B, m+1) int32 histograms — m counters
    per query instead of the k (dist, id) pairs a naive distributed top-k
    all-gathers.  From the summed histogram every shard derives the same
    per-query threshold bucket tau; lanes at or below tau survive, compacted
    into the fixed ``budget``.  The global top-``count`` stays intact as
    long as no single shard owns more than ``budget`` of it (round-robin
    sharding makes shares ~count/S; see ``survivor_budget``).

    ``tau_floor`` is the predictive subsystem's hook: the engine-owned
    cross-batch predictor supplies its tau_pred and the survivor threshold
    becomes max(tau, tau_floor), so a shard whose scan already early-exacted
    the predicted buckets keeps those lanes even when this batch's true tau
    lands lower (overshoot only widens the pool — the final exact top-k is
    unchanged; undershoot is a no-op because tau dominates).

    ``spec`` is the fused scan-collect fast path
    (``ops.shard_collect_batch``): ``(spec_pos, spec_ok, spec_count,
    tau_spec)`` — lanes at or below the provisional ``tau_spec`` already
    compacted in stream order while the scan tiles were resident.  Three
    tiers, cheapest that is exact wins:

      1. covered (tau_spec >= tau everywhere, no buffer overflow): filter
         the buffer down to tau — O(budget), no second stream pass;
      2. undershoot but every shard's survivors fit ``budget``: one bounded
         O(F) stream-order compaction correction pass;
      3. overflow: the exact key-priority ``top_k`` fallback (survivors
         beyond ``budget`` drop farthest-first, as the pre-fused collector
         always did).

    Every tier yields the same survivor ID SET as the pre-fused collector
    (tiers 1-2 are stream-ordered rather than key-ordered — downstream
    selection is order-invariant).  Without ``spec`` tier 3 runs
    unconditionally (the legacy behavior, with ``budget`` clamped to the
    stream length so short-stream shards cannot crash the top_k).

    Returns ``(pos, ok, tau, n_survive, global_hist)``: local survivor stream
    positions (B, budget) with validity, the per-query threshold bucket (B,),
    this shard's per-query survivor count (B,) before budgeting, and the
    psum'd (B, m+1) histogram (replicated — the predictor's update input).
    """
    f = key.shape[1]
    global_hist = hier_psum(hist, axis_name)
    tau, _ = jax.vmap(rb.threshold_bucket, in_axes=(0, None))(
        global_hist, count)
    if tau_floor is not None:
        tau = jnp.maximum(tau, tau_floor)
    survive = valid & (bucket <= tau[:, None])
    n_survive = jnp.sum(survive, axis=1)

    def exact_topk(_):
        kk = min(budget, f)
        masked = jnp.where(survive, key, INF)
        neg, pos = jax.lax.top_k(-masked, kk)
        ok = jnp.isfinite(-neg)
        if kk < budget:
            pos = jnp.pad(pos, ((0, 0), (0, budget - kk)))
            ok = jnp.pad(ok, ((0, 0), (0, budget - kk)))
        return pos, ok

    if spec is None:
        pos, ok = exact_topk(None)
        return pos, ok, tau, n_survive, global_hist

    spos, sok, scount, tau_spec = spec

    def fast(_):
        safe = jnp.minimum(spos, f - 1)
        sb = jnp.take_along_axis(bucket, safe, axis=1)
        sk = jnp.take_along_axis(key, safe, axis=1)
        keep = sok & (sb <= tau[:, None]) & jnp.isfinite(sk)
        return safe, keep

    def correction(_):
        idx, okc = jax.vmap(lambda s: rb.compact_mask(s, budget))(survive)
        return jnp.minimum(idx, f - 1), okc

    covered = jnp.all((tau_spec >= tau) & (scount <= budget))
    fits = jnp.all(n_survive <= budget)
    pos, ok = jax.lax.cond(
        covered, fast,
        lambda op: jax.lax.cond(fits, correction, exact_topk, op), None)
    return pos, ok, tau, n_survive, global_hist


def split_certified_survivors(pos: jax.Array, ok: jax.Array,
                              certified: jax.Array):
    """Partition a shard's budget-compacted survivors by the bound-fused
    scan's inline coverage.

    ``pos``/``ok`` are ``bbc_survivors_batch``'s (B, budget) local survivor
    positions; ``certified`` is the scan's (B, F) inline-coverage mask
    (lower-bound bucket at or below the gate — those lanes' exact distances
    came out of the fused kernel while their vector tile was resident).
    Returns ``(cert_ok, strag_ok)``: survivors whose values the scan already
    holds, and the STRAGGLERS — the only rows the on-shard second gather
    pass must touch, and the quantity the psum'd measured ``n_second_pass``
    counts.
    """
    cert_ok = jnp.take_along_axis(certified, pos, axis=1) & ok
    return cert_ok, ok & ~cert_ok


def gather_survivors(axis_name, *rows: jax.Array) -> tuple[jax.Array, ...]:
    """All-gather per-shard (B, budget) survivor rows into (B, S * budget)
    — the survivor-only collective (~count total elements across shards,
    vs n_scanned for a full gather).  ``axis_name`` may be a tuple of mesh
    axes for the hierarchical schedule (innermost gathered first)."""
    axes = _axes_tuple(axis_name)
    return tuple(_gather_cols(r, axes) for r in rows)


def naive_shard_search(
    local_dists: jax.Array,
    local_ids: jax.Array,
    local_valid: jax.Array,
    k: int,
    axis_name="model",
) -> tuple[jax.Array, jax.Array]:
    """Baseline distributed collector: local exact top-k, all-gather k per
    shard, re-select.  Collective payload k*8 bytes/chip."""
    axes = _axes_tuple(axis_name)
    d = jnp.where(local_valid, local_dists, INF)
    kk = min(k, d.shape[0])
    neg, idx = jax.lax.top_k(-d, kk)
    gd = _gather_cols(-neg[None], axes)[0]
    gi = _gather_cols(local_ids[idx][None], axes)[0]
    neg2, order = jax.lax.top_k(-gd, k)
    return -neg2, gi[order]


def collective_cost_model(k: int, m: int, n_shards: int, budget: int | None = None,
                          link_bw: float = 50e9, n_hosts: int = 1,
                          dcn_bw: float = 25e9) -> dict:
    """Bytes on the wire per query: BBC vs naive distributed top-k.

    ring all-reduce of h bytes  ~ 2*h*(S-1)/S per link;
    ring all-gather of b bytes/shard ~ b*(S-1) per link.

    ``n_hosts > 1`` additionally prices the hierarchical (intra-host ICI,
    then inter-host DCN) schedule: the DCN all-reduce moves the SAME O(m)
    histogram (already host-reduced) over the ``n_hosts`` ring, and the DCN
    all-gather moves each host's concatenated survivor block — the naive
    collector pays k pairs per *shard* on that tier too.
    """
    if budget is None:
        budget = survivor_budget(k, n_shards)
    s = n_shards
    hist_bytes = 4 * (m + 1)
    bbc_wire = 2 * hist_bytes * (s - 1) / s + 8 * budget * (s - 1)
    naive_wire = 8 * k * (s - 1)
    out = {
        "bbc_bytes_per_link": bbc_wire,
        "naive_bytes_per_link": naive_wire,
        "ratio": naive_wire / max(bbc_wire, 1e-9),
        "bbc_collective_seconds": bbc_wire / link_bw,
        "naive_collective_seconds": naive_wire / link_bw,
    }
    if n_hosts > 1:
        sh = n_hosts
        per_host = max(s // sh, 1)
        bbc_dcn = 2 * hist_bytes * (sh - 1) / sh \
            + 8 * budget * per_host * (sh - 1)
        naive_dcn = 8 * k * per_host * (sh - 1)
        out.update({
            "n_hosts": sh,
            "bbc_dcn_bytes_per_link": bbc_dcn,
            "naive_dcn_bytes_per_link": naive_dcn,
            "dcn_ratio": naive_dcn / max(bbc_dcn, 1e-9),
            "bbc_dcn_seconds": bbc_dcn / dcn_bw,
            "naive_dcn_seconds": naive_dcn / dcn_bw,
        })
    return out
