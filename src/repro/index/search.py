"""End-to-end ANN searchers: IVF / IVF+PQ / IVF+RaBitQ, each ± BBC.

Two families of entry points:

  * Single-query functions (``ivf_search`` & co.), jit-compiled with static
    hyper-parameters.  Intermediates are O(n_probe * cap) over the padded
    member table.
  * Natively batched ``*_batch`` functions: one routing matmul for the whole
    query batch, ONE shared candidate-stream gather (the compact
    ``ivf.FlatLayout``, zero per-cluster padding), per-query probe masks, and
    batched estimate / bucketize / histogram / re-rank matmuls that run
    through the Pallas kernels on TPU (``kernels.ops.*_batch``) and their
    jnp mirrors on CPU.  Use these instead of ``jax.vmap`` over the single
    query functions — vmap replicates the padded gathers per query.

All paths return ``SearchResult`` with instrumentation counters used by the
benchmark suite (re-rank counts, second-pass gathers — the TPU analogues of
the paper's VTune/perf numbers); batched paths return per-query (B,) counters.

The batched and sharded searchers additionally support the predictive
early-exact subsystem: pass ``pred_state`` (a ``rerank.PredictorState``, the
engine-owned EMA of previous batches' bucket histograms) and the call returns
``(SearchResult, new_state)`` with the re-rank pool sized by the predicted
threshold bucket instead of the static knobs (see the predictive section
below and ``core.rerank.predict_tau``).

The single-device batched searchers wrap their stages in ``jax.named_scope``
(``bbc.route``, ``bbc.plan``, ``bbc.scan``, ``bbc.collect``,
``bbc.rerank``, ``bbc.final``: routing, the codebook / gate plan, the
stream scan, the collection, the exact re-rank, the final selection), so
every op of the compiled program carries its stage in its HLO ``op_name``
and a profile can charge device time to a stage.  A scope is metadata only: it costs nothing
at run time.

Method map (paper Table / Fig. 1):
  ivf_search(use_bbc=False)          -> IVF
  ivf_pq_search(use_bbc=False)       -> IVF+PQ          (unbounded, n_cand)
  ivf_pq_search(use_bbc=True)        -> IVF+PQ+BBC      (Alg. 4 early rerank)
  ivf_rabitq_search(use_bbc=False)   -> IVF+RaBitQ      (threshold rerank)
  ivf_rabitq_search(use_bbc=True)    -> IVF+RaBitQ+BBC  (Alg. 3 greedy)
  flat.search                        -> BFC
(IVF+RaBitQ+MIN lives in benchmarks — host-side heap baseline, Alg. 2.)
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from repro.core import buffer as rb
from repro.core import collector as col
from repro.core import distributed as dist
from repro.core import rerank
from repro.index import ivf as ivf_mod
from repro.index import pq as pq_mod
from repro.index import rabitq as rq_mod
from repro.kernels import ops
from repro.kernels import ref as kref

INF = jnp.inf


class PQIndex(NamedTuple):
    """IVF + PQ index bundle (codes plus fp32 vectors for exact re-rank)."""
    ivf: ivf_mod.IVFIndex
    pq: pq_mod.PQCodebook
    codes: jax.Array    # (N, M) uint8
    vectors: jax.Array  # (N, d) fp32 (re-rank source)


class RabitqIndex(NamedTuple):
    """IVF + RaBitQ index bundle (codes plus fp32 vectors for exact re-rank).
    """
    ivf: ivf_mod.IVFIndex
    rq: rq_mod.RabitqCodes
    vectors: jax.Array


class RabitqStream(NamedTuple):
    """Layout-ordered RaBitQ candidate stream (the per-call gather of the
    codes/vectors/factors into FlatLayout order, hoisted out of the
    searchers).  The engine materializes it once at build time — at stream
    scale the two 30+ MB gathers cost as much as the bounds matmul, every
    batch, on BOTH the fused and two-phase paths."""

    codes: jax.Array    # (n_flat, d) fp32 ±1
    vectors: jax.Array  # (n_flat, d) fp32
    norm_o: jax.Array   # (n_flat,)
    f_o: jax.Array      # (n_flat,)
    cl: jax.Array       # (n_flat,) clamped owning cluster per lane


def rabitq_stream(index: RabitqIndex,
                  layout: ivf_mod.FlatLayout) -> RabitqStream:
    rq = index.rq
    return RabitqStream(
        codes=rq.codes[layout.order].astype(jnp.float32),
        vectors=index.vectors[layout.order],
        norm_o=rq.norm_o[layout.order],
        f_o=rq.f_o[layout.order],
        cl=jnp.minimum(layout.cluster_of, index.ivf.n_clusters - 1))


class SearchResult(NamedTuple):
    """Top-k result with per-query re-rank work counters."""
    dists: jax.Array
    ids: jax.Array
    n_reranked: jax.Array       # exact distance computations spent
    n_second_pass: jax.Array    # re-rank gathers NOT covered inline (Alg. 4)


# --------------------------------------------------------------------------
# Index builders (offline)
# --------------------------------------------------------------------------

def build_pq_index(key, x, n_clusters: int, n_sub: int | None = None,
                   n_bits: int = 4, n_iter: int = 10) -> PQIndex:
    d = x.shape[1]
    n_sub = n_sub or d // 4          # paper: M = d/4, B = 4
    k1, k2 = jax.random.split(key)
    with jax.profiler.TraceAnnotation("index.build", rows=x.shape[0],
                                      n_clusters=n_clusters):
        index = ivf_mod.build(k1, x, n_clusters, n_iter)
        cb = pq_mod.train(k2, x, n_sub, n_bits, n_iter)
        codes = pq_mod.encode(cb, x)
    return PQIndex(ivf=index, pq=cb, codes=codes, vectors=x)


def build_rabitq_index(key, x, n_clusters: int, n_iter: int = 10) -> RabitqIndex:
    """Each row is encoded against the centroid of the list ``ivf.build``
    packed it into, the one the searchers read it with."""
    k1, k2 = jax.random.split(key)
    with jax.profiler.TraceAnnotation("index.build", rows=x.shape[0],
                                      n_clusters=n_clusters):
        index, assignment = ivf_mod.build_assigned(k1, x, n_clusters, n_iter)
        rq = jax.block_until_ready(
            rq_mod.encode(k2, x, index.centroids, assignment))
    return RabitqIndex(ivf=index, rq=rq, vectors=x)


# --------------------------------------------------------------------------
# Shared helpers
# --------------------------------------------------------------------------

def _exact_dists(vectors: jax.Array, ids: jax.Array, q: jax.Array) -> jax.Array:
    """Exact Euclidean distances for a gathered id set (ids may contain -1
    padding; callers mask)."""
    v = vectors[jnp.maximum(ids, 0)]
    vq = jnp.matmul(v, q, precision="highest")
    return jnp.sqrt(jnp.maximum(
        jnp.sum(v * v, -1) - 2.0 * vq + jnp.sum(q * q), 0.0))


def _stream_from(est, ids, valid) -> col.StreamInput:
    return col.StreamInput(dists=est, ids=ids, valid=valid)


def _rerank_budget(k: int, cap: int) -> int:
    b = max(8 * k, 2048)
    return ((b + 127) // 128) * 128


# --------------------------------------------------------------------------
# Predictive early-exact re-rank (cross-batch tau_pred subsystem)
# --------------------------------------------------------------------------
#
# The static BBC paths size the exact-re-rank pool with a blunt static knob
# (n_cand for PQ; the full uncertain band for RaBitQ).  In predictive mode a
# searcher additionally takes the engine-owned ``rerank.PredictorState`` (the
# EMA of previous batches' bucket histograms) and returns
# ``(SearchResult, new_state)``:
#
#   * tau_pred = predict_tau(state, pred_count) is the bucket the cumulative
#     histogram is EXPECTED to reach pred_count at.  The scan early-exacts
#     lanes at or below it inline (fused kernel on TPU).
#   * tau_true from THIS batch's histogram guards correctness: survivors are
#     bucket <= max(tau_pred, tau_true), and survivors the prediction missed
#     (bucket in (tau_pred, tau_true]) get a fallback second-pass re-rank —
#     exactly the static path's gather, just (usually) empty.
#   * the new state folds this batch's histogram into the EMA.
#
# For PQ the pool shrinks from n_cand to ~pred_count (fewer re-ranks); for
# IVF/RaBitQ distances/bounds already bound the pool, so prediction moves
# work inline (fewer second-pass gathers) without changing the pool.


def _resolve_pred_count(pred_count: int | None, k: int,
                        n_cand: int | None = None) -> int:
    """Default predictive re-rank pool target (~2.5k): deep enough that the
    exact top-k inside it matches the static n_cand cut on realistic
    estimate error, ~3x shallower than the n_cand=8k default.  This is the
    single source of the default — the engine and bench_tau_pred both
    resolve through it (BENCH_tau_pred.json is measured at this value)."""
    if pred_count is None:
        pred_count = max(5 * k // 2, k + 1024)
    pred_count = max(pred_count, k)
    if n_cand is not None:
        pred_count = min(pred_count, n_cand)
    return pred_count


def _pred_budget(count: int, n: int) -> int:
    """Static selection width over the survivor pool: the threshold bucket
    overshoots ``count`` by at most its own occupancy; slack covers skew."""
    b = count + max(count // 2, 256)
    return int(min(n, ((b + 127) // 128) * 128))


def _sample_codebooks(layout: ivf_mod.FlatLayout, probed: jax.Array,
                      vals: jax.Array, st: int, cap: int, k_cb: int, m: int):
    """Per-query codebooks from the nearest ``st`` probed cluster tiles of a
    (B, n_flat) value matrix (the batched analogue of the paper's 5-10
    nearest-cluster sample)."""
    spos, sok = ivf_mod.tile_positions(layout, probed[:, :st], cap)
    sample = jnp.where(sok, jnp.take_along_axis(vals, spos, axis=1), INF)
    k_cb = min(k_cb, sample.shape[1])
    return jax.vmap(lambda s: rb.build_codebook(s, k=k_cb, m=m))(sample)


def _pq_sample_est(layout: ivf_mod.FlatLayout, probed: jax.Array,
                   stream_codes: jax.Array, luts: jax.Array, st: int,
                   cap: int) -> jax.Array:
    """Per-query ADC estimates over the nearest ``st`` probed cluster tiles
    (the codebook sample of the batched PQ paths — static fused and
    predictive MUST sample identically so bucket indices stay comparable
    across batches for the EMA)."""
    spos, sok = ivf_mod.tile_positions(layout, probed[:, :st], cap)

    def one(a):
        pos, ok, lut = a
        e = pq_mod.estimate(lut, stream_codes[pos])
        return jnp.where(ok, jnp.sqrt(jnp.maximum(e, 0.0)), INF)

    return jax.lax.map(one, (spos, sok, luts))


# Lanes per fused-scan call.  The kernel's per-lane outputs are (lanes, B)
# arrays, which the chip lays out with B padded to 128 lanes (8x at
# B=16): one call over a 10M-lane stream would need 14 GiB for them alone.
# A stream of at most SCAN_CHUNK lanes is scanned in one call.
SCAN_CHUNK = 1 << 20


def _fused_scan_stream(index: "PQIndex", stream_codes: jax.Array,
                       layout: ivf_mod.FlatLayout, lane_valid: jax.Array,
                       luts: jax.Array, qs: jax.Array, d_min: jax.Array,
                       delta: jax.Array, ew_map: jax.Array, m: int,
                       tau_pred: jax.Array, backend: str | None):
    """``ops.fused_scan_batch`` over the layout-ordered stream, with the
    vectors gathered into stream order, ``SCAN_CHUNK`` lanes per call.
    Every lane's outputs are the one-call outputs, and the histograms and
    miss counts are integer sums over the chunks."""
    def scan(codes, order, valid):
        return ops.fused_scan_batch(codes, index.vectors[order], valid,
                                    luts, qs, d_min, delta, ew_map, m,
                                    tau_pred, backend=backend)
    n_flat = layout.n_flat
    if n_flat <= SCAN_CHUNK:
        return scan(stream_codes, layout.order, lane_valid)
    b = qs.shape[0]

    def add(acc, out, start):
        est, bucket, early = (jax.lax.dynamic_update_slice_in_dim(
            a, _lanes_minor(o), start, 1)
            for a, o in zip(acc[:3], (out[0], out[1], out[3])))
        return est, bucket, early, acc[3] + out[2], acc[4] + out[4]

    def chunk(i, acc):
        start = i * SCAN_CHUNK
        return add(acc, scan(
            jax.lax.dynamic_slice_in_dim(stream_codes, start, SCAN_CHUNK),
            jax.lax.dynamic_slice_in_dim(layout.order, start, SCAN_CHUNK),
            jax.lax.dynamic_slice_in_dim(lane_valid, start, SCAN_CHUNK, 1)),
            start)
    full = n_flat // SCAN_CHUNK
    acc = (jnp.zeros((b, n_flat), jnp.float32),
           jnp.zeros((b, n_flat), jnp.int32),
           jnp.zeros((b, n_flat), jnp.float32),
           jnp.zeros((b, m + 1), jnp.int32), jnp.zeros((b,), jnp.int32))
    acc = jax.lax.fori_loop(0, full, chunk, acc)
    if n_flat % SCAN_CHUNK:
        start = full * SCAN_CHUNK
        acc = add(acc, scan(stream_codes[start:], layout.order[start:],
                            lane_valid[:, start:]), start)
    est, bucket, early, hist, nmiss = acc
    return (_lanes_minor(est), _lanes_minor(bucket), hist,
            _lanes_minor(early), nmiss)


def _lanes_minor(a: jax.Array) -> jax.Array:
    """``a`` (B, lanes) laid out with the lanes minor, so that the compiler
    does not keep it in the kernel's lane-padded (lanes, B) order."""
    return with_layout_constraint(a, Layout(major_to_minor=(0, 1)))


def _predictive_select(est: jax.Array, bucket: jax.Array, hist: jax.Array,
                       lane_valid: jax.Array, tau_pred: jax.Array,
                       count: int, budget: int, gids: jax.Array):
    """Survivor selection under the predicted threshold.

    Survivors are lanes with bucket <= max(tau_pred, tau_true-at-count);
    they are picked est-priority into the static ``budget`` (ascending,
    boundary ties broken by smallest global id — see ``_topk_est_id`` —
    so the truncated pool matches the sharded deployment's re-cut on tied
    estimates), and the first k columns are the exact top-k of the pool.
    Returns (sel_est ascending (B, budget), sel_pos, sel_ok, tau_true).
    """
    tau_true, _ = jax.vmap(rb.threshold_bucket, in_axes=(0, None))(hist, count)
    tau_used = jnp.maximum(tau_pred, tau_true)
    masked = jnp.where(lane_valid & (bucket <= tau_used[:, None]), est, INF)
    neg, sel_pos = _topk_est_id(masked, gids, budget)
    return -neg, sel_pos, jnp.isfinite(-neg), tau_true


# --------------------------------------------------------------------------
# IVF (no quantization): exact distances in-scan + collector
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "n_probe", "use_bbc", "m"))
def ivf_search(index: ivf_mod.IVFIndex, vectors: jax.Array, q: jax.Array,
               k: int, n_probe: int, use_bbc: bool = False,
               m: int = 128) -> SearchResult:
    probed = ivf_mod.route(index, q, n_probe)
    ids, valid = ivf_mod.gather_candidates(index, probed)    # (n_probe, cap)
    dists = jax.vmap(lambda i: _exact_dists(vectors, i, q))(ids)
    dists = jnp.where(valid, dists, INF)
    s = _stream_from(dists, ids, valid)
    if use_bbc:
        d, i = col.bbc_collect(s, k, m=m)
    else:
        d, i = col.topk_collect(s, k)
    n = jnp.sum(valid)
    return SearchResult(d, i, n, jnp.int32(0))


# --------------------------------------------------------------------------
# IVF + PQ (unbounded): ADC estimate -> n_cand selection -> re-rank
# --------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probe", "n_cand", "use_bbc", "m", "early_slack"),
)
def ivf_pq_search(
    index: PQIndex,
    q: jax.Array,
    k: int,
    n_probe: int,
    n_cand: int,
    use_bbc: bool = False,
    m: int = 128,
    early_slack: float = 4.0,
) -> SearchResult:
    """IVF+PQ (baseline) and IVF+PQ+BBC (Alg. 4 early re-rank).

    Baseline: running top-n_cand by estimate across cluster tiles ("Heap"
    collector), then one gather+exact pass over the n_cand selection.

    +BBC: bucket collector for the n_cand selection, plus early re-ranking —
    per cluster tile, objects whose estimate bucketizes at or below tau_pred
    have exact distances computed inline while the cluster's vectors are
    resident (TPU: same VMEM tile; see kernels/fused_scan.py).  The second
    gather pass only covers the few selected-but-not-predicted stragglers
    (``n_second_pass`` — the cache-miss analogue the paper counts in Table 2).
    """
    ivf = index.ivf
    probed = ivf_mod.route(ivf, q, n_probe)
    ids, valid = ivf_mod.gather_candidates(ivf, probed)       # (n_probe, cap)
    cap = ids.shape[1]
    lut = pq_mod.adc_table(index.pq, q)

    codes = index.codes[jnp.maximum(ids, 0)]                  # (n_probe, cap, M)
    est = jax.vmap(lambda c: pq_mod.estimate(lut, c))(codes)  # squared dists
    est = jnp.sqrt(jnp.maximum(jnp.where(valid, est, INF), 0.0))

    flat_est = est.reshape(-1)
    flat_ids = ids.reshape(-1)
    flat_valid = valid.reshape(-1)

    if not use_bbc:
        # ---- baseline: heap-analogue selection, full second-pass re-rank --
        s = _stream_from(est, ids, valid)
        cd, ci = col.topk_collect(s, n_cand)
        ex = _exact_dists(index.vectors, ci, q)
        ex = jnp.where(ci >= 0, ex, INF)
        neg, order = jax.lax.top_k(-ex, k)
        return SearchResult(-neg, ci[order], jnp.int32(n_cand),
                            jnp.int32(n_cand))

    # ---- BBC path (Alg. 4) ------------------------------------------------
    n_sample_tiles = min(4, n_probe)
    sample = jnp.where(valid[:n_sample_tiles],
                       est[:n_sample_tiles], INF).reshape(-1)
    n_total = flat_valid.shape[0]
    # The TPU formulation materializes the whole estimate pass before the
    # early re-rank (tile-parallel, not streamed), so the sample prefix
    # seeds the CODEBOOK only while tau_pred comes from the full scan at
    # Alg. 4 line-14 granularity — the nearest-cluster prefix is
    # distance-skewed and its rank heuristic (early_rerank_plan, used by
    # the streaming fused-kernel path) lands systematically low on
    # concentrated corpora.  The refresh is the O(m) histogram threshold
    # (bucketize is monotone, so the first bucket whose cumulative count
    # reaches n_cand IS the bucket of the n_cand-th estimate — no O(n_cand)
    # selection), and the histogram is reused by the collection.
    cb = rb.build_codebook(sample, k=min(n_cand, sample.shape[0]), m=m)
    bucket_ids = rb.bucketize(cb, flat_est)
    hist = rb.histogram(bucket_ids, m, flat_valid)
    tau_scan, _ = rb.threshold_bucket(hist, n_cand)
    plan = rerank.EarlyRerankPlan(tau_pred=tau_scan, cb=cb)

    # Early re-rank: per-cluster inline exact for predicted survivors.
    early_budget = int(min(cap, max(128, round(n_cand / n_probe * early_slack))))
    early_budget = ((early_budget + 127) // 128) * 128
    early_budget = min(early_budget, cap)

    positions = jnp.arange(n_total, dtype=jnp.int32)
    flat_pos_matrix = positions.reshape(n_probe, cap)

    def per_cluster(c_est, c_ids, c_valid, row_pos):
        """Inline exact distances for predicted survivors of one cluster tile
        (Alg. 4 lines 9-11: the vectors are 'hot' — on TPU, the fused kernel
        streams them in the same VMEM tile as the codes)."""
        pred = rerank.early_rerank_mask(plan, c_est) & c_valid
        pos, ok = rb.compact_mask(pred, early_budget)
        safe = jnp.minimum(pos, cap - 1)
        e_ids = jnp.where(ok, c_ids[safe], -1)
        e_d = jnp.where(ok, _exact_dists(index.vectors, e_ids, q), INF)
        tgt = jnp.where(ok, row_pos[safe], n_total)  # flat scatter targets
        return e_d, tgt, jnp.sum(ok)

    e_d, e_tgt, e_counts = jax.vmap(per_cluster)(est, ids, valid, flat_pos_matrix)
    n_early = jnp.sum(e_counts)
    flat_e_d = jnp.full((n_total + 1,), INF, est.dtype)
    flat_e_d = flat_e_d.at[e_tgt.reshape(-1)].set(e_d.reshape(-1), mode="drop")
    flat_e_d = flat_e_d[:n_total]

    # n_cand selection by estimate with the bucket collector (Alg. 1 Collect).
    _, sel_pos = rb.collect(
        plan.cb, flat_est, positions, bucket_ids, n_cand, flat_valid,
        hist=hist)
    sel_ids = flat_ids[jnp.maximum(sel_pos, 0)]
    sel_ids = jnp.where(sel_pos >= 0, sel_ids, -1)

    # Inline results cover most of the selection; one small second pass for
    # the stragglers (n_second_pass ~ the paper's Table-2 cache-miss story).
    have = jnp.isfinite(flat_e_d[jnp.maximum(sel_pos, 0)]) & (sel_pos >= 0)
    miss = ~have & (sel_ids >= 0)
    second = jnp.sum(miss)
    miss_d = _exact_dists(index.vectors, jnp.where(miss, sel_ids, 0), q)
    ex = jnp.where(have, flat_e_d[jnp.maximum(sel_pos, 0)],
                   jnp.where(miss, miss_d, INF))

    neg, order = jax.lax.top_k(-ex, k)
    return SearchResult(-neg, sel_ids[order],
                        (n_early + second).astype(jnp.int32),
                        second.astype(jnp.int32))


# --------------------------------------------------------------------------
# IVF + RaBitQ (bounded): estimate+bounds -> rerank
# --------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probe", "use_bbc", "m", "eps0"),
)
def ivf_rabitq_search(
    index: RabitqIndex,
    q: jax.Array,
    k: int,
    n_probe: int,
    use_bbc: bool = False,
    m: int = 128,
    eps0: float = 3.0,
) -> SearchResult:
    """IVF+RaBitQ baseline (per-cluster threshold re-rank) and +BBC (Alg. 3
    closed-form greedy on two result buffers)."""
    ivf = index.ivf
    probed = ivf_mod.route(ivf, q, n_probe)
    ids, valid = ivf_mod.gather_candidates(ivf, probed)
    n_probe_, cap = ids.shape
    rq = index.rq

    def est_cluster(cid, c_ids, c_valid):
        qf = rq_mod.query_factors(rq, q, ivf.centroids[cid])
        c = rq.codes[jnp.maximum(c_ids, 0)]
        no = rq.norm_o[jnp.maximum(c_ids, 0)]
        fo = rq.f_o[jnp.maximum(c_ids, 0)]
        est, lb, ub = rq_mod.estimate(c, no, fo, qf, eps0)
        bad = ~c_valid
        return (jnp.where(bad, INF, est), jnp.where(bad, INF, lb),
                jnp.where(bad, INF, ub))

    est, lb, ub = jax.vmap(est_cluster)(probed, ids, valid)

    if not use_bbc:
        # ---- baseline: per-cluster threshold re-ranking -------------------
        budget = min(cap, _rerank_budget(k, cap))

        def step(carry, xs):
            pool_d, pool_i, n_rr = carry
            c_lb, c_ids, c_valid = xs
            thresh = pool_d[k - 1]
            mask = c_valid & (c_lb < thresh)
            pos, ok = rb.compact_mask(mask, budget)
            safe = jnp.minimum(pos, cap - 1)
            r_ids = jnp.where(ok, c_ids[safe], -1)
            r_d = _exact_dists(index.vectors, r_ids, q)
            r_d = jnp.where(ok, r_d, INF)
            alld = jnp.concatenate([pool_d, r_d])
            alli = jnp.concatenate([pool_i, r_ids])
            neg, idx = jax.lax.top_k(-alld, k)
            return (-neg, alli[idx], n_rr + jnp.sum(ok)), None

        pool0 = (jnp.full((k,), INF, est.dtype), jnp.full((k,), -1, jnp.int32),
                 jnp.int32(0))
        (pd, pi, n_rr), _ = jax.lax.scan(step, pool0, (lb, ids, valid))
        order = jnp.argsort(pd)
        return SearchResult(pd[order], pi[order], n_rr, n_rr)

    # ---- BBC path (Alg. 3, two-phase greedy) -------------------------------
    flat_lb, flat_ub = lb.reshape(-1), ub.reshape(-1)
    flat_est = est.reshape(-1)
    flat_ids, flat_valid = ids.reshape(-1), valid.reshape(-1)
    n_flat = flat_ids.shape[0]
    plan = rerank.greedy_rerank_plan(flat_lb, flat_ub, k, flat_valid, m=m)

    exact_flat = jnp.full((n_flat,), INF, est.dtype)

    def eval_mask(mask, budget, exact_flat):
        """Exact distances for up to ``budget`` masked lanes (est-priority)."""
        key_est = jnp.where(mask, flat_est, INF)
        _, pos = jax.lax.top_k(-key_est, budget)
        ok = jnp.isfinite(key_est[pos])
        safe = jnp.minimum(pos, n_flat - 1)
        r_ids = jnp.where(ok, flat_ids[safe], -1)
        r_d = jnp.where(ok, _exact_dists(index.vectors, r_ids, q), INF)
        exact_flat = exact_flat.at[jnp.where(ok, safe, n_flat)].set(
            r_d, mode="drop")
        return exact_flat, r_d, jnp.sum(ok)

    # Phase 1: likely-in items (ub at/below the k-th-ub bucket).  Their exact
    # distances tighten the threshold, as in the paper's iterative loop.
    p1 = rerank.phase1_mask(plan)
    budget1 = min(n_flat, ((k + 1024 + 127) // 128) * 128)
    exact_flat, p1_d, n1 = eval_mask(p1, budget1, exact_flat)
    t2 = rerank.phase2_threshold(plan, p1_d, k)

    # Phase 2: remaining uncertain items whose lower bound is under the
    # tightened threshold (anything above is certainly out).
    p2 = plan.rerank_mask & ~p1 & jnp.isinf(exact_flat) & (flat_lb <= t2)
    budget2 = min(n_flat, _rerank_budget(k, cap))
    exact_flat, _, n2 = eval_mask(p2, budget2, exact_flat)

    res = rerank.greedy_rerank_finalize(
        plan, exact_flat, jnp.where(flat_valid, flat_lb, INF), flat_ids, k,
        est=flat_est)
    n_evals = (n1 + n2).astype(jnp.int32)
    return SearchResult(res.topk_dists, res.topk_ids, n_evals, n_evals)


# --------------------------------------------------------------------------
# Natively batched searchers (shared candidate stream + batched kernels)
# --------------------------------------------------------------------------

def _exact_dists_rows(vectors: jax.Array, ids: jax.Array,
                      qs: jax.Array) -> jax.Array:
    """Per-query exact distances for (B, w) id rows.  Sequential map keeps
    the (w, d) gather per query (the batched-gather alternative materializes
    (B, w, d)); each row uses the same formula as ``_exact_dists`` so values
    match the single-query path."""
    return jax.lax.map(lambda a: _exact_dists(vectors, a[0], a[1]), (ids, qs))


def _routing(ivf: ivf_mod.IVFIndex, layout: ivf_mod.FlatLayout,
             qs: jax.Array, n_probe: int):
    """Shared batch routing: probed clusters, per-query lane masks over the
    flat stream, and the (B, C) squared query-centroid distances (for
    estimators that need them, e.g. RaBitQ's norm_q)."""
    probed, d2 = ivf_mod.route_batch_d2(ivf, qs, n_probe)
    lane_valid = ivf_mod.probe_mask(layout, probed, ivf.n_clusters)
    return probed, lane_valid, d2


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probe", "use_bbc", "m", "backend", "pred_count"))
def ivf_search_batch(
    index: ivf_mod.IVFIndex,
    vectors: jax.Array,
    qs: jax.Array,                 # (B, d)
    layout: ivf_mod.FlatLayout,
    k: int,
    n_probe: int,
    use_bbc: bool = False,
    m: int = 128,
    backend: str | None = None,
    pred_state: rerank.PredictorState | None = None,
    pred_count: int | None = None,
    live: jax.Array | None = None,
) -> SearchResult:
    """Batched IVF (exact distances in-scan): one shared vector-stream gather,
    one (B, n_flat) distance matmul, per-query bucket collection.

    With ``pred_state`` the selection runs predictively (survivors under
    max(tau_pred, tau_true) instead of a histogram-driven collect) and the
    call returns ``(SearchResult, new_state)``; distances are exact in-scan,
    so the result is identical to the static path for ANY prediction.

    ``live`` is an optional (n_flat,) stream-ordered tombstone mask
    (streaming-ingest deletes): dead lanes are ANDed out of the per-query
    probe masks, so every downstream consumer — distances, histograms, the
    collection — sees them exactly like unprobed lanes.  The value is
    traced (not static): flipping tombstones never recompiles.
    """
    with jax.named_scope("bbc.route"):
        probed, lane_valid, _ = _routing(index, layout, qs, n_probe)
        if live is not None:
            lane_valid = lane_valid & live[None, :]
    with jax.named_scope("bbc.scan"):
        stream_vecs = vectors[layout.order]                   # shared gather
        dists = ops.l2_exact_batch(stream_vecs, qs, backend=backend)
        dists = jnp.where(lane_valid, dists, INF)
    if pred_state is not None:
        if not use_bbc:
            raise ValueError("predictive search requires use_bbc=True")
        # distances are exact in-scan, so the pool target is k itself
        count = max(pred_count, k) if pred_count is not None else k
        st = min(4, n_probe)
        with jax.named_scope("bbc.plan"):
            cbs = _sample_codebooks(layout, probed, dists, st, index.cap, k,
                                    m)
            tau_pred = rerank.predict_tau(pred_state, count)
        with jax.named_scope("bbc.scan"):
            bucket, hist = ops.bucket_hist_batch(
                dists, lane_valid, cbs.d_min, cbs.delta, cbs.ew_map, m,
                backend=backend)
        with jax.named_scope("bbc.collect"):
            budget = _pred_budget(count, layout.n_flat)
            sel_d, sel_pos, sel_ok, _ = _predictive_select(
                dists, bucket, hist, lane_valid, tau_pred, count, budget,
                layout.order)
        with jax.named_scope("bbc.final"):
            n = jnp.sum(lane_valid, axis=1).astype(jnp.int32)
            ids = jnp.where(sel_ok, layout.order[sel_pos], -1)
            res = SearchResult(sel_d[:, :k], ids[:, :k], n, jnp.zeros_like(n))
            return res, rerank.predictor_update(pred_state, hist)
    with jax.named_scope("bbc.collect"):
        if use_bbc and ops.resolve_backend(backend) == "pallas":
            # Kernel path: O(m) histogram collection (bucket_hist kernel) +
            # one (k + slack)-wide selection.
            st = min(4, n_probe)
            spos, sok = ivf_mod.tile_positions(layout, probed[:, :st],
                                               index.cap)
            sample = jnp.where(sok, jnp.take_along_axis(dists, spos, axis=1),
                               INF)
            d, i = col.bbc_collect_batch(dists, layout.order, lane_valid, k,
                                         m=m, sample=sample, sample_valid=sok,
                                         backend=backend)
        else:
            # CPU fallback: XLA's flat top_k beats scatter-based compaction
            # at these widths; the selected set is identical (bucketize is
            # monotone in distance, so the bucket collection selects the
            # exact top-k set).
            d, i = col.topk_collect_batch(dists, layout.order, lane_valid, k)
    with jax.named_scope("bbc.final"):
        n = jnp.sum(lane_valid, axis=1).astype(jnp.int32)
        return SearchResult(d, i, n, jnp.zeros_like(n))


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probe", "n_cand", "use_bbc", "m", "backend",
                     "fused", "pred_count"),
)
def ivf_pq_search_batch(
    index: PQIndex,
    qs: jax.Array,                 # (B, d)
    layout: ivf_mod.FlatLayout,
    k: int,
    n_probe: int,
    n_cand: int,
    use_bbc: bool = False,
    m: int = 128,
    backend: str | None = None,
    fused: bool | None = None,
    pred_state: rerank.PredictorState | None = None,
    pred_count: int | None = None,
    live: jax.Array | None = None,
) -> SearchResult:
    """Batched IVF+PQ (±BBC).

    The candidate stream (codes, and vectors for the fused path) is gathered
    once per batch; ADC runs for every query against the shared stream; the
    n_cand selection is the batched bucket collection.  With ``fused=True``
    (default on TPU) the whole estimate+bucketize+hist+early-exact pass is
    ``ops.fused_scan_batch`` — Alg. 4's early re-ranking happens while the
    vector tile is VMEM-resident and the second gather pass covers only the
    stragglers.  In the dense regime (``_pq_dense_regime``: ``n_cand``
    covers a quarter of the stream, or a query's expected probed lanes),
    the fused pass early-exacts every lane and the n_cand cut is a
    (B, n_flat) mask (``_dense_select``): no compaction, no n_cand-wide
    gathers, no second pass.  Its final top-k runs over the probed tiles
    when they are at most a quarter of the stream (``_pq_probed_final``),
    else over the stream.  So DEEP-10M's shard (n_cand 400,000 above every
    query's ~300k probed lanes of 10M) takes this path, not the
    compaction's overflow fallback over the stream.  With ``fused=False``
    (default on CPU, where there is no fusion win to collect) exact
    distances are computed once for the final selection; results are
    identical, only the ``n_second_pass`` accounting differs.

    With ``pred_state`` the blunt n_cand cut is replaced by the predictive
    early-exact pool: exact distances are spent on the ~pred_count candidates
    under max(tau_pred, tau_true) instead of all n_cand, tau_pred comes from
    the cross-batch EMA, and the call returns ``(SearchResult, new_state)``.
    """
    if fused is None:
        fused = ops.on_tpu()
    ivf = index.ivf
    b = qs.shape[0]
    with jax.named_scope("bbc.route"):
        probed, lane_valid, _ = _routing(ivf, layout, qs, n_probe)
        if live is not None:
            # tombstoned lanes (streaming-ingest deletes) behave exactly like
            # unprobed lanes from here on: masked out of estimates,
            # histograms, and the collection alike
            lane_valid = lane_valid & live[None, :]
    with jax.named_scope("bbc.scan"):
        stream_codes = index.codes[layout.order]              # shared gather
    with jax.named_scope("bbc.plan"):
        luts = jax.vmap(lambda q: pq_mod.adc_table(index.pq, q))(qs)

    if pred_state is not None:
        if not use_bbc:
            raise ValueError("predictive search requires use_bbc=True")
        return _ivf_pq_predictive_batch(
            index, qs, layout, probed, lane_valid, stream_codes, luts, k,
            n_probe, n_cand, m, backend, fused, pred_state, pred_count)

    # the unfused paths' re-rank: one stream-wide matmul beats n_cand
    # per-row gathers once the selection is a quarter of the stream
    dense_rerank = 4 * n_cand >= layout.n_flat

    if not use_bbc:
        with jax.named_scope("bbc.scan"):
            est2 = ops.pq_adc_batch(stream_codes, luts, backend=backend)
            est = jnp.where(lane_valid, jnp.sqrt(jnp.maximum(est2, 0.0)), INF)
        with jax.named_scope("bbc.collect"):
            sel_est, sel_pos = jax.lax.top_k(-est, n_cand)
            ci = jnp.where(jnp.isfinite(sel_est), layout.order[sel_pos], -1)
        with jax.named_scope("bbc.rerank"):
            if dense_rerank:
                stream_vecs = index.vectors[layout.order]
                exact_all = ops.l2_exact_batch(stream_vecs, qs,
                                               backend=backend)
                ex = jnp.take_along_axis(exact_all, sel_pos, axis=1)
            else:
                ex = _exact_dists_rows(index.vectors, ci, qs)
            ex = jnp.where(ci >= 0, ex, INF)
        with jax.named_scope("bbc.final"):
            neg, order = jax.lax.top_k(-ex, k)
            counts = jnp.full((b,), n_cand, jnp.int32)
            return SearchResult(-neg, jnp.take_along_axis(ci, order, axis=1),
                                counts, counts)

    # ---- BBC path (Alg. 4, batched) ---------------------------------------
    n_flat = layout.n_flat
    if fused:
        # Kernel path: per-query codebooks + tau_pred from the nearest-tile
        # sample prefix, then ONE fused pass (est+bucketize+hist+early-exact)
        # over the shared stream; selection via the histogram; second gather
        # pass only for selected-but-not-predicted stragglers.
        st = min(4, n_probe)
        with jax.named_scope("bbc.plan"):
            sample_est = _pq_sample_est(layout, probed, stream_codes, luts,
                                        st, ivf.cap)
            n_total = n_probe * ivf.cap
            plans = jax.vmap(
                lambda s: rerank.early_rerank_plan(
                    s, n_cand=n_cand, n_sample=s.shape[0], n_total=n_total,
                    m=m)
            )(sample_est)

        # Dense regime: the selection is a large share of the stream or of
        # the probed lanes, so the kernel exacts every lane (tau_pred = the
        # overflow bucket, nmiss = 0) and the n_cand cut stays a
        # full-width mask.
        dense = _pq_dense_regime(n_cand, n_probe, n_flat, ivf.n_clusters)
        tau_pred = (jnp.full((b,), m, jnp.int32) if dense
                    else plans.tau_pred)
        with jax.named_scope("bbc.scan"):
            est, bucket, hist, early, nmiss = _fused_scan_stream(
                index, stream_codes, layout, lane_valid, luts, qs,
                plans.cb.d_min, plans.cb.delta, plans.cb.ew_map, m,
                tau_pred, backend)
            est = jnp.where(lane_valid, est, INF)
        if dense:
            with jax.named_scope("bbc.collect"):
                selected = _dense_select(est, bucket, hist, lane_valid,
                                         n_cand)
            with jax.named_scope("bbc.rerank"):
                ex = jnp.where(selected, early, INF)
            with jax.named_scope("bbc.final"):
                if _pq_probed_final(k, n_probe, ivf.cap, n_flat):
                    # Every selected lane lies in a probed tile.  Ascending
                    # cluster ids give ascending offsets, so the tiles keep
                    # stream order over live lanes, and top_k's ties (lower
                    # index first) fall as at full width.
                    tpos, tok = ivf_mod.tile_positions(
                        layout, jnp.sort(probed, axis=1), ivf.cap)
                    ex_t = jnp.where(
                        tok, jnp.take_along_axis(ex, tpos, axis=1), INF)
                    neg, at = jax.lax.top_k(-ex_t, k)
                    pos = jnp.take_along_axis(tpos, at, axis=1)
                else:
                    neg, pos = jax.lax.top_k(-ex, k)
                ids = jnp.where(jnp.isfinite(neg), layout.order[pos], -1)
                n_sel = jnp.sum(selected, axis=1).astype(jnp.int32)
                return SearchResult(-neg, ids, n_sel, jnp.zeros_like(n_sel))
        with jax.named_scope("bbc.collect"):
            positions = jnp.arange(n_flat, dtype=jnp.int32)
            _, sel_pos = col.collect_batch(est, positions, lane_valid, bucket,
                                           hist, n_cand, m)
            safe_pos = jnp.maximum(sel_pos, 0)
            sel_ids = jnp.where(sel_pos >= 0, layout.order[safe_pos], -1)
            e_at_sel = jnp.take_along_axis(early, safe_pos, axis=1)
            have = jnp.isfinite(e_at_sel) & (sel_pos >= 0)
            n_early = (jnp.sum(lane_valid, axis=1) - nmiss).astype(jnp.int32)
    else:
        # CPU fallback: there is no VMEM-residency win to collect inline, so
        # skip the prediction machinery and select the exact top-n_cand by
        # estimate with one batched top_k (same set the bucket collection
        # yields — bucketize is monotone in the estimate; boundary ties
        # break by global id to match the sharded re-cut), then one exact
        # pass over the selection.
        with jax.named_scope("bbc.scan"):
            est2 = ops.pq_adc_batch(stream_codes, luts, backend=backend)
            est = jnp.where(lane_valid, jnp.sqrt(jnp.maximum(est2, 0.0)), INF)
        with jax.named_scope("bbc.collect"):
            sel_est, sel_pos = _topk_est_id(est, layout.order, n_cand)
            sel_ids = jnp.where(jnp.isfinite(-sel_est), layout.order[sel_pos],
                                -1)
            e_at_sel = jnp.full(sel_pos.shape, INF, est.dtype)
            have = jnp.zeros(sel_pos.shape, bool)
            n_early = jnp.zeros((b,), jnp.int32)

    with jax.named_scope("bbc.rerank"):
        miss = ~have & (sel_ids >= 0)
        if fused:
            # stragglers only — keep the targeted per-row gather
            miss_d = _exact_dists_rows(index.vectors,
                                       jnp.where(miss, sel_ids, 0), qs)
        elif dense_rerank:
            # the whole selection misses (no inline pass on CPU): one shared
            # matmul over the stream beats n_cand per-row gathers
            stream_vecs = index.vectors[layout.order]
            exact_all = ops.l2_exact_batch(stream_vecs, qs, backend=backend)
            miss_d = jnp.take_along_axis(exact_all, jnp.maximum(sel_pos, 0),
                                         axis=1)
        else:
            miss_d = _exact_dists_rows(index.vectors,
                                       jnp.where(miss, sel_ids, 0), qs)
        ex = jnp.where(have, e_at_sel, jnp.where(miss, miss_d, INF))
        second = jnp.sum(miss, axis=1).astype(jnp.int32)

    with jax.named_scope("bbc.final"):
        neg, order = jax.lax.top_k(-ex, k)
        return SearchResult(-neg, jnp.take_along_axis(sel_ids, order, axis=1),
                            n_early + second, second)


def _ivf_pq_predictive_batch(index, qs, layout, probed, lane_valid,
                             stream_codes, luts, k, n_probe, n_cand, m,
                             backend, fused, pred_state, pred_count):
    """Predictive early-exact IVF+PQ (the tau_pred subsystem's PQ core).

    The re-rank pool is {bucket <= max(tau_pred, tau_true-at-pred_count)}
    instead of the top-n_cand-by-estimate cut: with a warm predictor that is
    ~pred_count candidates (default ~2k) instead of n_cand (default 8k).  On
    the fused path lanes under tau_pred were exacted inline during the scan;
    the fallback pass re-ranks only survivors the prediction missed.  The
    per-query codebooks are built exactly like the static fused path's, so
    bucket indices stay comparable batch-to-batch for the EMA.
    """
    ivf = index.ivf
    b = qs.shape[0]
    n_flat = layout.n_flat
    count = _resolve_pred_count(pred_count, k, n_cand)
    st = min(4, n_probe)
    with jax.named_scope("bbc.plan"):
        sample_est = _pq_sample_est(layout, probed, stream_codes, luts, st,
                                    ivf.cap)
        k_cb = min(n_cand, sample_est.shape[1])
        cbs = jax.vmap(lambda s: rb.build_codebook(s, k=k_cb, m=m))(
            sample_est)
        tau_pred = rerank.predict_tau(pred_state, count)

    with jax.named_scope("bbc.scan"):
        if fused:
            est, bucket, hist, early, nmiss = _fused_scan_stream(
                index, stream_codes, layout, lane_valid, luts, qs,
                cbs.d_min, cbs.delta, cbs.ew_map, m,
                jnp.full((b,), tau_pred, jnp.int32), backend)
            est = jnp.where(lane_valid, est, INF)
            n_early = (jnp.sum(lane_valid, axis=1) - nmiss).astype(jnp.int32)
        else:
            # CPU: no VMEM-residency win to collect inline — the whole pool
            # goes through the (much smaller than n_cand) fallback gather
            # instead.
            est2 = ops.pq_adc_batch(stream_codes, luts, backend=backend)
            est = jnp.where(lane_valid, jnp.sqrt(jnp.maximum(est2, 0.0)), INF)
            bucket, hist = ops.bucket_hist_batch(
                est, lane_valid, cbs.d_min, cbs.delta, cbs.ew_map, m,
                backend=backend)
            early = None
            n_early = jnp.zeros((b,), jnp.int32)

    # Survivors form an est-prefix (bucketize is monotone), so est-priority
    # truncation at a budget <= n_cand keeps the pool a SUBSET of the static
    # n_cand-by-estimate cut: the predictive result can only match or shrink
    # the static selection, never pull in ids the static path couldn't see.
    with jax.named_scope("bbc.collect"):
        budget = min(_pred_budget(count, n_flat), n_cand)
        _, sel_pos, sel_ok, tau_true = _predictive_select(
            est, bucket, hist, lane_valid, tau_pred, count, budget,
            layout.order)
        sel_ids = jnp.where(sel_ok, layout.order[sel_pos], -1)

        # Fallback pass (undershoot correctness): survivors not covered
        # inline — the fallback-plan mask at the selected positions.  On the
        # unfused path nothing was computed inline, so the whole selection is
        # fallback work.
        if early is not None:
            e_at_sel = jnp.take_along_axis(early, sel_pos, axis=1)
            fb = rerank.predicted_fallback_mask(
                bucket, lane_valid, jnp.full((b,), tau_pred, jnp.int32),
                tau_true)
            miss = jnp.take_along_axis(fb, sel_pos, axis=1) & sel_ok
            have = sel_ok & ~miss
        else:
            e_at_sel = jnp.full(sel_pos.shape, INF, est.dtype)
            have = jnp.zeros(sel_pos.shape, bool)
            miss = sel_ok
    with jax.named_scope("bbc.rerank"):
        if not fused and 4 * budget >= n_flat:
            # pool is a large fraction of the stream (large-k regime): one
            # shared matmul beats per-row gathers, as in the static
            # dense_rerank path
            exact_all = ops.l2_exact_batch(index.vectors[layout.order], qs,
                                           backend=backend)
            miss_d = jnp.take_along_axis(exact_all, jnp.maximum(sel_pos, 0),
                                         axis=1)
        else:
            miss_d = _exact_dists_rows(index.vectors,
                                       jnp.where(miss, sel_ids, 0), qs)
        ex = jnp.where(have, e_at_sel, jnp.where(miss, miss_d, INF))
        second = jnp.sum(miss, axis=1).astype(jnp.int32)

    with jax.named_scope("bbc.final"):
        neg, order = jax.lax.top_k(-ex, k)
        res = SearchResult(-neg, jnp.take_along_axis(sel_ids, order, axis=1),
                           n_early + second, second)
        return res, rerank.predictor_update(pred_state, hist)


def _rabitq_batch_bounds(index: RabitqIndex, stream: RabitqStream,
                         qs: jax.Array, lane_valid: jax.Array, eps0: float,
                         d2: jax.Array):
    """Batched RaBitQ bounds over the single-device shared stream.  The
    stream-level estimator itself lives with the kernels
    (``kernels.ref.rabitq_bounds_stream`` — it is the inner math of the
    bound-fused kernel's mirror, shared by the mesh-sharded path)."""
    return kref.rabitq_bounds_stream(
        codes_s=stream.codes, norm_o=stream.norm_o, f_o=stream.f_o,
        cl=stream.cl, centroids=index.ivf.centroids, rot=index.rq.rot,
        qs=qs, d2=d2, lane_valid=lane_valid, eps0=eps0)


# --------------------------------------------------------------------------
# Bound-fused RaBitQ scan plumbing (the executed Table-2 path)
# --------------------------------------------------------------------------
#
# The fused RaBitQ searchers size their band from per-query SAMPLE-prefix
# codebooks (the paper's 5-10-nearest-cluster sample, like the PQ paths and
# the sharded deployment) instead of the full-stream upper-bound top-k the
# two-phase path sorts for: the band threshold tau_ub then comes from the
# scan's own histogram/bucket outputs, which is exact at bucket granularity
# — any lane excluded has lb beyond the bucket containing the k-th smallest
# ub, hence beyond Dist_k (certainly out) for ANY codebook.  The inline
# gate tau_inline only decides WHERE a band member's exact distance comes
# from (the fused scan vs the straggler gather), never whether it is
# evaluated, so correctness cannot ride on it.

_TAU_INLINE_MARGIN = 2   # buckets of slack on the static sample-derived gate
# Stride of the predictor's ub-histogram subsample: the EMA must track the
# FULL probed set's upper-bound distribution (the nearest-tile sample prefix
# is distance-skewed and lands systematically low at depth — the same effect
# bench_tau_pred documents for PQ prefix ranks), but the full scatter
# histogram is the CPU bottleneck.  A strided slice of the cluster-ordered
# stream is an unbiased (roughly cluster-stratified) subsample; predict_tau
# is queried at the stride-scaled count.
_PRED_HIST_STRIDE = 8
# Predictive-gate margin: per-query band thresholds scatter a few buckets
# around the EMA's global prediction; overshooting certifies extra lanes for
# free (their exact distances ride the resident tile) while every
# undershot bucket is real second-gather traffic, so the gate leans high.
_PRED_GATE_MARGIN = 3


def _tau_bucket_search(bucket: jax.Array, valid: jax.Array, count: int,
                       m: int) -> jax.Array:
    """First bucket whose cumulative in-range count reaches ``count`` —
    exactly ``rb.threshold_bucket`` of the bucket histogram, computed by
    bisection over row-wise compare-sums.  On CPU the (m+1)-bin scatter
    histogram is the stream-scale bottleneck (~5x the cost of the bounds
    matmul); ceil(log2(m+2)) masked compare-sums replace it.  Rows are
    independent, so callers stack several searches (e.g. both bounds) into
    one call.  Returns m (overflow id) when fewer than ``count`` in-range
    lanes exist, matching ``threshold_bucket``."""
    rows = bucket.shape[0]
    # fold validity and the overflow bucket into one effective array so the
    # bisection body is a single compare + reduce per step
    eff = jnp.where(valid & (bucket < m), bucket, m)
    lo = jnp.zeros((rows,), jnp.int32)
    hi = jnp.full((rows,), m, jnp.int32)
    for _ in range((m + 1).bit_length()):
        mid = (lo + hi) // 2
        cnt = jnp.sum(eff <= mid[:, None], axis=1)
        ok = cnt >= count
        hi = jnp.where(ok, mid, hi)
        lo = jnp.where(ok, lo, mid + 1)
    return hi


def _rabitq_inline_rank(k: int, st: int, n_probe: int, k_cb: int) -> int:
    """Sample-prefix rank of the k-th upper bound (Alg. 4 line 4's
    |sample|/|O| scaling with the static tile ratio st/n_probe)."""
    return max(1, min(k_cb, round(k * st / max(n_probe, 1))))


def _rabitq_sample_plan(sample_ub: jax.Array, k: int, count: int, st: int,
                        n_probe: int, m: int, scale_rank: bool = True):
    """Per-query codebook + static inline gate from the sample-prefix upper
    bounds.  One top-k serves both: the codebook quantiles (anchored at k,
    like the two-phase plan's ub top-k) and the rank-scaled ``count``-th-ub
    seed whose bucket (+ margin) is the static ``tau_inline``.

    ``scale_rank=False`` takes the ``count``-th sample ub itself: the sample
    is a subset of the probed lanes, so its ``count``-th smallest ub is at
    or above the probed set's, and the gate covers the whole band."""
    k_cb = min(k, sample_ub.shape[1])
    topk_s = -jax.lax.top_k(-sample_ub, k_cb)[0]              # (B, k_cb) asc
    cbs = jax.vmap(lambda t: rb.build_codebook_from_topk(t, m=m))(topk_s)
    rank = (_rabitq_inline_rank(count, st, n_probe, k_cb) if scale_rank
            else min(count, k_cb))
    kth_s = topk_s[:, rank - 1]
    tau_static = jax.vmap(lambda c, v: rb.bucketize(c, v[None])[0])(cbs,
                                                                    kth_s)
    tau_static = jnp.minimum(tau_static + _TAU_INLINE_MARGIN, m - 1)
    return cbs, tau_static.astype(jnp.int32)


def _rabitq_sample_ub(codes, norm_o, f_o, cl, centroids, rot,
                      layout: ivf_mod.FlatLayout, probed: jax.Array,
                      qs: jax.Array, d2: jax.Array, st: int, cap: int,
                      eps0: float):
    """Sample-prefix upper bounds for the kernel paths: a small dedicated
    bounds pass over the nearest ``st`` probed tiles, run BEFORE the fused
    kernel (which needs the codebook as an input).  Stream-level arrays in,
    so the batched path (the engine's ``RabitqStream``) and each shard's
    local stream share the one implementation; the composed CPU path
    instead samples the full bounds it has already computed."""
    spos, sok = ivf_mod.tile_positions(layout, probed[:, :st], cap)

    def one(a):
        pos, okr, q, d2q = a
        safe = jnp.where(okr, pos, 0)
        _, _, ubq = kref.rabitq_bounds_stream(
            codes[safe].astype(jnp.float32), norm_o[safe], f_o[safe],
            cl[safe], centroids, rot, q[None], d2q[None], okr[None], eps0)
        return ubq[0]

    sample_ub = jax.lax.map(one, (spos, sok, qs, d2))
    return sample_ub, sok


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probe", "use_bbc", "m", "eps0", "backend",
                     "fused", "pred_count"))
def ivf_rabitq_search_batch(
    index: RabitqIndex,
    qs: jax.Array,                 # (B, d)
    layout: ivf_mod.FlatLayout,
    k: int,
    n_probe: int,
    use_bbc: bool = False,
    m: int = 128,
    eps0: float = 3.0,
    backend: str | None = None,
    fused: bool | None = None,
    stream: RabitqStream | None = None,
    pred_state: rerank.PredictorState | None = None,
    pred_count: int | None = None,
    live: jax.Array | None = None,
) -> SearchResult:
    """Batched IVF+RaBitQ (±BBC) on the shared candidate stream.

    ``stream`` is the layout-ordered ``RabitqStream`` (pass the engine's
    build-time copy to skip the per-call gathers; built on the fly when
    None, e.g. for direct test calls).

    The BBC path runs the bound-fused scan by default (``fused=None`` ->
    True): per stream tile the scan computes estimates AND bounds,
    bucketizes them against the sample-prefix codebook, and exact-re-ranks
    lanes whose lower-bound bucket the inline gate certifies while the
    vector tile is resident — on TPU inside ``ops.fused_rabitq_scan_batch``
    (codes and vectors co-tiled in VMEM), on CPU as the composed
    restructure of the same math (one shared exact matmul; the win there is
    the planning — sample codebooks + bisected threshold buckets replace
    the two full-stream top-k sorts of the two-phase path).  Only
    bound-uncertain stragglers (band members the gate missed) take a second
    gather pass, and ``n_second_pass`` is their MEASURED count — the
    executed form of the Table-2 cache-miss story PR 3 only modeled.
    ``fused=False`` keeps the two-phase reference path (full-stream
    ub-top-k plan + one dense band matmul; its predictive counters are the
    modeled volume the fused path's measured counts are benchmarked
    against in ``bench_rabitq_fused``).

    With ``pred_state``: the bounds already make the band minimal, so
    prediction cannot shrink the re-rank count (the paper's RaBitQ gain is
    cache misses, not re-ranks); instead the engine's EMA ``tau_pred``
    gates the inline band exactly as it gates the PQ pool — while cold
    (tau_pred = -1) nothing is certified and the whole band goes through
    the gather, exactly like the two-phase path.  Returns
    ``(SearchResult, new_state)``; on this deployment the EMA tracks a
    strided-subsample upper-bound histogram and is queried at the
    stride-scaled count (``_PRED_HIST_STRIDE``) — the sharded deployment
    tracks the psum'd full histogram at k; states are engine-owned and
    never cross deployments.  Results are id-set identical to the
    two-phase path for any gate (the band always covers the bound-straddle
    set).
    """
    if pred_state is not None and not use_bbc:
        raise ValueError("predictive search requires use_bbc=True")
    if fused is None:
        fused = True
    if stream is None:
        with jax.named_scope("bbc.scan"):
            stream = rabitq_stream(index, layout)
    ivf = index.ivf
    b = qs.shape[0]
    cap = ivf.cap
    with jax.named_scope("bbc.route"):
        probed, lane_valid, d2 = _routing(ivf, layout, qs, n_probe)
        if live is not None:
            # tombstones ride the lane-mask mechanism: every downstream
            # consumer (bounds, band, histogram, collection) already honors
            # it
            lane_valid = lane_valid & live[None, :]
    n_flat = layout.n_flat
    stream_ids = layout.order

    if use_bbc and fused:
        return _ivf_rabitq_fused_batch(
            index, stream, qs, layout, probed, lane_valid, d2, k, n_probe,
            m, eps0, backend, pred_state, pred_count)

    with jax.named_scope("bbc.scan"):
        est, lb, ub = _rabitq_batch_bounds(index, stream, qs, lane_valid,
                                           eps0, d2=d2)

    if not use_bbc:
        # ---- baseline: per-cluster threshold re-ranking, vmapped ----------
        with jax.named_scope("bbc.collect"):
            tpos, tok = ivf_mod.tile_positions(layout, probed, cap)
            lb_t = jnp.where(tok, jnp.take_along_axis(lb, tpos, axis=1), INF)
            ids_t = jnp.where(tok, stream_ids[tpos], -1)
            lb_t = lb_t.reshape(b, n_probe, cap)
            ids_t = ids_t.reshape(b, n_probe, cap)
            ok_t = tok.reshape(b, n_probe, cap)
        budget = min(cap, _rerank_budget(k, cap))

        def one_query(args):
            c_lb, c_ids, c_ok, q = args

            def step(carry, xs):
                pool_d, pool_i, n_rr = carry
                t_lb, t_ids, t_ok = xs
                thresh = pool_d[k - 1]
                mask = t_ok & (t_lb < thresh)
                pos, okc = rb.compact_mask(mask, budget)
                safe = jnp.minimum(pos, cap - 1)
                r_ids = jnp.where(okc, t_ids[safe], -1)
                r_d = _exact_dists(index.vectors, r_ids, q)
                r_d = jnp.where(okc, r_d, INF)
                alld = jnp.concatenate([pool_d, r_d])
                alli = jnp.concatenate([pool_i, r_ids])
                neg, idx = jax.lax.top_k(-alld, k)
                return (-neg, alli[idx], n_rr + jnp.sum(okc)), None

            pool0 = (jnp.full((k,), INF, lb.dtype),
                     jnp.full((k,), -1, jnp.int32), jnp.int32(0))
            (pd, pi, n_rr), _ = jax.lax.scan(step, pool0,
                                             (c_lb, c_ids, c_ok))
            order = jnp.argsort(pd)
            return pd[order], pi[order], n_rr

        with jax.named_scope("bbc.rerank"):
            pd, pi, n_rr = jax.lax.map(one_query, (lb_t, ids_t, ok_t, qs))
        with jax.named_scope("bbc.final"):
            return SearchResult(pd, pi, n_rr.astype(jnp.int32),
                                n_rr.astype(jnp.int32))

    # ---- two-phase BBC reference path (Alg. 3, batched greedy) -------------
    # Plan from the full-stream ub top-k (order-statistic thresholds), then
    # resolve the whole uncertain band in ONE shared exact-distance matmul
    # over the stream — the separate estimate-then-gather structure whose
    # second-pass traffic the fused path eliminates.  Kept as the reference
    # contender (``fused=False``): bench_rabitq_fused measures the fused
    # path against it, and its predictive counters are the MODELED
    # second-pass volume the fused path's measured counts must reproduce.
    with jax.named_scope("bbc.plan"):
        plan = rerank.greedy_rerank_plan_batch(lb, ub, k, lane_valid, m=m)
    with jax.named_scope("bbc.collect"):
        n_evals = jnp.sum(plan.rerank_mask, axis=1).astype(jnp.int32)
    with jax.named_scope("bbc.rerank"):
        exact_all = ops.l2_exact_batch(stream.vectors, qs, backend=backend)
        exact_flat = jnp.where(plan.rerank_mask, exact_all, INF)

    with jax.named_scope("bbc.final"):
        res = jax.vmap(
            lambda p, ef, lbv, e: rerank.greedy_rerank_finalize(
                p, ef, lbv, stream_ids, k, est=e)
        )(plan, exact_flat, jnp.where(lane_valid, lb, INF), est)
        if pred_state is not None:
            # inline coverage: band members predicted by the cross-batch tau;
            # the fallback (second-pass gather) shrinks to the unpredicted
            # remainder
            count = max(pred_count, k) if pred_count is not None else k
            tau_pred = rerank.predict_tau(pred_state, count)
            covered = plan.rerank_mask & (plan.a_lb <= tau_pred)
            n_second = jnp.sum(plan.rerank_mask & ~covered,
                               axis=1).astype(jnp.int32)
            hist_ub = jax.vmap(rb.histogram, in_axes=(0, None, 0))(
                plan.a_ub, m, lane_valid)
            res_p = SearchResult(res.topk_dists, res.topk_ids, n_evals,
                                 n_second)
            return res_p, rerank.predictor_update(pred_state, hist_ub)
        return SearchResult(res.topk_dists, res.topk_ids, n_evals, n_evals)


def _ivf_rabitq_fused_batch(index, stream, qs, layout, probed, lane_valid,
                            d2, k, n_probe, m, eps0, backend, pred_state,
                            pred_count):
    """Bound-fused RaBitQ batch core (the executed Table-2 path).

    One logical pass over the stream: estimates + bounds + bucketization +
    the inline exact re-rank of gate-certified lanes, then a straggler-only
    second gather for band members the gate missed.  The band itself is
    exact at bucket granularity for any codebook (tau_ub comes from the
    scan's own ub histogram at k), so the id set matches the two-phase path
    — the gate moves memory traffic, never correctness.
    """
    ivf = index.ivf
    rq = index.rq
    b = qs.shape[0]
    n_flat = layout.n_flat
    kernel = ops.resolve_backend(backend) == "pallas"
    st = min(4, n_probe)
    count = k if pred_count is None else max(pred_count, k)

    est = lb = ub = None
    if kernel:
        with jax.named_scope("bbc.plan"):
            sample_ub, sok = _rabitq_sample_ub(
                stream.codes, stream.norm_o, stream.f_o, stream.cl,
                ivf.centroids, index.rq.rot, layout, probed, qs, d2, st,
                ivf.cap, eps0)
    else:
        with jax.named_scope("bbc.scan"):
            est, lb, ub = _rabitq_batch_bounds(index, stream, qs, lane_valid,
                                               eps0, d2=d2)
        with jax.named_scope("bbc.plan"):
            spos, sok = ivf_mod.tile_positions(layout, probed[:, :st],
                                               ivf.cap)
            sample_ub = jnp.where(sok, jnp.take_along_axis(ub, spos, axis=1),
                                  INF)
    with jax.named_scope("bbc.plan"):
        # the kernel takes its gate before the scan and cannot refresh it
        # from the scan's own histogram (the composed form below does), so
        # its static gate is the unscaled sample order statistic, which
        # covers the band
        cbs, tau_static = _rabitq_sample_plan(sample_ub, k, count, st,
                                              n_probe, m,
                                              scale_rank=not kernel)
        if pred_state is not None:
            # the EMA gate, exactly as it gates the PQ pool: -1 while cold
            # (nothing certified inline — the first batch behaves like the
            # two-phase path), the predicted bucket once warm.  The EMA
            # tracks the strided-subsample ub histogram, so the query count
            # scales by the stride.
            count_s = max(1, -(-count // _PRED_HIST_STRIDE))
            # margin biased up: an overshooting gate certifies a few extra
            # lanes (free — their exact distances ride the resident tile),
            # an undershooting one pays real second-pass gathers
            tau_inline = jnp.full(
                (b,), rerank.predict_tau(pred_state, count_s,
                                         margin=_PRED_GATE_MARGIN),
                jnp.int32)
        else:
            tau_inline = tau_static

    with jax.named_scope("bbc.scan"):
        if kernel:
            # the fused kernel: codes + vectors co-tiled through VMEM, exact
            # distances of certified lanes computed while the tile is
            # resident
            (est, lb, ub, bucket_lb, bucket_ub, _hist_lb, hist_ub, exact_c,
             certified, _nmiss) = ops.fused_rabitq_scan_batch(
                stream.codes, stream.vectors, stream.norm_o, stream.f_o,
                stream.cl, ivf.centroids, rq.rot, qs, d2, lane_valid,
                cbs.d_min, cbs.delta, cbs.ew_map, m, tau_inline, eps0=eps0,
                backend=backend)
            tau_ub = jax.vmap(rb.threshold_bucket, in_axes=(0, None))(
                hist_ub, k)[0]
            tau_lb = jax.vmap(rb.threshold_bucket, in_axes=(0, None))(
                _hist_lb, k)[0]
        else:
            # composed CPU form of the same math: the scatter histograms the
            # kernel accumulates for free are replaced by bisected threshold
            # buckets (identical values), and the certified mask is applied
            # to one shared exact matmul — no gather/fusion axis exists on
            # CPU, so the restructured planning IS the speedup
            bucket_lb = jax.vmap(rb.bucketize)(cbs, lb)
            bucket_ub = jax.vmap(rb.bucketize)(cbs, ub)
            taus = _tau_bucket_search(
                jnp.concatenate([bucket_ub, bucket_lb], axis=0),
                jnp.concatenate([lane_valid, lane_valid], axis=0), k, m)
            tau_ub, tau_lb = taus[:b], taus[b:]
            if pred_state is None:
                # the stream-parallel CPU form has the full scan before the
                # re-rank leg, so the static gate refreshes to the true band
                # threshold (Alg. 4 line 14 at full progress — the same
                # refresh the single-query PQ path documents); the
                # predictive gate stays exactly tau_pred so the measured
                # straggler count is the EMA's miss, comparable with the
                # modeled volume
                tau_inline = jnp.maximum(tau_inline, tau_ub)
            certified = lane_valid & (bucket_lb <= tau_inline[:, None])

    with jax.named_scope("bbc.collect"):
        certain_in = lane_valid & (bucket_ub < tau_lb[:, None])
        band = lane_valid & (bucket_lb <= tau_ub[:, None]) & ~certain_in
        straggler = band & ~certified
        n_second = jnp.sum(straggler, axis=1).astype(jnp.int32)
        n_evals = jnp.sum(band, axis=1).astype(jnp.int32)
        if kernel:
            # straggler-only second gather (the measured residue of Table
            # 2): lb-priority compaction into a static budget, per-row
            # exact, with a dense fallback should the gate miss more than
            # the budget (a cold/undershooting predictor) — correctness
            # never rides on it
            budget = int(min(n_flat, ((max(2 * k, 2048) + 127) // 128) * 128))
            key_lb = jnp.where(straggler, lb, INF)
            neg, pos = jax.lax.top_k(-key_lb, budget)
            okp = jnp.isfinite(-neg)
            sids = jnp.where(okp, layout.order[pos], -1)

    with jax.named_scope("bbc.rerank"):
        if kernel:
            sd = _exact_dists_rows(index.vectors, jnp.where(okp, sids, 0), qs)
            # one flat scatter over the batch, each row with a spare slot
            # for the empty picks: the TPU compiler rewrites a batched
            # (vmapped) scatter into this form itself, and drops its
            # op_name, and so its stage, on the way
            row = (n_flat + 1) * jnp.arange(b, dtype=pos.dtype)[:, None]
            flat = (jnp.where(okp, pos, n_flat) + row).reshape(-1)
            filled = jnp.full((b * (n_flat + 1),), INF, sd.dtype).at[
                flat].set(sd.reshape(-1))
            filled = filled.reshape(b, n_flat + 1)[:, :n_flat]
            exact_band = jnp.where(certified, exact_c, filled)
            # only the overflowing queries take the dense values: a query's
            # result must not depend on which other queries share its batch
            # (the dense and gathered exact legs round differently on TPU)
            overflow = n_second > budget                      # (B,)

            def dense(_):
                allx = ops.l2_exact_batch(stream.vectors, qs, backend=backend)
                return jnp.where(overflow[:, None],
                                 jnp.where(certified, exact_c, allx),
                                 exact_band)

            exact_band = jax.lax.cond(jnp.any(overflow), dense,
                                      lambda _: exact_band, None)
            exact_band = jnp.where(band, exact_band, INF)
        else:
            # one shared matmul serves the inline AND straggler legs (single
            # float source: cold/warm/static variants stay bitwise
            # identical); the counter is still the straggler-lane count of
            # the executed certified gate — on TPU those lanes are the
            # literal second gather
            exact_all = ops.l2_exact_batch(stream.vectors, qs,
                                           backend=backend)
            exact_band = jnp.where(band, exact_all, INF)

    with jax.named_scope("bbc.final"):
        plan = rerank.GreedyRerankPlan(
            rerank_mask=band, certain_in=certain_in,
            certain_out=lane_valid & ~band & ~certain_in,
            tau_ub=tau_ub, tau_lb=tau_lb, a_lb=bucket_lb, a_ub=bucket_ub)
        res = jax.vmap(
            lambda p, ef, lbv, e: rerank.greedy_rerank_finalize(
                p, ef, lbv, layout.order, k, est=e)
        )(plan, exact_band, lb, est)
        out = SearchResult(res.topk_dists, res.topk_ids, n_evals, n_second)
        if pred_state is not None:
            # EMA over the strided-subsample ub histogram: unbiased for the
            # full probed set (see _PRED_HIST_STRIDE) at 1/stride of the
            # scatter cost; bucket indices stay comparable batch-to-batch
            # because the codebooks are equal-depth over samples of the same
            # distribution
            hist_s = jax.vmap(rb.histogram, in_axes=(0, None, 0))(
                bucket_ub[:, ::_PRED_HIST_STRIDE], m,
                lane_valid[:, ::_PRED_HIST_STRIDE])
            return out, rerank.predictor_update(pred_state, hist_s)
        return out


# --------------------------------------------------------------------------
# Mesh-sharded searchers (corpus row-sharded over the mesh's 'model' axis)
# --------------------------------------------------------------------------
#
# The corpus stream is partitioned by ``ivf.sharded_layout`` (round-robin
# within each cluster) and the per-shard stream tensors (vectors / PQ codes /
# RaBitQ codes) are materialized offline with a leading shard axis, so under
# ``shard_map`` each chip scans ONLY its own rows.  One search step per batch:
#
#   1. replicated routing matmul (every chip computes the same probe sets),
#   2. per-shard fused scan over the local stream (the same ops.* kernels the
#      single-device batched path runs — a shard's stream is just shorter),
#   3. per-query local (m+1)-histograms; ``psum`` over 'model'
#      <- (m+1)*4 bytes per query, NOT k*8,
#   4. relaxed-threshold survivor compaction to a fixed per-shard budget
#      (~count/S * slack, key-priority),
#   5. exact re-rank of local survivors ON the shard that owns their rows
#      (the distributed analogue of Alg. 4's "compute exact while the vector
#      tile is hot": survivor vectors never cross the interconnect),
#   6. ``all_gather`` of survivors only, final replicated selection.
#
# ``use_bbc=False`` selects the naive distributed collector baseline: every
# shard maintains and gathers a full local top-k (k*8 bytes per shard on the
# wire), the quantity ``core.distributed.collective_cost_model`` prices.

SHARD_AXIS = "model"
HOST_AXIS = "host"


def _shard_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the corpus stream is sharded over.  A 2-D multi-host mesh
    (("host", "model")) selects the hierarchical collective schedule —
    intra-host reduce over 'model' first, then the inter-host round over
    'host' (see ``dist.hier_psum``); a flat 1-D mesh stays single-stage."""
    if HOST_AXIS in mesh.axis_names:
        return (HOST_AXIS, SHARD_AXIS)
    return (SHARD_AXIS,)


def _n_shards(mesh) -> int:
    n = 1
    for ax in _shard_axes(mesh):
        n *= mesh.shape[ax]
    return n


def _layout_spec(axes):
    return P(axes, None)        # every ShardedLayout leaf: (S, ...)


def _stream2_spec(axes):
    return P(axes, None)        # (S, F) stream scalars


def _stream3_spec(axes):
    return P(axes, None, None)  # (S, F, d) stream tensors


def _mesh_sizes(mesh, axes) -> tuple:
    """Static mesh axis sizes for ``dist.shard_rows`` call sites."""
    return tuple(int(mesh.shape[ax]) for ax in axes)


def _shard_budget(budget: int | None, count: int, mesh, shard_flat: int,
                  slack: float) -> int:
    if budget is None:
        budget = dist.survivor_budget(count, _n_shards(mesh), slack=slack)
    return max(8, min(budget, shard_flat))


def _local_block(sl: ivf_mod.ShardedLayout) -> ivf_mod.FlatLayout:
    """Inside a shard_map body the ShardedLayout arrives as a (1, ...) block;
    squeeze it into this shard's FlatLayout view."""
    return ivf_mod.FlatLayout(order=sl.order[0], cluster_of=sl.cluster_of[0],
                              offsets=sl.offsets[0], valid=sl.valid[0])


def _local_routing(centroids: jax.Array, qs: jax.Array, n_probe: int):
    """Replicated routing (identical on every shard): the same
    implementation the single-device path routes with, so probe sets match
    bit-for-bit."""
    return ivf_mod.route_batch_centroids(centroids, qs, n_probe)


def _exact_at_positions(svecs: jax.Array, qs: jax.Array, pos: jax.Array,
                        ok: jax.Array) -> jax.Array:
    """Per-query exact distances for (B, w) local stream positions (the
    budget-sized survivor sets; INF where not ok)."""

    def one(a):
        p, o, q = a
        v = svecs[jnp.where(o, p, 0)]
        vq = jnp.matmul(v, q, precision="highest")
        d = jnp.sqrt(jnp.maximum(
            jnp.sum(v * v, -1) - 2.0 * vq + jnp.sum(q * q), 0.0))
        return jnp.where(o, d, INF)

    return jax.lax.map(one, (pos, ok, qs))


def _sharded_codebooks(layout: ivf_mod.FlatLayout, probed: jax.Array,
                       vals: jax.Array, st: int, cap_shard: int, k_cb: int,
                       m: int, axes=(SHARD_AXIS,), sizes=()):
    """Per-query codebooks from the nearest ``st`` probed clusters, gathered
    across shards.  Each shard contributes its slice of those clusters; the
    union is exactly their full membership, so the codebook sees the same
    sample population as the single-device batched path (order differs,
    which build_codebook's top-k absorbs).  The gather is small: st * cap
    lanes per query, the codebook-sample prefix only.  Returns
    ``(codebooks, sample)`` — the gathered sample doubles as the seed for
    the speculative compaction threshold (``_sample_spec_tau``)."""
    spos, sok = ivf_mod.tile_positions(layout, probed[:, :st], cap_shard)
    s_local = jnp.where(sok, jnp.take_along_axis(vals, spos, axis=1), INF)
    (sample,) = dist.gather_survivors(axes, s_local)
    k_cb = min(k_cb, sample.shape[1])

    # ONE ascending sort serves both consumers: the codebook prefix here
    # and the order-statistic threshold in _sample_spec_tau (which would
    # otherwise re-sort the same sample).  The sample is replicated after
    # the gather, so the sort + codebook build are row-split across the
    # shard axis instead of running S identical copies.
    def _sort_and_build(s):
        asc = jax.lax.sort(s, dimension=1)
        cbs = jax.vmap(lambda t: rb.build_codebook_from_topk(t, m=m))(
            asc[:, :k_cb])
        return cbs, asc

    return dist.shard_rows(axes, sizes, _sort_and_build, sample)


_SPEC_TAU_MARGIN = 2   # buckets of slack on the speculative threshold


def _sample_spec_tau(cbs, sample: jax.Array, count: int,
                     n_probed: jax.Array, m: int) -> jax.Array:
    """Sample-derived speculative compaction threshold for the fused
    shard-collect pass: the bucket of the rank-scaled ``count``-th smallest
    sample value (rank = count * |sample| / |probed|, Alg. 4 line 4's
    scaling), plus margin.  Overshoot is cheap — a few extra lanes in the
    budget buffer; undershoot costs the bounded correction pass — so the
    threshold leans high.  Returns m (compact the full in-range stream)
    when the scaled rank runs off the sample: that is the degenerate
    count >= n_probed regime, where the true tau is m as well.

    ``sample`` must be sorted ascending per query (``_sharded_codebooks``
    returns it that way — the sort is shared with the codebook build)."""
    ns = sample.shape[1]
    n_valid = jnp.sum(jnp.isfinite(sample), axis=1)
    frac = n_valid.astype(jnp.float32) / jnp.maximum(
        n_probed.astype(jnp.float32), 1.0)
    rank = jnp.ceil(count * frac).astype(jnp.int32)
    kth = jnp.take_along_axis(
        sample, jnp.clip(rank - 1, 0, ns - 1)[:, None], axis=1)[:, 0]
    tau = jax.vmap(lambda c, v: rb.bucketize(c, v[None])[0])(cbs, kth)
    tau = jnp.minimum(tau + _SPEC_TAU_MARGIN, m).astype(jnp.int32)
    return jnp.where(rank >= n_valid, m, tau)


def _kth_value_mask(vals: jax.Array, ids: jax.Array,
                    kth: int | jax.Array) -> jax.Array:
    """Exact-width mask of the per-row ``kth`` smallest (value, global-id)
    pairs (``kth`` one width, or (rows,) widths): every lane strictly below
    the kth-smallest value, plus the smallest-id lanes at the boundary value
    up to the remaining width.
    Global ids are unique, so the kept SET is a deterministic function of
    the (value, id) multiset — identical for the batched stream order and
    the sharded gathered-pool order.  PQ estimates tie exactly whenever two
    vectors share codes, and a tie-inclusive or pool-order-arbitrary cut
    diverges between the two deployments exactly there.  Bisection on int32
    bit patterns — monotone for the nonnegative-or-INF distances used here
    — so the cut costs ~62 compare-sum passes instead of a pool-wide
    ``top_k`` at ``kth`` ~ pool/2, the dominant replicated cost of the
    post-gather re-cut at large n_cand."""
    bits = jax.lax.bitcast_convert_type(vals, jnp.int32)
    rows = vals.shape[0]
    lo = jnp.zeros((rows,), jnp.int32)
    hi = jnp.full((rows,), jnp.int32(0x7F800000))   # +inf bit pattern
    for _ in range(31):
        mid = lo + (hi - lo) // 2
        cnt = jnp.sum(bits <= mid[:, None], axis=1)
        ok = cnt >= kth
        hi = jnp.where(ok, mid, hi)
        lo = jnp.where(ok, lo, mid + 1)
    below = bits < hi[:, None]
    tied = bits == hi[:, None]
    rem = (kth - jnp.sum(below, axis=1)).astype(jnp.int32)
    # Boundary ties: keep the ``rem`` smallest global ids among the tied
    # lanes.  Padding lanes (id -1) map to int32 max, so they lose every
    # tie-break against a real lane; they only tie at +inf, where keeping
    # them is harmless (masked to (INF, -1) downstream either way).
    eid = jnp.broadcast_to(ids, vals.shape) & jnp.int32(0x7FFFFFFF)
    tlo = jnp.zeros((rows,), jnp.int32)
    thi = jnp.full((rows,), jnp.int32(0x7FFFFFFF))
    for _ in range(31):
        mid = tlo + (thi - tlo) // 2
        cnt = jnp.sum(tied & (eid <= mid[:, None]), axis=1)
        ok = cnt >= rem
        thi = jnp.where(ok, mid, thi)
        tlo = jnp.where(ok, tlo, mid + 1)
    return below | (tied & (eid <= thi[:, None]))


def _pq_dense_regime(n_cand: int, n_probe: int, n_flat: int,
                     n_clusters: int) -> bool:
    """Whether the fused PQ searcher selects with ``_dense_select``: when
    ``n_cand`` covers a quarter of the stream, or a query's expected probed
    lanes (``n_probe / n_clusters`` of the stream).  There the compaction
    would keep most probed lanes, or overflow and fall back to a
    stream-wide ``top_k``.  Static shapes only, so one trace per shape and
    a batch's rows equal their B=1 calls."""
    return 4 * n_cand >= n_flat or n_cand * n_clusters >= n_probe * n_flat


def _pq_probed_final(k: int, n_probe: int, cap: int, n_flat: int) -> bool:
    """Whether the dense regime's final ``top_k`` runs over the probed
    tiles (``n_probe * cap`` lanes) instead of the stream: when they hold
    ``k`` lanes and are at most a quarter of the stream, so that the
    tile gather buys a sort at least 4x narrower."""
    return k <= n_probe * cap <= n_flat // 4


def _dense_select(est: jax.Array, bucket: jax.Array, hist: jax.Array,
                  valid: jax.Array, n_cand: int) -> jax.Array:
    """(B, n) mask of each row's ``n_cand`` smallest (estimate, stream
    position) valid lanes, or of every valid lane when fewer exist: the set
    ``col.collect_batch`` selects, kept at full width.  Lanes below the
    threshold bucket are in; the cut is made only inside it, by
    ``_kth_value_mask`` on (estimate, position), and skipped when no row's
    buckets up to the threshold hold more than ``n_cand`` lanes.  Both
    branches give the same mask, so batch-mates stay independent."""
    tau, n_before = jax.vmap(rb.threshold_bucket, in_axes=(0, None))(
        hist, n_cand)
    below = valid & (bucket < tau[:, None])
    at_tau = valid & (bucket == tau[:, None])
    n_at = jnp.take_along_axis(hist, tau[:, None], axis=1)[:, 0]

    def cut(_):
        # |est| folds a -0.0 onto +0.0, whose bit pattern the bisection
        # orders; positions break ties, as the compaction's order does
        vals = jnp.where(at_tau, jnp.abs(est), INF)
        pos = jnp.arange(est.shape[1], dtype=jnp.int32)
        return at_tau & _kth_value_mask(vals, pos, n_cand - n_before)

    over = jnp.any(n_before + n_at > n_cand)
    return below | jax.lax.cond(over, cut, lambda _: at_tau, None)


def _topk_est_id(est: jax.Array, gids: jax.Array, width: int):
    """Top-``width``-smallest selection over ``est`` with boundary-value
    ties broken by smallest global id — the batched counterpart of the
    sharded paths' ``_kth_value_mask`` re-cut, so both deployments keep the
    identical candidate SET when estimates tie at the cut (PQ estimates tie
    whenever two vectors share codes, which makes straddles routine, not
    rare).  The tie-free case pays exactly the plain ``top_k`` (no straddle
    means every boundary-tied lane is already selected, making the set
    tie-order independent); the cond-gated repair needs no value bisection
    — the plain ``top_k`` already yields the boundary value, and the id
    threshold among its tied lanes is one more ``top_k`` — so even
    straddling batches pay ~3 top_k passes, not a stream-wide bisection.
    Returns ``(neg_est, sel_pos)`` with ``jax.lax.top_k(-est, width)``
    semantics."""
    _, pos = jax.lax.top_k(-est, width)
    # XLA CPU's fast TopK rewrite only fires when the sorted VALUES output
    # feeds nothing but the slice; any second consumer (even the boundary
    # column) demotes the whole thing to a ~4x full sort.  So the values
    # output stays dead and the selection is re-gathered from ``est`` —
    # bit-identical, and a gather is free next to the sort it avoids.
    sel = jnp.take_along_axis(est, pos, axis=1)
    neg = -sel
    v = sel[:, -1:]                        # width-th smallest value per row
    bits = jax.lax.bitcast_convert_type(est, jnp.int32)
    vb = jax.lax.bitcast_convert_type(v, jnp.int32)
    tied = bits == vb
    tsel = sel == v                        # boundary columns in the selection
    rem = jnp.sum(tsel, axis=1)            # boundary-tied lanes selected
    straddle = jnp.any(jnp.isfinite(v[:, 0])
                       & (jnp.sum(tied, axis=1) > rem))
    # padding ids (-1) map to int32 max, losing every tie-break that
    # matters; they only tie at +inf, where keeping them is harmless
    eid = jnp.broadcast_to(gids, est.shape) & jnp.int32(0x7FFFFFFF)
    # Integer top_k is pathologically slow on CPU XLA (~20x the float
    # form), so the tie-breaks run on a float view of the ids: patterns
    # below 0x7F800000 bitcast to nonnegative floats whose ordering IS the
    # bit-pattern (= id) ordering.  The clamp collapses only padding (and
    # ids beyond ~2.13B, far past the int32 stream-key bound) onto the max
    # finite pattern — duplicates only at +inf boundaries, harmless.
    fid = jax.lax.bitcast_convert_type(
        jnp.minimum(eid, jnp.int32(0x7F7FFFFF)), jnp.float32)
    cap = min(width, 256)

    def _patch(_):
        # Tied lanes all carry the SAME est value, so only positions need
        # fixing: swap the plain top_k's arbitrary tied subset for the
        # rem smallest-id tied lanes.  One narrow top_k finds their stream
        # positions (ascending id), a rank-gather drops them into the
        # boundary columns; ``neg`` is already correct as-is.
        _, cand = jax.lax.top_k(jnp.where(tied, -fid, -INF), cap)
        rank = jnp.cumsum(tsel, axis=1) - 1
        patched = jnp.take_along_axis(cand, jnp.clip(rank, 0, cap - 1),
                                      axis=1)
        return neg, jnp.where(tsel, patched, pos)

    def _exact(_):
        # > cap boundary lanes selected in some row (pathological tie
        # plateau): fall back to the full-width threshold construction
        nfid, _ = jax.lax.top_k(jnp.where(tied, -fid, -INF), width)
        thr = jnp.take_along_axis(
            -nfid, jnp.maximum(rem - 1, 0)[:, None], axis=1)
        keep = (bits < vb) | (tied & (fid <= thr))
        rneg, rpos = jax.lax.top_k(jnp.where(keep, -est, -INF), width)
        return rneg, rpos

    def _repair(_):
        return jax.lax.cond(jnp.any(rem > cap), _exact, _patch, None)

    return jax.lax.cond(straddle, _repair, lambda _: (neg, pos), None)


def _naive_local_topk(vals: jax.Array, layout: ivf_mod.FlatLayout, k: int):
    """Naive distributed collector's local half: full top-k per shard."""
    kk = min(k, vals.shape[1])
    neg, pos = jax.lax.top_k(-vals, kk)
    ok = jnp.isfinite(-neg)
    gids = jnp.where(ok, layout.order[pos], -1)
    return pos, ok, gids


def _final_topk(gd: jax.Array, gi: jax.Array, k: int):
    """Replicated final selection over the gathered survivors."""
    neg, order = jax.lax.top_k(-gd, k)
    d = -neg
    i = jnp.where(jnp.isfinite(d), jnp.take_along_axis(gi, order, axis=1), -1)
    return d, i


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "k", "n_probe", "use_bbc", "m", "cap_shard",
                     "budget", "backend", "pred_count"))
def ivf_search_sharded(
    mesh,
    qs: jax.Array,                   # (B, d) replicated
    centroids: jax.Array,            # (C, d) replicated
    slayout: ivf_mod.ShardedLayout,  # (S, ...) sharded over 'model'
    svecs: jax.Array,                # (S, F, d) sharded stream vectors
    k: int,
    n_probe: int,
    use_bbc: bool = True,
    m: int = 128,
    cap_shard: int = 1,
    budget: int | None = None,
    backend: str | None = None,
    pred_state: rerank.PredictorState | None = None,
    pred_count: int | None = None,
    slive: jax.Array | None = None,
) -> SearchResult:
    """Sharded batched IVF (exact distances in-scan).

    With ``pred_state`` the engine's predicted tau enters the survivor
    threshold as a floor (see ``dist.bbc_survivors_batch``) and the psum'd
    histogram feeds the EMA; returns ``(SearchResult, new_state)``.
    Distances are exact in-scan, so results match the static path exactly.

    ``slive`` is an optional (S, F) stream-ordered tombstone mask, sharded
    like the other stream scalars; each shard ANDs its block into the local
    probe masks (tombstoned lanes == unprobed lanes everywhere downstream).
    """
    predictive = pred_state is not None
    if predictive and not use_bbc:
        raise ValueError("predictive search requires use_bbc=True")
    has_live = slive is not None
    n_clusters = centroids.shape[0]
    shard_flat = svecs.shape[1]
    axes = _shard_axes(mesh)
    sizes = _mesh_sizes(mesh, axes)
    bud = _shard_budget(budget, k, mesh, shard_flat, slack=2.0)

    def body(qs, cent, sl, vecs, *extra):
        rest = list(extra)
        live = rest.pop(0)[0] if has_live else None     # (1, F) block -> (F,)
        tau_floor = rest.pop(0) if predictive else None
        layout = _local_block(sl)
        vecs = vecs[0]
        probed, _ = _local_routing(cent, qs, n_probe)
        lane_valid = ivf_mod.probe_mask(layout, probed, n_clusters)
        if live is not None:
            lane_valid = lane_valid & live[None, :]
        dists = ops.l2_exact_batch(vecs, qs, backend=backend)
        dv = jnp.where(lane_valid, dists, INF)
        n = dist.hier_psum(jnp.sum(lane_valid, axis=1), axes)
        ghist = None
        if use_bbc:
            st = min(4, n_probe)
            cbs, sample = _sharded_codebooks(layout, probed, dv, st,
                                             cap_shard, k, m, axes, sizes)
            tau_spec = _sample_spec_tau(cbs, sample, k, n, m)
            if tau_floor is not None:
                tau_spec = jnp.maximum(tau_spec, tau_floor)
            bucket, hist, spos, sok, scnt = ops.shard_collect_batch(
                dv, lane_valid, cbs.d_min, cbs.delta, cbs.ew_map, m,
                tau_spec, bud, backend=backend)
            pos, ok, _, _, ghist = dist.bbc_survivors_batch(
                bucket, dv, lane_valid, hist, k, bud, axes,
                tau_floor=tau_floor, spec=(spos, sok, scnt, tau_spec))
            sd = jnp.where(ok, jnp.take_along_axis(dv, pos, axis=1), INF)
            gids = jnp.where(ok, layout.order[pos], -1)
        else:
            pos, ok, gids = _naive_local_topk(dv, layout, k)
            sd = jnp.where(ok, jnp.take_along_axis(dv, pos, axis=1), INF)
        gd, gi = dist.gather_survivors(axes, sd, gids)
        # the gathered pool is replicated: row-split the final selection
        d, i = dist.shard_rows(axes, sizes,
                               lambda a, b_: _final_topk(a, b_, k), gd, gi)
        if predictive:
            return d, i, n.astype(jnp.int32), ghist
        return d, i, n.astype(jnp.int32)

    args = [qs, centroids, slayout, svecs]
    in_specs = [P(), P(), _layout_spec(axes), _stream3_spec(axes)]
    if has_live:
        args.append(slive)
        in_specs.append(_stream2_spec(axes))
    out_specs = (P(), P(), P())
    if predictive:
        count = max(pred_count, k) if pred_count is not None else k
        args.append(rerank.predict_tau(pred_state, count))
        in_specs.append(P())
        fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=out_specs + (P(),), check_vma=False)
        d, i, n, ghist = fn(*args)
        res = SearchResult(d, i, n, jnp.zeros_like(n))
        return res, rerank.predictor_update(pred_state, ghist)
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, check_vma=False)
    d, i, n = fn(*args)
    return SearchResult(d, i, n, jnp.zeros_like(n))


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "k", "n_probe", "n_cand", "use_bbc", "m",
                     "cap_shard", "budget", "backend", "pred_count"))
def ivf_pq_search_sharded(
    mesh,
    qs: jax.Array,
    pq_cb: pq_mod.PQCodebook,        # replicated codebook
    centroids: jax.Array,
    slayout: ivf_mod.ShardedLayout,
    scodes: jax.Array,               # (S, F, M) sharded PQ codes
    svecs: jax.Array,                # (S, F, d) sharded re-rank vectors
    k: int,
    n_probe: int,
    n_cand: int,
    use_bbc: bool = True,
    m: int = 128,
    cap_shard: int = 1,
    budget: int | None = None,
    backend: str | None = None,
    pred_state: rerank.PredictorState | None = None,
    pred_count: int | None = None,
    slive: jax.Array | None = None,
) -> SearchResult:
    """Sharded batched IVF+PQ.

    BBC path: the histogram collective runs at ``n_cand`` granularity (the
    selection the single-device path makes by estimate), survivors are
    exact-re-ranked on their owning shard, and the final replicated pass
    re-applies the top-``n_cand``-by-estimate cut before the top-k by exact
    distance — the same selection semantics as ``ivf_pq_search_batch``.
    Naive path: each shard maintains a full local top-k by estimate and
    gathers k (dist, id) pairs (plus its local exact re-rank).

    Predictive path (``pred_state``): the histogram collective runs at
    ``pred_count`` granularity with the engine's tau_pred as a floor, each
    shard exact-re-ranks only its ~pred_count/S survivors (instead of
    ~n_cand/S), and the blunt post-gather n_cand-by-estimate re-cut is gone —
    the survivor pool IS the selection, matching the predictive batched
    path's semantics.  Returns ``(SearchResult, new_state)``.

    ``slive``: optional (S, F) sharded tombstone mask (see
    ``ivf_search_sharded``).
    """
    predictive = pred_state is not None
    if predictive and not use_bbc:
        raise ValueError("predictive search requires use_bbc=True")
    has_live = slive is not None
    n_clusters = centroids.shape[0]
    shard_flat = svecs.shape[1]
    axes = _shard_axes(mesh)
    sizes = _mesh_sizes(mesh, axes)
    count = _resolve_pred_count(pred_count, k, n_cand) if predictive \
        else n_cand
    bud = _shard_budget(budget, count, mesh, shard_flat, slack=2.0)

    def body(qs, cb, cent, sl, codes, vecs, *extra):
        rest = list(extra)
        live = rest.pop(0)[0] if has_live else None
        tau_floor = rest.pop(0) if predictive else None
        layout = _local_block(sl)
        codes, vecs = codes[0], vecs[0]
        probed, _ = _local_routing(cent, qs, n_probe)
        lane_valid = ivf_mod.probe_mask(layout, probed, n_clusters)
        if live is not None:
            lane_valid = lane_valid & live[None, :]
        luts = jax.vmap(lambda q: pq_mod.adc_table(cb, q))(qs)
        est2 = ops.pq_adc_batch(codes, luts, backend=backend)
        est = jnp.where(lane_valid, jnp.sqrt(jnp.maximum(est2, 0.0)), INF)
        ghist = None
        if use_bbc:
            st = min(4, n_probe)
            cbs, sample = _sharded_codebooks(layout, probed, est, st,
                                             cap_shard, n_cand, m, axes,
                                             sizes)
            n_probed = dist.hier_psum(jnp.sum(lane_valid, axis=1), axes)
            tau_spec = _sample_spec_tau(cbs, sample, count, n_probed, m)
            if tau_floor is not None:
                tau_spec = jnp.maximum(tau_spec, tau_floor)
            bucket, hist, spos, sok, scnt = ops.shard_collect_batch(
                est, lane_valid, cbs.d_min, cbs.delta, cbs.ew_map, m,
                tau_spec, bud, backend=backend)
            pos, ok, _, _, ghist = dist.bbc_survivors_batch(
                bucket, est, lane_valid, hist, count, bud, axes,
                tau_floor=tau_floor, spec=(spos, sok, scnt, tau_spec))
        else:
            pos, ok, _ = _naive_local_topk(est, layout, k)
        sel_est = jnp.where(ok, jnp.take_along_axis(est, pos, axis=1), INF)
        ex = _exact_at_positions(vecs, qs, pos, ok)
        gids = jnp.where(ok, layout.order[pos], -1)
        n_rr = dist.hier_psum(jnp.sum(ok, axis=1), axes)
        ge, gx, gi = dist.gather_survivors(axes, sel_est, ex, gids)
        if use_bbc:
            # Replicated selection alignment with the single-device batched
            # path.  Static: the blunt n_cand-by-estimate re-cut (the full
            # two-stage selection re-applied after the gather).  Predictive:
            # that re-cut is gone — the pool is already tau-thresholded at
            # pred_count granularity; only the SAME est-priority truncation
            # the batched predictive path applies (its static top_k width)
            # remains, so both deployments select the identical pool.
            # Either way the cut only bites when the gathered pool holds
            # MORE than ncs finite lanes; n_rr (the psum'd survivor count)
            # is replicated, so when every query's pool already fits the
            # cut is provably vacuous and skipped at run time.
            if predictive:
                ncs = min(_pred_budget(count, shard_flat * _n_shards(mesh)),
                          n_cand, ge.shape[1])
            else:
                ncs = min(n_cand, ge.shape[1])
            fit = jnp.all(n_rr <= ncs)

            # re-cut + final selection over the replicated gathered pool,
            # row-split across the shard axis (one slice+gather covers
            # both).  The re-cut is a value threshold at the ncs-th
            # smallest estimate with boundary ties broken by smallest
            # global id (see _kth_value_mask) — the exact SET the batched
            # path's tie-broken top_k keeps, so tied PQ estimates cannot
            # make the two deployments' pools diverge.  Lanes outside are
            # masked, widths unchanged, so both cond branches are
            # shape-identical without re-padding
            def _tail(ge, gx, gi):
                def _recut(_):
                    keep = _kth_value_mask(ge, gi, ncs)
                    return (jnp.where(keep, gx, INF),
                            jnp.where(keep, gi, -1))

                cx, ci = jax.lax.cond(fit, lambda _: (gx, gi), _recut, None)
                return _final_topk(cx, ci, k)

            d, i = dist.shard_rows(axes, sizes, _tail, ge, gx, gi)
        else:
            d, i = dist.shard_rows(axes, sizes,
                                   lambda a, b_: _final_topk(a, b_, k),
                                   gx, gi)
        if predictive:
            return d, i, n_rr.astype(jnp.int32), ghist
        return d, i, n_rr.astype(jnp.int32)

    args = [qs, pq_cb, centroids, slayout, scodes, svecs]
    in_specs = [P(), P(), P(), _layout_spec(axes), _stream3_spec(axes),
                _stream3_spec(axes)]
    if has_live:
        args.append(slive)
        in_specs.append(_stream2_spec(axes))
    out_specs = (P(), P(), P())
    if predictive:
        args.append(rerank.predict_tau(pred_state, count))
        in_specs.append(P())
        fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=out_specs + (P(),), check_vma=False)
        d, i, n_rr, ghist = fn(*args)
        res = SearchResult(d, i, n_rr, jnp.zeros_like(n_rr))
        return res, rerank.predictor_update(pred_state, ghist)
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, check_vma=False)
    d, i, n_rr = fn(*args)
    return SearchResult(d, i, n_rr, jnp.zeros_like(n_rr))


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "k", "n_probe", "use_bbc", "m", "eps0",
                     "cap_shard", "budget", "backend", "fused",
                     "pred_count"))
def ivf_rabitq_search_sharded(
    mesh,
    qs: jax.Array,
    rot: jax.Array,                  # (d, d) replicated rotation
    centroids: jax.Array,
    slayout: ivf_mod.ShardedLayout,
    scodes: jax.Array,               # (S, F, d) sharded ±1 codes
    snorm_o: jax.Array,              # (S, F)
    sf_o: jax.Array,                 # (S, F)
    svecs: jax.Array,                # (S, F, d) sharded re-rank vectors
    k: int,
    n_probe: int,
    use_bbc: bool = True,
    m: int = 128,
    eps0: float = 3.0,
    cap_shard: int = 1,
    budget: int | None = None,
    backend: str | None = None,
    fused: bool | None = None,
    pred_state: rerank.PredictorState | None = None,
    pred_count: int | None = None,
    slive: jax.Array | None = None,
) -> SearchResult:
    """Sharded batched IVF+RaBitQ.

    BBC path: the codebook is built from upper bounds, the histogram
    collective thresholds the UB distribution at k (tau_ub), and a lane
    survives iff its LOWER bound bucketizes at or below tau_ub — the
    distributed form of Alg. 3's certainly-out test (lb above the relaxed
    k-th-ub threshold means at least k objects are surely closer).  Survivors
    are exact-re-ranked on their shard; the gathered top-k by exact distance
    therefore equals the single-device result set.

    Bound-fused form (``fused=None`` -> True): each shard's scan certifies
    survivors whose lb-bucket sits at or below the inline gate — the
    sample-derived static tau, or the engine's ``tau_pred`` floor on the
    predictive path, exactly as on the batched deployment — and the
    on-shard second gather pass covers ONLY the straggler survivors the
    gate missed (on TPU the certified survivors' exact distances come out
    of the fused kernel; survivor values and the collective payload are
    unchanged).  ``n_second_pass`` is the psum'd measured straggler count.

    Predictive path (``pred_state``): the survivor band is bound-determined
    (already minimal), so prediction does not floor the survivor tau; the
    psum'd UB histogram feeds the engine's EMA (full-histogram convention,
    queried at max(pred_count, k) — k under the engine's RaBitQ default;
    unlike the batched deployment's strided-subsample EMA; states never
    cross deployments).  Returns ``(SearchResult, new_state)``; results
    are identical to the static path.
    """
    predictive = pred_state is not None
    if predictive and not use_bbc:
        raise ValueError("predictive search requires use_bbc=True")
    if fused is None:
        fused = True
    has_live = slive is not None
    n_clusters = centroids.shape[0]
    shard_flat = svecs.shape[1]
    axes = _shard_axes(mesh)
    sizes = _mesh_sizes(mesh, axes)
    bud = _shard_budget(budget, k, mesh, shard_flat, slack=4.0)
    count = k if pred_count is None else max(pred_count, k)
    kernelized = fused and ops.resolve_backend(backend) == "pallas"
    tau_p_val = rerank.predict_tau(pred_state, count) \
        if predictive and fused else None
    has_tau = tau_p_val is not None

    def body(qs, rot, cent, sl, codes, norm_o, f_o, vecs, *extra):
        rest = list(extra)
        live = rest.pop(0)[0] if has_live else None
        tau_p = rest.pop(0) if has_tau else None
        layout = _local_block(sl)
        codes, norm_o, f_o, vecs = codes[0], norm_o[0], f_o[0], vecs[0]
        b = qs.shape[0]
        probed, d2 = _local_routing(cent, qs, n_probe)
        lane_valid = ivf_mod.probe_mask(layout, probed, n_clusters)
        if live is not None:
            lane_valid = lane_valid & live[None, :]
        cl = jnp.minimum(layout.cluster_of, n_clusters - 1)
        ghist = None
        n_second = jnp.zeros((b,), jnp.int32)
        if not use_bbc:
            est, _, _ = kref.rabitq_bounds_stream(
                codes.astype(jnp.float32), norm_o, f_o, cl, cent, rot, qs,
                d2, lane_valid, eps0)
            pos, ok, _ = _naive_local_topk(est, layout, k)
            ex = _exact_at_positions(vecs, qs, pos, ok)
        else:
            st = min(4, n_probe)
            if kernelized:
                s_local, _ = _rabitq_sample_ub(codes, norm_o, f_o, cl,
                                               cent, rot, layout, probed,
                                               qs, d2, st, cap_shard, eps0)
            else:
                _, lb, ub = kref.rabitq_bounds_stream(
                    codes.astype(jnp.float32), norm_o, f_o, cl, cent, rot,
                    qs, d2, lane_valid, eps0)
                spos, sok_l = ivf_mod.tile_positions(layout,
                                                     probed[:, :st],
                                                     cap_shard)
                s_local = jnp.where(sok_l,
                                    jnp.take_along_axis(ub, spos, axis=1),
                                    INF)
            # gathered sample = the union of the nearest st clusters' full
            # membership, as on every sharded path; identical codebooks to
            # the pre-fused formulation (build_codebook = topk + from_topk)
            (sample,) = dist.gather_survivors(axes, s_local)
            cbs, tau_static = dist.shard_rows(
                axes, sizes,
                lambda s: _rabitq_sample_plan(s, k, count, st, n_probe, m),
                sample)
            tau_spec = tau_static
            if fused:
                tau_inline = jnp.full((b,), tau_p, jnp.int32) \
                    if tau_p is not None else tau_static
                tau_spec = jnp.maximum(tau_spec, tau_inline)
            if kernelized:
                (_, lb, _, bucket_lb, _, _, hist_ub, exact_c, certified,
                 _nm) = ops.fused_rabitq_scan_batch(
                    codes, vecs, norm_o, f_o, cl, cent, rot, qs, d2,
                    lane_valid, cbs.d_min, cbs.delta, cbs.ew_map, m,
                    tau_inline, eps0=eps0, backend=backend)
            else:
                bucket_lb = jax.vmap(rb.bucketize)(cbs, lb)
                _, hist_ub = ops.bucket_hist_batch(
                    ub, lane_valid, cbs.d_min, cbs.delta, cbs.ew_map, m,
                    backend=backend)
                if fused:
                    certified = lane_valid & \
                        (bucket_lb <= tau_inline[:, None])
            # speculative survivor compaction over the lb buckets (one
            # extra compact-only pass here — the lb/ub value split means
            # the histogram and the survivor test read different bound
            # streams, so the fully-fused collect applies to the other
            # methods only)
            spos, sok_b, scnt = ops.spec_compact_batch(
                bucket_lb, lane_valid, tau_spec, bud, backend=backend)
            pos, ok, _, _, ghist = dist.bbc_survivors_batch(
                bucket_lb, lb, lane_valid, hist_ub, k, bud, axes,
                spec=(spos, sok_b, scnt, tau_spec))
            if fused:
                cert_pos, strag = dist.split_certified_survivors(
                    pos, ok, certified)
                n_second = dist.hier_psum(
                    jnp.sum(strag, axis=1), axes).astype(jnp.int32)
                if kernelized:
                    # certified survivors: inline exacts from the fused
                    # kernel; the on-shard gather covers only stragglers
                    ex_in = jnp.take_along_axis(exact_c, pos, axis=1)
                    ex_st = _exact_at_positions(vecs, qs, pos, strag)
                    ex = jnp.where(cert_pos, ex_in,
                                   jnp.where(strag, ex_st, INF))
                else:
                    # CPU: one position-gather serves both legs (single
                    # float source keeps static/cold/warm variants
                    # bitwise identical); the counter is the executed
                    # gate's straggler-survivor count
                    ex = _exact_at_positions(vecs, qs, pos, ok)
            else:
                ex = _exact_at_positions(vecs, qs, pos, ok)
        gids = jnp.where(ok, layout.order[pos], -1)
        n_rr = dist.hier_psum(jnp.sum(ok, axis=1), axes)
        gx, gi = dist.gather_survivors(axes, ex, gids)
        d, i = dist.shard_rows(axes, sizes,
                               lambda a, b_: _final_topk(a, b_, k), gx, gi)
        if predictive:
            return d, i, n_rr.astype(jnp.int32), n_second, ghist
        return d, i, n_rr.astype(jnp.int32), n_second

    args = [qs, rot, centroids, slayout, scodes, snorm_o, sf_o, svecs]
    in_specs = [P(), P(), P(), _layout_spec(axes), _stream3_spec(axes),
                _stream2_spec(axes), _stream2_spec(axes),
                _stream3_spec(axes)]
    if has_live:
        args.append(slive)
        in_specs.append(_stream2_spec(axes))
    if has_tau:
        args.append(tau_p_val)
        in_specs.append(P())
    out_specs = (P(), P(), P(), P())
    if predictive:
        fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=out_specs + (P(),), check_vma=False)
        d, i, n_rr, n_second, ghist = fn(*args)
        res = SearchResult(d, i, n_rr, n_second)
        return res, rerank.predictor_update(pred_state, ghist)
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, check_vma=False)
    d, i, n_rr, n_second = fn(*args)
    return SearchResult(d, i, n_rr, n_second)
