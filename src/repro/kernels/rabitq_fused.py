"""Pallas TPU kernel: bound-fused RaBitQ scan (estimate + bounds + bucketize
+ histogram + bound-certified inline exact re-rank).

This is the RaBitQ counterpart of ``fused_scan.py``'s Alg.-4 kernel — the
paper's second re-ranking algorithm executed, not modeled.  A two-phase
RaBitQ search streams the candidate block once for estimates/bounds and then
gathers the uncertain band a second time for the exact re-rank; at large k
that second gather dominates (the cache-miss cost the paper's Table 2
counts).  The fused kernel streams the ±1 code block AND the fp32 vector
block of a cluster tile through VMEM together and, per tile, produces

    est/lb/ub   — the RaBitQ estimator with its error bounds (the batched
                  ``P(q-c) = Pq - Pc`` decomposition: (TILE, d) x (d, BQ)
                  MXU matmuls against the rotated queries plus a per-lane
                  centroid correction ``s2`` that is query-independent),
    bucket_lb / bucket_ub — Eq. 6 bucket ids of both bounds against the
                  per-query codebook (one-hot LUT, shared helper with the
                  PQ kernel),
    hist_lb / hist_ub — (m+1)-histograms of both bounds, accumulated across
                  the grid (VMEM-resident; hist_ub anchors the band
                  threshold and the cross-batch predictor's EMA),
    exact       — exact ||q - x|| for lanes whose LOWER-bound bucket is at
                  or below ``tau_inline`` (the bound-certified inline band),
                  +inf elsewhere — computed while the vector tile is
                  VMEM-resident, so certified lanes never pay the second
                  gather,
    certified   — the inline-coverage mask itself,
    nmiss       — per-query count of valid lanes NOT covered inline (the
                  upper bound on second-pass gather work; the searcher's
                  measured ``n_second_pass`` is the band ∩ ~certified
                  subset of these).

``tau_inline`` is per query: the predictive path passes the engine's EMA
``tau_pred`` (-1 while cold — nothing certified, everything falls through
to the gather, exactly like the static two-phase path), the static path
passes the sample-prefix rank-scaled threshold (Alg. 4 line 4 applied to
the k-th upper bound).

VMEM working set at defaults (TILE=256, d<=1536, B<=32, n_ew=256):
  codes + vectors 2 * 256*1536*4 = 3 MiB, per-lane factors < 16 KiB,
  (TILE, B) masks/outputs ~ 8 * 32 KiB, LUTs + scalars < 64 KiB -> ~3.4 MiB,
comfortably inside ~16 MiB VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.fused_scan import bucketize_hist_tile
from repro.kernels.l2_rerank import BQ, exact_tile, query_dot
from repro.kernels.platform import resolve_interpret

TILE = 256


def _rabitq_fused_batch_kernel(codes_ref, vecs_ref, s2_ref, norm_ref, f_ref,
                               wmask_ref, nq_ref, g_ref, qt_ref, ew_ref,
                               scal_ref, est_ref, lb_ref, ub_ref, blb_ref,
                               bub_ref, exact_ref, cert_ref, hist_lb_ref,
                               hist_ub_ref, nmiss_ref, *, m: int,
                               hist_pad: int, bq: int, eps0: float,
                               sqrt_d: float, dm1: float):
    codes = codes_ref[...].astype(jnp.float32)    # (TILE, d) ±1
    vecs = vecs_ref[...]                          # (TILE, d)
    s2 = s2_ref[...][0]                           # (TILE,) codes · Pc[cl]
    no = norm_ref[...][0]                         # (TILE,)
    fo = f_ref[...][0]                            # (TILE,)
    w = wmask_ref[...]                            # (TILE, B) int32
    nq = nq_ref[...]                              # (TILE, B) ||q - c[lane]||
    g = g_ref[...]                                # (d, B) rotated queries Pq
    qt = qt_ref[...]                              # (d, B) raw queries
    ew = ew_ref[...]                              # (B, n_ew)
    s = scal_ref[...]                             # (B, 128)
    d_min, delta = s[:, 0], s[:, 1]
    tau_inline = s[:, 2].astype(jnp.int32)
    q_sq = s[:, 3]
    tile, b = w.shape
    inf = jnp.float32(jnp.inf)

    # --- RaBitQ estimator + bounds: MXU matmuls against the code tile ---
    s1 = query_dot(codes, g)                                  # (TILE, B)
    xv = (s1 - s2[:, None]) / (sqrt_d * jnp.maximum(nq, 1e-12))
    ip = xv / fo[:, None]
    err = eps0 * jnp.sqrt((1.0 - fo * fo) / (fo * fo * dm1))      # (TILE,)
    scale = 2.0 * nq * no[:, None]
    base = nq * nq + no[:, None] * no[:, None]
    zero = jnp.zeros_like(base)
    live = w > 0
    est = jnp.sqrt(jnp.maximum(base - scale * ip, zero))
    lb = jnp.sqrt(jnp.maximum(base - scale * (ip + err[:, None]), zero))
    ub = jnp.sqrt(jnp.maximum(base - scale * (ip - err[:, None]), zero))
    est = jnp.where(live, est, inf)
    lb = jnp.where(live, lb, inf)
    ub = jnp.where(live, ub, inf)
    est_ref[...] = est
    lb_ref[...] = lb
    ub_ref[...] = ub

    # --- bucketize both bounds + per-query histograms ---
    bucket_lb, tile_hist_lb = bucketize_hist_tile(lb, w, ew, d_min, delta, m,
                                                  hist_pad, bq)
    bucket_ub, tile_hist_ub = bucketize_hist_tile(ub, w, ew, d_min, delta, m,
                                                  hist_pad, bq)
    blb_ref[...] = bucket_lb
    bub_ref[...] = bucket_ub

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_lb_ref[...] = jnp.zeros_like(hist_lb_ref)
        hist_ub_ref[...] = jnp.zeros_like(hist_ub_ref)
        nmiss_ref[...] = jnp.zeros_like(nmiss_ref)

    hist_lb_ref[...] += tile_hist_lb
    hist_ub_ref[...] += tile_hist_ub

    # --- bound-certified inline exact: vectors are already in VMEM ---
    exact = exact_tile(vecs, qt, q_sq)
    cert = live & (bucket_lb <= tau_inline[None, :])
    exact_ref[...] = jnp.where(cert, exact, inf)
    cert_ref[...] = cert.astype(jnp.int32)

    # --- per-query miss counts (lanes left to the second gather pass) ---
    cnt = jnp.sum((live & ~cert).astype(jnp.int32), axis=0)       # (B,)
    miota = jax.lax.broadcasted_iota(jnp.int32, (b, 128), 1)
    nmiss_ref[...] += jnp.where(miota == 0, cnt[:, None], 0)


def fused_rabitq_scan_batch_pallas(
    codes: jax.Array,      # (n, d) int8 ±1, n % tile == 0, d lane-padded
    vectors: jax.Array,    # (n, d) fp32 — co-tiled re-rank source
    s2: jax.Array,         # (n,) query-independent centroid correction
    norm_o: jax.Array,     # (n,)
    f_o: jax.Array,        # (n,)
    valid: jax.Array,      # (n, B) bool per-query lane validity
    nq_lane: jax.Array,    # (n, B) per-lane query-centroid norms
    g: jax.Array,          # (B, d) rotated queries (qs @ rot.T)
    qs: jax.Array,         # (B, d) raw queries (for the exact re-rank)
    d_min: jax.Array,      # (B,)
    delta: jax.Array,      # (B,)
    ew_maps: jax.Array,    # (B, n_ew) int32
    m: int,
    tau_inline: jax.Array,  # (B,) int32; -1 certifies nothing
    d_logical: int,
    eps0: float = 3.0,
    tile: int = TILE,
    bq: int = BQ,
    interpret: bool | None = None,
):
    """Batched bound-fused RaBitQ scan over a shared candidate stream.

    Returns ``(est, lb, ub, bucket_lb, bucket_ub, hist_lb, hist_ub, exact,
    certified, nmiss)`` with (B, n) lane tensors, (B, m+1) histograms and
    (B,) miss counts.  Requires B % bq == 0 (wrappers pad the query batch).
    """
    interpret = resolve_interpret(interpret)
    n, d = codes.shape
    b = qs.shape[0]
    assert b % bq == 0, (b, bq)
    g_tiles = n // tile
    n_ew = ew_maps.shape[1]
    hist_pad = ((m + 1 + 127) // 128) * 128
    scal = jnp.zeros((b, 128), jnp.float32)
    scal = scal.at[:, 0].set(d_min.astype(jnp.float32))
    scal = scal.at[:, 1].set(delta.astype(jnp.float32))
    scal = scal.at[:, 2].set(tau_inline.astype(jnp.float32))
    scal = scal.at[:, 3].set(jnp.sum(qs * qs, axis=1))
    w = valid.astype(jnp.int32)                                   # (n, B)
    lane_f32 = jax.ShapeDtypeStruct((n, b), jnp.float32)
    lane_i32 = jax.ShapeDtypeStruct((n, b), jnp.int32)
    lane_spec = pl.BlockSpec((tile, b), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(
            _rabitq_fused_batch_kernel, m=m, hist_pad=hist_pad, bq=bq,
            eps0=eps0, sqrt_d=float(np.float32(math.sqrt(d_logical))),
            dm1=float(d_logical - 1)),
        grid=(g_tiles,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),     # codes
            pl.BlockSpec((tile, d), lambda i: (i, 0)),     # vectors
            pl.BlockSpec((1, tile), lambda i: (0, i)),     # s2
            pl.BlockSpec((1, tile), lambda i: (0, i)),     # norm_o
            pl.BlockSpec((1, tile), lambda i: (0, i)),     # f_o
            lane_spec,                                     # valid
            lane_spec,                                     # nq_lane
            pl.BlockSpec((d, b), lambda i: (0, 0)),        # g
            pl.BlockSpec((d, b), lambda i: (0, 0)),        # qs
            pl.BlockSpec((b, n_ew), lambda i: (0, 0)),     # ew_maps
            pl.BlockSpec((b, 128), lambda i: (0, 0)),      # scal
        ],
        out_specs=[
            lane_spec, lane_spec, lane_spec,               # est, lb, ub
            lane_spec, lane_spec,                          # bucket_lb/ub
            lane_spec, lane_spec,                          # exact, certified
            pl.BlockSpec((b, hist_pad), lambda i: (0, 0)),
            pl.BlockSpec((b, hist_pad), lambda i: (0, 0)),
            pl.BlockSpec((b, 128), lambda i: (0, 0)),
        ],
        out_shape=[
            lane_f32, lane_f32, lane_f32,
            lane_i32, lane_i32,
            lane_f32, lane_i32,
            jax.ShapeDtypeStruct((b, hist_pad), jnp.int32),
            jax.ShapeDtypeStruct((b, hist_pad), jnp.int32),
            jax.ShapeDtypeStruct((b, 128), jnp.int32),
        ],
        interpret=interpret,
    )(codes, vectors, s2.reshape(1, n), norm_o.reshape(1, n),
      f_o.reshape(1, n), w, nq_lane, g.T, qs.T,
      ew_maps.astype(jnp.int32), scal)
    est, lb, ub, blb, bub, exact, cert, hist_lb, hist_ub, nmiss = outs
    return (est.T, lb.T, ub.T, blb.T, bub.T, hist_lb[:, : m + 1],
            hist_ub[:, : m + 1], exact.T, cert.T.astype(bool), nmiss[:, 0])
