"""Pallas TPU kernel: fused estimate + bucketize + histogram + early exact.

This is the flagship kernel — the TPU-native realization of the paper's
Algorithm 4 (early re-ranking).  On CPU the paper co-locates PQ codes with the
fp32 vector and computes the exact distance "while the data is hot in cache".
On TPU the analogue is HBM-traffic fusion: one pass streams the code block AND
the vector block of a cluster tile through VMEM and produces

    est    — ADC estimate (one-hot matmul, see pq_adc.py),
    bucket — Eq. 6 bucket id (one-hot LUT),
    hist   — (m+1)-histogram accumulated across the grid (VMEM-resident),
    early  — exact ||q - x|| for lanes whose bucket <= tau_pred, else +inf,

eliminating the second gather pass over the re-rank pool (the cache-miss /
HBM-re-read saving of Table 2).  Exact distances are computed for all lanes
of the tile and masked — TPUs prefer redundant lanes over divergence; the
saving is memory traffic, not FLOPs.

VMEM working set at defaults (TILE=256, d<=1536, M<=384, K=16):
  vectors block 256*1536*4 = 1.5 MiB, codes 256*384*4 = 384 KiB,
  one-hot chunk 256*32*16*4 = 512 KiB, LUT + maps < 64 KiB  -> ~2.5 MiB,
comfortably inside ~16 MiB VMEM; m (Eq. 3') can stay in the hundreds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.l2_rerank import BQ, exact_tile
from repro.kernels.platform import resolve_interpret
from repro.kernels.pq_adc import adc_tile

TILE = 256
MC = 32


# --------------------------------------------------------------------------
# Batched (multi-query) fused scan
# --------------------------------------------------------------------------

def bucketize_hist_tile(est, w, ew, d_min, delta, m, hist_pad, bq):
    """Shared per-tile bucketize + histogram for the batched kernels.

    ``est`` (tile, B) distances, ``w`` (tile, B) int32 validity, ``ew``
    (B, n_ew) equal-width -> equal-depth LUTs, ``d_min``/``delta`` (B,).
    Returns (bucket (tile, B) int32, hist (B, hist_pad) int32).  The one-hot
    LUT application and histogram are chunked over the query axis in blocks
    of ``bq`` so the (tile, bq, n_ew) intermediates stay VMEM-sized.
    """
    tile, b = est.shape
    n_ew = ew.shape[1]
    bin_f = jnp.floor((est - d_min[None, :]) / delta[None, :])
    overflow = bin_f >= n_ew
    bin_id = jnp.clip(bin_f, 0, n_ew - 1).astype(jnp.int32)

    buckets = []
    for j in range(b // bq):
        bc = bin_id[:, j * bq:(j + 1) * bq]
        ewc = ew[j * bq:(j + 1) * bq, :]
        iota = jax.lax.broadcasted_iota(jnp.int32, (tile, bq, n_ew), 2)
        onehot = (iota == bc[:, :, None]).astype(jnp.float32)
        buckets.append(jnp.sum(onehot * ewc[None, :, :].astype(jnp.float32),
                               axis=2).astype(jnp.int32))    # (tile, bq)
    bucket = jnp.concatenate(buckets, axis=1)
    bucket = jnp.where(overflow, m, bucket)

    hists = []
    for j in range(b // bq):
        bkt = bucket[:, j * bq:(j + 1) * bq]
        wc = w[:, j * bq:(j + 1) * bq]
        hiota = jax.lax.broadcasted_iota(jnp.int32, (tile, bq, hist_pad), 2)
        hoh = jnp.where(hiota == bkt[:, :, None], wc[:, :, None], 0)
        hists.append(jnp.sum(hoh, axis=0, dtype=jnp.int32))   # (bq, hist_pad)
    hist = jnp.concatenate(hists, axis=0)
    return bucket, hist


def _fused_batch_kernel(codes_ref, vecs_ref, wmask_ref, luts_ref, qt_ref,
                        ew_ref, scal_ref, est_ref, bucket_ref, early_ref,
                        hist_ref, nmiss_ref, *, m: int, hist_pad: int,
                        mc: int, bq: int):
    codes = codes_ref[...].astype(jnp.int32)      # (TILE, M)
    vecs = vecs_ref[...]                          # (TILE, d)
    w = wmask_ref[...]                            # (TILE, B)
    luts = luts_ref[...]                          # (M*K, B)
    qt = qt_ref[...]                              # (d, B)
    ew = ew_ref[...]                              # (B, n_ew)
    s = scal_ref[...]                             # (B, 128)
    d_min, delta = s[:, 0], s[:, 1]
    tau_pred = s[:, 2].astype(jnp.int32)
    q_sq = s[:, 3]
    b = w.shape[1]
    inf = jnp.float32(jnp.inf)

    # --- ADC estimates for all B queries: chunked one-hot MXU matmul ---
    est2 = adc_tile(codes, luts, mc)
    est = jnp.sqrt(jnp.maximum(est2, 0.0))
    est = jnp.where(w > 0, est, inf)
    est_ref[...] = est

    # --- bucketize + per-query histogram ---
    bucket, tile_hist = bucketize_hist_tile(est, w, ew, d_min, delta, m,
                                            hist_pad, bq)
    bucket_ref[...] = bucket

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)
        nmiss_ref[...] = jnp.zeros_like(nmiss_ref)

    hist_ref[...] += tile_hist

    # --- early exact for all B queries: MXU matmuls on the hot tile ---
    exact = exact_tile(vecs, qt, q_sq)
    pred = (w > 0) & (bucket <= tau_pred[None, :])
    early_ref[...] = jnp.where(pred, exact, inf)

    # --- per-query miss counts (lanes left to the second gather pass) ---
    cnt = jnp.sum(((w > 0) & ~pred).astype(jnp.int32), axis=0)     # (B,)
    miota = jax.lax.broadcasted_iota(jnp.int32, (b, 128), 1)
    nmiss_ref[...] += jnp.where(miota == 0, cnt[:, None], 0)


def fused_scan_batch_pallas(
    codes: jax.Array,     # (n, M) int32/uint8, n % tile == 0, M % mc == 0
    vectors: jax.Array,   # (n, d) fp32 — shared candidate stream
    valid: jax.Array,     # (n, B) bool — per-query lane validity
    luts: jax.Array,      # (B, M, K) fp32 — one ADC table per query
    qs: jax.Array,        # (B, d) fp32
    d_min: jax.Array,     # (B,)
    delta: jax.Array,     # (B,)
    ew_maps: jax.Array,   # (B, n_ew) int32
    m: int,
    tau_pred: jax.Array,  # (B,) int32
    tile: int = TILE,
    mc: int = MC,
    bq: int = BQ,
    interpret: bool | None = None,
):
    """Batched fused scan: one pass over the shared candidate stream computes
    est/bucket/early for every query and accumulates a (B, m+1) histogram.

    The candidate gather happens ONCE per cluster tile (codes/vectors are the
    shared stream); all per-query work is MXU matmuls against the resident
    tile.  Returns (est (B, n), bucket (B, n), hist (B, m+1), early (B, n),
    nmiss (B,)).  Requires B % bq == 0 (wrappers pad the query batch).
    """
    interpret = resolve_interpret(interpret)
    n, m_sub = codes.shape
    d = vectors.shape[1]
    b = qs.shape[0]
    assert b % bq == 0, (b, bq)
    g = n // tile
    n_ew = ew_maps.shape[1]
    k_codes = luts.shape[2]
    hist_pad = ((m + 1 + 127) // 128) * 128
    scal = jnp.zeros((b, 128), jnp.float32)
    scal = scal.at[:, 0].set(d_min.astype(jnp.float32))
    scal = scal.at[:, 1].set(delta.astype(jnp.float32))
    scal = scal.at[:, 2].set(tau_pred.astype(jnp.float32))
    scal = scal.at[:, 3].set(jnp.sum(qs * qs, axis=1))
    w = valid.astype(jnp.int32)                                  # (n, B)
    luts_t = luts.reshape(b, m_sub * k_codes).T                  # (M*K, B)
    qt = qs.T                                                    # (d, B)
    est, bucket, early, hist, nmiss = pl.pallas_call(
        functools.partial(_fused_batch_kernel, m=m, hist_pad=hist_pad,
                          mc=mc, bq=bq),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((tile, m_sub), lambda i: (i, 0)),
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            pl.BlockSpec((m_sub * k_codes, b), lambda i: (0, 0)),
            pl.BlockSpec((d, b), lambda i: (0, 0)),
            pl.BlockSpec((b, n_ew), lambda i: (0, 0)),
            pl.BlockSpec((b, 128), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            pl.BlockSpec((b, hist_pad), lambda i: (0, 0)),
            pl.BlockSpec((b, 128), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, b), jnp.float32),
            jax.ShapeDtypeStruct((n, b), jnp.int32),
            jax.ShapeDtypeStruct((n, b), jnp.float32),
            jax.ShapeDtypeStruct((b, hist_pad), jnp.int32),
            jax.ShapeDtypeStruct((b, 128), jnp.int32),
        ],
        interpret=interpret,
    )(codes, vectors, w, luts_t, qt, ew_maps.astype(jnp.int32), scal)
    return est.T, bucket.T, hist[:, : m + 1], early.T, nmiss[:, 0]
