"""Pallas TPU kernel: bucketize (Eq. 6) + m-histogram accumulation.

The paper's result-buffer Push is per-object append + threshold compare; the
TPU version streams distance tiles and keeps the (m+1)-histogram as the ONLY
cross-tile state, resident in VMEM for the whole grid (the L1-residency
analogue).  The equal-width -> equal-depth LUT (256 uint8 entries on CPU) is a
256-lane VMEM vector here, applied by one-hot matmul (gathers are slow on
TPU; 256-wide one-hot fits the MXU exactly).

Grid accumulation: the histogram output block maps to (0, 0) on every step;
step 0 initializes, later steps accumulate — Pallas TPU grids iterate
sequentially on a core, so this is race-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_scan import bucketize_hist_tile
from repro.kernels.platform import resolve_interpret

TILE = 512
BQ = 8   # query-batch chunk width inside the batched kernel


# --------------------------------------------------------------------------
# Batched (multi-query) bucketize + histogram
# --------------------------------------------------------------------------

def _bucket_batch_kernel(dists_ref, wmask_ref, ew_ref, scal_ref,
                         bucket_ref, hist_ref, *, m: int, hist_pad: int,
                         bq: int):
    d = dists_ref[...]                           # (TILE, B)
    w = wmask_ref[...]                           # (TILE, B)
    ew = ew_ref[...]                             # (B, n_ew)
    s = scal_ref[...]                            # (B, 128)
    d_min, delta = s[:, 0], s[:, 1]

    bucket, tile_hist = bucketize_hist_tile(d, w, ew, d_min, delta, m,
                                            hist_pad, bq)
    bucket_ref[...] = bucket

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    hist_ref[...] += tile_hist


def bucket_hist_batch_pallas(
    dists: jax.Array,    # (B, n) fp32, n % tile == 0 (invalid lanes = +inf)
    valid: jax.Array,    # (B, n) bool
    d_min: jax.Array,    # (B,)
    delta: jax.Array,    # (B,)
    ew_maps: jax.Array,  # (B, n_ew) int32
    m: int,
    tile: int = TILE,
    bq: int = BQ,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched Eq. 6 + histogram: per-query codebooks over a (B, n) distance
    matrix, one (B, m+1) histogram as the only cross-tile state.

    Returns (bucket_ids (B, n), hist (B, m+1)).  Requires B % bq == 0
    (wrappers pad the query batch).
    """
    interpret = resolve_interpret(interpret)
    b, n = dists.shape
    assert b % bq == 0, (b, bq)
    g = n // tile
    n_ew = ew_maps.shape[1]
    hist_pad = ((m + 1 + 127) // 128) * 128
    scal = jnp.zeros((b, 128), jnp.float32)
    scal = scal.at[:, 0].set(d_min.astype(jnp.float32))
    scal = scal.at[:, 1].set(delta.astype(jnp.float32))
    w = valid.astype(jnp.int32).T                 # (n, B)
    bucket, hist = pl.pallas_call(
        functools.partial(_bucket_batch_kernel, m=m, hist_pad=hist_pad,
                          bq=bq),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            pl.BlockSpec((b, n_ew), lambda i: (0, 0)),
            pl.BlockSpec((b, 128), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            pl.BlockSpec((b, hist_pad), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, b), jnp.int32),
            jax.ShapeDtypeStruct((b, hist_pad), jnp.int32),
        ],
        interpret=interpret,
    )(dists.T, w, ew_maps.astype(jnp.int32), scal)
    return bucket.T, hist[:, : m + 1]
