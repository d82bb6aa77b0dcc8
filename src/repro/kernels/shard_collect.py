"""Pallas TPU kernel: fused shard scan-collect — bucketize (Eq. 6) +
(m+1)-histogram + *speculative* survivor compaction in one stream pass.

The sharded deployment's collector used to be three passes over the local
stream: bucketize+histogram (fused), then — after the psum round-trip — a
full-stream masked ``top_k`` to compact survivors into the fixed per-shard
budget.  That post-hoc compaction re-reads the whole (B, F) stream from HBM
and its sort is the single most expensive per-shard stage at large k.

This kernel removes it: while each distance tile is resident it ALSO
compacts the lanes at or below a *provisional* threshold ``tau_spec`` (the
engine's tau_pred, or the sample-derived seed) into a budget-sized position
buffer, in stream order, with the running per-query fill count as the only
extra cross-tile state.  After the psum, the true tau is compared against
``tau_spec``:

  * covered  (tau_spec >= tau, buffer not overflowed): the speculative
    buffer is filtered down to tau — no second stream pass at all;
  * undershoot: one bounded O(F) cumsum-compaction correction pass;
  * overflow: the exact key-priority ``top_k`` fallback.

(The tiering lives in ``core.distributed.bbc_survivors_batch``; this module
only produces the buffer.)  ``tau_spec = -1`` compacts nothing — the cold
path degrades to exactly the old behavior.

Compaction inside the kernel: per tile the masked lanes' prefix sums give
their slots; a (tile, tile) slot==prefix one-hot reduce scatters the global
lane positions into a compacted (tile,) vector (each slot matches at most
one lane), which is written at the buffer's current fill offset: a
(tile + 128)-lane window store at the 128-aligned base below the offset,
the compacted row rotated into place.  The buffer is ``budget + tile + 128``
wide so a partially-filling window never clips; empty window tails hold the
sentinel ``n_pad`` and are overwritten by the next tile's window.

Grid accumulation (histogram, fill counts, buffer) relies on Pallas TPU
grids iterating sequentially on a core, exactly like bucket_hist.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_scan import bucketize_hist_tile
from repro.kernels.platform import resolve_interpret

TILE = 256
BQ = 8   # query-batch chunk width inside the bucketize helper


def _compact_tile(bucket, w, tau_spec, spec_ref, cnt_ref, budget: int,
                  n_pad: int):
    """Append this tile's at-or-below-``tau_spec`` lanes to the resident
    survivor buffer, in stream order.  ``bucket``/``w`` are (tile, b);
    ``spec_ref`` is the (b, budget + tile + 128) position buffer,
    ``cnt_ref`` the (b, 128) running fill counts (col 0; kept as the TRUE
    unclamped totals so the wrapper can report them — only the write
    offset clamps)."""
    tile, b = bucket.shape
    specm = (w > 0) & (bucket <= tau_spec[None, :])
    mi = specm.astype(jnp.int32)
    # in-tile prefix count as a lower-triangular matmul (exact: 0/1 inputs,
    # sums <= tile)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
    pref = jax.lax.dot_general(
        tri.astype(jnp.float32), mi.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST).astype(jnp.int32) - 1  # (tile, b)
    tile_counts = jnp.sum(mi, axis=0)                        # (b,)
    # each lane's slot in its query's compacted row; `tile` = no slot
    slots = jnp.where(specm, pref, tile).astype(jnp.float32)  # (tile, b)
    gpos = pl.program_id(0) * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, 1), 0)                             # (tile, 1)
    slot_ids = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1).astype(
        jnp.float32)
    qids = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile + 128), 1)
    pad = jnp.full((1, 128), n_pad, jnp.int32)

    def one_query(q, carry):
        # column q of `slots` (a one-hot matmul: Mosaic slices no lane
        # dimension at a traced offset); values <= tile are exact
        slots_q = jax.lax.dot_general(
            slots, (qids == q).astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)             # (tile, 1)
        eq = slots_q == slot_ids                             # eq[lane, slot]
        compact = jnp.sum(jnp.where(eq, gpos, 0), axis=0, keepdims=True)
        filled = jnp.sum(eq.astype(jnp.int32), axis=0, keepdims=True)
        compact = jnp.where(filled > 0, compact, n_pad)      # (1, tile)
        # Mosaic stores lane windows only at 128-aligned offsets: write the
        # (tile + 128)-lane window at the aligned base below the fill offset,
        # with the compacted row rotated right by the remainder and the
        # window's leading lanes (already-filled slots) kept as they were
        off = jnp.minimum(cnt_ref[q, 0], budget)
        base = pl.multiple_of((off // 128) * 128, 128)
        rem = off - base
        win = spec_ref[pl.ds(q, 1), pl.ds(base, tile + 128)]
        shifted = pltpu.roll(jnp.concatenate([compact, pad], axis=1), rem, 1)
        spec_ref[pl.ds(q, 1), pl.ds(base, tile + 128)] = jnp.where(
            lane < rem, win, shifted)
        return carry

    jax.lax.fori_loop(0, b, one_query, 0)
    cio = jax.lax.broadcasted_iota(jnp.int32, (b, 128), 1)
    cnt_ref[...] += jnp.where(cio == 0, tile_counts[:, None], 0)


def _collect_batch_kernel(dists_ref, wmask_ref, ew_ref, scal_ref,
                          bucket_ref, hist_ref, spec_ref, cnt_ref,
                          *, m: int, hist_pad: int, bq: int, budget: int,
                          n_pad: int):
    d = dists_ref[...]                           # (TILE, B)
    w = wmask_ref[...]                           # (TILE, B) int32
    ew = ew_ref[...]                             # (B, n_ew)
    s = scal_ref[...]                            # (B, 128)
    d_min, delta = s[:, 0], s[:, 1]
    tau_spec = s[:, 2].astype(jnp.int32)         # (B,) exact in fp32

    bucket, tile_hist = bucketize_hist_tile(d, w, ew, d_min, delta, m,
                                            hist_pad, bq)
    bucket_ref[...] = bucket

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        spec_ref[...] = jnp.full_like(spec_ref, n_pad)

    hist_ref[...] += tile_hist
    _compact_tile(bucket, w, tau_spec, spec_ref, cnt_ref, budget, n_pad)


def shard_collect_batch_pallas(
    dists: jax.Array,    # (B, n) fp32, n % tile == 0 (invalid lanes = +inf)
    valid: jax.Array,    # (B, n) bool
    d_min: jax.Array,    # (B,)
    delta: jax.Array,    # (B,)
    ew_maps: jax.Array,  # (B, n_ew) int32
    m: int,
    tau_spec: jax.Array,  # (B,) int32; -1 compacts nothing
    budget: int,
    tile: int = TILE,
    bq: int = BQ,
    interpret: bool | None = None,
):
    """Fused bucketize + histogram + speculative compaction.

    Returns ``(bucket (B, n), hist (B, m+1), spec_pos (B, budget),
    spec_count (B,))``; ``spec_pos`` holds stream positions of the first
    ``budget`` lanes with bucket <= tau_spec in stream order (sentinel
    ``n`` beyond the fill), ``spec_count`` the TOTAL matching-lane count
    (may exceed ``budget`` — the overflow signal).  Requires B % bq == 0.
    """
    interpret = resolve_interpret(interpret)
    b, n = dists.shape
    assert b % bq == 0, (b, bq)
    g = n // tile
    n_ew = ew_maps.shape[1]
    hist_pad = ((m + 1 + 127) // 128) * 128
    bud_pad = ((budget + 127) // 128) * 128
    spec_w = bud_pad + tile + 128
    scal = jnp.zeros((b, 128), jnp.float32)
    scal = scal.at[:, 0].set(d_min.astype(jnp.float32))
    scal = scal.at[:, 1].set(delta.astype(jnp.float32))
    scal = scal.at[:, 2].set(tau_spec.astype(jnp.float32))
    w = valid.astype(jnp.int32).T                 # (n, B)
    bucket, hist, spec, cnt = pl.pallas_call(
        functools.partial(_collect_batch_kernel, m=m, hist_pad=hist_pad,
                          bq=bq, budget=bud_pad, n_pad=n),
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
            pl.BlockSpec((b, spec_w), lambda i: (0, 0)),
            pl.BlockSpec((b, 128), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, b), jnp.int32),
            jax.ShapeDtypeStruct((b, hist_pad), jnp.int32),
            jax.ShapeDtypeStruct((b, spec_w), jnp.int32),
            jax.ShapeDtypeStruct((b, 128), jnp.int32),
        ],
        interpret=interpret,
    )(dists.T, w, ew_maps.astype(jnp.int32), scal)
    return bucket.T, hist[:, : m + 1], spec[:, :budget], cnt[:, 0]


def _compact_only_kernel(bucket_ref, wmask_ref, taus_ref, spec_ref, cnt_ref,
                         *, budget: int, n_pad: int):
    bucket = bucket_ref[...]                     # (TILE, B)
    w = wmask_ref[...]                           # (TILE, B) int32
    tau_spec = taus_ref[...][:, 0]               # (B,)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        spec_ref[...] = jnp.full_like(spec_ref, n_pad)

    _compact_tile(bucket, w, tau_spec, spec_ref, cnt_ref, budget, n_pad)


def spec_compact_batch_pallas(
    bucket: jax.Array,   # (B, n) int32, n % tile == 0
    valid: jax.Array,    # (B, n) bool
    tau_spec: jax.Array,  # (B,) int32
    budget: int,
    tile: int = TILE,
    interpret: bool | None = None,
):
    """Compaction-only form for scans whose bucket ids already exist (the
    bound-fused RaBitQ kernel emits bucket_lb itself).  Same buffer
    contract as ``shard_collect_batch_pallas``; returns (spec_pos
    (B, budget), spec_count (B,))."""
    interpret = resolve_interpret(interpret)
    b, n = bucket.shape
    g = n // tile
    bud_pad = ((budget + 127) // 128) * 128
    spec_w = bud_pad + tile + 128
    taus = jnp.broadcast_to(tau_spec.astype(jnp.int32)[:, None],
                            (b, 128))
    w = valid.astype(jnp.int32).T
    spec, cnt = pl.pallas_call(
        functools.partial(_compact_only_kernel, budget=bud_pad, n_pad=n),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            pl.BlockSpec((tile, b), lambda i: (i, 0)),
            pl.BlockSpec((b, 128), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, spec_w), lambda i: (0, 0)),
            pl.BlockSpec((b, 128), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, spec_w), jnp.int32),
            jax.ShapeDtypeStruct((b, 128), jnp.int32),
        ],
        interpret=interpret,
    )(bucket.T, w, taus)
    return spec[:, :budget], cnt[:, 0]
