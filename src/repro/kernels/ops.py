"""Public jit'd wrappers around the Pallas kernels.

Handles padding to tile/lane multiples, dtype plumbing, and the
interpret-mode switch (CPU containers execute the kernel bodies in Python via
``interpret=True``; on TPU the same calls compile to Mosaic).

The ``*_batch`` wrappers additionally route between two backends:

  * ``"pallas"`` — the batched Pallas kernels (Mosaic on TPU; the interpret
    emulator elsewhere).  The emulator is a correctness tool, ~100x slower
    than XLA on CPU, so it is never the default off-TPU.
  * ``"ref"``    — the pure-jnp mirrors in kernels/ref.py: the same batched
    math (shared candidate stream, batched matmuls) compiled by XLA.  This is
    the production CPU fallback.

``backend=None`` selects pallas on TPU and ref elsewhere, so the batched
search engine runs the fused kernels wherever they pay off and stays fast on
CPU containers/CI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bucket_hist as _bh
from repro.kernels import fused_scan as _fs
from repro.kernels import l2_rerank as _l2
from repro.kernels import pq_adc as _adc
from repro.kernels import rabitq_fused as _rqf
from repro.kernels import ref as _ref
from repro.kernels import shard_collect as _sc
from repro.kernels.platform import default_interpret, on_tpu

INF = jnp.inf


def _interpret() -> bool:
    return default_interpret()


def resolve_backend(backend: str | None) -> str:
    if backend is None:
        return "pallas" if on_tpu() else "ref"
    if backend not in ("pallas", "ref"):
        raise ValueError(f"unknown kernel backend: {backend!r}")
    return backend


def _pad_rows(x: jax.Array, mult: int, fill) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, width, constant_values=fill)


def _pad_cols(x: jax.Array, mult: int, fill) -> jax.Array:
    c = x.shape[1]
    pad = (-c) % mult
    if pad == 0:
        return x
    width = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, width, constant_values=fill)


# --------------------------------------------------------------------------
# Batched (multi-query) wrappers
# --------------------------------------------------------------------------

def _pad_batch(b: int, bq: int) -> int:
    return (-b) % bq


@functools.partial(jax.jit, static_argnames=("tile", "mc", "backend"))
def pq_adc_batch(codes: jax.Array, luts: jax.Array, tile: int = _adc.TILE,
                 mc: int = _adc.MC, backend: str | None = None) -> jax.Array:
    """Shared (n, M) codes x per-query (B, M, K) LUTs -> (B, n) squared
    estimates."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return _ref.pq_adc_batch(codes, luts)
    n = codes.shape[0]
    b = luts.shape[0]
    codes_p = _pad_cols(_pad_rows(codes.astype(jnp.int32), tile, 0), mc, 0)
    m_pad = codes_p.shape[1] - luts.shape[1]
    luts_p = jnp.pad(luts, ((0, _pad_batch(b, _adc.BQ)), (0, m_pad), (0, 0)))
    out = _adc.adc_batch_pallas(codes_p, luts_p, tile=tile, mc=mc,
                                interpret=_interpret())
    return out[:b, :n]


@functools.partial(jax.jit, static_argnames=("m", "tile", "mc", "backend"))
def fused_scan_batch(codes: jax.Array, vectors: jax.Array, valid: jax.Array,
                     luts: jax.Array, qs: jax.Array, d_min: jax.Array,
                     delta: jax.Array, ew_maps: jax.Array, m: int,
                     tau_pred: jax.Array, tile: int = _fs.TILE,
                     mc: int = _fs.MC, backend: str | None = None):
    """Batched fused estimate+bucketize+hist+early-exact over a shared
    candidate stream.

    ``codes`` (n, M) / ``vectors`` (n, d) are the stream shared by every
    query; ``valid`` (B, n) masks each query's probed lanes; ``luts``
    (B, M, K), ``qs`` (B, d), codebook params and ``tau_pred`` are per-query.
    Returns (est (B, n), bucket (B, n), hist (B, m+1), early (B, n),
    nmiss (B,)) — nmiss counts valid lanes with bucket > tau_pred, the lanes
    the predictive early-exact pass leaves to the second gather.
    """
    backend = resolve_backend(backend)
    if backend == "ref":
        return _ref.fused_scan_batch(codes, vectors, valid, luts, qs, d_min,
                                     delta, ew_maps, m, tau_pred)
    n, d = vectors.shape
    b = qs.shape[0]
    bp = _pad_batch(b, _fs.BQ)
    codes_p = _pad_cols(_pad_rows(codes.astype(jnp.int32), tile, 0), mc, 0)
    m_pad = codes_p.shape[1] - luts.shape[1]
    luts_p = jnp.pad(luts, ((0, bp), (0, m_pad), (0, 0)))
    vecs_p = _pad_cols(_pad_rows(vectors, tile, 0.0), 128, 0.0)
    qs_p = jnp.pad(qs, ((0, bp), (0, vecs_p.shape[1] - d)))
    valid_p = jnp.pad(_pad_cols(valid, tile, False), ((0, bp), (0, 0)))
    d_min_p = jnp.pad(d_min, (0, bp))
    delta_p = jnp.pad(delta, (0, bp), constant_values=1.0)
    ew_p = jnp.pad(ew_maps.astype(jnp.int32), ((0, bp), (0, 0)))
    tau_p = jnp.pad(tau_pred.astype(jnp.int32), (0, bp), constant_values=-1)
    est, bucket, hist, early, nmiss = _fs.fused_scan_batch_pallas(
        codes_p, vecs_p, valid_p.T, luts_p, qs_p, d_min_p, delta_p, ew_p, m,
        tau_p, tile=tile, mc=mc, interpret=_interpret())
    return est[:b, :n], bucket[:b, :n], hist[:b], early[:b, :n], nmiss[:b]


@functools.partial(jax.jit, static_argnames=("m", "eps0", "tile", "backend"))
def fused_rabitq_scan_batch(codes: jax.Array, vectors: jax.Array,
                            norm_o: jax.Array, f_o: jax.Array,
                            cl: jax.Array, centroids: jax.Array,
                            rot: jax.Array, qs: jax.Array, d2: jax.Array,
                            valid: jax.Array, d_min: jax.Array,
                            delta: jax.Array, ew_maps: jax.Array, m: int,
                            tau_inline: jax.Array, eps0: float = 3.0,
                            tile: int = _rqf.TILE,
                            backend: str | None = None):
    """Batched bound-fused RaBitQ scan over a shared candidate stream.

    ``codes``/``vectors``/``norm_o``/``f_o``/``cl`` are the stream shared by
    every query (``cl`` maps each lane to its clamped owning cluster);
    ``qs``, the (B, C) squared routing distances ``d2``, the per-query
    codebook params and ``tau_inline`` are per-query.  Returns
    ``(est, lb, ub, bucket_lb, bucket_ub, hist_lb, hist_ub, exact,
    certified, nmiss)`` — see ``kernels.ref.fused_rabitq_scan_batch`` for
    the contract; ``exact`` is finite exactly on certified lanes (the
    bound-certified inline band the second gather pass can skip).
    """
    backend = resolve_backend(backend)
    tau_inline = tau_inline.astype(jnp.int32)
    if backend == "ref":
        return _ref.fused_rabitq_scan_batch(
            codes.astype(jnp.float32), vectors, norm_o, f_o, cl, centroids,
            rot, qs, d2, valid, d_min, delta, ew_maps, m, tau_inline, eps0)
    n, d = vectors.shape
    b = qs.shape[0]
    bp = _pad_batch(b, _rqf.BQ)
    codes_f = codes.astype(jnp.float32)
    # query-independent decomposition inputs (see ref.rabitq_bounds_stream)
    h = jnp.matmul(centroids, rot.T, precision="highest")
    s2 = jnp.sum(codes_f * h[cl], axis=1)
    qs_b = jnp.pad(qs, ((0, bp), (0, 0)))
    g = jnp.matmul(qs_b, rot.T, precision="highest")
    nq_lane = jnp.sqrt(d2)[:, cl]                              # (B, n)
    codes_p = _pad_cols(_pad_rows(codes_f, tile, 0.0), 128, 0.0)
    vecs_p = _pad_cols(_pad_rows(vectors, tile, 0.0), 128, 0.0)
    dp = vecs_p.shape[1] - d
    s2_p = _pad_rows(s2, tile, 0.0)
    norm_p = _pad_rows(norm_o, tile, 0.0)
    f_p = _pad_rows(f_o, tile, 1.0)
    valid_p = jnp.pad(_pad_cols(valid, tile, False), ((0, bp), (0, 0)))
    nq_p = jnp.pad(_pad_cols(nq_lane, tile, 1.0), ((0, bp), (0, 0)),
                   constant_values=1.0)
    g_p = jnp.pad(g, ((0, 0), (0, dp)))
    qs_p = jnp.pad(qs_b, ((0, 0), (0, dp)))
    d_min_p = jnp.pad(d_min, (0, bp))
    delta_p = jnp.pad(delta, (0, bp), constant_values=1.0)
    ew_p = jnp.pad(ew_maps.astype(jnp.int32), ((0, bp), (0, 0)))
    tau_p = jnp.pad(tau_inline, (0, bp), constant_values=-1)
    outs = _rqf.fused_rabitq_scan_batch_pallas(
        codes_p, vecs_p, s2_p, norm_p, f_p, valid_p.T, nq_p.T, g_p, qs_p,
        d_min_p, delta_p, ew_p, m, tau_p, d_logical=d, eps0=eps0, tile=tile,
        interpret=_interpret())
    (est, lb, ub, blb, bub, hist_lb, hist_ub, exact, cert, nmiss) = outs
    return (est[:b, :n], lb[:b, :n], ub[:b, :n], blb[:b, :n], bub[:b, :n],
            hist_lb[:b], hist_ub[:b], exact[:b, :n], cert[:b, :n],
            nmiss[:b])


@functools.partial(jax.jit, static_argnames=("m", "tile", "backend"))
def bucket_hist_batch(dists: jax.Array, valid: jax.Array, d_min: jax.Array,
                      delta: jax.Array, ew_maps: jax.Array, m: int,
                      tile: int = _bh.TILE, backend: str | None = None):
    """(B, n) distances, per-query codebooks -> (bucket (B, n), hist
    (B, m+1))."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return _ref.bucket_hist_batch(dists, valid, d_min, delta,
                                      ew_maps.astype(jnp.int32), m)
    b, n = dists.shape
    bp = _pad_batch(b, _bh.BQ)
    d_p = jnp.pad(_pad_cols(dists, tile, jnp.inf), ((0, bp), (0, 0)),
                  constant_values=jnp.inf)
    v_p = jnp.pad(_pad_cols(valid, tile, False), ((0, bp), (0, 0)))
    d_min_p = jnp.pad(d_min, (0, bp))
    delta_p = jnp.pad(delta, (0, bp), constant_values=1.0)
    ew_p = jnp.pad(ew_maps.astype(jnp.int32), ((0, bp), (0, 0)))
    bucket, hist = _bh.bucket_hist_batch_pallas(
        d_p, v_p, d_min_p, delta_p, ew_p, m, tile=tile,
        interpret=_interpret())
    return bucket[:b, :n], hist[:b]


@functools.partial(jax.jit,
                   static_argnames=("m", "budget", "tile", "backend"))
def shard_collect_batch(dists: jax.Array, valid: jax.Array,
                        d_min: jax.Array, delta: jax.Array,
                        ew_maps: jax.Array, m: int, tau_spec: jax.Array,
                        budget: int, tile: int = _sc.TILE,
                        backend: str | None = None):
    """Fused shard collect: (B, n) distances -> (bucket (B, n), hist
    (B, m+1), spec_pos (B, budget), spec_ok (B, budget), spec_count (B,)).

    One stream pass computes the bucket ids and histogram AND speculatively
    compacts the lanes at or below the provisional ``tau_spec`` into the
    fixed ``budget`` position buffer, in stream order (``tau_spec = -1``
    compacts nothing).  ``spec_count`` is the total matching-lane count —
    above ``budget`` signals overflow.  Feed the buffer to
    ``core.distributed.bbc_survivors_batch(spec=...)``.
    """
    backend = resolve_backend(backend)
    tau_spec = tau_spec.astype(jnp.int32)
    if backend == "ref":
        return _ref.shard_collect_batch(
            dists, valid, d_min, delta, ew_maps.astype(jnp.int32), m,
            tau_spec, budget)
    b, n = dists.shape
    bp = _pad_batch(b, _sc.BQ)
    d_p = jnp.pad(_pad_cols(dists, tile, jnp.inf), ((0, bp), (0, 0)),
                  constant_values=jnp.inf)
    v_p = jnp.pad(_pad_cols(valid, tile, False), ((0, bp), (0, 0)))
    d_min_p = jnp.pad(d_min, (0, bp))
    delta_p = jnp.pad(delta, (0, bp), constant_values=1.0)
    ew_p = jnp.pad(ew_maps.astype(jnp.int32), ((0, bp), (0, 0)))
    tau_p = jnp.pad(tau_spec, (0, bp), constant_values=-1)
    bucket, hist, pos, cnt = _sc.shard_collect_batch_pallas(
        d_p, v_p, d_min_p, delta_p, ew_p, m, tau_p, budget, tile=tile,
        interpret=_interpret())
    pos = pos[:b]
    ok = pos < n                  # padded-lane sentinel (n_pad) -> invalid
    return (bucket[:b, :n], hist[:b], jnp.where(ok, pos, n), ok, cnt[:b])


@functools.partial(jax.jit, static_argnames=("budget", "tile", "backend"))
def spec_compact_batch(bucket: jax.Array, valid: jax.Array,
                       tau_spec: jax.Array, budget: int,
                       tile: int = _sc.TILE, backend: str | None = None):
    """Compaction-only form of ``shard_collect_batch`` for scans whose
    bucket ids already exist (the bound-fused RaBitQ kernel emits
    bucket_lb itself).  Returns (spec_pos, spec_ok, spec_count)."""
    backend = resolve_backend(backend)
    tau_spec = tau_spec.astype(jnp.int32)
    if backend == "ref":
        return _ref.spec_compact_batch(bucket, valid, tau_spec, budget)
    b, n = bucket.shape
    b_p = _pad_cols(bucket.astype(jnp.int32), tile, 0)
    v_p = _pad_cols(valid, tile, False)
    pos, cnt = _sc.spec_compact_batch_pallas(
        b_p, v_p, tau_spec, budget, tile=tile, interpret=_interpret())
    ok = pos < n
    return jnp.where(ok, pos, n), ok, cnt


@functools.partial(jax.jit, static_argnames=("tile", "backend"))
def l2_exact_batch(x: jax.Array, qs: jax.Array, tile: int = _l2.TILE,
                   backend: str | None = None) -> jax.Array:
    """(n, d) shared vectors x (B, d) queries -> (B, n) exact distances."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return _ref.l2_exact_batch(x, qs)
    n, d = x.shape
    b = qs.shape[0]
    bp = _pad_batch(b, _l2.BQ)
    x_p = _pad_cols(_pad_rows(x, tile, 0.0), 128, 0.0)
    qs_p = jnp.pad(qs, ((0, bp), (0, x_p.shape[1] - d)))
    return _l2.l2_batch_pallas(x_p, qs_p, tile=tile,
                               interpret=_interpret())[:b, :n]
