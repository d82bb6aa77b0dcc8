"""Batched Lloyd k-means — the coarse quantizer for IVF and PQ codebooks.

Pure JAX, jit-compiled, random distinct initial picks, fixed iteration
count (Faiss-style niter=10 default).  Every row takes part in every
iteration.  Rows are assigned in chunks of ``chunk_rows(K)`` so that no
(rows, K) distance or one-hot array exceeds ``CHUNK_BYTES`` (a 10M-row
corpus against 16,384 centroids would otherwise need 655 GB per array);
per-cluster sums and counts are accumulated chunk by chunk.  Where one
chunk covers every row, the computation is the unchunked one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK_BYTES = 1 << 30
LANE = 128


def chunk_rows(n_clusters: int) -> int:
    """Rows per chunk: a (rows, n_clusters) f32 array, its lanes padded to
    128 as the chip lays them out, stays within ``CHUNK_BYTES``."""
    lanes = -(-n_clusters // LANE) * LANE
    return max(8, CHUNK_BYTES // (4 * lanes) // 8 * 8)


def n_chunks(n: int, n_clusters: int) -> int:
    return -(-n // chunk_rows(n_clusters))


def _pairwise_sq(x: jax.Array, c: jax.Array) -> jax.Array:
    """||x - c||^2 via the matmul identity (MXU-friendly)."""
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    return x2 + c2 - 2.0 * jnp.matmul(x, c.T, precision="highest")


def assign(x: jax.Array, centroids: jax.Array) -> jax.Array:
    return jnp.argmin(_pairwise_sq(x, centroids), axis=-1).astype(jnp.int32)


def _sums(x: jax.Array, cent: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(K, d) sums and (K,) counts of the rows nearest each centroid."""
    one = jax.nn.one_hot(assign(x, cent), cent.shape[0], dtype=x.dtype)
    return (jnp.matmul(one.T, x, precision="highest"),
            jnp.sum(one, axis=0))


def _fold_chunks(x: jax.Array, rows: int, chunk_fn, combine, out):
    """``combine(out, chunk_fn(chunk), start)`` over consecutive ``rows``
    -row chunks of ``x``: a loop over the whole chunks, then the tail."""
    n = x.shape[0]
    rows = min(rows, n)
    full = n // rows

    def body(i, acc):
        start = i * rows
        return combine(acc, chunk_fn(
            jax.lax.dynamic_slice_in_dim(x, start, rows)), start)
    out = jax.lax.fori_loop(0, full, body, out)
    if n % rows:
        out = combine(out, chunk_fn(x[full * rows:]), full * rows)
    return out


def map_chunks(x: jax.Array, rows: int, chunk_fn, out: jax.Array):
    """``out`` with each ``rows``-row chunk of ``x`` mapped by ``chunk_fn``
    into the same rows."""
    return _fold_chunks(x, rows, chunk_fn,
                        lambda acc, r, start:
                        jax.lax.dynamic_update_slice_in_dim(acc, r, start, 0),
                        out)


def _chunked_sums(x: jax.Array, cent: jax.Array, rows: int):
    k, d = cent.shape
    zero = (jnp.zeros((k, d), x.dtype), jnp.zeros((k,), x.dtype))
    return _fold_chunks(x, rows, lambda xc: _sums(xc, cent),
                        lambda acc, s, _: (acc[0] + s[0], acc[1] + s[1]),
                        zero)


def lloyd(key: jax.Array, x: jax.Array, n_clusters: int,
          n_iter: int = 10) -> jax.Array:
    """(n_clusters, d) centroids after ``n_iter`` Lloyd iterations."""
    return _lloyd(key, x, n_clusters, n_iter, chunk_rows(n_clusters))


@functools.partial(jax.jit, static_argnames=("n_clusters", "n_iter", "rows"))
def _lloyd(key, x, n_clusters, n_iter, rows):
    n, d = x.shape
    idx = jax.random.choice(key, n, (n_clusters,), replace=False)
    cent0 = x[idx]

    def step(cent, _):
        sums, counts = _chunked_sums(x, cent, rows)
        newc = sums / jnp.maximum(counts, 1.0)[:, None]
        # keep empty clusters where they were
        newc = jnp.where(counts[:, None] > 0, newc, cent)
        return newc, None

    cent, _ = jax.lax.scan(step, cent0, None, length=n_iter)
    return cent


def assign_chunked(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """(n,) nearest-centroid ids, assigned ``chunk_rows(K)`` rows at a
    time."""
    return _assign_chunked(x, centroids, chunk_rows(centroids.shape[0]))


@functools.partial(jax.jit, static_argnames=("rows",))
def _assign_chunked(x, centroids, rows):
    return map_chunks(x, rows, lambda xc: assign(xc, centroids),
                      jnp.zeros((x.shape[0],), jnp.int32))


def kmeans(key: jax.Array, x: jax.Array, n_clusters: int,
           n_iter: int = 10) -> tuple[jax.Array, jax.Array]:
    """Returns (centroids (n_clusters, d), assignment (n,))."""
    cent = lloyd(key, x, n_clusters, n_iter)
    return cent, assign_chunked(x, cent)
