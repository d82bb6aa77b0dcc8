"""Batched Lloyd k-means — the coarse quantizer for IVF and PQ codebooks.

Pure JAX, jit-compiled, k-means++-lite init (random distinct picks + one
refinement round), fixed iteration count (Faiss-style niter=10 default).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _pairwise_sq(x: jax.Array, c: jax.Array) -> jax.Array:
    """||x - c||^2 via the matmul identity (MXU-friendly)."""
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    return x2 + c2 - 2.0 * jnp.matmul(x, c.T, precision="highest")


def assign(x: jax.Array, centroids: jax.Array) -> jax.Array:
    return jnp.argmin(_pairwise_sq(x, centroids), axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_clusters", "n_iter"))
def kmeans(
    key: jax.Array, x: jax.Array, n_clusters: int, n_iter: int = 10
) -> tuple[jax.Array, jax.Array]:
    """Returns (centroids (n_clusters, d), assignment (n,))."""
    n, d = x.shape
    idx = jax.random.choice(key, n, (n_clusters,), replace=False)
    cent0 = x[idx]

    def step(cent, _):
        a = assign(x, cent)
        one = jax.nn.one_hot(a, n_clusters, dtype=x.dtype)      # (n, K)
        counts = jnp.sum(one, axis=0)                            # (K,)
        sums = jnp.matmul(one.T, x, precision="highest")         # (K, d)
        newc = sums / jnp.maximum(counts, 1.0)[:, None]
        # keep empty clusters where they were
        newc = jnp.where(counts[:, None] > 0, newc, cent)
        return newc, None

    cent, _ = jax.lax.scan(step, cent0, None, length=n_iter)
    return cent, assign(x, cent)
