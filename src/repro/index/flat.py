"""Brute-force exact search (BFC baseline + ground-truth generator)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("k",))
def search(x: jax.Array, q: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Exact top-k by Euclidean distance for one query."""
    xq = jnp.matmul(x, q, precision="highest")
    d2 = jnp.sum(x * x, axis=1) - 2.0 * xq + jnp.sum(q * q)
    neg, idx = jax.lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def search_batch(x: jax.Array, qs: jax.Array, k: int):
    return jax.vmap(lambda q: search(x, q, k))(qs)
