"""Product Quantization (unbounded estimator) — encode + ADC tables.

Paper settings: M = d/4 sub-vectors, B = 4 bits (16 centroids / subspace).
The ADC (asymmetric distance computation) table is (M, 2^B) per query; the
estimate for an object is sum_m LUT[m, code[m]].  kernels/pq_adc.py performs
the lookup as a one-hot matmul on the MXU (the FastScan analogue); this module
provides training/encoding and the jnp reference estimator.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.index import kmeans as km


class PQCodebook(NamedTuple):
    """Product-quantization codebook: per-subspace centroid tables."""
    centroids: jax.Array  # (M, 2^B, dsub)

    @property
    def n_sub(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_codes(self) -> int:
        return self.centroids.shape[1]

    @property
    def dsub(self) -> int:
        return self.centroids.shape[2]


def train(key: jax.Array, x: jax.Array, n_sub: int, n_bits: int = 4,
          n_iter: int = 10) -> PQCodebook:
    n, d = x.shape
    assert d % n_sub == 0, (d, n_sub)
    dsub = d // n_sub
    xs = x.reshape(n, n_sub, dsub)
    keys = jax.random.split(key, n_sub)
    cents = []
    for m in range(n_sub):  # offline; loop fine
        c, _ = km.kmeans(keys[m], xs[:, m, :], 2 ** n_bits, n_iter)
        cents.append(c)
    return PQCodebook(centroids=jnp.stack(cents))


@jax.jit
def encode(cb: PQCodebook, x: jax.Array) -> jax.Array:
    """(n, M) uint8 codes."""
    n, d = x.shape
    xs = x.reshape(n, cb.n_sub, cb.dsub)

    def enc_sub(xm, cm):  # (n, dsub), (K, dsub)
        d2 = (
            jnp.sum(xm * xm, -1, keepdims=True)
            + jnp.sum(cm * cm, -1)
            - 2.0 * jnp.matmul(xm, cm.T, precision="highest")
        )
        return jnp.argmin(d2, -1)

    codes = jax.vmap(enc_sub, in_axes=(1, 0), out_axes=1)(xs, cb.centroids)
    return codes.astype(jnp.uint8)


@jax.jit
def adc_table(cb: PQCodebook, q: jax.Array) -> jax.Array:
    """(M, 2^B) table of squared sub-distances for one query."""
    qs = q.reshape(cb.n_sub, 1, cb.dsub)
    return jnp.sum((qs - cb.centroids) ** 2, axis=-1)


def estimate(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """Reference ADC estimate: sum_m LUT[m, code[m]] -> squared distance."""
    m = lut.shape[0]
    take = jax.vmap(lambda row, c: row[c], in_axes=(0, 1), out_axes=1)(
        lut, codes.astype(jnp.int32)
    )
    return jnp.sum(take, axis=1)
