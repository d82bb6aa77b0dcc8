"""Product Quantization (unbounded estimator) — encode + ADC tables.

Paper settings: M = d/4 sub-vectors, B = 4 bits (16 centroids / subspace).
The ADC (asymmetric distance computation) table is (M, 2^B) per query; the
estimate for an object is sum_m LUT[m, code[m]].  kernels/pq_adc.py performs
the lookup as a one-hot matmul on the MXU (the FastScan analogue); this module
provides training/encoding and the jnp reference estimator.
"""
from __future__ import annotations

import functools
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
    keys = jax.random.split(key, n_sub)
    with jax.profiler.TraceAnnotation(
            "pq.train", rows=n, chunks=km.n_chunks(n, 2 ** n_bits),
            n_clusters=2 ** n_bits):
        cents = [km.lloyd(keys[m], x[:, m * dsub:(m + 1) * dsub],
                          2 ** n_bits, n_iter)
                 for m in range(n_sub)]   # offline; loop fine
        return jax.block_until_ready(PQCodebook(centroids=jnp.stack(cents)))


def encode(cb: PQCodebook, x: jax.Array) -> jax.Array:
    """(n, M) uint8 codes, encoded in row chunks so that no (rows, M, 2^B)
    distance array exceeds ``kmeans.CHUNK_BYTES``."""
    n, width = x.shape[0], cb.n_sub * cb.n_codes
    with jax.profiler.TraceAnnotation("pq.encode", rows=n,
                                      chunks=km.n_chunks(n, width)):
        return jax.block_until_ready(_encode(cb, x, km.chunk_rows(width)))


@functools.partial(jax.jit, static_argnames=("rows",))
def _encode(cb: PQCodebook, x: jax.Array, rows: int) -> jax.Array:
    return km.map_chunks(x, rows, lambda xc: _encode_rows(cb, xc),
                         jnp.zeros((x.shape[0], cb.n_sub), jnp.uint8))


def _encode_rows(cb: PQCodebook, x: jax.Array) -> jax.Array:
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
