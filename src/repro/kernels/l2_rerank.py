"""Pallas TPU kernel: tiled exact ||q - x|| for the re-rank pool.

Straight MXU matmul per tile with the norm identity — the exact-distance
hot spot of every re-rank phase.  Included so the whole search inner loop
(estimate -> bucketize -> select -> re-rank) runs on Pallas kernels.

Every per-query matmul of the batched kernels goes through ``query_dot``:
the queries are taken ``BQ`` columns at a time, each chunk its own matmul.
Mosaic may lower a matmul differently at a different width, so a fixed
chunk width keeps a query's estimates and distances bit-identical whatever
batch it rides in (a served request and a direct call of the same query).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

TILE = 256
BQ = 8   # query chunk width of every per-query matmul; wrappers pad B to it


def query_dot(lhs: jax.Array, rhs: jax.Array) -> jax.Array:
    """``lhs`` (r, c) @ ``rhs`` (c, B) -> (r, B) at full f32 precision, as
    B // BQ matmuls of ``BQ`` query columns each (B % BQ == 0)."""
    b = rhs.shape[1]
    assert b % BQ == 0, (b, BQ)
    outs = [jax.lax.dot_general(lhs, rhs[:, j * BQ:(j + 1) * BQ],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST)
            for j in range(b // BQ)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def exact_tile(x: jax.Array, qt: jax.Array, q_sq: jax.Array) -> jax.Array:
    """Exact ||q_b - x_i|| of one (tile, d) vector tile against the (d, B)
    queries ``qt`` with squared norms ``q_sq`` (B,).  Returns (tile, B)."""
    xv = query_dot(x, qt)
    x_sq = jnp.sum(x * x, axis=1)
    return jnp.sqrt(jnp.maximum(x_sq[:, None] - 2.0 * xv + q_sq[None, :],
                                0.0))


def _l2_batch_kernel(x_ref, qt_ref, scal_ref, out_ref):
    out_ref[...] = exact_tile(x_ref[...], qt_ref[...], scal_ref[...][:, 0])


def l2_batch_pallas(x: jax.Array, qs: jax.Array, tile: int = TILE,
                    interpret: bool | None = None) -> jax.Array:
    """Exact ||q_b - x_i|| for a batch of queries.

    ``x`` (n, d) shared candidate vectors, ``qs`` (B, d) with B % BQ == 0
    (the ops wrapper pads).  Returns (B, n).
    """
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    b = qs.shape[0]
    g = n // tile
    scal = jnp.zeros((b, 128), jnp.float32).at[:, 0].set(
        jnp.sum(qs * qs, axis=1))
    out = pl.pallas_call(
        _l2_batch_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((d, b), lambda i: (0, 0)),
            pl.BlockSpec((b, 128), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        interpret=interpret,
    )(x, qs.T, scal)
    return out.T
