"""Pallas TPU kernel: PQ asymmetric distance computation (FastScan analogue).

CPU FastScan uses AVX shuffles to look 16-entry LUTs up for 16 codes at once.
The MXU analogue recasts the lookup as a one-hot matmul:

    est[n, b] = sum_m LUT_b[m, code[n, m]]
              = onehot(codes) (TILE, M*K) @ LUT (M*K, B)

The one-hot tile is built in VMEM in M-chunks of ``mc`` sub-quantizers so the
working set stays bounded: (TILE, mc*K) fp32 = 256*512*4 = 512 KiB per chunk
at the default tile.  It is built with 2-D operations only (Mosaic lowers no
3-D reshape cheaply): a 0/1 expansion matmul repeats each code K times along
the lanes, and a compare against the code value each lane tests for gives the
one-hot.

Tiling: grid over row tiles of ``TILE`` codes; LUT replicated to every step
(index_map -> (0, 0)); code block (TILE, M) streams HBM->VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.l2_rerank import BQ, query_dot
from repro.kernels.platform import resolve_interpret

TILE = 256
MC = 32  # sub-quantizer chunk


# --------------------------------------------------------------------------
# Batched (multi-query) ADC
# --------------------------------------------------------------------------

def adc_tile(codes: jax.Array, luts: jax.Array, mc: int) -> jax.Array:
    """Squared ADC estimates of one code tile for every query.

    ``codes`` (tile, M) int32 with M % mc == 0, ``luts`` (M*K, B) fp32 (row
    ``m*K + c`` holds sub-quantizer m's distance to centroid c), B % BQ ==
    0.  Returns (tile, B).
    """
    tile, m_sub = codes.shape
    k_codes = luts.shape[0] // m_sub
    width = mc * k_codes
    row = jax.lax.broadcasted_iota(jnp.int32, (mc, width), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (mc, width), 1)
    # expand[r, j] = 1 where one-hot column j belongs to sub-quantizer r
    expand = ((col >= row * k_codes)
              & (col < (row + 1) * k_codes)).astype(jnp.float32)
    # the code value column j tests for: j - K * (j // K)
    starts = (k_codes * jax.lax.broadcasted_iota(
        jnp.int32, (1, mc), 1)).astype(jnp.float32)
    want = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1).astype(
        jnp.float32) - jax.lax.dot_general(
            starts, expand, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    acc = jnp.zeros((tile, luts.shape[1]), jnp.float32)
    for i in range(m_sub // mc):
        cs = codes[:, i * mc:(i + 1) * mc].astype(jnp.float32)
        # codes are < K <= 256: exact at any matmul precision
        rep = jax.lax.dot_general(cs, expand, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        onehot = (rep == want).astype(jnp.float32)            # (tile, width)
        acc = acc + query_dot(onehot, luts[i * width:(i + 1) * width, :])
    return acc


def _adc_batch_kernel(codes_ref, luts_ref, out_ref, *, mc: int):
    out_ref[...] = adc_tile(codes_ref[...].astype(jnp.int32), luts_ref[...],
                            mc)


def adc_batch_pallas(codes: jax.Array, luts: jax.Array, *, tile: int = TILE,
                     mc: int = MC,
                     interpret: bool | None = None) -> jax.Array:
    """Shared (n, M) codes x per-query (B, M, K) LUTs -> (B, n) squared
    estimates: one code-block stream, ADC for every query as MXU matmuls
    against the resident one-hot chunk.

    Caller guarantees n % tile == 0, M % mc == 0 and B % BQ == 0 (ops.py
    pads).
    """
    interpret = resolve_interpret(interpret)
    n, m_sub = codes.shape
    b, _, k_codes = luts.shape
    luts_t = luts.reshape(b, m_sub * k_codes).T      # (M*K, B)
    out = pl.pallas_call(
        functools.partial(_adc_batch_kernel, mc=mc),
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, m_sub), lambda i: (i, 0)),
            pl.BlockSpec((m_sub * k_codes, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        interpret=interpret,
    )(codes, luts_t)
    return out.T
