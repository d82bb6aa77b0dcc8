"""Plain reference for the served search: exact Euclidean top-k.

Imports nothing of the program under test.  ``exact_topk`` is brute force
over the whole corpus in f32 at matmul precision ``highest`` (what the
configurations state), run in blocks of queries so it fits beside nothing
else once the window's state is freed.  ``row_dists`` gives the distances
of given rows by the same formula: the yardstick for the distances the
server reports.

``exact_topk_bf16x3`` is the control: the same search with every product
taken at the ``high`` precision (three bf16 passes: hi*hi + hi*lo + lo*hi),
spelled out so that it rounds the same way on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 8


def _dot_highest(qs, x):
    return jnp.matmul(qs, x.T, precision=jax.lax.Precision.HIGHEST)


def _split_bf16(a):
    """(hi, lo) bf16 with a ~= hi + lo.  ``reduce_precision`` rounds in
    f32, where a bf16 round trip could be folded away as excess
    precision."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _dot_bf16x3(qs, x):
    """``qs @ x.T`` as hi*hi + hi*lo + lo*hi of single-pass bf16 products,
    summed in f32.  One dot over the three pairs laid side by side, so that
    no compiler can fold the terms into fewer passes."""
    qh, ql = _split_bf16(qs)
    xh, xl = _split_bf16(x)
    a = jnp.concatenate([qh, qh, ql], axis=1)
    b = jnp.concatenate([xh, xl, xh], axis=1)
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _topk(dot, x, x_sq, qs, k):
    d2 = x_sq[None, :] - 2.0 * dot(qs, x) + jnp.sum(qs * qs, 1)[:, None]
    neg, idx = jax.lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "control"))
def _topk_block(x, x_sq, qs, k, control=False):
    return _topk(_dot_bf16x3 if control else _dot_highest, x, x_sq, qs, k)


def _blocked(x, qs: np.ndarray, k: int, control: bool):
    x_sq = jnp.sum(x * x, axis=1)
    dists, ids = [], []
    for i in range(0, len(qs), QUERY_BLOCK):
        blk = np.asarray(qs[i:i + QUERY_BLOCK], np.float32)
        pad = QUERY_BLOCK - len(blk)
        blk = np.concatenate([blk, np.repeat(blk[:1], pad, 0)]) if pad else blk
        d, j = _topk_block(x, x_sq, jnp.asarray(blk), k, control)
        dists.append(np.asarray(d)[:QUERY_BLOCK - pad])
        ids.append(np.asarray(j)[:QUERY_BLOCK - pad])
    return np.concatenate(dists), np.concatenate(ids)


def exact_topk(x, qs: np.ndarray, k: int):
    """(dists, ids), each (len(qs), k): exact top-k, nearest first."""
    return _blocked(x, qs, k, control=False)


def exact_topk_bf16x3(x, qs: np.ndarray, k: int):
    """The control: ``exact_topk`` at three-pass bf16 precision."""
    return _blocked(x, qs, k, control=True)


@jax.jit
def row_dists(x, ids, qs):
    """Distance of corpus row ``ids[i]`` to ``qs[i]``, by the same f32
    formula and precision as ``exact_topk``."""
    rows = x[ids]
    return jnp.sqrt(jnp.maximum(
        jnp.sum(rows * rows, 1)
        - 2.0 * jnp.sum(rows * qs, 1) + jnp.sum(qs * qs, 1), 0.0))
