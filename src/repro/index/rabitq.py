"""RaBitQ (bounded estimator): 1-bit codes with a probabilistic error bound.

Faithful implementation of the 1-bit RaBitQ estimator (Gao & Long, 2024):

  index time (per object o, cluster centroid c):
    r = o - c, norm_o = ||r||, unit ō = r / norm_o
    u = P ō                      (P: random orthonormal rotation)
    b = sign(u) in {-1,+1}^d     (the stored code; x̄ = b/√d)
    f_o = <x̄, u> = (1/√d) Σ|u_i|   (stored fp32 factor)

  query time (per probed cluster):
    q_r = q - c, norm_q = ||q_r||, v = P (q_r / norm_q)
    <x̄, v> = (1/√d) Σ b_i v_i      (code matmul — MXU-friendly)
    ip_est = <x̄, v> / f_o  ~ <ō, q̄>
    err    = eps0 * sqrt((1 - f_o^2) / (f_o^2 (d - 1)))   (w.h.p. bound)
    dist^2 = norm_q^2 + norm_o^2 - 2 norm_q norm_o <ō, q̄>
    lb/ub  from ip_est ± err.

eps0 is a z-score in our normalization (the estimator error divided by the
formula above is empirically ~N(0,1)); default eps0 = 3.0 gives ~99.7%
validity.  The original paper quotes eps0 = 1.9 under a different constant
convention for the same confidence regime.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class RabitqCodes(NamedTuple):
    """RaBitQ sign codes with the rotation and per-vector correction factors.
    """
    rot: jax.Array      # (d, d) orthonormal
    codes: jax.Array    # (n, d) int8 in {-1, +1}
    norm_o: jax.Array   # (n,)
    f_o: jax.Array      # (n,)


def random_rotation(key: jax.Array, d: int) -> jax.Array:
    g = jax.random.normal(key, (d, d), jnp.float32)
    qmat, r = jnp.linalg.qr(g)
    # fix signs for a Haar-ish distribution
    return qmat * jnp.sign(jnp.diag(r))[None, :]


def encode(key: jax.Array, x: jax.Array, centroids: jax.Array,
           assignment: jax.Array) -> RabitqCodes:
    d = x.shape[1]
    rot = random_rotation(key, d)
    r = x - centroids[assignment]
    norm_o = jnp.linalg.norm(r, axis=1)
    unit = r / jnp.maximum(norm_o, 1e-12)[:, None]
    u = jnp.matmul(unit, rot.T, precision="highest")     # P ō
    codes = jnp.where(u >= 0, 1, -1).astype(jnp.int8)
    f_o = jnp.sum(jnp.abs(u), axis=1) / jnp.sqrt(jnp.float32(d))
    return RabitqCodes(rot=rot, codes=codes, norm_o=norm_o,
                       f_o=jnp.maximum(f_o, 1e-6))


class QueryFactors(NamedTuple):
    """Per-query RaBitQ factors: rotated unit residual and its norm."""
    v: jax.Array        # (d,) rotated unit residual
    norm_q: jax.Array   # scalar


def query_factors(rq: RabitqCodes, q: jax.Array, centroid: jax.Array) -> QueryFactors:
    qr = q - centroid
    norm_q = jnp.linalg.norm(qr)
    v = jnp.matmul(qr / jnp.maximum(norm_q, 1e-12), rq.rot.T,
                   precision="highest")
    return QueryFactors(v=v, norm_q=norm_q)


def estimate(
    codes: jax.Array,    # (c, d) int8 codes of one cluster's members
    norm_o: jax.Array,   # (c,)
    f_o: jax.Array,      # (c,)
    qf: QueryFactors,
    eps0: float = 3.0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (est_dist, lb, ub) — actual distances (sqrt of the squared
    form), lower bound clamped at 0."""
    d = codes.shape[1]
    xv = jnp.matmul(codes.astype(jnp.float32), qf.v,
                    precision="highest") / jnp.sqrt(jnp.float32(d))  # <x̄,v>
    ip = xv / f_o
    err = eps0 * jnp.sqrt((1.0 - f_o ** 2) / (f_o ** 2 * (d - 1)))
    scale = 2.0 * qf.norm_q * norm_o
    base = qf.norm_q ** 2 + norm_o ** 2
    est2 = base - scale * ip
    lb2 = base - scale * (ip + err)
    ub2 = base - scale * (ip - err)
    zero = jnp.zeros_like(est2)
    return (
        jnp.sqrt(jnp.maximum(est2, zero)),
        jnp.sqrt(jnp.maximum(lb2, zero)),
        jnp.sqrt(jnp.maximum(ub2, zero)),
    )
