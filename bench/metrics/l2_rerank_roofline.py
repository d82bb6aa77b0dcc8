"""Roofline share of the exact-distance kernel ``l2_exact_batch``
(``kernels/l2_rerank.py``), where the search runs it as its second-pass
re-rank leg.

Needed work per call: the f32 vectors of the lanes re-ranked in the
second pass (``n_second_pass``, mean over the sampled requests; the union
over a block's queries is at least that many lanes, read once) and a
d-long dot product (2d f32 operations) per (query, re-ranked lane).
"""
from roofline import kernel_share

KERNEL = "l2_exact_batch."


def _work(ctx, b):
    lanes = float(ctx.n_second_pass.mean())
    d = ctx.config["d"]
    return ctx.traffic["clients"] * lanes * 2 * d, lanes * d * 4


def read(ctx):
    if not len(ctx.n_second_pass):
        return None
    return kernel_share(ctx, KERNEL, _work)
