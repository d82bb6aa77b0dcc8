"""Roofline share of the bound-fused RaBitQ scan kernel
(``kernels/rabitq_fused.py``).

Needed work per call, from the algorithm and the cell's shapes: the 1-bit
codes (d / 8 bytes) and the two f32 factors (norm, f) of each lane that
some query of the block probes, read once, and a d-long +-1 dot product
(2d f32 operations) for each (query, probed lane).  The probed lanes are
the list sizes of each query's ``n_probe`` nearest lists.
"""
from roofline import kernel_share

KERNEL = "fused_rabitq_scan_batch."


def _work(ctx, b):
    union, per_query = ctx.probed[b]
    d = ctx.config["d"]
    return per_query * 2 * d, union * (d / 8 + 8)


def read(ctx):
    return kernel_share(ctx, KERNEL, _work)
