"""Roofline share of the fused PQ scan kernel (``kernels/fused_scan.py``).

Needed work per call, from the algorithm and the cell's shapes: the codes
of the lanes that some query of the block probes, read once at their
stored width (``pq_m`` codes of ``pq_bits`` bits), and ``pq_m`` table
additions for each (query, probed lane).  The probed lanes are the list
sizes of each query's ``n_probe`` nearest lists, not the whole stream.
"""
from roofline import kernel_share

KERNEL = "fused_scan_batch."


def _work(ctx, b):
    union, per_query = ctx.probed[b]
    idx = ctx.config["index"]
    return per_query * idx["pq_m"], union * idx["pq_m"] * idx["pq_bits"] / 8


def read(ctx):
    return kernel_share(ctx, KERNEL, _work)
