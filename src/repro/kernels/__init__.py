"""Pallas TPU kernels for the BBC hot paths, with jnp reference mirrors.

One module per kernel (fused_scan, bucket_hist, pq_adc,
rabitq_fused, l2_rerank, shard_collect); ``ops.py`` wraps them behind the
pallas/ref backend switch and ``ref.py`` holds the jnp oracles.
"""
# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
