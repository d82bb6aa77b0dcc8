"""Share of the exact re-ranks that the inline legs did not cover: the
sum of ``SearchResult.n_second_pass`` over the sum of ``n_reranked``, over
the same direct calls as ``collector.rerank_lanes_per_req``."""


def read(ctx):
    total = float(ctx.n_reranked.sum()) if len(ctx.n_reranked) else 0.0
    if not total:
        return None
    return float(ctx.n_second_pass.sum()) / total
