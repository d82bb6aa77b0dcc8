"""Exact re-ranks per request: the mean of the program's
``SearchResult.n_reranked`` over the sampled requests, from direct engine
calls outside the window."""


def read(ctx):
    if not len(ctx.n_reranked):
        return None
    return float(ctx.n_reranked.mean())
