import os

# Tests and benches see the single real CPU device; ONLY launch/dryrun.py sets
# the 512-placeholder-device flag (see system design).  Keep x64 off; fp32.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _rabitq_bound_width(index, qs, eps0):
    """(B, N) width ``ub - lb`` of every corpus row's RaBitQ distance bounds
    for each query (the batched estimator over the whole flat stream)."""
    import jax.numpy as jnp

    from repro.index import ivf as ivf_mod, search
    from repro.kernels import ref
    lay = ivf_mod.flat_layout(index.ivf)
    stream = search.rabitq_stream(index, lay)
    _, d2 = ivf_mod.route_batch_d2(index.ivf, qs, 1)
    valid = jnp.broadcast_to(lay.valid, (qs.shape[0], lay.n_flat))
    _, lb, ub = ref.rabitq_bounds_stream(
        stream.codes, stream.norm_o, stream.f_o, stream.cl,
        index.ivf.centroids, index.rq.rot, qs, d2, valid, eps0)
    order, ok = np.asarray(lay.order), np.asarray(lay.valid)
    width = np.full((qs.shape[0], index.vectors.shape[0]), np.inf)
    width[:, order[ok]] = np.asarray(ub - lb)[:, ok]
    return width


def _check_rabitq_reported(index, qs, rows_a, rows_b, min_overlap=1.0,
                           atol=1e-4, rtol=0.0, eps0=3.0):
    """Two RaBitQ+BBC top-k results of the same queries agree as the
    reporting contract allows.

    A certain-in member (its upper bound below the k-th lower bound) is
    reported with its RaBitQ estimate and never re-ranked; a re-ranked
    member is reported with its exact distance.  Which boundary lanes are
    certain-in depends on the bucket codebook, so two paths can report one
    id differently.  Checked per query:

    * id-set overlap >= ``min_overlap``;
    * every reported distance, on both sides and shared or not, lies within
      that lane's bound width ``ub - lb`` of its exact distance (an
      estimate never strays further than its own error band);
    * where the two sides report a shared id differently, one of them is
      the exact distance;
    * an id only one side returned sits at the other side's boundary: its
      exact distance less its bound width does not exceed the other side's
      k-th reported distance.

    ``rows_a`` / ``rows_b`` are per-query (ids, dists) pairs."""
    xs = np.asarray(index.vectors, np.float64)
    width = _rabitq_bound_width(index, qs, eps0)
    for bi, ((ia, da), (ib, db)) in enumerate(zip(rows_a, rows_b)):
        a = dict(zip(np.asarray(ia).tolist(), np.asarray(da).tolist()))
        b = dict(zip(np.asarray(ib).tolist(), np.asarray(db).tolist()))
        overlap = len(a.keys() & b.keys()) / len(b)
        assert overlap >= min_overlap, (bi, overlap)
        q = np.asarray(qs[bi], np.float64)

        def exact(j):
            return float(np.sqrt(np.sum((xs[j] - q) ** 2)))

        def close(v, e):
            return abs(v - e) <= atol + rtol * abs(e)

        for side in (a, b):
            for j, v in side.items():
                e = exact(j)
                assert abs(v - e) <= width[bi, j] + atol + rtol * e, (
                    bi, j, v, e, width[bi, j])
        for j in a.keys() & b.keys():
            if not close(a[j], b[j]):
                e = exact(j)
                assert close(a[j], e) or close(b[j], e), (bi, j, a[j], b[j],
                                                          e)
        for own, other in ((a, b), (b, a)):
            kth = max(other.values())
            for j in own.keys() - other.keys():
                e = exact(j)
                assert e - width[bi, j] <= kth + atol + rtol * kth, (
                    bi, j, e, width[bi, j], kth)


@pytest.fixture
def check_rabitq_reported():
    return _check_rabitq_reported
